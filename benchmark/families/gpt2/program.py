"""The one place where the benchmark touches the system under test for a
GPT-2 model: a configuration file becomes the program's conf, and the
harness's weights take the program's layout.  Everything else the jobs need
from the program they import themselves, by its public names."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.families.gpt2 import reference


def build_conf(cfg: dict):
    """The program's conf for `cfg`: `models/zoo.char_transformer` (GPT-2
    blocks) at the file's sizes, with the file's flags."""
    from deeplearning4j_tpu.models.zoo import char_transformer

    s = reference.sizes(cfg)
    if s["ffn"] != 4 * s["d"]:
        raise ValueError("the program's FFN is 4x wide; the file says "
                         f"{s['ffn']} for d {s['d']}")
    flags = cfg["flags"]
    conf = char_transformer(
        s["vocab"], d_model=s["d"], n_blocks=s["layers"], n_heads=s["heads"],
        max_seq_len=s["positions"], lr=reference.ADAM["lr"], updater="adam",
        sparse_labels=flags["sparse_labels"],
        fused_updater=flags["fused_updater"],
        attention_block_skip=flags["attention_block_skip"],
        attention_fused_bwd=flags["attention_fused_bwd"])
    return conf.replace(confs=tuple(
        c.replace(compute_dtype=flags["compute_dtype"],
                  attention_impl=flags["attention_impl"])
        for c in conf.confs))


def to_program(weights: list) -> tuple:
    """Reference layout (a dict a layer) -> the program's tuple of dicts."""
    out = []
    for w in weights:
        if "wte" in w:
            out.append({"W": w["wte"], "P": w["wpe"]})
        elif "Wq" in w:
            out.append({
                "Wqkv": jnp.concatenate([w["Wq"], w["Wk"], w["Wv"]], axis=1),
                "bqkv": jnp.concatenate([w["bq"], w["bk"], w["bv"]]),
                "Wo": w["Wo"], "bo": w["bo"],
                "ln_g": w["ln_g"], "ln_b": w["ln_b"]})
        else:
            out.append(dict(w))
    return tuple(out)


def from_program(params) -> list:
    """The program's layout -> the reference's, for norms leaf by leaf."""
    out = []
    for p in params:
        if "P" in p:
            out.append({"wte": p["W"], "wpe": p["P"]})
        elif "Wqkv" in p:
            wq, wk, wv = jnp.split(p["Wqkv"], 3, axis=1)
            bq, bk, bv = jnp.split(p["bqkv"], 3)
            out.append({"Wq": wq, "Wk": wk, "Wv": wv, "bq": bq, "bk": bk,
                        "bv": bv, "Wo": p["Wo"], "bo": p["bo"],
                        "ln_g": p["ln_g"], "ln_b": p["ln_b"]})
        else:
            out.append(dict(p))
    return out
