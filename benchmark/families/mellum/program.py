"""The one place where the benchmark touches the system under test for this
family: a configuration file becomes an ordinary `MultiLayerConfiguration`
of the program's own layer types, and the harness's weights take the
program's layout.  Everything else the jobs need from the program they
import themselves, by its public names."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.families.mellum import reference


def build_conf(cfg: dict):
    """Embedding, then per block an attention layer (`gqa`, over a window or
    full) and the routed FFN (`moe`: softmax scores, one group, no bias, no
    shared expert, every expert held), then the head; parameters in
    `flags.param_dtype`, matrix products in `flags.compute_dtype`."""
    try:
        from deeplearning4j_tpu.nn.conf import (GQASpec, HeadSpec, LayerType,
                                                MoESpec,
                                                MultiLayerConfiguration,
                                                NeuralNetConfiguration)
    except ImportError as e:    # a commit from before the layer type
        raise SystemExit(f"this program cannot run model_type 'mellum': its "
                         f"nn/conf.py has no gqa layer type ({e})")

    s = reference.sizes(cfg)
    d, eps = s["d"], s["eps"]
    base = NeuralNetConfiguration(
        n_in=d, n_out=d, dtype=cfg["flags"]["param_dtype"],
        compute_dtype=cfg["flags"]["compute_dtype"], weight_init="normalized",
        loss_function="mcxent")

    def attention(kind: str):
        rope = s["rope"][kind]
        return GQASpec(n_heads=s["heads"], n_kv_heads=s["kv_heads"],
                       head_dim=s["head_dim"],
                       window=s["window"] if kind == "window" else 0,
                       rope_theta=rope["theta"], yarn=rope["yarn"],
                       qk_norm=True, eps=eps)

    experts = MoESpec(n_routed=s["experts_held"], n_held=s["experts_held"],
                      hidden=s["expert_ffn"], shared_hidden=0,
                      top_k=s["top_k"], eps=eps, score="softmax",
                      router_bias=False)
    confs = []
    for kind in reference.layer_kinds(cfg):
        if kind == "embed":
            confs.append(base.replace(layer_type=LayerType.EMBEDDING,
                                      n_in=s["vocab"], n_out=d))
        elif kind == "head":
            confs.append(base.replace(layer_type=LayerType.OUTPUT, n_in=d,
                                      n_out=s["vocab"],
                                      layer_spec=HeadSpec(eps=eps)))
        elif kind == "moe":
            confs.append(base.replace(layer_type=LayerType.MOE,
                                      layer_spec=experts))
        else:
            confs.append(base.replace(layer_type=LayerType.GQA,
                                      layer_spec=attention(kind)))
    return MultiLayerConfiguration(confs=tuple(confs))


def to_program(weights: list) -> tuple:
    """Reference layout (a dict a layer) -> the program's tuple of dicts: an
    expert layer's gate and up side by side; the rest leaf for leaf."""
    out = []
    for w in weights:
        if "Wr" in w:
            out.append({"ln": w["ln"], "Wr": w["Wr"], "Wd": w["Wdown"],
                        "Wgu": jnp.concatenate([w["Wgate"], w["Wup"]], axis=-1)})
        else:                           # embed, attention, head
            out.append(dict(w))
    return tuple(out)


def from_program(params) -> list:
    """The program's layout -> the reference's, for norms leaf by leaf."""
    out = []
    for p in params:
        if "Wr" in p:
            gate, up = jnp.split(p["Wgu"], 2, axis=-1)
            out.append({"ln": p["ln"], "Wr": p["Wr"], "Wgate": gate, "Wup": up,
                        "Wdown": p["Wd"]})
        else:
            out.append(dict(p))
    return out
