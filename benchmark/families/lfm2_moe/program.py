"""The one place where the benchmark touches the system under test for this
family: a configuration file becomes an ordinary `MultiLayerConfiguration`
of the program's own layer types, and the harness's weights take the
program's layout.  Everything else the jobs need from the program they
import themselves, by its public names."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.families.lfm2_moe import reference


def build_conf(cfg: dict):
    """Embedding, then per decoder layer its mixer (`shortconv`, or `gqa`
    over every earlier position) and its FFN (`swiglu` for the leading dense
    layers, else `moe`: sigmoid scores, one group, a bias in the choice, no
    shared expert, every expert held), then the head; parameters in
    `flags.param_dtype`, matrix products in `flags.compute_dtype`."""
    try:
        from deeplearning4j_tpu.nn.conf import (GQASpec, HeadSpec, LayerType,
                                                MoESpec,
                                                MultiLayerConfiguration,
                                                NeuralNetConfiguration,
                                                ShortConvSpec, SwiGLUSpec)
    except ImportError as e:    # a commit from before the layer type
        raise SystemExit(f"this program cannot run model_type 'lfm2_moe': its "
                         f"nn/conf.py has no short convolution ({e})")

    s = reference.sizes(cfg)
    d, eps = s["d"], s["eps"]
    base = NeuralNetConfiguration(
        n_in=d, n_out=d, dtype=cfg["flags"]["param_dtype"],
        compute_dtype=cfg["flags"]["compute_dtype"], weight_init="normalized",
        loss_function="mcxent")
    specs = {
        "conv": (LayerType.SHORT_CONV, ShortConvSpec(kernel=s["conv_k"], eps=eps)),
        "attn": (LayerType.GQA, GQASpec(
            n_heads=s["heads"], n_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
            rope_theta=s["theta"], qk_norm=True, eps=eps)),
        "dense": (LayerType.SWIGLU, SwiGLUSpec(hidden=s["ffn"], eps=eps)),
        "moe": (LayerType.MOE, MoESpec(
            n_routed=s["experts_held"], n_held=s["experts_held"], hidden=s["expert_ffn"],
            shared_hidden=0, top_k=s["top_k"], routed_scaling=s["scaling"],
            eps=eps, score="sigmoid", router_bias=True)),
    }
    confs = []
    for kind in reference.layer_kinds(cfg):
        if kind == "embed":
            confs.append(base.replace(layer_type=LayerType.EMBEDDING,
                                      n_in=s["vocab"], n_out=d))
        elif kind == "head":
            confs.append(base.replace(layer_type=LayerType.OUTPUT, n_in=d,
                                      n_out=s["vocab"],
                                      layer_spec=HeadSpec(eps=eps)))
        else:
            layer_type, spec = specs[kind]
            confs.append(base.replace(layer_type=layer_type, layer_spec=spec))
    return MultiLayerConfiguration(confs=tuple(confs))


def to_program(weights: list) -> tuple:
    """Reference layout (a dict a layer) -> the program's tuple of dicts: an
    FFN's gate and up side by side; the rest leaf for leaf."""
    out = []
    for w in weights:
        if "Wgate" in w:
            gu = {"Wgu": jnp.concatenate([w["Wgate"], w["Wup"]], axis=-1),
                  "Wd": w["Wdown"]}
            out.append({**{k: v for k, v in w.items() if k not in _FFN}, **gu})
        else:                           # embed, mixers, head
            out.append(dict(w))
    return tuple(out)


_FFN = ("Wgate", "Wup", "Wdown")


def from_program(params) -> list:
    """The program's layout -> the reference's, for norms leaf by leaf."""
    out = []
    for p in params:
        if "Wgu" in p:
            gate, up = jnp.split(p["Wgu"], 2, axis=-1)
            rest = {k: v for k, v in p.items() if k not in ("Wgu", "Wd")}
            out.append({**rest, "Wgate": gate, "Wup": up, "Wdown": p["Wd"]})
        else:
            out.append(dict(p))
    return out
