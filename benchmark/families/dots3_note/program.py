"""The one place where the benchmark touches the system under test for this
family: a configuration file becomes an ordinary `MultiLayerConfiguration`
of the program's own layer types, and the harness's weights take the
program's layout.  Everything else the jobs need from the program they
import themselves, by its public names."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from benchmark.families.dots3_note import reference

#: what `MLASpec` has to have for this family's two geometries
_MLA_OPTIONS = ("q_lora_rank", "lora_rescale", "window", "gate",
                "index_n_heads", "index_head_dim", "index_topk")


def build_conf(cfg: dict):
    """Embedding, then per block an attention layer (`mla`, in the full
    geometry with its indexer or in the window's) and an FFN layer (`swiglu`
    or `moe`: sigmoid scores, one group, a bias in the choice, one shared
    expert, the rank's experts held), then the head; parameters in
    `flags.param_dtype`, matrix products in `flags.compute_dtype`."""
    try:
        from deeplearning4j_tpu.nn.conf import (HeadSpec, LayerType, MLASpec,
                                                MoESpec,
                                                MultiLayerConfiguration,
                                                NeuralNetConfiguration,
                                                SwiGLUSpec)
    except ImportError as e:    # a commit from before the layer types
        raise SystemExit(f"this program cannot run model_type 'dots3_note': "
                         f"its nn/conf.py has no mla, swiglu and moe layer "
                         f"types ({e})")
    missing = sorted(set(_MLA_OPTIONS)
                     - {f.name for f in dataclasses.fields(MLASpec)})
    if missing:                 # a commit from before the options
        raise SystemExit(f"this program cannot run model_type 'dots3_note': "
                         f"its MLASpec has no fields {missing}")

    s = reference.sizes(cfg)
    d, eps = s["d"], s["eps"]
    base = NeuralNetConfiguration(
        n_in=d, n_out=d, dtype=cfg["flags"]["param_dtype"],
        compute_dtype=cfg["flags"]["compute_dtype"], weight_init="normalized",
        loss_function="mcxent")

    def attention(kind: str):
        g = s[kind]
        indexer = ({"index_n_heads": s["index_heads"],
                    "index_head_dim": s["index_dim"],
                    "index_topk": s["index_topk"]} if kind == "full" else
                   {"window": g["span"]})
        return MLASpec(
            n_heads=g["heads"], kv_lora_rank=g["kv_rank"],
            qk_nope_head_dim=g["nope"], qk_rope_head_dim=g["rope"],
            v_head_dim=g["v_dim"], rope_theta=g["theta"], eps=eps,
            q_lora_rank=g["q_rank"], lora_rescale=s["rescale"], gate=True,
            **indexer)

    specs = {
        "full": (LayerType.MLA, attention("full")),
        "window": (LayerType.MLA, attention("window")),
        "swiglu": (LayerType.SWIGLU, SwiGLUSpec(hidden=s["ffn"], eps=eps)),
        "moe": (LayerType.MOE, MoESpec(
            n_routed=s["experts_routed"], n_held=s["experts_held"],
            first_held=s["first_expert"], hidden=s["expert_ffn"],
            shared_hidden=s["shared_ffn"], top_k=s["top_k"],
            routed_scaling=s["routed_scaling"], eps=eps)),
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
    FFN's gate and up side by side, the router's bias under the program's
    name; embedding, attention and head leaf for leaf."""
    out = []
    for w in weights:
        if "Wr" in w:
            out.append({
                "ln": w["ln"], "Wr": w["Wr"], "rb": w["b"],
                "Wgu": jnp.concatenate([w["Wgate"], w["Wup"]], axis=-1),
                "Wd": w["Wdown"],
                "sWgu": jnp.concatenate([w["sWgate"], w["sWup"]], axis=-1),
                "sWd": w["sWdown"]})
        elif "Wgate" in w:
            out.append({"ln": w["ln"], "Wd": w["Wdown"],
                        "Wgu": jnp.concatenate([w["Wgate"], w["Wup"]], axis=-1)})
        else:
            out.append(dict(w))
    return tuple(out)


def from_program(params) -> list:
    """The program's layout -> the reference's, for norms leaf by leaf."""
    out = []
    for p in params:
        if "Wr" in p:
            gate, up = jnp.split(p["Wgu"], 2, axis=-1)
            sgate, sup = jnp.split(p["sWgu"], 2, axis=-1)
            out.append({"ln": p["ln"], "Wr": p["Wr"], "b": p["rb"],
                        "Wgate": gate, "Wup": up, "Wdown": p["Wd"],
                        "sWgate": sgate, "sWup": sup, "sWdown": p["sWd"]})
        elif "Wgu" in p:
            gate, up = jnp.split(p["Wgu"], 2, axis=-1)
            out.append({"ln": p["ln"], "Wgate": gate, "Wup": up,
                        "Wdown": p["Wd"]})
        else:
            out.append(dict(p))
    return out
