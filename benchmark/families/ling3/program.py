"""The one place where the benchmark touches the system under test for this
family: a configuration file becomes an ordinary `MultiLayerConfiguration`
of the program's own layer types, and the harness's weights take the
program's layout.  Everything else the jobs need from the program they
import themselves, by its public names."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.families.ling3 import reference


def build_conf(cfg: dict):
    """Embedding, then per block an attention layer (`kda` or `mla`) and an
    FFN layer (`swiglu` or `moe`), then the head, each layer type with its
    own typed settings; parameters in `flags.param_dtype`, matrix products
    in `flags.compute_dtype`."""
    try:
        from deeplearning4j_tpu.nn.conf import (HeadSpec, KDASpec, LayerType,
                                                MLASpec, MoESpec,
                                                MultiLayerConfiguration,
                                                NeuralNetConfiguration,
                                                SwiGLUSpec)
    except ImportError as e:    # a commit from before the layer types
        raise SystemExit(f"this program cannot run model_type 'ling3': its "
                         f"nn/conf.py has no kda, mla, swiglu and moe layer "
                         f"types ({e})")

    s = reference.sizes(cfg)
    d, eps = s["d"], s["eps"]
    base = NeuralNetConfiguration(
        n_in=d, n_out=d, dtype=cfg["flags"]["param_dtype"],
        compute_dtype=cfg["flags"]["compute_dtype"], weight_init="normalized",
        loss_function="mcxent")
    specs = {
        "kda": (LayerType.KDA, KDASpec(
            n_heads=s["heads"], head_dim=s["kda_dim"], conv_kernel=s["conv"],
            gate_lower_bound=s["kda_lower_bound"], eps=eps)),
        "mla": (LayerType.MLA, MLASpec(
            n_heads=s["heads"], kv_lora_rank=s["kv_rank"],
            qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
            v_head_dim=s["v_dim"], rope_theta=s["rope_theta"], eps=eps)),
        "swiglu": (LayerType.SWIGLU, SwiGLUSpec(hidden=s["ffn"], eps=eps)),
        "moe": (LayerType.MOE, MoESpec(
            n_routed=s["experts_routed"], n_held=s["experts_held"],
            first_held=s["first_expert"], hidden=s["expert_ffn"],
            shared_hidden=s["shared_ffn"], top_k=s["top_k"],
            n_group=s["n_group"], topk_group=s["topk_group"],
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
    """Reference layout (a dict a layer) -> the program's tuple of dicts:
    q, k and v side by side (their convolutions too, a tap a row), beta and
    the output gate side by side, gate and up side by side."""
    out = []
    for w in weights:
        if "conv_q" in w:
            out.append({
                "ln": w["ln"],
                "Wqkv": jnp.concatenate([w["Wq"], w["Wk"], w["Wv"]], axis=1),
                "conv": jnp.concatenate(
                    [w["conv_q"], w["conv_k"], w["conv_v"]], axis=0).T,
                "Wa": w["Wa"], "A_log": w["A_log"], "dt_bias": w["dt_bias"],
                "Wbg": jnp.concatenate([w["Wb"], w["Wg"]], axis=1),
                "o_norm": w["o_norm"], "Wo": w["Wo"]})
        elif "Wr" in w:
            out.append({
                "ln": w["ln"], "Wr": w["Wr"], "rb": w["b"],
                "Wgu": jnp.concatenate([w["Wgate"], w["Wup"]], axis=-1),
                "Wd": w["Wdown"],
                "sWgu": jnp.concatenate([w["sWgate"], w["sWup"]], axis=-1),
                "sWd": w["sWdown"]})
        elif "Wgate" in w:
            out.append({"ln": w["ln"], "Wd": w["Wdown"],
                        "Wgu": jnp.concatenate([w["Wgate"], w["Wup"]], axis=-1)})
        else:                           # embed, mla, head: leaf for leaf
            out.append(dict(w))
    return tuple(out)


def from_program(params) -> list:
    """The program's layout -> the reference's, for norms leaf by leaf."""
    out = []
    for p in params:
        if "Wqkv" in p:
            wq, wk, wv = jnp.split(p["Wqkv"], 3, axis=1)
            cq, ck, cv = jnp.split(p["conv"].T, 3, axis=0)
            wb, wg = jnp.split(p["Wbg"], 2, axis=1)
            out.append({"ln": p["ln"], "Wq": wq, "Wk": wk, "Wv": wv,
                        "conv_q": cq, "conv_k": ck, "conv_v": cv,
                        "Wa": p["Wa"], "A_log": p["A_log"],
                        "dt_bias": p["dt_bias"], "Wb": wb, "Wg": wg,
                        "o_norm": p["o_norm"], "Wo": p["Wo"]})
        elif "Wr" in p:
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
