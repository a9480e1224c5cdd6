"""The one place where the benchmark touches the system under test for this
family: a configuration file becomes an ordinary `MultiLayerConfiguration`
of the program's own layer types, and the harness's weights take the
program's layout.  Everything else the jobs need from the program they
import themselves, by its public names."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.families.qwen3_next import reference

_GDN = ("ln", "A_log", "dt_bias", "o_norm", "Wo")


def build_conf(cfg: dict):
    """Embedding, then per block a mixer (`gdn`, or `gqa` with an output
    gate a element, qk-norm and rotary over part of a head) and `moe`
    (softmax over every published expert, top k, no groups and no bias,
    this rank's held experts, which serve the rows of every rank of the
    deployment's expert-parallel group, the shared expert behind a learned
    sigmoid gate), then the head; parameters in `flags.param_dtype`, matrix
    products in `flags.compute_dtype`."""
    try:
        from deeplearning4j_tpu.nn.conf import (GDNSpec, GQASpec, HeadSpec,
                                                LayerType, MoESpec,
                                                MultiLayerConfiguration,
                                                NeuralNetConfiguration)
    except ImportError as e:    # a commit from before the layer type
        raise SystemExit(f"this program cannot run model_type 'qwen3_next': "
                         f"its nn/conf.py has no gdn layer type ({e})")

    s = reference.sizes(cfg)
    d, eps = s["d"], s["eps"]
    base = NeuralNetConfiguration(
        n_in=d, n_out=d, dtype=cfg["flags"]["param_dtype"],
        compute_dtype=cfg["flags"]["compute_dtype"], weight_init="normalized",
        loss_function="mcxent")
    specs = {
        "gdn": (LayerType.GDN, GDNSpec(
            n_k_heads=s["k_heads"], n_v_heads=s["v_heads"], k_dim=s["k_dim"],
            v_dim=s["v_dim"], conv_kernel=s["conv"], eps=eps)),
        "attn": (LayerType.GQA, GQASpec(
            n_heads=s["heads"], n_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
            rope_theta=s["theta"], qk_norm=True, eps=eps,
            rotary_dim=s["rotary_dim"], out_gate=True)),
        "moe": (LayerType.MOE, MoESpec(
            n_routed=s["experts_routed"], n_held=s["experts_held"],
            first_held=s["first_expert"], hidden=s["expert_ffn"],
            shared_hidden=s["shared_ffn"], top_k=s["top_k"], eps=eps,
            score="softmax", router_bias=False, shared_gate=True,
            ranks=s["ranks"])),
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
    """Reference layout (a dict a layer) -> the program's tuple of dicts: a
    delta-rule layer's q, k, v and z side by side, b and a side by side,
    its convolution a tap a row; an expert's gate and up side by side, the
    shared expert's too; attention, the embedding and the head leaf for
    leaf."""
    out = []
    for w in weights:
        if "conv1d" in w:
            out.append({
                **{k: w[k] for k in _GDN},
                "Wqkvz": jnp.concatenate([w["Wq"], w["Wk"], w["Wv"], w["Wz"]], axis=1),
                "conv": w["conv1d"].T,
                "Wba": jnp.concatenate([w["Wb"], w["Wa"]], axis=1)})
        elif "Wr" in w:
            out.append({
                "ln": w["ln"], "Wr": w["Wr"], "Wsg": w["Wsg"],
                "Wgu": jnp.concatenate([w["Wgate"], w["Wup"]], axis=-1),
                "Wd": w["Wdown"],
                "sWgu": jnp.concatenate([w["sWgate"], w["sWup"]], axis=-1),
                "sWd": w["sWdown"]})
        else:                           # embed, attention, head: leaf for leaf
            out.append(dict(w))
    return tuple(out)


def from_program(params) -> list:
    """The program's layout -> the reference's, for norms leaf by leaf."""
    out = []
    for p in params:
        if "Wqkvz" in p:
            qk = p["conv"].shape[1] - p["Wo"].shape[0]      # q and k together
            wq, wk, wv, wz = jnp.split(p["Wqkvz"], [qk // 2, qk, p["conv"].shape[1]],
                                       axis=1)
            wb, wa = jnp.split(p["Wba"], 2, axis=1)
            out.append({**{k: p[k] for k in _GDN}, "Wq": wq, "Wk": wk, "Wv": wv,
                        "Wz": wz, "Wb": wb, "Wa": wa, "conv1d": p["conv"].T})
        elif "Wr" in p:
            gate, up = jnp.split(p["Wgu"], 2, axis=-1)
            sgate, sup = jnp.split(p["sWgu"], 2, axis=-1)
            out.append({"ln": p["ln"], "Wr": p["Wr"], "Wsg": p["Wsg"],
                        "Wgate": gate, "Wup": up, "Wdown": p["Wd"],
                        "sWgate": sgate, "sWup": sup, "sWdown": p["sWd"]})
        else:
            out.append(dict(p))
    return out
