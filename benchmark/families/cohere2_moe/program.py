"""The one place where the benchmark touches the system under test for this
family: a configuration file becomes an ordinary `MultiLayerConfiguration`
of the program's own layer types, and the harness's weights take the
program's layout.  Everything else the jobs need from the program they
import themselves, by its public names."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.families.cohere2_moe import reference

_ATTN = ("Wq", "Wk", "Wv", "Wo")


def build_conf(cfg: dict):
    """Embedding, then a `parallel` block a decoder layer (one LayerNorm,
    then `gqa` over a window with interleaved rotary or over every earlier
    position with none, beside `moe`: sigmoid scores, one group, no bias,
    this rank's held experts, which serve the rows of every rank of the
    deployment's expert-parallel group, the shared experts as one SwiGLU of
    their widths summed and scaled by one over their number), then the head: a
    LayerNorm and the embedding's table, tied.  Parameters in
    `flags.param_dtype`, matrix products in `flags.compute_dtype`."""
    try:
        from deeplearning4j_tpu.nn.conf import (GQASpec, HeadSpec, LayerType,
                                                MoESpec,
                                                MultiLayerConfiguration,
                                                NeuralNetConfiguration,
                                                ParallelSpec)
    except ImportError as e:    # a commit from before the layer type
        raise SystemExit(f"this program cannot run model_type 'cohere2_moe': "
                         f"its nn/conf.py has no parallel block ({e})")

    s = reference.sizes(cfg)
    if s["logit_scale"] != 1.0:
        raise ValueError("a logit scale other than 1 is not written down here")
    d, eps = s["d"], s["eps"]
    base = NeuralNetConfiguration(
        n_in=d, n_out=d, dtype=cfg["flags"]["param_dtype"],
        compute_dtype=cfg["flags"]["compute_dtype"], weight_init="normalized",
        loss_function="mcxent")
    ffn = MoESpec(
        n_routed=s["experts_routed"], n_held=s["experts_held"], hidden=s["expert_ffn"],
        shared_hidden=s["shared"] * s["expert_ffn"], first_held=s["first_expert"],
        top_k=s["top_k"], eps=eps, score="sigmoid", router_bias=False,
        shared_scale=1.0 / s["shared"],
        ranks=int(cfg.get("deployment", {}).get("chips_per_layer", 1)))
    attn = {"window": dict(window=s["window"], rotary="pairs"),
            "full": dict(window=0, rotary="none")}
    confs = []
    for kind in reference.layer_kinds(cfg):
        if kind == "embed":
            confs.append(base.replace(layer_type=LayerType.EMBEDDING,
                                      n_in=s["vocab"], n_out=d))
        elif kind == "head":
            confs.append(base.replace(layer_type=LayerType.OUTPUT, n_in=d,
                                      n_out=s["vocab"], layer_spec=HeadSpec(
                                          eps=eps, norm="layer", tied=True)))
        else:
            confs.append(base.replace(layer_type=LayerType.PARALLEL, layer_spec=ParallelSpec(
                attn=GQASpec(n_heads=s["heads"], n_kv_heads=s["kv_heads"],
                             head_dim=s["head_dim"], rope_theta=s["theta"], eps=eps,
                             **attn[kind]),
                ffn=ffn, eps=eps)))
    return MultiLayerConfiguration(confs=tuple(confs))


def _side_by_side(stacked):
    """[S, a, b] -> [a, S b]: S matrices' columns one after the other."""
    s, a, b = stacked.shape
    return jnp.moveaxis(stacked, 0, 1).reshape(a, s * b)


def to_program(weights: list) -> tuple:
    """Reference layout (a dict a layer) -> the program's tuple of dicts: a
    block's norm, its attention's matrices and its experts' (an expert's gate
    and up side by side; the shared experts' gates side by side, then their
    ups, and their downs one under the other: one SwiGLU of their widths
    summed); the embedding and the head's norm leaf for leaf."""
    out = []
    for w in weights:
        if "Wq" not in w:
            out.append(dict(w))
            continue
        ffn = {"Wr": w["Wr"],
               "Wgu": jnp.concatenate([w["Wgate"], w["Wup"]], axis=-1), "Wd": w["Wdown"],
               "sWgu": jnp.concatenate([_side_by_side(w["sWgate"]),
                                        _side_by_side(w["sWup"])], axis=-1),
               "sWd": w["sWdown"].reshape(-1, w["sWdown"].shape[-1])}
        out.append({"ln": w["ln"], "attn": {k: w[k] for k in _ATTN}, "ffn": ffn})
    return tuple(out)


def from_program(params) -> list:
    """The program's layout -> the reference's, for norms leaf by leaf."""
    out = []
    for p in params:
        if "attn" not in p:
            out.append(dict(p))
            continue
        f = p["ffn"]
        width = f["Wd"].shape[1]                # one expert's
        gate, up = jnp.split(f["Wgu"], 2, axis=-1)
        sgate, sup = jnp.split(f["sWgu"], 2, axis=-1)
        shared = sgate.shape[-1] // width

        def stacked(m):
            return jnp.moveaxis(m.reshape(m.shape[0], shared, width), 1, 0)

        out.append({"ln": p["ln"], **p["attn"], "Wr": f["Wr"],
                    "Wgate": gate, "Wup": up, "Wdown": f["Wd"],
                    "sWgate": stacked(sgate), "sWup": stacked(sup),
                    "sWdown": f["sWd"].reshape(shared, width, -1)})
    return out
