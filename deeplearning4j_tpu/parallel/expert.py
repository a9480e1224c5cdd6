"""Expert parallelism: Switch-style top-1 mixture-of-experts FFN.

Not the supported expert layer: that is `nn/layers/experts.MoELayer`
(LayerType.MOE), a layer type of the conf that is told which experts it
holds.  This side module waits for the `simplicity` PR that folds or removes
it (ROADMAP.md, Design 6).

New-scope capability (no MoE anywhere in the 2015 reference — SURVEY.md §2
parallelism census lists EP as absent): the TPU-native expert-parallel
design.  Experts are sharded over an `ep` mesh axis; tokens are routed
top-1, packed into per-expert capacity buckets with one-hot einsums (dense,
MXU-friendly — no dynamic shapes), exchanged with `lax.all_to_all` over ICI,
transformed by the locally-resident experts, and combined back gated by the
router probability.  Over-capacity tokens fall through on the residual path
(standard Switch behavior).

`moe_ffn_dense` is the single-device reference with identical routing
semantics; the EP version must match it whenever capacity is ample, which is
exactly what the tests assert on the virtual 8-device mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.sequence import _shard_map
from deeplearning4j_tpu.reliability import faults


def init_moe_params(key, d_model: int, d_hidden: int, n_experts: int,
                    dtype=jnp.float32):
    kr, k1, k2 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(jnp.asarray(d_model, jnp.float32))
    s2 = 1.0 / jnp.sqrt(jnp.asarray(d_hidden, jnp.float32))
    return {
        "router": (jax.random.normal(kr, (d_model, n_experts), dtype) * s1),
        "W1": jax.random.normal(k1, (n_experts, d_model, d_hidden),
                                dtype) * s1,
        "b1": jnp.zeros((n_experts, d_hidden), dtype),
        "W2": jax.random.normal(k2, (n_experts, d_hidden, d_model),
                                dtype) * s2,
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def _route(params, x, capacity: int):
    """Top-1 routing with capacity buckets.

    x: [T, d].  Returns (dispatch [T, E, C] one-hot, combine [T, E, C]
    gate-weighted, (frac [E], mean_prob [E]) aux-loss statistics — feed
    them to `_aux_loss`, pmean-ing across shards first when sharded).
    """
    t, _ = x.shape
    e = params["router"].shape[1]
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)             # [T, E]
    expert = jnp.argmax(probs, axis=-1)                 # [T]
    onehot = jax.nn.one_hot(expert, e, dtype=x.dtype)   # [T, E]
    gate = jnp.sum(probs * onehot, axis=-1)             # [T]
    # position of each token within its expert's bucket (0-based); the
    # onehot factor zeroes non-assigned experts' contributions
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # [T, E]
    pos_tok = jnp.sum(pos, axis=-1)                     # [T]
    keep = pos_tok < capacity
    pos_oh = jax.nn.one_hot(pos_tok, capacity, dtype=x.dtype)  # [T, C]
    dispatch = (onehot[:, :, None] * pos_oh[:, None, :]
                * keep[:, None, None].astype(x.dtype))  # [T, E, C]
    combine = dispatch * gate[:, None, None]
    # Raw per-expert statistics for the Switch load-balancing aux loss
    # E * sum_e fraction_e * mean-prob_e.  Returned unreduced so the
    # expert-parallel caller can pmean frac/mean_prob across shards FIRST
    # and only then take the product: per-shard frac and mean_prob are
    # correlated, so mean-of-products != product-of-global-means.
    frac = jnp.mean(onehot, axis=0)                     # [E]
    mean_prob = jnp.mean(probs, axis=0)                 # [E]
    return dispatch, combine, (frac, mean_prob)


def _aux_loss(frac, mean_prob):
    e = frac.shape[0]
    return e * jnp.sum(frac * mean_prob)


def _expert_apply(w1, b1, w2, b2, xs):
    """xs: [E, G, C, d] token buckets (G = sender groups)."""
    h = jax.nn.gelu(jnp.einsum("egcd,edh->egch", xs, w1)
                    + b1[:, None, None, :])
    return jnp.einsum("egch,ehd->egcd", h, w2) + b2[:, None, None, :]


def moe_ffn_dense(params, x, capacity_factor: float = 2.0):
    """Single-device reference MoE: identical routing, all experts local.

    x: [T, d] -> ([T, d], aux_loss).
    """
    t, d = x.shape
    e = params["router"].shape[1]
    capacity = max(1, int(capacity_factor * t / e))
    dispatch, combine, (frac, mean_prob) = _route(params, x, capacity)
    aux = _aux_loss(frac, mean_prob)
    xs = jnp.einsum("tec,td->ecd", dispatch, x)          # [E, C, d]
    ys = _expert_apply(params["W1"], params["b1"], params["W2"],
                       params["b2"], xs[:, None])[:, 0]  # [E, C, d]
    y = jnp.einsum("tec,ecd->td", combine, ys)
    # over-capacity (and all-zero-dispatch) tokens ride the residual
    return x + y, aux


def moe_ffn(params, x, mesh: Optional[Mesh] = None, axis: str = "ep",
            capacity_factor: float = 2.0, plan=None):
    """Expert-parallel MoE: tokens sharded over `axis`, experts too.

    x: [T, d] with T divisible by the axis size; n_experts divisible by the
    axis size.  Returns ([T, d], aux_loss averaged over shards).
    mesh=None derives the mesh from `plan` (a `parallel.plan.ShardPlan`)
    or from every platform device (`pipeline.resolve_stage_mesh`).
    """
    from deeplearning4j_tpu.parallel.pipeline import resolve_stage_mesh

    mesh = resolve_stage_mesh(mesh, plan, axis)
    n = mesh.shape[axis]
    # host-side fault point, fired at dispatch-build (trace) time
    faults.fire("expert.dispatch", axis=axis, shards=int(n))
    e = params["router"].shape[1]
    if e % n:
        raise ValueError(f"n_experts={e} not divisible by {axis}={n}")
    t = x.shape[0]
    if t % n:
        raise ValueError(f"tokens={t} not divisible by {axis}={n}")
    e_loc = e // n
    capacity = max(1, int(capacity_factor * (t // n) / e))

    def local(router, w1, b1, w2, b2, xs):
        dispatch, combine, (frac, mean_prob) = _route(
            {"router": router}, xs, capacity)
        buckets = jnp.einsum("tec,td->ecd", dispatch, xs)    # [E, C, d]
        buckets = buckets.reshape(n, e_loc, capacity, -1)
        # send each peer its experts' buckets; receive [e_loc, n, C, d]
        recv = lax.all_to_all(buckets, axis, split_axis=0, concat_axis=1,
                              tiled=False)
        # w1/b1/w2/b2 arrive already sharded: this device's e_loc experts
        ys = _expert_apply(w1, b1, w2, b2, recv)
        # route results back to the owning token shards: [n, e_loc, C, d]
        back = lax.all_to_all(ys, axis, split_axis=1, concat_axis=0,
                              tiled=False)
        back = back.reshape(e, capacity, -1)
        y = jnp.einsum("tec,ecd->td", combine, back)
        # Globalize the routing statistics BEFORE the product: with equal
        # shard sizes pmean(frac) / pmean(mean_prob) are exactly the dense
        # global statistics, so the aux loss (and its router gradients)
        # match moe_ffn_dense bit-for-bit in expectation.
        frac_g = lax.pmean(frac, axis)
        mean_prob_g = lax.pmean(mean_prob, axis)
        return xs + y, _aux_loss(frac_g, mean_prob_g)

    out = _shard_map(
        local, mesh,
        (P(), P(axis), P(axis), P(axis), P(axis), P(axis)),
        (P(axis), P()),
    )(params["router"], params["W1"], params["b1"], params["W2"],
      params["b2"], x)
    return out
