"""The gated FFNs: `SwiGLULayer` (LayerType.SWIGLU, `SwiGLUSpec`) and
`MoELayer` (LayerType.MOE, `MoESpec`), both residual over [.., n_in] under
an RMSNorm.

The expert layer is told which experts it holds.  Its router scores all
`n_routed` published experts in float32 (`spec.score`: a sigmoid each, or a
softmax over all of them), picks `top_k` of them a token from the best
`topk_group` of `n_group` groups (a group scores the sum of its two best;
with one group it is a plain top-k; a learned bias, where the spec has one,
enters the choice and not the weights), and weighs the picks by their
scores, normalised over all `top_k` and scaled.  The layer then computes the
picks that landed on its own `n_held` experts, `[first_held, first_held +
n_held)`, adds its shared expert where it has one (scaled by a constant, and
where the spec says so by a learned sigmoid gate a token), and leaves out
what the absent experts would add: on one chip of an expert-parallel group
this is that chip's part of the layer, without the exchange.

Dropless: every pick of a held expert is computed, at 1 row or at 1024;
there is no capacity and no token is dropped.  The product has two forms,
and `experts_form` chooses between them from the call's static shapes and
the ranks whose rows reach the held experts (`MoESpec.ranks`).
Sorted: the picks are sorted by expert and computed by `jax.lax.ragged_dot`
over the sorted rows; the picks of absent experts sort to the end and fall
outside every group.  Where this layer holds a fraction of the experts, its
picks usually fit a fraction of the rows: when they fit the first 3/8 the
products run over those alone, otherwise over all of them (`jax.lax.cond`;
the result is the same either way); a layer that holds every expert has
nothing to save there and builds no such choice.  Batched: every held expert
over all the rows as one batched product, the picks selected after it by a
dense matrix of the routing weights; taken where a call of so many rows is
expected to hit nearly every expert anyway and the rows are few (a decode
step of 64 rows over 64 experts of which a row picks 8), because the chip's
grouped kernel charges every group a tile of 512 rows there.  The scope is
`experts` for the sorted form and `experts_batched` for the other.

Neither keeps a decode state (`base.StatelessDecode`).  `MoELayer.apply`
also returns two counts of the call, `[picks that landed on held experts,
distinct held experts hit]`; `MoELayer.counted_step` is the decode step that
keeps them (`nn.decode.has_experts`), for the programs to hand the batcher.
`MoELayer.branch` is the expert layer from a normed input to its output,
with no norm and no residual: what a block that norms once for two branches
(`parallel.py`) calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import StatelessDecode, compute_dtype
from deeplearning4j_tpu.nn.layers.rms import (F32, initializer, pre_norm,
                                              swiglu)
from deeplearning4j_tpu.utils.profiling import scope


class SwiGLULayer(StatelessDecode):
    OPERANDS = ("Wgu", "Wd")

    @staticmethod
    def init(key, conf):
        s = conf.layer_spec
        d, n = jnp.dtype(conf.dtype), conf.n_in
        k1, k2 = jax.random.split(key)
        w = initializer(conf)
        return {"ln": jnp.ones((n,), d), "Wgu": w(k1, (n, 2 * s.hidden)),
                "Wd": w(k2, (s.hidden, n))}

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        u = pre_norm(params, x, conf.layer_spec.eps)
        with scope("ffn"):
            out = swiglu(u, params["Wgu"], params["Wd"], compute_dtype(conf))
        return x.astype(F32) + out


def route(scores, bias, spec):
    """scores [R, n_routed] float32 -> (ids [R, top_k] int32, weights
    [R, top_k] float32): group-limited top-k on `scores + bias` (`bias`
    None: on the scores), weights from `scores` alone."""
    r, n = scores.shape
    sp = scores if bias is None else scores + bias
    if spec.n_group > 1:
        sp = sp.reshape(r, spec.n_group, n // spec.n_group)
        group = jnp.sum(jax.lax.top_k(sp, 2)[0], axis=-1)
        kept = jax.lax.top_k(group, spec.topk_group)[1]
        mask = jnp.sum(jax.nn.one_hot(kept, spec.n_group, dtype=jnp.int32),
                       axis=1) > 0
        sp = jnp.where(mask[..., None], sp, -jnp.inf).reshape(r, n)
    ids = jax.lax.top_k(sp, spec.top_k)[1]
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    weights = spec.routed_scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), weights


#: The rule's two constants, from one sweep of both forms alone on a TPU v5e
#: (PERF.md 6, PR 33; ms of device time, sorted / batched, bfloat16).
#: `HIT_SHARE_FLOOR`: the batched form reads every held expert, the sorted one
#: those that were hit.  128 held of 512 routed x [2560, 2 x 768], top 8: 64 rows
#: (expected hit share 63.5 %) 1.72 / 2.01, 96 rows (77.9 %) 1.88 / 2.01, 128
#: rows (86.7 %) 2.38 / 2.02: the forms cross between 0.78 and 0.87, and from
#: 0.9 on the batched form reads next to nothing that the sorted one would skip.
#: `BATCHED_ROWS_MOST`: the batched form computes every row against every held
#: expert, 2 x rows FLOPs a weight of 2 bytes.  64 of 64 x [2304, 2 x 896]
#: (793 MB, 0.97 ms at the memory's 819 GB/s): 8 to 128 rows 1.05 to 1.07 (the
#: FLOPs hide behind the read of the weights), 192 rows 1.16, 256 1.30, 512 2.88;
#: the sorted form 2.18 at 8 rows, 4.22 at 64, 4.86 at 512.
HIT_SHARE_FLOOR = 0.9
BATCHED_ROWS_MOST = 128


def experts_form(spec, rows: int) -> str:
    """`"batched"` or `"sorted"`: the form of the held experts' product for a
    call of `rows` rows, from static shapes alone.  Batched where a call of
    so many rows is expected to hit nearly every expert anyway (uniform
    picks: an expert is missed by all rows with probability (1 - top_k /
    n_routed) ** rows) and the rows are few enough for the batched FLOPs to
    hide behind the read of the weights.  Where the layer is one chip of an
    expert-parallel group (`spec.ranks`), its held experts serve every
    rank's rows, so the hit share counts `rows x ranks` of them."""
    hit_share = 1.0 - (1.0 - spec.top_k / spec.n_routed) ** (rows * spec.ranks)
    if hit_share >= HIT_SHARE_FLOOR and rows <= BATCHED_ROWS_MOST:
        return "batched"
    return "sorted"


def _sorted_experts(params, spec, cd, u, ids, weights):
    """The sorted form: the picks sorted by expert, `jax.lax.ragged_dot`
    over the sorted rows."""
    r, n = u.shape
    picks = r * spec.top_k
    with scope("dispatch"):
        local = ids.reshape(picks) - spec.first_held
        held = (local >= 0) & (local < spec.n_held)
        group = jnp.where(held, local, spec.n_held)         # the absent sort last
        order = jnp.argsort(group, stable=True)
        place = jnp.zeros((picks,), jnp.int32).at[order].set(
            jnp.arange(picks, dtype=jnp.int32))             # a pick's sorted row
        sizes = jnp.zeros((spec.n_held + 1,), jnp.int32).at[group].add(1)[:-1]
        here = jnp.sum(sizes)

    def over(m: int):
        """The products over the first `m` sorted rows, back in pick order."""
        with scope("dispatch"):
            rows = u[order[:m] // spec.top_k].astype(cd)
        with scope("experts"):
            h = jax.lax.ragged_dot(rows, params["Wgu"].astype(cd), sizes,
                                   preferred_element_type=F32)
            f = h.shape[-1] // 2
            a = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(cd)
            y = jax.lax.ragged_dot(a, params["Wd"].astype(cd), sizes,
                                   preferred_element_type=F32)
        with scope("combine"):
            # rows outside every group are not defined: select, do not scale
            mine = jnp.where((held & (place < m))[:, None],
                             y[jnp.minimum(place, m - 1)], 0.0)
            return jnp.sum(mine.reshape(r, spec.top_k, n)
                           * weights[..., None], axis=1)

    few = (3 * picks // 8) // 8 * 8
    if few >= 64 and spec.n_held < spec.n_routed:
        y = jax.lax.cond(here <= few, lambda: over(few), lambda: over(picks))
    else:
        y = over(picks)
    return y, jnp.stack([here, jnp.sum(sizes > 0)]).astype(jnp.int32)


def _batched_experts(params, spec, cd, u, ids, weights):
    """The batched form: every held expert over all the rows as one batched
    product, the picks selected after it by a dense [R, n_held] matrix of
    the routing weights."""
    with scope("dispatch"):
        # [R, top_k, n_held]: a pick of an absent expert matches no column
        at = (ids - spec.first_held)[..., None] == jnp.arange(
            spec.n_held, dtype=ids.dtype)
        picked = jnp.any(at, axis=1)                        # [R, n_held]
        dense = jnp.sum(jnp.where(at, weights[..., None], 0.0), axis=1)
    with scope("experts_batched"):
        h = jnp.einsum("rn,enf->erf", u.astype(cd), params["Wgu"].astype(cd),
                       preferred_element_type=F32)
        f = h.shape[-1] // 2
        a = (jax.nn.silu(h[..., :f]) * h[..., f:]).astype(cd)
        y = jnp.einsum("erf,efn->ern", a, params["Wd"].astype(cd),
                       preferred_element_type=F32)
    with scope("combine"):
        # an unpicked expert's output is dropped, not multiplied by 0
        y = jnp.sum(jnp.where(picked.T[..., None], y * dense.T[..., None], 0.0),
                    axis=0)
    counts = jnp.stack([jnp.sum(picked), jnp.sum(jnp.any(picked, axis=0))])
    return y, counts.astype(jnp.int32)


def held_experts(params, spec, cd, u, ids, weights):
    """What the held experts give for rows u [R, n]: (y [R, n] float32,
    counts [2] int32).  Every pick of a held expert is computed, in the
    form that `experts_form` gives for this many rows."""
    if experts_form(spec, u.shape[0]) == "batched":
        return _batched_experts(params, spec, cd, u, ids, weights)
    return _sorted_experts(params, spec, cd, u, ids, weights)


#: `MoESpec.score` -> what turns the router's logits into scores
_SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}


class MoELayer(StatelessDecode):
    #: the experts' and the shared expert's matrices; the router's `Wr` and
    #: the shared expert's gate `Wsg` are multiplied in float32
    OPERANDS = ("Wgu", "Wd", "sWgu", "sWd")

    @staticmethod
    def init(key, conf):
        s = conf.layer_spec
        d, n = jnp.dtype(conf.dtype), conf.n_in
        ks = jax.random.split(key, 6 if s.shared_gate else 5)
        w = initializer(conf)
        out = {"ln": jnp.ones((n,), d),
               "Wr": w(ks[0], (n, s.n_routed)),
               "Wgu": w(ks[1], (s.n_held, n, 2 * s.hidden)),
               "Wd": w(ks[2], (s.n_held, s.hidden, n))}
        if s.router_bias:
            out["rb"] = jnp.zeros((s.n_routed,), d)
        if s.shared_hidden:
            out.update(sWgu=w(ks[3], (n, 2 * s.shared_hidden)),
                       sWd=w(ks[4], (s.shared_hidden, n)))
        if s.shared_gate:
            out["Wsg"] = w(ks[5], (n, 1))
        return out

    @staticmethod
    def apply(params, conf, x):
        """x [.., n] -> (hidden [.., n], counts [2] int32)."""
        u = pre_norm(params, x, conf.layer_spec.eps)
        y, counts = MoELayer.branch(params, conf, u)
        return x.astype(F32) + y, counts

    @staticmethod
    def branch(params, conf, u):
        """The normed input u [.., n] -> (the experts' output [.., n]
        float32, counts [2] int32), no residual."""
        s, cd = conf.layer_spec, compute_dtype(conf)
        rows = u.reshape(-1, u.shape[-1])
        with scope("router"):
            scores = _SCORES[s.score](jnp.matmul(
                rows, params["Wr"].astype(F32),
                precision=jax.lax.Precision.HIGHEST))
            ids, weights = route(
                scores, params["rb"].astype(F32) if s.router_bias else None, s)
        y, counts = held_experts(params, s, cd, rows, ids, weights)
        if s.shared_hidden:
            with scope("shared"):
                shared = swiglu(rows, params["sWgu"], params["sWd"], cd)
                if s.shared_scale != 1.0:
                    shared = shared * s.shared_scale
                if s.shared_gate:
                    shared = shared * jax.nn.sigmoid(jnp.matmul(
                        rows, params["Wsg"].astype(F32),
                        precision=jax.lax.Precision.HIGHEST))
                y = y + shared
        return y.reshape(u.shape), counts

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        return MoELayer.apply(params, conf, x)[0]

    @staticmethod
    def product_form(conf, rows: int) -> str:
        """The form of the experts' product in a call of `rows` rows."""
        return experts_form(conf.layer_spec, rows)

    @staticmethod
    def counted_step(params, conf, x, state, pos, page_table=None):
        """`decode_step` with the counts of the call: (hidden, state, counts)."""
        x, counts = MoELayer.apply(params, conf, x)
        return x, state, counts
