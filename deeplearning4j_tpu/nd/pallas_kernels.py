"""Pallas TPU kernels for the framework's hot ops.

The reference delegates its hot loops to external native BLAS (SURVEY.md §2
row 1: ND4J jblas/jcublas — e.g. LSTM gates `LSTM.java:161-228`, word2vec
`InMemoryLookupTable.iterateSample` BLAS dot/axpy at :198-260).  Here the
equivalent native layer is XLA plus these hand-written Pallas kernels for the
ops where fusion control matters:

- `flash_attention`     — tiled online-softmax attention entirely in VMEM
                          (one pass over KV per Q tile; no [S,S] matrix in HBM).
- `fused_lstm_step`     — one LSTM cell update: both matmuls on the MXU plus
                          all gate nonlinearities and the state update fused
                          into a single kernel (one HBM round-trip).
- `scatter_add_rows`    — embedding-row scatter-add (the word2vec/GloVe
                          update) using scalar-prefetch block indexing, the
                          TPU replacement for HogWild row axpy.

Every entry point auto-falls back to interpreter mode off-TPU so the same
code path is exercised by the CPU test suite (`interpret=None` -> detect).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nd.attention import blockwise_attention
from deeplearning4j_tpu.nd.platform import is_tpu

_NEG_BIG = -1e30

#: fast-memory (VMEM) scope one kernel may allocate on a v5e before the
#: compiler refuses it ("Scoped allocation ... limit 16.00M")
VMEM_LIMIT_BYTES = 16 << 20


def _interpret(flag: Optional[bool]) -> bool:
    if flag is not None:
        return flag
    # off the chip the kernels run interpreted, which is how the CPU tests
    # reach them; the CLI's JSON names the platform, so such a run cannot
    # pass for a chip run.  Cached: this runs at every kernel call site.
    return not is_tpu()


def _block_table():
    """The (seq, head_dim) -> (fwd_q, fwd_k, bwd_q, bwd_k) defaults —
    kept in the tunables registry
    (`optimize.tunables.ATTENTION_BLOCK_TABLE`; provenance: one v5e run of
    2026-07-29, not reproduced); lazy-imported because the kernel layer
    sits below optimize/ in the import graph."""
    from deeplearning4j_tpu.optimize import tunables

    return tunables.ATTENTION_BLOCK_TABLE


def pick_attention_blocks(seq: int, head_dim: int, bwd: bool = False) -> tuple:
    """(block_q, block_k) for `flash_attention` at this (S, head_dim).

    Resolution order: tuned-table override (`optimize.tunables.resolve`,
    qualified per "{seq}x{head_dim}" — installed by `cli tune` for this
    device kind) -> the measured default table -> largest power-of-two
    blocks that divide S (the kernels require S % block == 0; ragged S
    falls back to `blockwise_attention` anyway), capped at 256/512 to
    stay inside VMEM with f32 scores tiles.  `bwd=True` returns the
    backward kernels' sizes, capped one notch lower (128/256) because the
    dK/dV and dQ kernels hold two [block_q, block_k] f32 intermediates
    (p and ds) live per tile.  With no tuned table installed the answer
    is byte-identical to the historical `_BLOCK_TABLE` lookup.
    """
    from deeplearning4j_tpu.optimize import tunables

    name = "attention.block_bwd" if bwd else "attention.block_fwd"
    tuned = tunables.resolve(name, "%dx%d" % (seq, head_dim))
    if tuned is not None:
        return tuple(tuned)
    hit = _block_table().get((seq, head_dim))
    if hit is not None:
        return hit[2:] if bwd else hit[:2]

    def fit(cap):
        b = 8
        while b * 2 <= cap and seq % (b * 2) == 0:
            b *= 2
        return b

    caps = (128, 256) if bwd else (256, 512)
    return (fit(caps[0]), fit(caps[1])) if seq % 8 == 0 else (128, 128)


# ---------------------------------------------------------------- attention

def _flash_attn_kernel(q_ref, k_ref, v_ref, o_ref, *lse_out, block_k: int,
                       causal: bool, q_block: int, scale: float,
                       block_skip: bool = False):
    """One Q tile vs all KV tiles, online softmax in VMEM.

    q_ref: [block_q, D]; k_ref/v_ref: [S, D]; o_ref: [block_q, D].
    Grid: (BH, num_q_blocks) — batch*heads is grid dim 0.

    When invoked with a second output ref (`lse_out`, [block_q, 1]) the
    kernel also emits the per-row logsumexp `m + log(l)` — the softmax
    normalizer residual the fused backward needs to rebuild probabilities
    as `p = exp(s - lse)` without re-running the forward.  The o output is
    computed identically either way.

    `block_skip` (causal only) splits the KV loop at the diagonal: tiles
    strictly below it need no mask at all (every kpos < every qpos, so
    `where(kpos <= qpos, s, NEG)` is the identity there — the split is
    bitwise-identical, it just skips the iota/compare/select work on the
    ~half of tiles where the mask is a no-op).
    """
    qi = pl.program_id(1)
    s_total = k_ref.shape[0]
    d = q_ref.shape[1]
    nk = s_total // block_k

    q = q_ref[:] * scale

    def make_body(masked):
        def body(j, carry):
            o, m, l = carry
            k = k_ref[pl.ds(j * block_k, block_k), :]
            v = v_ref[pl.ds(j * block_k, block_k), :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            if masked:
                qpos = qi * q_block + lax.broadcasted_iota(
                    jnp.int32, (q_block, block_k), 0)
                kpos = j * block_k + lax.broadcasted_iota(
                    jnp.int32, (q_block, block_k), 1)
                s = jnp.where(kpos <= qpos, s, _NEG_BIG)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            o_new = o * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            return o_new, m_new, l_new

        return body

    carry = (jnp.zeros((q_block, d), jnp.float32),
             jnp.full((q_block, 1), _NEG_BIG, jnp.float32),
             jnp.zeros((q_block, 1), jnp.float32))
    if causal:
        # tiles strictly after this q tile's last row contribute nothing
        nk_needed = lax.min(((qi + 1) * q_block + block_k - 1) // block_k,
                            nk)
        if block_skip:
            # tile j is fully unmasked iff its last key position
            # (j+1)*block_k - 1 <= first query position qi*q_block
            nk_full = (qi * q_block) // block_k
            carry = lax.fori_loop(0, nk_full, make_body(False), carry)
            carry = lax.fori_loop(nk_full, nk_needed, make_body(True), carry)
        else:
            carry = lax.fori_loop(0, nk_needed, make_body(True), carry)
    else:
        carry = lax.fori_loop(0, nk, make_body(False), carry)
    o, m, l = carry
    o_ref[:] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_out:
        lse_out[0][:] = m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_attention_fwd_impl(q, k, v, causal: bool, block_q: int,
                              block_k: int, interpret: Optional[bool],
                              block_skip: bool = False,
                              with_lse: bool = False):
    b, s, h, d = q.shape
    bh = b * h
    # [B,S,H,D] -> [BH,S,D]
    qr = q.transpose(0, 2, 1, 3).reshape(bh, s, d)
    kr = k.transpose(0, 2, 1, 3).reshape(bh, s, d)
    vr = v.transpose(0, 2, 1, 3).reshape(bh, s, d)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        # ragged sequence: stay on the jax-level blockwise path
        out = blockwise_attention(q, k, v, block_size=block_k, causal=causal)
        return (out, None) if with_lse else out
    grid = (bh, s // block_q)
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_flash_attn_kernel, block_k=block_k,
                               causal=causal, q_block=block_q, scale=scale,
                               block_skip=block_skip and causal)
    q_spec = pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0))
    kv_spec = pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0))
    if with_lse:
        # logsumexp residual rides along in the kernels' [BH, S, 1] layout
        # (trailing singleton keeps every ref 2-D for TPU tiling)
        out, lse = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                       jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)),
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=(q_spec,
                       pl.BlockSpec((None, block_q, 1),
                                    lambda i, j: (i, j, 0))),
            interpret=_interpret(interpret),
            name="dl4j_flash_fwd",
        )(qr, kr, vr)
        return out.reshape(b, h, s, d).transpose(0, 2, 1, 3), lse
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        interpret=_interpret(interpret),
        name="dl4j_flash_fwd",
    )(qr, kr, vr)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# ------------------------------------------------------- fused flash bwd

def _flash_bwd_delta_kernel(o_ref, do_ref, delta_ref):
    """delta = rowsum(dO ∘ O): the softmax-grad correction term.

    One cheap fused pass shared by the dK/dV and dQ kernels (each would
    otherwise re-derive it per tile).  o_ref/do_ref: [block, D];
    delta_ref: [block, 1] f32.
    """
    delta_ref[:] = jnp.sum(o_ref[:].astype(jnp.float32)
                           * do_ref[:].astype(jnp.float32),
                           axis=1, keepdims=True)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, q_block: int, causal: bool,
                          block_k: int, scale: float,
                          block_skip: bool = False):
    """One K/V tile vs all Q tiles: accumulate dK and dV.

    k_ref/v_ref: [block_k, D] (this grid step's tile); q_ref/do_ref: [S, D];
    lse_ref/delta_ref: [S, 1] f32.  Grid: (BH, num_k_blocks).

    Probabilities are rebuilt from the saved logsumexp (p = exp(s - lse)) —
    no softmax recompute, no forward re-run, no [S, S] intermediate.  The
    causal bounds mirror the forward's: q tiles that end before this k
    tile's first key are fully masked and skipped outright (always, not
    just under block_skip — they contribute exact zeros), and `block_skip`
    additionally splits the loop at the first fully-unmasked q tile so the
    unmasked majority skips the iota/compare/select (value-identity there,
    same argument as the forward).
    """
    ki = pl.program_id(1)
    s_total = q_ref.shape[0]
    d = q_ref.shape[1]
    nq = s_total // q_block
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            q = q_ref[pl.ds(i * q_block, q_block), :].astype(jnp.float32)
            do = do_ref[pl.ds(i * q_block, q_block), :].astype(jnp.float32)
            lse = lse_ref[pl.ds(i * q_block, q_block), :]
            delta = delta_ref[pl.ds(i * q_block, q_block), :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if masked:
                qpos = i * q_block + lax.broadcasted_iota(
                    jnp.int32, (q_block, block_k), 0)
                kpos = ki * block_k + lax.broadcasted_iota(
                    jnp.int32, (q_block, block_k), 1)
                s = jnp.where(kpos <= qpos, s, _NEG_BIG)
            p = jnp.exp(s - lse)
            dv_new = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dk_new = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
            return dk_new, dv_new

        return body

    carry = (jnp.zeros((block_k, d), jnp.float32),
             jnp.zeros((block_k, d), jnp.float32))
    if causal:
        # q tiles whose last row precedes this k tile's first key are
        # entirely above the diagonal: exact-zero contribution, skip
        q_start = (ki * block_k) // q_block
        if block_skip:
            # q tile i is fully unmasked iff its first row i*q_block is at
            # or past the tile's last key (ki+1)*block_k - 1
            q_full = lax.min(
                ((ki + 1) * block_k - 1 + q_block - 1) // q_block, nq)
            carry = lax.fori_loop(q_start, q_full, make_body(True), carry)
            carry = lax.fori_loop(q_full, nq, make_body(False), carry)
        else:
            carry = lax.fori_loop(q_start, nq, make_body(True), carry)
    else:
        carry = lax.fori_loop(0, nq, make_body(False), carry)
    dk, dv = carry
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool,
                         q_block: int, scale: float,
                         block_skip: bool = False):
    """One Q tile vs all K/V tiles: accumulate dQ.

    q_ref/do_ref: [block_q, D] (this grid step's tile); k_ref/v_ref: [S, D];
    lse_ref/delta_ref: [block_q, 1] f32.  Grid: (BH, num_q_blocks).  The
    loop bounds are exactly the forward's (`nk_needed`, and `nk_full` under
    block_skip).
    """
    qi = pl.program_id(1)
    s_total = k_ref.shape[0]
    d = k_ref.shape[1]
    nk = s_total // block_k
    q = q_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:]
    delta = delta_ref[:]

    def make_body(masked):
        def body(j, dq):
            k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
            v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if masked:
                qpos = qi * q_block + lax.broadcasted_iota(
                    jnp.int32, (q_block, block_k), 0)
                kpos = j * block_k + lax.broadcasted_iota(
                    jnp.int32, (q_block, block_k), 1)
                s = jnp.where(kpos <= qpos, s, _NEG_BIG)
            p = jnp.exp(s - lse)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

        return body

    dq = jnp.zeros((q_block, d), jnp.float32)
    if causal:
        nk_needed = lax.min(((qi + 1) * q_block + block_k - 1) // block_k,
                            nk)
        if block_skip:
            nk_full = (qi * q_block) // block_k
            dq = lax.fori_loop(0, nk_full, make_body(False), dq)
            dq = lax.fori_loop(nk_full, nk_needed, make_body(True), dq)
        else:
            dq = lax.fori_loop(0, nk_needed, make_body(True), dq)
    else:
        dq = lax.fori_loop(0, nk, make_body(False), dq)
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _fused_bwd_blocks(seq: int, head_dim: int, block_q_bwd: int,
                      block_k_bwd: int):
    """Resolve backward tile sizes; None when no size divides S (ragged S
    keeps the jax-level fallback — same rule as the forward)."""
    pq, pk = pick_attention_blocks(seq, head_dim, bwd=True)
    bq = min(block_q_bwd or pq, seq)
    bk = min(block_k_bwd or pk, seq)
    if seq % bq or seq % bk:
        return None
    return bq, bk


def _flash_fused_bwd_impl(q, k, v, out, lse, g, causal, block_q, block_k,
                          interpret, block_skip):
    """Fused flash backward: delta precompute, then dK/dV and dQ kernels.

    `block_q`/`block_k` are the *backward* tile sizes (see
    `pick_attention_blocks(..., bwd=True)`); `lse` arrives in the kernels'
    [BH, S, 1] layout straight from the forward.
    """
    b, s, h, d = q.shape
    bh = b * h

    def to_bh(t):
        return t.transpose(0, 2, 1, 3).reshape(bh, s, d)

    qr, kr, vr, orr, gr = to_bh(q), to_bh(k), to_bh(v), to_bh(out), to_bh(g)
    interp = _interpret(interpret)
    scale = 1.0 / (d ** 0.5)
    tile_q = pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0))
    tile_k = pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0))
    full_sd = pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0))
    tile_r = pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0))
    full_r = pl.BlockSpec((None, s, 1), lambda i, j: (i, 0, 0))

    delta = pl.pallas_call(
        _flash_bwd_delta_kernel,
        out_shape=jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        grid=(bh, s // block_q),
        in_specs=[tile_q, tile_q],
        out_specs=tile_r,
        interpret=interp,
        name="dl4j_flash_bwd_delta",
    )(orr, gr)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, q_block=block_q, causal=causal,
        block_k=block_k, scale=scale, block_skip=block_skip)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
        grid=(bh, s // block_k),
        in_specs=[full_sd, tile_k, tile_k, full_sd, full_r, full_r],
        out_specs=(tile_k, tile_k),
        interpret=interp,
        name="dl4j_flash_bwd_dkv",
    )(qr, kr, vr, gr, lse, delta)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, block_k=block_k, causal=causal,
        q_block=block_q, scale=scale, block_skip=block_skip)
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=(bh, s // block_q),
        in_specs=[tile_q, full_sd, full_sd, tile_q, tile_r, tile_r],
        out_specs=tile_q,
        interpret=interp,
        name="dl4j_flash_bwd_dq",
    )(qr, kr, vr, gr, lse, delta)

    def from_bh(t):
        return t.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return from_bh(dq), from_bh(dk), from_bh(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
# API-level fallbacks, not serving defaults: in-repo callers pass
# blocks from pick_attention_blocks (the tunable-resolved site); 0 is
# the bwd autotune sentinel
def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = 128,  # lint: allow(hardcoded-tunable)
                    block_k: int = 128,  # lint: allow(hardcoded-tunable)
                    interpret: Optional[bool] = None,
                    block_skip: bool = False, fused_bwd: bool = False,
                    block_q_bwd: int = 0,  # lint: allow(hardcoded-tunable)
                    block_k_bwd: int = 0):  # lint: allow(hardcoded-tunable)
    """Flash attention: [B,S,H,D] inputs, Pallas forward, optional fused
    Pallas backward.

    `fused_bwd=False` (default): backward recomputes attention blockwise
    (flash-style memory profile) via the jax-level implementation's VJP, so
    grads never materialize [S,S] — but the whole forward is re-derived.
    `fused_bwd=True`: the forward additionally saves per-row logsumexp
    residuals and the backward runs three Pallas kernels (delta precompute,
    dK/dV with a k-tile outer loop, dQ with a q-tile outer loop) that
    rebuild probabilities tile-by-tile from the residuals — no forward
    re-run, still no [S,S].  `block_q_bwd`/`block_k_bwd` pin the backward
    tile sizes (0 -> autotuned via `pick_attention_blocks(..., bwd=True)`).
    The fused path silently degrades to the jax-level fallback when no
    backward block divides S, and in auto-detected interpret mode
    (`interpret=None` off-TPU — emulated kernels lose to XLA's batched
    scan there; pass `interpret=True` to force the fused kernels on CPU).  `block_skip=True` (causal only) splits every
    kernel's inner loop at the diagonal so fully-unmasked tiles skip the
    mask arithmetic — same values, fewer VPU ops; see `_flash_attn_kernel`.
    """
    return _flash_attention_fwd_impl(q, k, v, causal, block_q, block_k,
                                     interpret, block_skip)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, block_skip,
               fused_bwd, block_q_bwd, block_k_bwd):
    s, d = q.shape[1], q.shape[3]
    # the fused kernels engage on a real TPU lowering, or when the caller
    # pinned `interpret` (tests exercise the kernels that way on CPU).
    # Auto-detected interpret mode (interpret=None off-TPU) keeps the
    # jax-level recompute fallback: emulated per-tile kernels lose to
    # XLA's batched blockwise scan on host CPUs, which only tests use.
    fused = (fused_bwd
             and (interpret is not None or is_tpu())
             and s % min(block_q, s) == 0 and s % min(block_k, s) == 0
             and _fused_bwd_blocks(s, d, block_q_bwd, block_k_bwd)
             is not None)
    if fused:
        out, lse = _flash_attention_fwd_impl(
            q, k, v, causal, block_q, block_k, interpret, block_skip,
            with_lse=True)
        return out, (q, k, v, out, lse)
    out = _flash_attention_fwd_impl(q, k, v, causal, block_q, block_k,
                                    interpret, block_skip)
    # None residuals are static pytree leaves: the backward sees exactly
    # the pre-fused residual set and stays bitwise-identical
    return out, (q, k, v, None, None)


def _flash_bwd(causal, block_q, block_k, interpret, block_skip, fused_bwd,
               block_q_bwd, block_k_bwd, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        bq, bk = _fused_bwd_blocks(q.shape[1], q.shape[3],
                                   block_q_bwd, block_k_bwd)
        return _flash_fused_bwd_impl(q, k, v, out, lse, g, causal, bq, bk,
                                     interpret, block_skip and causal)
    # jax-level fallback (fused_bwd off, ragged S where no Pallas block
    # divides it, or auto-detected interpret mode — see `_flash_fwd`):
    # recompute blockwise and take that VJP.  `block_k` is the
    # caller's pick_attention_blocks choice and the only knob
    # blockwise_attention has: it processes every query row at once per KV
    # block, so there is no q tiling for `block_q` to size.  `block_skip`
    # cannot apply either — the KV loop is a lax.scan whose body must be
    # uniform across iterations, so the mask select runs on every block
    # (it is value-identity below the diagonal, which is exactly the no-op
    # the Pallas kernels' split elides).
    _, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, block_size=block_k,
                                            causal=causal), q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------- LSTM cell

def _lstm_cell_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref,
                      h_out_ref, c_out_ref):
    """Fused LSTM cell: gates = x@Wx + h@Wh + b.

    Gate layout along the 4H axis: [i | f | o | g] — the same order as
    `nn/layers/lstm.LSTMLayer` (the TPU analog of the reference's
    concatenated iFog weight matrix, `LSTM.java:161-228`).
    """
    hdim = h_ref.shape[1]
    z = (jnp.dot(x_ref[:], wx_ref[:], preferred_element_type=jnp.float32)
         + jnp.dot(h_ref[:], wh_ref[:], preferred_element_type=jnp.float32)
         + b_ref[:])
    i = jax.nn.sigmoid(z[:, 0 * hdim:1 * hdim])
    f = jax.nn.sigmoid(z[:, 1 * hdim:2 * hdim])
    o = jax.nn.sigmoid(z[:, 2 * hdim:3 * hdim])
    g = jnp.tanh(z[:, 3 * hdim:4 * hdim])
    c_new = f * c_ref[:] + i * g
    h_out_ref[:] = (o * jnp.tanh(c_new)).astype(h_out_ref.dtype)
    c_out_ref[:] = c_new.astype(c_out_ref.dtype)


def _lstm_reference(x, h, c, wx, wh, b):
    """jax-level twin of the kernel (same [i f o g] order) for the VJP."""
    hdim = h.shape[1]
    z = x @ wx + h @ wh + b
    i = jax.nn.sigmoid(z[:, :hdim])
    f = jax.nn.sigmoid(z[:, hdim:2 * hdim])
    o = jax.nn.sigmoid(z[:, 2 * hdim:3 * hdim])
    g = jnp.tanh(z[:, 3 * hdim:])
    c_new = f * c + i * g
    return o * jnp.tanh(c_new), c_new


def fused_lstm_vmem_bytes(batch: int, n_in: int, n_hidden: int,
                          dtype) -> int:
    """Fast memory the gridless cell keeps resident: x, h, c, Wx, Wh, b
    and both outputs in `dtype`, plus the f32 gate pre-activations
    [B, 4H].  Within ~12% of the compiler's own scoped-allocation figure
    at every width tried (compile only, described v5e: 40.95M reported
    vs 41.0M here at B256·I1024·H1024 f32)."""
    itemsize = jnp.dtype(dtype).itemsize
    resident = (batch * n_in + 4 * batch * n_hidden
                + (n_in + n_hidden + 1) * 4 * n_hidden)
    return itemsize * resident + 4 * batch * 4 * n_hidden


def fused_lstm_fits(batch: int, n_in: int, n_hidden: int, dtype) -> bool:
    """True when the cell compiles inside `VMEM_LIMIT_BYTES`; an eighth
    of headroom covers what the estimate leaves out."""
    need = fused_lstm_vmem_bytes(batch, n_in, n_hidden, dtype)
    return need + need // 8 <= VMEM_LIMIT_BYTES


def _fused_lstm_impl(x, h, c, wx, wh, b, interpret):
    bsz, hdim = h.shape
    interpret = _interpret(interpret)
    if not interpret and not fused_lstm_fits(bsz, x.shape[1], hdim, wx.dtype):
        raise ValueError(
            "fused LSTM cell at batch=%d n_in=%d hidden=%d %s keeps ~%d "
            "bytes resident in fast memory, over the %d-byte limit of one "
            "kernel; use lstm_impl='scan' (or 'auto', which picks it)"
            % (bsz, x.shape[1], hdim, jnp.dtype(wx.dtype).name,
               fused_lstm_vmem_bytes(bsz, x.shape[1], hdim, wx.dtype),
               VMEM_LIMIT_BYTES))
    out_shape = (jax.ShapeDtypeStruct((bsz, hdim), h.dtype),
                 jax.ShapeDtypeStruct((bsz, hdim), c.dtype))
    return pl.pallas_call(
        _lstm_cell_kernel,
        out_shape=out_shape,
        interpret=interpret,
        name="dl4j_lstm_cell",
    )(x, h, c, wx, wh, b[None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def fused_lstm_step(x, h, c, wx, wh, b, interpret: Optional[bool] = None):
    """One fused LSTM cell update.  x:[B,I] h,c:[B,H] wx:[I,4H] wh:[H,4H]
    b:[4H] -> (h_new, c_new).  Differentiable: backward recomputes the
    cell at jax level (cheap — one cell) and uses its VJP, so the layer
    can train through the Pallas forward."""
    return _fused_lstm_impl(x, h, c, wx, wh, b, interpret)


def _lstm_fwd(x, h, c, wx, wh, b, interpret):
    out = _fused_lstm_impl(x, h, c, wx, wh, b, interpret)
    return out, (x, h, c, wx, wh, b)


def _lstm_bwd(interpret, res, g):
    _, vjp = jax.vjp(_lstm_reference, *res)
    return vjp(g)


fused_lstm_step.defvjp(_lstm_fwd, _lstm_bwd)


# ------------------------------------------------------------- scatter-add

_SCATTER_GROUP = 8  # update rows per grid step (sublane tile height)
_LANES = 128  # row width must tile the lane axis


def _scatter_add_kernel(idx_ref, upd_ref, tbl_ref, out_ref, scratch, sem):
    """Serial read-modify-write of table rows via manual HBM<->VMEM DMA.

    The table stays in HBM (arbitrary row indices can't be block-mapped
    under TPU tiling rules); each update row DMAs its destination row into
    VMEM scratch, accumulates, and DMAs back.  Grid steps run serially on
    the core, so duplicate indices accumulate correctly.
    """
    del tbl_ref  # alias source for out_ref; never read directly
    g = pl.program_id(0)

    def body(r, _):
        row = idx_ref[g * _SCATTER_GROUP + r]
        dst = out_ref.at[pl.ds(row, 1), :]
        cin = pltpu.make_async_copy(dst, scratch.at[pl.ds(0, 1), :], sem)
        cin.start()
        cin.wait()
        scratch[pl.ds(0, 1), :] += upd_ref[pl.ds(r, 1), :]
        cout = pltpu.make_async_copy(scratch.at[pl.ds(0, 1), :], dst, sem)
        cout.start()
        cout.wait()
        return 0

    lax.fori_loop(0, _SCATTER_GROUP, body, 0)


def scatter_add_rows(table, indices, updates,
                     interpret: Optional[bool] = None):
    """table[indices[n]] += updates[n] with duplicate indices accumulating.

    The TPU-native replacement for the reference's HogWild per-row
    `axpy` embedding updates (`InMemoryLookupTable.java:198-260`).
    """
    n, d = updates.shape
    interpret = _interpret(interpret)
    if not interpret and d % _LANES:
        raise ValueError(
            "scatter_add_rows: row width %d is not a multiple of the %d-lane "
            "tile the chip's compiler requires; pad the table's rows"
            % (d, _LANES))
    pad = (-n) % _SCATTER_GROUP
    if pad:
        # padded rows add zeros to row 0 — a no-op
        indices = jnp.concatenate([indices.astype(jnp.int32),
                                   jnp.zeros((pad,), jnp.int32)])
        updates = jnp.concatenate(
            [updates, jnp.zeros((pad, d), updates.dtype)])
    n_pad = n + pad
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // _SCATTER_GROUP,),
        in_specs=[
            pl.BlockSpec((_SCATTER_GROUP, d),
                         lambda g, idx_ref: (g, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((_SCATTER_GROUP, d), table.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        _scatter_add_kernel,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        grid_spec=grid_spec,
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
        name="dl4j_scatter_add",
    )(indices.astype(jnp.int32), updates, table)
