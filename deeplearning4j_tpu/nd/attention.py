"""Core attention math: full, and blockwise (flash-style) online-softmax.

New-scope capability — the 2015 reference predates attention (its sequence
model is the scalar-loop LSTM, `LSTM.java:161-228`).  These are the
single-chip primitives; the sequence-parallel (ring / Ulysses) wrappers live
in `parallel/sequence.py`.  Shapes are [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.utils.profiling import scope

_NEG_BIG = -1e30  # finite -inf stand-in: keeps exp() NaN-free on fully-masked rows


def _scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """[B,Sq,H,D] x [B,Sk,H,D] -> [B,H,Sq,Sk], scaled."""
    d = q.shape[-1]
    return jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))


def _causal_mask(sq: int, sk: int, q_off, k_off, dtype) -> jax.Array:
    qpos = q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    kpos = k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return jnp.where(kpos <= qpos, jnp.asarray(0.0, dtype),
                     jnp.asarray(_NEG_BIG, dtype))


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = False, q_offset=0, k_offset=0) -> jax.Array:
    """Reference softmax attention (materializes the [Sq,Sk] score matrix)."""
    with scope("scores"):
        s = _scores(q, k)
        if causal:
            s = s + _causal_mask(q.shape[1], k.shape[1], q_offset, k_offset,
                                 s.dtype)
        p = jax.nn.softmax(s, axis=-1)
    with scope("attend"):
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _online_update(o, m, l, q, kblk, vblk, q_off, k_off, causal: bool):
    """One online-softmax accumulation step.

    o: [B,Sq,H,D] unnormalized output, m/l: [B,H,Sq] running max / denom.
    """
    s = _scores(q, kblk)  # [B,H,Sq,Sk]
    if causal:
        s = s + _causal_mask(q.shape[1], kblk.shape[1], q_off, k_off, s.dtype)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)                      # [B,H,Sq]
    p = jnp.exp(s - m_new[..., None])               # [B,H,Sq,Sk]
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * jnp.transpose(alpha, (0, 2, 1))[..., None] \
        + jnp.einsum("bhqk,bkhd->bqhd", p, vblk)
    return o_new, m_new, l_new


def _finalize(o, l):
    denom = jnp.transpose(l, (0, 2, 1))[..., None]  # [B,Sq,H,1]
    return o / jnp.maximum(denom, 1e-30)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_size: int = 512, causal: bool = False) -> jax.Array:
    """Memory-efficient attention: scan over KV blocks with online softmax.

    Equivalent to `full_attention` but never materializes the full score
    matrix — the single-chip half of ring attention.
    """
    b, sk, h, d = k.shape
    sq = q.shape[1]
    block_size = min(block_size, sk)
    nb = sk // block_size
    tail = sk - nb * block_size  # ragged tail handled as one short final block
    kb = k[:, :nb * block_size].reshape(b, nb, block_size, h, d).transpose(
        1, 0, 2, 3, 4)
    vb = v[:, :nb * block_size].reshape(b, nb, block_size, h, d).transpose(
        1, 0, 2, 3, 4)

    def step(carry, blk):
        o, m, l = carry
        (kblk, vblk), j = blk
        o, m, l = _online_update(o, m, l, q, kblk, vblk,
                                 q_off=0, k_off=j * block_size, causal=causal)
        return (o, m, l), None

    o0 = jnp.zeros_like(q)
    m0 = jnp.full((b, h, sq), _NEG_BIG, q.dtype)
    l0 = jnp.zeros((b, h, sq), q.dtype)
    (o, m, l), _ = lax.scan(step, (o0, m0, l0),
                            ((kb, vb), jnp.arange(nb)))
    if tail:
        o, m, l = _online_update(o, m, l, q, k[:, nb * block_size:],
                                 v[:, nb * block_size:], q_off=0,
                                 k_off=nb * block_size, causal=causal)
    return _finalize(o, l)


