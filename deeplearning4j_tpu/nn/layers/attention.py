"""Multi-head self-attention layer.

New-scope capability: the reference framework predates attention entirely
(its only sequence model is the scalar-loop LSTM, `LSTM.java:161-228`); this
layer plus `parallel/sequence.py` is the TPU-native long-context replacement.
Input/output shape [batch, seq, n_in]; params follow the framework's
dict-of-arrays convention ({"Wqkv", "bqkv", "Wo", "bo"}) so the layer
composes with `MultiLayerNetwork`, parameter averaging, and checkpoints like
any other layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nd import random as ndr
from deeplearning4j_tpu.nd.platform import is_tpu
from deeplearning4j_tpu.nn.layers import gqa
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.nn.layers.base import (StatelessDecode,
                                                compute_dtype, mixed_matmul)
from deeplearning4j_tpu.nd.attention import (blockwise_attention,
                                             full_attention)
from deeplearning4j_tpu.utils.profiling import scope


def _dtype(conf):
    return jnp.dtype(conf.dtype)


class MultiHeadAttentionLayer:
    """Pre-LN multi-head self-attention with residual connection."""

    OPERANDS = ("Wqkv", "Wo")   # read only as weight products' operands

    @staticmethod
    def init(key, conf):
        d = _dtype(conf)
        kq, ko = jax.random.split(key)
        dist = conf.dist.sampler() if conf.dist is not None else None
        n = conf.n_in
        if n % conf.n_heads != 0:
            raise ValueError(f"n_in={n} not divisible by n_heads={conf.n_heads}")
        if conf.n_out not in (0, n):
            raise ValueError(
                f"attention is residual: n_out must equal n_in={n} (or 0), "
                f"got {conf.n_out}")
        return {
            "Wqkv": init_weights(kq, (n, 3 * n), conf.weight_init, dist, d),
            "bqkv": jnp.zeros((3 * n,), d),
            "Wo": init_weights(ko, (n, n), conf.weight_init, dist, d),
            "bo": jnp.zeros((n,), d),
            "ln_g": jnp.ones((n,), d),
            "ln_b": jnp.zeros((n,), d),
        }

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        b, s, n = x.shape
        h = conf.n_heads
        hd = n // h
        cd = compute_dtype(conf)
        # projections AND the S^2 score/value matmuls run in compute_dtype
        # (bf16 feeds the MXU at full rate; f32 runs at half peak) — the
        # residual stream and layer norm stay in the param dtype
        q, k, v = _qkv(params, conf, x, cd)
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, h, hd)
        v = v.reshape(b, s, h, hd)
        o = MultiHeadAttentionLayer._attend(conf, q, k, v)
        o = _proj(params, conf, o.reshape(b, s, n).astype(x.dtype))
        if training and conf.dropout > 0.0 and key is not None:
            o = o * ndr.dropout_mask(key, 1.0 - conf.dropout, o.shape, o.dtype)
        return x + o

    @staticmethod
    def resolve_impl(conf, b: int, s: int, h: int) -> str:
        """The implementation `_attend` takes for [b, s, h, hd] inputs:
        `conf.attention_impl`, with "auto" settled from what the code can
        observe (platform, shape, the two flash-side flags)."""
        impl = conf.attention_impl
        if impl != "auto":
            return impl
        if not is_tpu():
            return "blockwise" if conf.attention_block_size else "full"
        # one v5e sweep of 2026-07-29, not reproduced: XLA's dense attention
        # (heads batched into big MXU matmuls) beat the Pallas flash kernel
        # up through S=2048; beyond that the [S,S] scores no longer fit
        # HBM and flash is the only option. The 8 GiB bound was that
        # sweep's per-layer failure boundary (S=2048/B=16/H=16 = 4.3 GiB
        # trained, S=4096/B=8 = 8.6 GiB ran out of memory); it is
        # per-LAYER because XLA rematerializes probs inside fusions rather
        # than retaining one [B,H,S,S] per block, and b here is the
        # per-device batch under shard_map. Overrides: conf.attention_impl
        # pins an impl, conf.remat frees HBM.  Each flash-side improvement
        # moves the crossover one doubling earlier (halves the bound): the
        # causal block-skip halves the kernel's tile visits, and the fused
        # backward removes the flash path's forward recompute.  Both
        # shifts are analytic, not measured.
        scores_bytes = 4 * b * h * s * s  # f32 fwd scores
        bound = 8 << 30
        if conf.attention_block_skip and conf.causal:
            bound >>= 1
        if conf.attention_fused_bwd:
            bound >>= 1
        return "full" if scores_bytes <= bound else "flash"

    @staticmethod
    def _attend(conf, q, k, v):
        """Impl dispatch shared by `forward` and `prefill` — q/k/v are
        [b, s, h, hd] and the result matches elementwise whichever path
        produced the projections (prefill hidden states are bitwise equal
        to a plain forward over the same prompt)."""
        b, s, h, hd = q.shape
        blk = conf.attention_block_size
        skip = conf.attention_block_skip and conf.causal
        fused_bwd = conf.attention_fused_bwd
        impl = MultiHeadAttentionLayer.resolve_impl(conf, b, s, h)
        if impl == "flash":
            from deeplearning4j_tpu.nd.pallas_kernels import (
                flash_attention, pick_attention_blocks)
            bq, bk = (blk, blk) if blk else pick_attention_blocks(s, hd)
            # pinned conf block pins the bwd tiles too; 0 -> bwd-aware
            # autotune inside flash_attention
            # one kernel computes scores and output: both under `attend`
            with scope("attend"):
                o = flash_attention(q, k, v, conf.causal, bq, bk,
                                    block_skip=skip, fused_bwd=fused_bwd,
                                    block_q_bwd=blk, block_k_bwd=blk)
        elif impl == "blockwise":
            with scope("attend"):
                o = blockwise_attention(q, k, v, block_size=blk or 512,
                                        causal=conf.causal)
        else:
            o = full_attention(q, k, v, causal=conf.causal)
        return o

    # -- decode protocol (`nn/layers/__init__.py`): a K/V table, or pages ----
    CARRY = False       # a table: a step writes the cell at `pos`
    SPLIT_HEADS = True  # a `model` axis splits the table's heads (`parallel.plan`)

    @staticmethod
    def kv_cells(conf, max_seq: int) -> int:
        """Positions a row's table holds: one cell a position."""
        return max_seq

    @staticmethod
    def kv_cells_read(conf, max_seq: int) -> int:
        """The most cells `decode_step` reads of a row: the table, masked
        whole, or walked up to the row's position (`kv_read_block`)."""
        return max_seq

    @staticmethod
    def kv_read_block(conf, max_seq: int) -> int:
        """The block in which `decode_step` walks a row's cells up to its
        position (`decode_kernel`), or 0 where it reads them all."""
        h = conf.n_heads
        return (gqa.walk_block(max_seq, h, conf.n_in // h, compute_dtype(conf))
                if MultiHeadAttentionLayer.decode_kernel(conf, max_seq) else 0)

    @staticmethod
    def decode_kernel(conf, max_seq: int) -> bool:
        """Does `decode_step` attend to the table through `dl4j_gqa_decode`,
        as a grouped-query layer with a K/V head a query head does
        (`gqa.walks_table`).  Under a plan with a `model` axis, whose table
        lies split by head, the step takes the masked read instead."""
        return gqa.walks_table(max_seq, conf.n_in // conf.n_heads)

    @staticmethod
    def kv_cell_bytes(conf, max_seq: int) -> int:
        """Bytes of one cell as the table lays it out: K and V of every
        head."""
        return 2 * conf.n_in * jnp.dtype(compute_dtype(conf)).itemsize

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        """K and V [B, H, max_S, hd]: heads before positions, so that a
        head's cells lie together, as a grouped-query layer lays its table
        out (`gqa.py`)."""
        h = conf.n_heads
        return _kv_zeros(conf, (batch, h, max_seq, conf.n_in // h))

    @staticmethod
    def init_paged_state(conf, batch: int, n_pages: int, page_size: int) -> dict:
        """One physical pool for all rows, addressed through each call's
        `page_table`: memory scales with pages, not batch x max_seq."""
        return _kv_zeros(conf, (n_pages, page_size, conf.n_in))

    @staticmethod
    def prefill(params, conf, x, state, length):
        """Prompt phase of KV-cache generation: run the normal causal
        forward over the whole prompt and record the projected K/V rows
        into the pre-allocated caches.

        x: [B, T, n]; state: K and V [B, H, max_S, hd] (T <= max_S), the
        prompt's T positions written heads before positions.  Returns
        (hidden [B, T, n], state).  Bucket padding beyond each row's true
        prompt `length` writes junk K/V at positions >= length, which is
        harmless: the causal mask hides them from every prompt position,
        and `decode_step` overwrites position `pos` before it ever attends
        to it.
        """
        b, s, n = x.shape
        h = conf.n_heads
        hd = n // h
        cd = compute_dtype(conf)
        q, k, v = _qkv(params, conf, x, cd)
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, h, hd)
        v = v.reshape(b, s, h, hd)
        with scope("kv_write"):
            state = {name: jax.lax.dynamic_update_slice(
                         state[name], jnp.swapaxes(new, 1, 2).astype(state[name].dtype),
                         (0, 0, 0, 0))
                     for name, new in (("k", k), ("v", v))}
        o = MultiHeadAttentionLayer._attend(conf, q, k, v)
        o = _proj(params, conf, o.reshape(b, s, n).astype(x.dtype))
        return x + o, state

    @staticmethod
    def decode_step(params, conf, x, state, pos, page_table=None):
        """One generated token against the KV cache.

        x: [B, n] (current token's hidden row); pos: [B] int32, the
        sequence position each row is writing.  Dense state: K and V
        [B, H, max_S, hd], the new row's heads scattered at `pos`.  With a
        `page_table` [B, pages_per_slot] int32 of physical page ids the
        state is the shared pool [n_pages, page_size, n]: the new row is
        scattered at (page_table[b, pos // ps], pos % ps) and the row's
        pages are gathered back into one [B, H, pages_per_slot * ps, hd]
        view.  Either way scores are [B, H, ctx] — one sequence-scaled
        axis, never [S, S] — and key positions > pos get the same additive
        -1e30 mask as `nd.attention.full_attention`, so a greedy decode
        reproduces the eager full-forward trajectory exactly in f32.
        Unallocated table entries point at the host's scratch page, whose
        junk sits behind the mask (exp(-1e30 + .) underflows to exactly
        0.0), so paged and dense trajectories are token-identical.  On the
        chip a dense table on one chip is not read whole:
        `dl4j_gqa_decode` walks each row's blocks up to its position
        (`decode_kernel`), scoring and normalising in float32.
        """
        b, n = x.shape
        h = conf.n_heads
        hd = n // h
        cd = compute_dtype(conf)
        q, k, v = _qkv(params, conf, x, cd)
        with scope("kv_write"):
            state = _kv_write(state, k, v, jnp.arange(b), pos, page_table)
        if (page_table is None and _on_one_chip(state["k"]) and
                MultiHeadAttentionLayer.decode_kernel(conf, state["k"].shape[2])):
            with scope("attend"):
                qs = q.astype(jnp.float32).reshape(b, h, 1, hd) / math.sqrt(hd)
                o = _walk_table(qs, state["k"], state["v"], pos)
            return x + _proj(params, conf, o.reshape(b, n).astype(x.dtype)), state
        with scope("kv_read"):
            qh = q.reshape(b, h, hd)
            kh, vh = _kv_read(state, page_table, b, h, hd, cd)
        with scope("scores"):
            s = jnp.einsum("bhd,bhkd->bhk", qh, kh) / jnp.sqrt(
                jnp.asarray(hd, qh.dtype))
            kpos = jnp.arange(kh.shape[2])[None, :]
            mask = jnp.where(kpos <= pos[:, None], 0.0,
                             -1e30).astype(s.dtype)
            p = jax.nn.softmax(s + mask[:, None, :], axis=-1)
        with scope("attend"):
            o = jnp.einsum("bhk,bhkd->bhd", p, vh)
        o = _proj(params, conf, o.reshape(b, n).astype(x.dtype))
        return x + o, state

    @staticmethod
    def verify_chunk(params, conf, x, state, pos, page_table=None):
        """Speculative verification: advance every row K tokens at once.

        x: [B, K, n] (chunk hidden rows); state and `page_table` as in
        `decode_step`; pos: [B] int32, the position of each row's FIRST
        chunk token.  Token i is written at pos + i and attends causally at
        kpos <= pos + i — the same mask `decode_step` would apply i calls
        later — so the chunk's hidden rows match K sequential decode steps
        exactly.  Mis-speculated suffixes need no rollback: the next call
        rewrites those positions before attending to them, hence no
        carries: returns (hidden [B, K, n], state, {}).
        """
        b, kk, n = x.shape
        h = conf.n_heads
        hd = n // h
        cd = compute_dtype(conf)
        q, k, v = _qkv(params, conf, x, cd)
        with scope("kv_write"):
            rows = jnp.arange(b)[:, None]
            idx = pos[:, None] + jnp.arange(kk)[None, :]
            state = _kv_write(state, k, v, rows, idx, page_table)
        with scope("kv_read"):
            qh = q.reshape(b, kk, h, hd)
            kh, vh = _kv_read(state, page_table, b, h, hd, cd)
        with scope("scores"):
            s = jnp.einsum("bqhd,bhkd->bhqk", qh, kh) / jnp.sqrt(
                jnp.asarray(hd, qh.dtype))
            kpos = jnp.arange(kh.shape[2])[None, None, :]
            mask = jnp.where(kpos <= idx[:, :, None], 0.0,
                             -1e30).astype(s.dtype)
            p = jax.nn.softmax(s + mask[:, None, :, :], axis=-1)
        with scope("attend"):
            o = jnp.einsum("bhqk,bhkd->bqhd", p, vh)
        o = _proj(params, conf, o.reshape(b, kk, n).astype(x.dtype))
        return x + o, state, {}


def _kv_zeros(conf, shape) -> dict:
    cd = compute_dtype(conf)
    return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)}


def _kv_write(state, k, v, rows, idx, page_table):
    """The new rows k, v ([B, n], or [B, K, n] at positions `idx` [B, K])
    at positions `idx` of rows `rows`: into the rows' own tables, a cell a
    (row, head), or through `page_table` into the pool's (page, offset)."""
    kc, vc = state["k"], state["v"]
    if page_table is not None:
        ps = kc.shape[1]
        at = (page_table[rows, idx // ps], idx % ps)
        return {"k": kc.at[at].set(k.astype(kc.dtype)),
                "v": vc.at[at].set(v.astype(vc.dtype))}
    # scattered with both indices on the leading axes, the table is written
    # where it lies (with the heads' axis inside the window the TPU's
    # compiler copies the whole table into a position-major layout and
    # back, a step)
    b, h, cells, hd = kc.shape
    heads = jnp.arange(b * h).reshape((b * h,) + (1,) * (idx.ndim - 1))
    cell = jnp.repeat(idx, h, axis=0)

    def put(table, new):
        new = new.reshape(new.shape[:-1] + (h, hd))
        if new.ndim == 4:                                   # [B, H, K, hd]
            new = jnp.swapaxes(new, 1, 2)
        flat = table.reshape(b * h, cells, hd)
        return flat.at[heads, cell].set(
            new.reshape((b * h,) + new.shape[2:]).astype(table.dtype)).reshape(table.shape)

    return {"k": put(kc, k), "v": put(vc, v)}


def _kv_read(state, page_table, b, h, hd, cd):
    """Every row's keys and values as [B, H, ctx, hd] in `cd`: its table
    (ctx = max_S), or its pages gathered (ctx = pages_per_slot * ps)."""
    if page_table is None:
        return state["k"].astype(cd), state["v"].astype(cd)
    return tuple(jnp.swapaxes(state[name][page_table].reshape(b, -1, h, hd),
                              1, 2).astype(cd) for name in ("k", "v"))


#: `gqa.walk_table` as one function: a decode step's layers call it with the
#: same shapes, so it is traced and lowered once, not once a layer
_walk_table = jax.jit(gqa.walk_table)


def _on_one_chip(table) -> bool:
    """Does `table` lie on one chip, not on a mesh (where a plan's `model`
    axis splits it by head)?"""
    return jax.typeof(table).sharding.mesh.size <= 1


def _layer_norm(x, g, b, eps: float = 1e-5):
    with scope("ln"):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _qkv(params, conf, x, cd):
    """Pre-LN, then the fused Q/K/V projection in `cd` (scopes `ln`, `qkv`)."""
    xn = _layer_norm(x, params["ln_g"], params["ln_b"])
    with scope("qkv"):
        qkv = (mixed_matmul(xn, params["Wqkv"], conf, params["bqkv"].dtype)
               + params["bqkv"])
        return jnp.split(qkv.astype(cd), 3, axis=-1)


def _proj(params, conf, o):
    """The output projection of the attended rows (scope `proj`)."""
    with scope("proj"):
        return mixed_matmul(o, params["Wo"], conf, params["bo"].dtype) + params["bo"]


class TransformerFFNLayer(StatelessDecode):
    """Pre-LN residual MLP — the second half of a transformer block.

    Hidden width = conf.ffn_hidden, defaulting to 4*n_in.  Pairs with
    MultiHeadAttentionLayer to form [attention, ffn] blocks in a
    MultiLayerConfiguration stack.  Keeps no decode state.
    """

    OPERANDS = ("W1", "W2")

    @staticmethod
    def init(key, conf):
        d = _dtype(conf)
        n = conf.n_in
        if conf.n_out not in (0, n):
            raise ValueError(
                f"ffn is residual: n_out must equal n_in={n} (or 0), "
                f"got {conf.n_out}")
        h = conf.ffn_hidden or 4 * n
        k1, k2 = jax.random.split(key)
        dist = conf.dist.sampler() if conf.dist is not None else None
        return {
            "W1": init_weights(k1, (n, h), conf.weight_init, dist, d),
            "b1": jnp.zeros((h,), d),
            "W2": init_weights(k2, (h, n), conf.weight_init, dist, d),
            "b2": jnp.zeros((n,), d),
            "ln_g": jnp.ones((n,), d),
            "ln_b": jnp.zeros((n,), d),
        }

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        xn = _layer_norm(x, params["ln_g"], params["ln_b"])
        with scope("ffn"):
            h = jax.nn.gelu(mixed_matmul(xn, params["W1"], conf, params["b1"].dtype)
                            + params["b1"])
            o = mixed_matmul(h, params["W2"], conf, params["b2"].dtype) + params["b2"]
        if training and conf.dropout > 0.0 and key is not None:
            o = o * ndr.dropout_mask(key, 1.0 - conf.dropout, o.shape, o.dtype)
        return x + o
