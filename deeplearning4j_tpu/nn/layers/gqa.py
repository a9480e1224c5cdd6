"""Attention with grouped K/V heads, full or over a sliding window.
`conf.layer_spec` is a `GQASpec`; the layer is residual, [.., n_in], under an
RMSNorm, and has no bias.

With u = RMSNorm(x): q = Wq u in [H, h], k = Wk u and v = Wv u in [G, h],
G = n_kv_heads; where `qk_norm`, an RMSNorm over h on each head of q and of
k; rotary positions on q and k by `rotary` (rotate-half, or interleaved
pairs; plain, or YaRN's frequencies and scale where the spec gives them;
`none`: no positions), over the first `rotary_dim` dimensions of a head
where the spec gives them.  Query head j reads K/V head j // (H // G).
Scores q . k / sqrt(h), float32 softmax over the keys a token may see: every
earlier position and its own (`window` 0), or its own and the `window - 1`
before it.  Where `out_gate`, Wq u gives each head [q, gate] in [2h], and
the heads' output is multiplied by sigmoid(gate) element by element.  Then
Wo.  `branch_prefill` and `branch_step` are the same attention from a normed
input to Wo's output, with no norm and no residual: what a block that norms
once for two branches (`parallel.py`) calls.

Decode state, a row a slot, in the compute dtype, K/V heads before positions
so that a head's cells lie together:
  full    {"k", "v": [B, G, max_seq, h]}   a table, cell p holds position p
  window  {"k", "v": [B, G, window, h]}    a ring, position p is in cell
          p % window; `max_seq` does not enter its size
`decode_step` writes the cell of `pos` and attends over the cells that hold
a position this row may see.  In a table those are the cells <= pos.  In a
ring, cell c holds position pos - (pos - c) % window, the newest one of its
residue: inside the window by construction, and written by this row
whenever it is >= 0, so the ring's valid cells are again c <= pos, and all
of them once pos >= window - 1.  What a slot's last row left in the other
cells is masked, never cleared.  On the chip a table's step reads no cell
past a row's block: `pallas_kernels.gqa_decode` walks each row's cells up to
its position where they lie (`decode_kernel`, `kv_read_block`); off it the
step reads the whole table under the mask, the kernel's oracle.

`prefill` attends a block of `q_block(n_heads)` queries at a time, each
against the keys it may see and no others (all earlier ones for a full
layer, at most `window` more for a window layer): the scores of a long
prompt are never whole in memory, and a block's rows of scores, heads times
queries, are `SCORE_ROWS` at most.  A full layer writes its T keys and
values to cells 0..T-1; padding needs no care there (cells past a row's
length are never seen by a real token and are overwritten before they are
read).  A window layer gathers into the ring, for every cell, the newest real position of
its residue, `length - 1 - (length - 1 - c) % window`: the prompt's last
`min(length, window)` real positions, and no padding.

Neither `init_paged_state` nor `verify_chunk`: the state lives in the dense
slot table only (`nn.decode.dense_only`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nd import pallas_kernels
from deeplearning4j_tpu.nd.platform import is_tpu
from deeplearning4j_tpu.nn.layers.base import compute_dtype
from deeplearning4j_tpu.nn.layers.rms import (F32, initializer, mm, pre_norm,
                                              precision_of, rms_norm, rope,
                                              rope_first)
from deeplearning4j_tpu.utils.profiling import scope

#: float32 rows of scores a block of `prefill` holds, heads x queries: at
#: 32 heads a block is 1,024 queries, whose scores against 8,192 keys are
#: 1 GiB; at 128 heads 256
SCORE_ROWS = 32 * 1024


def q_block(n_heads: int) -> int:
    """Queries a block of `prefill`, from the heads alone."""
    return SCORE_ROWS // n_heads


def walks_table(cells: int, head_dim: int) -> bool:
    """Does a decode step attend to a table of `cells` positions, K/V heads
    before positions, through `dl4j_gqa_decode`: on the chip, of whole 128s
    of positions, with heads whose table the kernel reads where it lies
    (whole 128s wide, or 64 with the positions innermost).  Off the chip
    the step reads the whole table under a mask, the kernel's oracle
    (interpreted, the kernel loses to XLA there).  The rule of both
    attention layers that keep such a table (this one, `attention.py`)."""
    return is_tpu() and cells % 128 == 0 and (head_dim % 128 == 0 or head_dim == 64)


def walk_block(cells: int, kv_heads: int, head_dim: int, dtype) -> int:
    """The block in which `walk_table` reads a table of `cells` positions
    of `kv_heads` heads `head_dim` wide in `dtype`: `pallas_kernels.walk_block`
    of its K (or V) cells, each `kv_heads x head_dim` wide."""
    return pallas_kernels.walk_block(cells, kv_heads * head_dim * jnp.dtype(dtype).itemsize)


def walk_table(q, k, v, pos):
    """`pallas_kernels.gqa_decode` of q [B, G, R, h] (scaled; cast to the
    table's type) over the tables k and v [B, G, cells, h] up to each row's
    `pos`: [B, G, R, h] float32.  The chip lays a table whose heads are not
    whole 128s wide out positions innermost: the kernel reads it as it
    lies."""
    last = k.shape[-1] % 128 != 0
    kt, vt = ((jnp.swapaxes(t, 2, 3) if last else t) for t in (k, v))
    return pallas_kernels.gqa_decode(q.astype(k.dtype), kt, vt, pos, cells_last=last)


class GQALayer:
    CARRY = False       # a table: a step writes the cell at `pos`
    OPERANDS = ("Wq", "Wk", "Wv", "Wo")   # read only as products' operands

    @staticmethod
    def init(key, conf):
        s = conf.layer_spec
        d, n = jnp.dtype(conf.dtype), conf.n_in
        ks = jax.random.split(key, 4)
        w = initializer(conf)
        out = {"ln": jnp.ones((n,), d),
               "Wq": w(ks[0], (n, s.n_heads * s.head_dim * (2 if s.out_gate else 1))),
               "Wk": w(ks[1], (n, s.n_kv_heads * s.head_dim)),
               "Wv": w(ks[2], (n, s.n_kv_heads * s.head_dim)),
               "Wo": w(ks[3], (s.n_heads * s.head_dim, n))}
        if s.qk_norm:
            out.update(q_norm=jnp.ones((s.head_dim,), d),
                       k_norm=jnp.ones((s.head_dim,), d))
        return out

    @staticmethod
    def kv_cells(conf, max_seq: int) -> int:
        """Positions a row's state holds: the table's length, or the ring's."""
        w = conf.layer_spec.window
        return min(w, max_seq) if w else max_seq

    @staticmethod
    def kv_cells_read(conf, max_seq: int) -> int:
        """Of those, the most cells `decode_step` reads for a row of the
        table, wherever the row stands and whether it is live: all, since
        `seen` masks the state and slices nothing off it, or the kernel walks
        a row's live blocks, at most the table (`kv_read_block`)."""
        return GQALayer.kv_cells(conf, max_seq)

    @staticmethod
    def kv_read_block(conf, max_seq: int) -> int:
        """The block in which `decode_step` walks a row's cells up to its
        position (`decode_kernel`), or 0 where it reads them all."""
        s = conf.layer_spec
        return (walk_block(max_seq, s.n_kv_heads, s.head_dim, compute_dtype(conf))
                if GQALayer.decode_kernel(conf, max_seq) else 0)

    @staticmethod
    def decode_kernel(conf, max_seq: int) -> bool:
        """Does `decode_step` attend through `dl4j_gqa_decode`: a table (a
        ring is all live from position window - 1 on) that `walks_table`
        admits."""
        s = conf.layer_spec
        return not s.window and walks_table(max_seq, s.head_dim)

    @staticmethod
    def kv_cell_bytes(conf, max_seq: int) -> int:
        """Bytes of one cell as the state lays it out: K and V of every K/V
        head."""
        s = conf.layer_spec
        return 2 * s.n_kv_heads * s.head_dim * jnp.dtype(compute_dtype(conf)).itemsize

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        s, cd = conf.layer_spec, compute_dtype(conf)
        shape = (batch, s.n_kv_heads, GQALayer.kv_cells(conf, max_seq), s.head_dim)
        return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)}

    @staticmethod
    def _project(params, conf, u, positions):
        """The normed input u [..., n] at `positions` [...] -> q [..., G,
        H/G, h] in float32 (rotated, scaled by 1/sqrt(h)), in the cache's
        type k and v [..., G, h] (k rotated), and the output's gate [..., H
        h] float32 (None without `out_gate`)."""
        s, cd = conf.layer_spec, compute_dtype(conf)
        g, gate = s.n_kv_heads, None
        with scope("qkv"):
            q = mm(u, params["Wq"], cd).reshape(u.shape[:-1] + (s.n_heads, -1))
            if s.out_gate:
                q, gate = q[..., :s.head_dim], q[..., s.head_dim:]
                gate = gate.reshape(u.shape[:-1] + (-1,))
            k = mm(u, params["Wk"], cd).reshape(u.shape[:-1] + (g, -1))
            v = mm(u, params["Wv"], cd).reshape(u.shape[:-1] + (g, -1))
            if s.qk_norm:
                q = rms_norm(q, params["q_norm"], s.eps)
                k = rms_norm(k, params["k_norm"], s.eps)
        if s.rotary == "none":
            q = q / math.sqrt(s.head_dim)
        elif s.rotary_dim:
            with scope("rope"):
                at = positions[..., None]
                q = rope_first(q, at, s.rope_theta, s.rotary_dim) / math.sqrt(s.head_dim)
                k = rope_first(k, at, s.rope_theta, s.rotary_dim)
        else:
            with scope("rope"):
                at, pairs = positions[..., None], s.rotary == "pairs"
                q = rope(q, at, s.rope_theta, s.yarn, pairs) / math.sqrt(s.head_dim)
                k = rope(k, at, s.rope_theta, s.yarn, pairs)
        q = q.reshape(u.shape[:-1] + (g, s.n_heads // g, s.head_dim))
        return q, k.astype(cd), v.astype(cd), gate

    @staticmethod
    def _gated(o, gate):
        """The heads' output [..., H h] times sigmoid(gate), where there is
        one."""
        if gate is None:
            return o
        with scope("gate"):
            return o * jax.nn.sigmoid(gate)

    @staticmethod
    def _attend(conf, q, k, v, seen):
        """q [B, G, R, (Q,) h] against k, v [B, G, K, h] under `seen`
        (broadcast against the scores [B, G, R, (Q,) K]): float32 softmax,
        the values' sum in float32, heads side by side [B, (Q,) H * h]."""
        cd = compute_dtype(conf)
        hi = precision_of(cd)
        block = q.ndim == 5
        with scope("scores"):
            sc = jnp.einsum("bgrqh,bgkh->bgrqk" if block else "bgrh,bgkh->bgrk",
                            q.astype(cd), k, precision=hi,
                            preferred_element_type=F32)
            p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
        with scope("attend"):
            o = jnp.einsum("bgrqk,bgkh->bgrqh" if block else "bgrk,bgkh->bgrh",
                           p.astype(cd), v, precision=hi,
                           preferred_element_type=F32)
            if block:
                o = jnp.moveaxis(o, 3, 1)                       # [B, Q, G, R, h]
        return o.reshape(o.shape[:-3] + (-1,))

    @staticmethod
    def prefill(params, conf, x, state, length):
        b, t, _ = x.shape
        at = jnp.broadcast_to(jnp.arange(t), (b, t))
        u = pre_norm(params, x, conf.layer_spec.eps)
        y, state = GQALayer.branch_prefill(params, conf, u, at, state, length)
        return x.astype(F32) + y, state

    @staticmethod
    def branch_prefill(params, conf, u, positions, state, length):
        """`prefill` from the normed prompt u [B, T, n] at `positions`
        [B, T]: (Wo's output [B, T, n] float32, state), no residual."""
        s, cd = conf.layer_spec, compute_dtype(conf)
        b, t, _ = u.shape
        w = s.window
        q, k, v, gate = GQALayer._project(params, conf, u, positions)
        q = jnp.moveaxis(q, 1, 3)                               # [B, G, R, T, h]
        k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)     # [B, G, T, h]
        with scope("kv_write"):
            cells = state["k"].shape[2]
            if w:
                # cell c takes the newest real position of its residue; one
                # whose residue the prompt has not reached takes position
                # 0's, and is masked until its own turn comes
                last = (jnp.full((b,), t, jnp.int32) if length is None
                        else length.astype(jnp.int32)) - 1
                at = last[:, None] - (last[:, None] - jnp.arange(cells)) % cells
                at = jnp.clip(at, 0, t - 1)[:, None, :, None]
                state = {"k": jnp.take_along_axis(k, at, axis=2),
                         "v": jnp.take_along_axis(v, at, axis=2)}
            else:
                state = {
                    "k": jax.lax.dynamic_update_slice(state["k"], k, (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(state["v"], v, (0, 0, 0, 0))}
        out, block = [], q_block(s.n_heads)
        for start in range(0, t, block):
            stop = min(start + block, t)
            lo = max(0, start - w) if w else 0
            qp = jnp.arange(start, stop)[:, None]
            kp = jnp.arange(lo, stop)[None, :]
            seen = kp <= qp
            if w:
                seen = seen & (kp > qp - w)
            out.append(GQALayer._attend(
                conf, q[:, :, :, start:stop], k[:, :, lo:stop],
                v[:, :, lo:stop], seen))
        o = out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
        o = GQALayer._gated(o, gate)
        with scope("proj"):
            return mm(o, params["Wo"], cd), state

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        u = pre_norm(params, x, conf.layer_spec.eps)
        y, state = GQALayer.branch_step(params, conf, u, state, pos)
        return x.astype(F32) + y, state

    @staticmethod
    def branch_step(params, conf, u, state, pos):
        """`decode_step` from the normed token u [B, n]: (Wo's output [B, n]
        float32, state), no residual."""
        s, cd = conf.layer_spec, compute_dtype(conf)
        b = u.shape[0]
        cells = state["k"].shape[2]
        q, k, v, gate = GQALayer._project(params, conf, u, pos)
        with scope("kv_write"):
            # one cell a (row, K/V head): scattered with both indices on the
            # leading axes, the table is written where it lies (with the
            # heads' axis inside the window the TPU's compiler copies the
            # whole table into a position-major layout and back, a step)
            g = s.n_kv_heads
            cell = jnp.repeat(pos % cells if s.window else pos, g)
            heads = jnp.arange(b * g)

            def put(table, new):
                flat = table.reshape(b * g, cells, s.head_dim)
                return flat.at[heads, cell].set(
                    new.reshape(b * g, s.head_dim)).reshape(table.shape)

            k_all, v_all = put(state["k"], k), put(state["v"], v)
        if GQALayer.decode_kernel(conf, cells):
            with scope("gqa_attend"):
                o = walk_table(q, k_all, v_all, pos).reshape(b, -1)
        else:
            with scope("kv_read"):
                seen = jnp.arange(cells)[None, :] <= pos[:, None]
                if s.window:
                    seen = seen | (pos[:, None] >= cells)
            o = GQALayer._attend(conf, q, k_all, v_all, seen[:, None, None, :])
        o = GQALayer._gated(o, gate)
        with scope("proj"):
            return mm(o, params["Wo"], cd), {"k": k_all, "v": v_all}

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        """The whole sequence; the state it fills is dropped."""
        b, t = x.shape[0], x.shape[1]
        out, _ = GQALayer.prefill(params, conf, x,
                                  GQALayer.init_state(conf, b, t), None)
        return out
