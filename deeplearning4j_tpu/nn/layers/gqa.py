"""Attention with grouped K/V heads, full or over a sliding window.
`conf.layer_spec` is a `GQASpec`; the layer is residual, [.., n_in], under an
RMSNorm, and has no bias.

With u = RMSNorm(x): q = Wq u in [H, h], k = Wk u and v = Wv u in [G, h],
G = n_kv_heads; where `qk_norm`, an RMSNorm over h on each head of q and of
k; rotate-half rotary positions on q and k (plain, or YaRN's frequencies and
scale where the spec gives them).  Query head j reads K/V head j // (H // G).
Scores q . k / sqrt(h), float32 softmax over the keys a token may see: every
earlier position and its own (`window` 0), or its own and the `window - 1`
before it.  Then Wo.

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
cells is masked, never cleared.

`prefill` attends a block of `Q_BLOCK` queries at a time, each against the
keys it may see and no others (all earlier ones for a full layer, at most
`window + Q_BLOCK` for a window layer): the scores of a long prompt are
never whole in memory.  A full layer writes its T keys and values to cells
0..T-1; padding needs no care there (cells past a row's length are never
seen by a real token and are overwritten before they are read).  A window
layer gathers into the ring, for every cell, the newest real position of
its residue, `length - 1 - (length - 1 - c) % window`: the prompt's last
`min(length, window)` real positions, and no padding.

Neither `init_paged_state` nor `verify_chunk`: the state lives in the dense
slot table only (`nn.decode.dense_only`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import compute_dtype
from deeplearning4j_tpu.nn.layers.rms import (F32, initializer, mm, pre_norm,
                                              precision_of, rms_norm, rope)
from deeplearning4j_tpu.utils.profiling import scope

#: queries a block of `prefill`; at 32 heads a block's float32 scores
#: against 8192 keys are 1 GiB
Q_BLOCK = 1024


class GQALayer:
    CARRY = False       # a finished row rewrites one cell with what it holds

    @staticmethod
    def init(key, conf):
        s = conf.layer_spec
        d, n = jnp.dtype(conf.dtype), conf.n_in
        ks = jax.random.split(key, 4)
        w = initializer(conf)
        out = {"ln": jnp.ones((n,), d),
               "Wq": w(ks[0], (n, s.n_heads * s.head_dim)),
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
        """Of those, the cells `decode_step` reads for a row of the table,
        wherever the row stands and whether it is live: all, since `seen`
        masks the state and slices nothing off it.  A read that stops at a
        row's live cells says so here."""
        return GQALayer.kv_cells(conf, max_seq)

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        s, cd = conf.layer_spec, compute_dtype(conf)
        shape = (batch, s.n_kv_heads, GQALayer.kv_cells(conf, max_seq), s.head_dim)
        return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)}

    @staticmethod
    def _project(params, conf, x, positions):
        """x [..., n] at `positions` [...] -> q [..., G, H/G, h] in float32
        (rotated, scaled by 1/sqrt(h)) and, in the cache's type, k and v
        [..., G, h] (k rotated)."""
        s, cd = conf.layer_spec, compute_dtype(conf)
        g = s.n_kv_heads
        u = pre_norm(params, x, s.eps)
        with scope("qkv"):
            q = mm(u, params["Wq"], cd).reshape(x.shape[:-1] + (s.n_heads, -1))
            k = mm(u, params["Wk"], cd).reshape(x.shape[:-1] + (g, -1))
            v = mm(u, params["Wv"], cd).reshape(x.shape[:-1] + (g, -1))
            if s.qk_norm:
                q = rms_norm(q, params["q_norm"], s.eps)
                k = rms_norm(k, params["k_norm"], s.eps)
        with scope("rope"):
            at = positions[..., None]
            q = rope(q, at, s.rope_theta, s.yarn) / math.sqrt(s.head_dim)
            k = rope(k, at, s.rope_theta, s.yarn)
        q = q.reshape(x.shape[:-1] + (g, s.n_heads // g, s.head_dim))
        return q, k.astype(cd), v.astype(cd)

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
        s, cd = conf.layer_spec, compute_dtype(conf)
        b, t, _ = x.shape
        w = s.window
        q, k, v = GQALayer._project(
            params, conf, x, jnp.broadcast_to(jnp.arange(t), (b, t)))
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
        out = []
        for start in range(0, t, Q_BLOCK):
            stop = min(start + Q_BLOCK, t)
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
        with scope("proj"):
            y = mm(o, params["Wo"], cd)
        return x.astype(F32) + y, state

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        s, cd = conf.layer_spec, compute_dtype(conf)
        b = x.shape[0]
        cells = state["k"].shape[2]
        q, k, v = GQALayer._project(params, conf, x, pos)
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
        with scope("kv_read"):
            seen = jnp.arange(cells)[None, :] <= pos[:, None]
            if s.window:
                seen = seen | (pos[:, None] >= cells)
        o = GQALayer._attend(conf, q, k_all, v_all, seen[:, None, None, :])
        with scope("proj"):
            y = mm(o, params["Wo"], cd)
        return x.astype(F32) + y, {"k": k_all, "v": v_all}

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        """The whole sequence; the state it fills is dropped."""
        b, t = x.shape[0], x.shape[1]
        out, _ = GQALayer.prefill(params, conf, x,
                                  GQALayer.init_state(conf, b, t), None)
        return out
