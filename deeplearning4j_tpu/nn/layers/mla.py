"""Multi-head latent attention (arXiv:2405.04434): every head's keys and
values come from one latent vector a token, which is all the cache keeps.
`conf.layer_spec` is an `MLASpec`; the layer is residual, [.., n_in].

With u = RMSNorm(x): q = Wq u in [H, nope + rope]; [c~; kr~] = Wkva u; c =
RMSNorm(c~), the latent; kr = RoPE(kr~), one rotary key for all heads;
[k_nope; v] = Wkvb c a head.  Scores are (q_nope . k_nope + RoPE(q_rope) .
kr) / sqrt(nope + rope), causal softmax, then Wo.

The spec's options, each absent from the plain layer's program:
  q_lora_rank   cq = RMSNorm(Wqa u), q = Wqb cq
  lora_rescale  cq times sqrt(n_in / q_lora_rank), c times sqrt(n_in /
                kv_lora_rank); the cache keeps the scaled latent
  gate          g = sigmoid(Wg u) in R^H; head h's output times g_h before Wo
  window W      a token sees positions t - W < s <= t
  index_topk K  the indexer: qi = Wiq cq in [J, n], ki = LayerNorm(Wik u) in
                R^n, both rotated over their first `rope` dimensions, w =
                Wiw u in R^J; I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])
                / sqrt(J n); a token attends to the K positions s <= t of
                largest I[t, s] alone (ties to the lower position; to all
                of them while t < K).  The choice is exact: `select_top`,
                no sort and nothing approximate

Decode state, a row a slot, in the compute dtype:
  full    {"c": [B, max_S, rank], "kr": [B, max_S, rope]}, cell p holds
          position p
  indexed {"ckr": [B, max_S, cell], "ki": [B, max_S, n]}: a layer that
          reads its state a cell at a time keeps a position's latent and
          rotary key side by side, `cell` = rank + rope rounded up to whole
          128s and the rest zeros, so that one read fetches both (a table
          whose rows are not whole 128s the chip lays out with the
          positions innermost, and a row of it is then as many strided
          reads as it is wide: PERF.md 6, PR 34)
  window  {"ckr": [B, W, cell]}, a ring: position p is in cell p % W,
          `max_S` does not enter its size, and what a slot's last row left
          in the other cells is masked, never cleared (cell c holds position
          pos - (pos - c) % W, which this row has written whenever it is
          >= 0: the valid cells are c <= pos, and all of them once pos >=
          W - 1)
One cache serves two ways.  `prefill` materialises keys and values from the
latents it has just written and attends a block of queries at a time
(`q_block`), each against the keys it may see and no others: all earlier
ones, under the indexer's choice where there are more than K; for a window
layer a band of at most W + block.  `decode_step` absorbs Wkvb instead:
the query goes through the key half of Wkvb into the latent's space,
scores are taken against `c` and `kr` as cached, the softmax's weights sum
the latents, and the value half of Wkvb is applied after the sum: no key or
value of a cached position is ever formed.  An indexed layer's step scores
the row's cached index keys and picks its K positions as a mask over the
table; on the chip one kernel (`pallas_kernels.dsa_decode`) walks the row's
live cells where they lie, a block at a time, and only the picked ones enter
its online softmax; off the chip the step reads the whole table under the
mask, which is the kernel's oracle.  No picked cell is copied out of the
table.  Both ways attend to the latents as rounded to the cache's type, so
the two agree to rounding.

A padded prompt needs no care in a table: positions past a row's length are
written, never attended to by a real one, and overwritten before they are
read.  A ring takes, for every cell, the newest real position of its
residue, `length - 1 - (length - 1 - c) % W`: the prompt's last `min(length,
W)` real positions, and no padding.

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
from deeplearning4j_tpu.nn.layers.rms import (F32, initializer, layer_norm, mm,
                                              pre_norm, precision_of, rms_norm,
                                              rope, rope_first)
from deeplearning4j_tpu.utils.profiling import scope

#: heads x queries of a block of `prefill`: a block's float32 scores against
#: 8,192 keys are 1 GiB (128 heads: 256 queries; 32 heads: 1,024)
SCORE_CELLS = 32768


def q_block(n_heads: int) -> int:
    return max(8, SCORE_CELLS // n_heads)


def _lower(rows: int, cols: int, k: int):
    """[rows, cols] bool, true where col - row <= k."""
    ones = jnp.ones((rows, cols), bool)
    return jnp.tril(ones) if k == 0 else jnp.tril(ones, k)


def _ordered(x):
    """float32 -> uint32 with the same order (no NaN; -0.0 as +0.0)."""
    u = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def select_top(scores, valid, k: int):
    """The mask [..., S] of the `k` largest of `scores` [..., S] float32
    among the `valid` ones (all of them where there are at most `k`), ties
    going to the lower index: what `jax.lax.top_k` over the valid scores
    picks, without a sort.  The k-th largest value is found bit by bit: the
    scores as order-keeping unsigned integers, and for each of the 32 bits
    from the top one count of the entries at or above the candidate."""
    u = jnp.where(valid, _ordered(scores), jnp.uint32(0))

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum((u >= cand).astype(jnp.int32), axis=-1,
                         keepdims=True) >= k
        return jnp.where(enough, cand, t)

    t = jax.lax.fori_loop(0, 32, bit,
                          jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))
    above = u > t                       # fewer than k; every valid one at t = 0
    tie = (u == t) & valid
    room = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie.astype(jnp.int32), axis=-1) <= room))


class MLALayer:
    CARRY = False       # a table: a step writes the cell at `pos`
    OPERANDS = ("Wq", "Wqa", "Wqb", "Wkva", "Wkvb", "Wiq", "Wik", "Wiw", "Wg",
                "Wo")

    @staticmethod
    def init(key, conf):
        s = conf.layer_spec
        d, n, h = jnp.dtype(conf.dtype), conf.n_in, s.n_heads
        ks = jax.random.split(key, 4)
        w = initializer(conf)
        q_out = h * (s.qk_nope_head_dim + s.qk_rope_head_dim)
        out = {
            "ln": jnp.ones((n,), d),
            "Wkva": w(ks[1], (n, s.kv_lora_rank + s.qk_rope_head_dim)),
            "c_norm": jnp.ones((s.kv_lora_rank,), d),
            "Wkvb": w(ks[2], (s.kv_lora_rank,
                              h * (s.qk_nope_head_dim + s.v_head_dim))),
            "Wo": w(ks[3], (h * s.v_head_dim, n)),
        }
        more = jax.random.split(jax.random.fold_in(key, 1), 5)
        if s.q_lora_rank:
            out.update(Wqa=w(ks[0], (n, s.q_lora_rank)),
                       q_norm=jnp.ones((s.q_lora_rank,), d),
                       Wqb=w(more[0], (s.q_lora_rank, q_out)))
        else:
            out["Wq"] = w(ks[0], (n, q_out))
        if s.gate:
            out["Wg"] = w(more[1], (n, h))
        if s.index_topk:
            if not s.q_lora_rank or s.window:
                raise ValueError("an indexer reads the low-rank query of a "
                                 "full layer: set q_lora_rank, and no window")
            out.update(
                Wiq=w(more[2], (s.q_lora_rank, s.index_n_heads * s.index_head_dim)),
                Wik=w(more[3], (n, s.index_head_dim)),
                ik_g=jnp.ones((s.index_head_dim,), d),
                ik_b=jnp.zeros((s.index_head_dim,), d),
                Wiw=w(more[4], (n, s.index_n_heads)))
        return out

    # ------------------------------------------------------------- the state

    @staticmethod
    def _cell_width(conf) -> int:
        """rank + rope, rounded up to whole 128s."""
        wide = conf.layer_spec.kv_lora_rank + conf.layer_spec.qk_rope_head_dim
        return wide + -wide % 128

    @staticmethod
    def _side_by_side(conf, c, kr):
        """[c | kr | zeros] a position, `_cell_width` wide."""
        spare = MLALayer._cell_width(conf) - c.shape[-1] - kr.shape[-1]
        return jnp.concatenate(
            [c, kr, jnp.zeros(c.shape[:-1] + (spare,), c.dtype)], axis=-1)

    @staticmethod
    def _cells(conf, max_seq: int) -> int:
        w = conf.layer_spec.window
        return min(w, max_seq) if w else max_seq

    @staticmethod
    def selects(conf, max_seq: int) -> int:
        """The positions `decode_step` picks for a row among those at or
        before it, at a table of `max_seq`: `index_topk`, or 0 where the
        layer has no indexer or the table no more cells than it would pick
        (the step then reads every cell, and its indexer does nothing)."""
        k = conf.layer_spec.index_topk
        return k if 0 < k < max_seq else 0

    @staticmethod
    def decode_kernel(conf, max_seq: int) -> bool:
        """Does `decode_step` attend through `dl4j_dsa_decode`: a layer that
        picks, on the chip.  Off it the step reads the whole table under the
        choice's mask, the kernel's oracle (interpreted, the kernel loses to
        XLA there)."""
        return MLALayer.selects(conf, max_seq) > 0 and is_tpu()

    @staticmethod
    def kv_cells(conf, max_seq: int) -> tuple:
        """For each table of the state, the most cells a step needs of a
        row (a row at position p needs min(p + 1, that)): the ring's
        length; of a table every cell, or where the step picks, the picked
        latents and beside them every index key."""
        k = MLALayer.selects(conf, max_seq)
        return (k, max_seq) if k else (MLALayer._cells(conf, max_seq),)

    @staticmethod
    def kv_cells_read(conf, max_seq: int) -> tuple:
        """Of each table, the cells `decode_step` reads for a row of it,
        wherever the row stands and whether it is live: the ring and the
        tables whole (`seen` masks the state and slices nothing off it; the
        kernel walks a row's live blocks, at most the whole table)."""
        k = MLALayer.selects(conf, max_seq)
        return (max_seq, max_seq) if k else MLALayer.kv_cells(conf, max_seq)

    @staticmethod
    def kv_read_block(conf, max_seq: int) -> tuple:
        """Of each table, the block in which `decode_step` walks a row's
        cells up to its position, or 0 where it reads them all: the picked
        latents' table under `dl4j_dsa_decode`, and no other."""
        if not MLALayer.selects(conf, max_seq):
            return (0,)
        cell = MLALayer._cell_width(conf) * jnp.dtype(compute_dtype(conf)).itemsize
        return (pallas_kernels.walk_block(max_seq, cell)
                if MLALayer.decode_kernel(conf, max_seq) else 0, 0)

    @staticmethod
    def kv_cell_bytes(conf, max_seq: int) -> tuple:
        """Of each table of `kv_cells`, the bytes of one cell as the state
        lays it out: a latent and a rotary key (apart, or side by side in
        whole 128s), and beside the picked latents an index key."""
        s = conf.layer_spec
        item = jnp.dtype(compute_dtype(conf)).itemsize
        if not (s.window or s.index_topk):
            return ((s.kv_lora_rank + s.qk_rope_head_dim) * item,)
        cell = MLALayer._cell_width(conf) * item
        return (cell, s.index_head_dim * item) if MLALayer.selects(conf, max_seq) else (cell,)

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        s, cd = conf.layer_spec, compute_dtype(conf)
        cells = MLALayer._cells(conf, max_seq)
        if not (s.window or s.index_topk):
            return {"c": jnp.zeros((batch, cells, s.kv_lora_rank), cd),
                    "kr": jnp.zeros((batch, cells, s.qk_rope_head_dim), cd)}
        out = {"ckr": jnp.zeros((batch, cells, MLALayer._cell_width(conf)), cd)}
        if s.index_topk:
            out["ki"] = jnp.zeros((batch, max_seq, s.index_head_dim), cd)
        return out

    # ------------------------------------------------------- the projections

    @staticmethod
    def _project(params, conf, x, positions):
        """x [..., n] at `positions` [...] -> q_nope [..., H, nope], q_rope
        [..., H, rope] (rotated), in the cache's type the latent c
        [..., rank] and the rotary key kr [..., rope], the gate [..., H]
        or None, and the indexer's (qi [..., J, n] rotated, w [..., J]
        scaled, ki [..., n] rotated, in the cache's type) or None."""
        s, cd = conf.layer_spec, compute_dtype(conf)
        u = pre_norm(params, x, s.eps)
        with scope("qkv"):
            if s.q_lora_rank:
                cq = rms_norm(mm(u, params["Wqa"], cd), params["q_norm"], s.eps)
                if s.lora_rescale:
                    cq = cq * math.sqrt(conf.n_in / s.q_lora_rank)
                q = mm(cq, params["Wqb"], cd)
            else:
                q = mm(u, params["Wq"], cd)
            q = q.reshape(q.shape[:-1] + (s.n_heads, -1))
            kva = mm(u, params["Wkva"], cd)
            c = rms_norm(kva[..., :s.kv_lora_rank], params["c_norm"], s.eps)
            if s.lora_rescale:
                c = c * math.sqrt(conf.n_in / s.kv_lora_rank)
            index = None
            if s.index_topk:
                qi = mm(cq, params["Wiq"], cd)
                qi = qi.reshape(qi.shape[:-1] + (s.index_n_heads, -1))
                ki = layer_norm(mm(u, params["Wik"], cd), params["ik_g"],
                                params["ik_b"], s.eps)
                w = mm(u, params["Wiw"], cd) / math.sqrt(
                    s.index_n_heads * s.index_head_dim)
        with scope("rope"):
            q_rope = rope(q[..., s.qk_nope_head_dim:], positions[..., None],
                          s.rope_theta)
            kr = rope(kva[..., s.kv_lora_rank:], positions, s.rope_theta)
            if s.index_topk:
                r = s.qk_rope_head_dim
                index = (rope_first(qi, positions[..., None], s.rope_theta, r), w,
                         rope_first(ki, positions, s.rope_theta, r).astype(cd))
        gate = None
        if s.gate:
            with scope("gate"):
                gate = jax.nn.sigmoid(mm(u, params["Wg"], cd))
        return (q[..., :s.qk_nope_head_dim], q_rope, c.astype(cd), kr.astype(cd),
                gate, index)

    @staticmethod
    def _halves(params, conf):
        """Wkvb as (key half, value half), each [rank, H, .]."""
        s = conf.layer_spec
        w = params["Wkvb"].reshape(s.kv_lora_rank, s.n_heads, -1)
        return w[..., :s.qk_nope_head_dim], w[..., s.qk_nope_head_dim:]

    @staticmethod
    def _index_scores(conf, qi, w, ki):
        """I [..., (Q,) K] in float32: qi [B, (Q,) J, n] and w [B, (Q,) J]
        of the queries against the index keys ki [B, K, n]."""
        cd = compute_dtype(conf)
        block = qi.ndim == 4
        dots = jnp.einsum("bqjd,bkd->bqjk" if block else "bjd,bkd->bjk",
                          qi.astype(cd), ki, precision=precision_of(cd),
                          preferred_element_type=F32)
        return jnp.sum(jax.nn.relu(dots) * w[..., None], axis=-2)

    # -------------------------------------------------------------- prefill

    @staticmethod
    def prefill(params, conf, x, state, length):
        s, cd = conf.layer_spec, compute_dtype(conf)
        b, t, _ = x.shape
        hi = precision_of(cd)
        win = s.window
        q_nope, q_rope, c, kr, gate, index = MLALayer._project(
            params, conf, x, jnp.broadcast_to(jnp.arange(t), (b, t)))
        with scope("latent_write"):
            if "ckr" in state:      # side by side, a cell a position
                new = {"ckr": MLALayer._side_by_side(conf, c, kr)}
            else:
                new = {"c": c, "kr": kr}
            if win:
                # cell c takes the newest real position of its residue; one
                # whose residue the prompt has not reached takes position
                # 0's, and is masked until its own turn comes
                cells = state["ckr"].shape[1]
                last = (jnp.full((b,), t, jnp.int32) if length is None
                        else length.astype(jnp.int32)) - 1
                at = last[:, None] - (last[:, None] - jnp.arange(cells)) % cells
                at = jnp.clip(at, 0, t - 1)[..., None]
                state = {k: jnp.take_along_axis(v, at, axis=1)
                         for k, v in new.items()}
            else:
                state = {**state, **{
                    k: jax.lax.dynamic_update_slice(state[k], v, (0, 0, 0))
                    for k, v in new.items()}}
        if index is not None:
            qi, w, ki = index
            with scope("index_write"):
                state["ki"] = jax.lax.dynamic_update_slice(state["ki"], ki, (0, 0, 0))
        w_k, w_v = MLALayer._halves(params, conf)
        with scope("qkv"):          # keys and values of the prompt, materialised
            k_nope = jnp.einsum("bsr,rhn->bshn", c, w_k.astype(cd), precision=hi,
                                preferred_element_type=F32).astype(cd)
            v = jnp.einsum("bsr,rhv->bshv", c, w_v.astype(cd), precision=hi,
                           preferred_element_type=F32).astype(cd)
        out, step = [], q_block(s.n_heads)
        for start in range(0, t, step):
            stop = min(start + step, t)
            lo = max(0, start - win + 1) if win else 0
            whole = start == 0 and stop == t
            cut = (lambda a, i, j: a) if whole else (lambda a, i, j: a[:, i:j])
            picked = None
            if index is not None and stop > s.index_topk:
                with scope("index_scores"):
                    scores = MLALayer._index_scores(
                        conf, cut(qi, start, stop), cut(w, start, stop),
                        cut(ki, 0, stop))
                with scope("select"):
                    earlier = _lower(stop - start, stop, start)
                    picked = select_top(scores, earlier, s.index_topk)
            with scope("scores"):
                sc = (jnp.einsum("bqhn,bkhn->bhqk", cut(q_nope, start, stop).astype(cd),
                                 cut(k_nope, lo, stop), precision=hi,
                                 preferred_element_type=F32)
                      + jnp.einsum("bqhe,bke->bhqk", cut(q_rope, start, stop).astype(cd),
                                   cut(kr, lo, stop), precision=hi,
                                   preferred_element_type=F32))
                sc = sc / math.sqrt(s.qk_nope_head_dim + s.qk_rope_head_dim)
                if picked is not None:
                    seen = picked[:, None]
                else:
                    seen = _lower(stop - start, stop - lo, start - lo)
                    if win:     # key lo + j, query start + i: j - i > start - lo - win
                        seen = seen & ~_lower(stop - start, stop - lo,
                                              start - lo - win)
                p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
            with scope("attend"):
                out.append(jnp.einsum("bhqk,bkhv->bqhv", p.astype(cd),
                                      cut(v, lo, stop), precision=hi,
                                      preferred_element_type=F32))
        o = out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
        if gate is not None:
            with scope("gate"):
                o = o * gate[..., None]
        with scope("proj"):
            y = mm(o.reshape(b, t, -1), params["Wo"], cd)
        return x.astype(F32) + y, state

    # ---------------------------------------------------------- decode step

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        s, cd = conf.layer_spec, compute_dtype(conf)
        b = x.shape[0]
        hi = precision_of(cd)
        q_nope, q_rope, c, kr, gate, index = MLALayer._project(params, conf, x, pos)
        joint = "ckr" in state
        cells = state["ckr" if joint else "c"].shape[1]
        with scope("latent_write"):
            rows = jnp.arange(b)
            at = pos % cells if s.window else pos
            if joint:
                new = {"ckr": state["ckr"].at[rows, at].set(
                    MLALayer._side_by_side(conf, c, kr))}
            else:
                new = {"c": state["c"].at[rows, at].set(c),
                       "kr": state["kr"].at[rows, at].set(kr)}
        k = MLALayer.selects(conf, cells)
        if index is not None:
            qi, w, ki = index
            with scope("index_write"):
                new["ki"] = state["ki"].at[rows, pos].set(ki)
        picked = None
        if k:
            earlier = jnp.arange(cells)[None, :] <= pos[:, None]
            with scope("index_scores"):
                scores = MLALayer._index_scores(conf, qi, w, new["ki"])
            with scope("select"):
                picked = select_top(scores, earlier, k)
        w_k, w_v = MLALayer._halves(params, conf)
        with scope("absorb"):
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(cd), w_k.astype(cd),
                               precision=hi, preferred_element_type=F32)
        scale = math.sqrt(s.qk_nope_head_dim + s.qk_rope_head_dim)
        if MLALayer.decode_kernel(conf, cells):
            with scope("dsa_attend"):
                lat = pallas_kernels.dsa_decode(
                    MLALayer._side_by_side(conf, q_lat.astype(cd), q_rope.astype(cd)),
                    new["ckr"], picked, pos, 1 / scale)[..., :s.kv_lora_rank]
        else:
            with scope("scores"):
                if joint:
                    sc = jnp.einsum(
                        "bhr,bsr->bhs", MLALayer._side_by_side(
                            conf, q_lat.astype(cd), q_rope.astype(cd)),
                        new["ckr"], precision=hi, preferred_element_type=F32)
                else:
                    sc = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(cd), new["c"],
                                     precision=hi, preferred_element_type=F32)
                          + jnp.einsum("bhe,bse->bhs", q_rope.astype(cd), new["kr"],
                                       precision=hi, preferred_element_type=F32))
                sc = sc / scale
                if k:       # the whole table under the choice: the kernel's oracle
                    seen = picked
                else:
                    seen = jnp.arange(cells)[None, :] <= pos[:, None]
                    if s.window:
                        seen = seen | (pos[:, None] >= cells)
                p = jax.nn.softmax(jnp.where(seen[:, None, :], sc, -1e30), axis=-1)
            with scope("attend"):
                # over a joint table the sum takes the rotary keys along (an
                # eighth more) and drops them after: no copy of the cells read
                lat = jnp.einsum("bhs,bsr->bhr", p.astype(cd),
                                 new["ckr" if joint else "c"], precision=hi,
                                 preferred_element_type=F32)
                if joint:
                    lat = lat[..., :s.kv_lora_rank]
        with scope("absorb"):
            o = jnp.einsum("bhr,rhv->bhv", lat.astype(cd), w_v.astype(cd),
                           precision=hi, preferred_element_type=F32)
        if gate is not None:
            with scope("gate"):
                o = o * gate[..., None]
        with scope("proj"):
            out = mm(o.reshape(b, -1), params["Wo"], cd)
        return x.astype(F32) + out, new

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        """The whole sequence, materialised; the cache it fills is dropped."""
        b, t = x.shape[0], x.shape[1]
        out, _ = MLALayer.prefill(params, conf, x,
                                  MLALayer.init_state(conf, b, t), None)
        return out
