"""Cache-aware autoregressive decoding over a stacked network.

The serving generation path (ISSUE 14) splits a generative forward into
two compiled programs instead of re-running the whole prefix every token:

  prefill      run the prompt once through the normal sequence forward,
               recording attention K/V rows into pre-allocated
               [B, H, max_S, hd] caches (LSTM carries (h, c) the same way),
               and return the next-token log-probs at each row's last
               real prompt position.
  decode_step  advance every row by ONE token against the recorded
               state: attention scores are [B, H, max_S] — one
               sequence-scaled axis, never a materialized [S, S] — and
               the LSTM applies its per-step cell exactly as the eager
               `models/char_lstm.py` sampler does.

Both entries return `log(clip(probs, 1e-9, 1))` — byte-for-byte the
transform the eager sampler applies — so a greedy compiled decode
reproduces the eager token trajectory exactly in f32.  The compiled
wrappers (key schema, donation, sampling) live in
`optimize/infer_cache.py`; the layer math and what each layer type keeps
between tokens live in `nn/layers/`.  This module is one loop over the
layers a phase: it calls the protocol that `nn/layers/__init__.py` states
and never asks a layer's type to know what its state is or how to step it.

State layout: one dict per layer, in layer order, as a tuple —
  LSTM/GRAVES_LSTM  {"h": [B, H] f32, "c": [B, H] f32}
  ATTENTION         {"k": [B, H, max_S, hd] compute_dtype, "v": same}
  KDA               {"S": [B, H, dk, dv] f32, "conv": [B, K-1, 3 H dk]}
  MLA               {"c": [B, max_S, rank], "kr": [.., rope]}, or side by
                    side {"ckr": [B, max_S or window, rank + rope]} and
                    with an indexer also {"ki": [B, max_S, index dim]}
  GQA               {"k": [B, G, max_S or window, h] compute_dtype, "v": same}
  everything else   {}
each made by its layer class's `init_state`; `CARRY` on the class says
whether the state advances with every token (a step reads it whole) or is
a table written at `pos`.  `zero_row` and `write_row` treat every kind
alike, leaf by leaf along axis 0.  A type
whose class has no `init_paged_state` and `verify_chunk` lives in the dense
table only: the paged pool and the verify chunk refuse it (`dense_only`).
The tuple-of-dicts shape makes the whole state one donatable jit
argument whose leaves keep their shapes/dtypes across steps, so the
compiled step can alias its cache buffers in place.

Paged variant (ISSUE 16): `init_paged_state` replaces each ATTENTION
layer's dense [B, H, max_S, hd] table with a shared physical page pool
  ATTENTION         {"k": [n_pages, page_size, n], "v": same}
addressed through a per-call `page_table` [B, pages_per_slot] int32 of
physical page ids — cache memory scales with LIVE pages, not
slots x max_seq.  `decode_step` and `verify_chunk` take the table as
`page_table=None` and hand it to the layers; how attention writes
and reads through it, token-identical to the dense step, is told at
`layers/attention.py:decode_step`.  The host (serving/batcher.py) owns
the free list and keeps physical page 0 as a scratch page every
inactive slot's table rows point at.

`verify_chunk` (speculative decoding) advances every row K tokens in
ONE program — the target-model verification step: token i of the chunk
attends causally at position pos + i against the cache, LSTM carries
step K times in-graph, and the returned [B, K, vocab] log-probs are
what greedy acceptance compares draft tokens against.  Rows re-walk a
mis-speculated suffix by simply rewriting those positions next call —
the cache never needs a rollback because `decode_step`/`verify_chunk`
always overwrite position `pos` before attending to it.

`StepCounts` is what the serving loop knows of the layers' reads: built once
for a slot table, it turns one completed step's row positions (and the
expert layers' counts) into the cells, picks and bytes that step read, and
running totals of those into the loop's counters.
"""

from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import LayerType, MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import GENERATIVE_HIDDEN, get_layer
from deeplearning4j_tpu.nn.layers.base import compute_dtype
from deeplearning4j_tpu.nn.layers.output import OutputLayer
from deeplearning4j_tpu.utils.profiling import layer_scope, scope

def check_generative(conf: MultiLayerConfiguration):
    """Validate that `conf` is a decodable generative stack and return
    its layer types: optional leading EMBEDDING, then hidden layers of
    `GENERATIVE_HIDDEN` (causal attention only), then a final OUTPUT layer;
    the only preprocessor
    allowed is the trailing rnn_to_ff (which the per-token decode skips —
    its activations are already [B, n])."""
    n = conf.n_layers
    if n < 2:
        raise ValueError("generation needs at least one hidden layer "
                         "and an OUTPUT layer")
    types = [LayerType(str(conf.conf(i).layer_type)) for i in range(n)]
    if types[-1] != LayerType.OUTPUT:
        raise ValueError(f"last layer must be OUTPUT, got {types[-1]}")
    start = 1 if types[0] == LayerType.EMBEDDING else 0
    for i, t in enumerate(types[start:-1], start):
        if t not in GENERATIVE_HIDDEN:
            raise ValueError(
                f"layer {i} ({t}) has no single-token decode path; "
                f"generative stacks may use {[str(x) for x in GENERATIVE_HIDDEN]}")
        if t == LayerType.ATTENTION and not conf.conf(i).causal:
            raise ValueError(
                f"layer {i}: only causal attention can decode "
                f"autoregressively")
    for idx, name in conf.input_preprocessors:
        if not (idx == n - 1 and name == "rnn_to_ff"):
            raise ValueError(
                f"preprocessor {name!r} at layer {idx} is incompatible "
                f"with token decoding (only the trailing rnn_to_ff is)")
    return types


def positional_bound(conf: MultiLayerConfiguration) -> int:
    """Hard sequence-length ceiling imposed by a learned positional
    table, or 0 when the stack has none (one-hot / recurrent stacks
    decode unbounded).  `params[0]["P"][pos]` clamps silently under jit
    past this bound, so admission (serving/batcher.py) must enforce it
    on the host — `init_state` only covers the dense-table path."""
    types = check_generative(conf)
    if types[0] == LayerType.EMBEDDING:
        return int(conf.conf(0).max_seq_len or 0)
    return 0


def _hidden(conf: MultiLayerConfiguration):
    """(index, layer conf, layer class) of every layer between the optional
    EMBEDDING and the OUTPUT layer: the ones that step a token and may keep
    a state.  The class is looked up anew at every walk."""
    types = check_generative(conf)
    first = 1 if types[0] == LayerType.EMBEDDING else 0
    return [(i, conf.conf(i), get_layer(conf.conf(i).layer_type))
            for i in range(first, conf.n_layers - 1)]


def dense_only(conf: MultiLayerConfiguration):
    """The layer types of `conf` whose state lives in the dense slot table
    alone, as strings ([] when there is none): those whose class declares
    neither `init_paged_state` nor `verify_chunk`.  The paged pool holds
    K/V pages and nothing else, a cached prefix row would have to carry a
    recurrent state that is only right at the prompt's end, and a verify
    chunk cannot roll such a state back."""
    return sorted({str(c.layer_type) for _, c, impl in _hidden(conf)
                   if not (hasattr(impl, "init_paged_state")
                           and hasattr(impl, "verify_chunk"))})


def has_experts(conf: MultiLayerConfiguration) -> bool:
    """Does a decode step of `conf` count expert picks (a layer type with
    a `counted_step`)?"""
    return any(hasattr(get_layer(c.layer_type), "counted_step")
               for c in conf.confs)


def experts_batched_layers(conf: MultiLayerConfiguration, rows: int) -> int:
    """Of the layers that count expert picks, how many compute them in the
    batched form (`product_form`) in a decode step of `rows` rows."""
    return sum(impl.product_form(c, rows) == "batched"
               for _, c, impl in _hidden(conf) if hasattr(impl, "counted_step"))


def kv_cells(conf: MultiLayerConfiguration, max_seq: int) -> list:
    """For every table of every layer whose class counts its state in cells
    a position (`kv_cells`, one number a layer or one a table): the most
    cells a step needs of a row at `max_seq`, how many its decode step reads
    (`kv_cells_read`: for a row that stands anywhere, the most), the block
    in which it walks a row's cells up to the row's position
    (`kv_read_block`; 0: it reads them all) and the bytes of a cell
    (`kv_cell_bytes`)."""
    out = []
    for _, c, impl in _hidden(conf):
        if hasattr(impl, "kv_cells"):
            out += zip(*(np.atleast_1d(f(c, max_seq)).tolist() for f in (
                impl.kv_cells, impl.kv_cells_read, impl.kv_read_block,
                impl.kv_cell_bytes)))
    return out


def state_bytes_read(conf: MultiLayerConfiguration, max_seq: int):
    """What a decode step reads of one row's state, in bytes as the state
    lies in memory, by kind: `table`, of every layer that counts its state
    in cells, the most cells its step reads times a cell's bytes
    (`kv_cells_read` x `kv_cell_bytes`; where the step walks a row's blocks
    up to its position, `StepCounts` counts them by the rows' positions),
    and `carry`, of every layer whose state advances
    with each token (`CARRY`), the whole of it.  None where a layer keeps a
    state that says neither: its read cannot be counted."""
    out = {"table": 0, "carry": 0}
    for _, c, impl in _hidden(conf):
        if hasattr(impl, "kv_cell_bytes"):
            out["table"] += int(np.dot(
                np.atleast_1d(impl.kv_cells_read(c, max_seq)),
                np.atleast_1d(impl.kv_cell_bytes(c, max_seq))))
            continue
        row = jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: impl.init_state(c, 1, max_seq)))
        if row and not impl.CARRY:
            return None
        out["carry"] += sum(a.size * a.dtype.itemsize for a in row)
    return out


def selected_cells(conf: MultiLayerConfiguration, max_seq: int) -> list:
    """For every layer whose decode step picks the cached positions it
    attends to (`selects`), how many it picks for a row at `max_seq`."""
    picks = [impl.selects(c, max_seq) for _, c, impl in _hidden(conf)
             if hasattr(impl, "selects")]
    return [k for k in picks if k]


def kernel_layers(conf: MultiLayerConfiguration, max_seq: int) -> int:
    """How many tables a decode step at `max_seq` walks through a kernel, a
    row's blocks up to its position (`kv_read_block` > 0): one a layer that
    attends through `dl4j_gqa_decode` or `dl4j_dsa_decode`."""
    return sum(block > 0 for _, _, block, _ in kv_cells(conf, max_seq))


def _cells_needed(pos, adv, most: int) -> int:
    """Summed over the rows that advance (`adv` 1, not 0), at their positions
    `pos`: `min(position + 1, most)` cells."""
    return int((adv * np.minimum(pos + 1, most)).sum())


def _cells_walked(pos, block: int, most: int) -> int:
    """Summed over every row, live or not: the cells of a table of `most`
    that a read walking blocks of `block` up to the row's position covers,
    `ceil((position + 1) / block) x block` (a position past the table walks
    it whole)."""
    return int(((np.clip(pos, -1, most - 1) + block) // block * block).sum())


#: the `stats()` key under which the serving loop reports each count's total
_TOTALS = {"picks_here": "expert_picks_total", "experts_hit": "experts_hit_total",
           "kv_cells_live": "kv_cells_live_total",
           "kv_cells_spanned": "kv_cells_spanned_total",
           "dsa_cells_live": "dsa_cells_live_total",
           "dsa_cells_selected": "dsa_cells_selected_total",
           "ring_cells_live": "ring_cells_live_total",
           "ring_cells_spanned": "ring_cells_spanned_total"}


class StepCounts:
    """What a decode step of `conf` over a slot table of `n_slots` rows and
    `max_seq` positions reads, counted from the rows' positions: the layers'
    descriptors above, asked once.  `step` turns one completed step into the
    counts its `decode` span carries; `totals` turns the counts summed over
    steps into the serving loop's `stats()` keys.  The loop names no layer
    kind: a stack without a kind of state has none of its counts."""

    def __init__(self, conf: MultiLayerConfiguration, n_slots: int,
                 max_seq: int):
        self.n_slots, self.max_seq = int(n_slots), int(max_seq)
        self.has_experts = has_experts(conf)
        # static a program: a decode step is one call of `n_slots` rows
        self.experts_batched_layers = experts_batched_layers(conf, self.n_slots)
        # {(cells a row holds, the most of them a step reads, the block it
        # walks them in or 0, a cell's bytes): tables} of the layers that
        # count their state in cells a position
        self.kv_cells = Counter(kv_cells(conf, self.max_seq))
        # of those, the rings: a table whose step reads fewer cells than a
        # row has positions holds a window's, `{cells: tables}` (an indexed
        # table NEEDS fewer, its picks, but is read whole)
        self.rings = Counter()
        for (_, read, _, _), n in self.kv_cells.items():
            if read < self.max_seq:
                self.rings[read] += n
        # {kind: bytes a decode step reads of one row's state}, or None
        self.state_bytes = state_bytes_read(conf, self.max_seq)
        # {positions a layer's step picks among a row's cached ones: layers}
        self.dsa_picks = Counter(selected_cells(conf, self.max_seq))
        # static a program: the tables a step walks through a kernel
        self.kernel_layers = kernel_layers(conf, self.max_seq)
        # the counts of `step` that `totals` reports, by what the stack has
        self._totalled = (
            ("picks_here", "experts_hit") * self.has_experts
            + ("kv_cells_live", "kv_cells_spanned") * bool(self.kv_cells)
            + ("dsa_cells_live", "dsa_cells_selected") * bool(self.kv_cells and self.dsa_picks)
            + ("ring_cells_live", "ring_cells_spanned") * bool(self.rings))

    def step(self, pos, adv, counts=None) -> dict:
        """The counts of one completed step in which row r, at position
        `pos[r]`, advanced `adv[r]` (1) or not (0: a free or a finished row),
        and the expert layers counted `counts` ([picks that landed on held
        experts, distinct held experts hit] summed over the layers; None for
        a stack without them), as `decode` span attributes:

        `picks_here`, `experts_hit` and `steps` (1), where there are counts.
        `kv_cells_live`: what the step has to read, `min(position + 1, the
        most the table can need)` a live row and table.  `kv_cells_spanned`:
        what the layers' reads cover, a row of the slot table and table:
        `kv_cells_read`, or where a layer walks a row's cells in blocks up to
        its position (`kv_read_block`) those blocks.  `state_bytes_read`:
        what the step read of every row's state, the spanned cells' bytes and
        every carry whole; `carry_bytes_read`, where the stack has a carry,
        the carries' part of it alone.  `dsa_cells_live` and
        `dsa_cells_selected`, for layers that pick the positions they attend
        to: the positions cached at or before a live row's own and
        `min(position + 1, picks)` of them, a row and such layer; they
        describe the traffic (how far the rows are past the picks), not the
        read.  `ring_cells_live` and
        `ring_cells_spanned`, for the rings among the tables (read whole a
        step): `min(position + 1, window)` a live row and ring, and `slots x
        window` a ring.  A count the stack cannot have is left out."""
        out = {}
        if counts is not None:
            picks, hit = (int(n) for n in counts)
            out.update(picks_here=picks, experts_hit=hit, steps=1)
        p, a = np.asarray(pos, np.int64), np.asarray(adv, np.int64)
        live = spanned = table_bytes = 0
        for (most, read, block, cell), n in self.kv_cells.items():
            live += n * _cells_needed(p, a, most)
            cells = n * (_cells_walked(p, block, read) if block
                         else self.n_slots * read)
            spanned += cells
            table_bytes += cells * cell
        if self.state_bytes and sum(self.state_bytes.values()):
            out["state_bytes_read"] = (table_bytes + self.n_slots
                                       * self.state_bytes["carry"])
            if self.state_bytes["carry"]:
                out["carry_bytes_read"] = self.n_slots * self.state_bytes["carry"]
        if not self.kv_cells:
            return out
        out.update(kv_cells_live=live, kv_cells_spanned=spanned)
        if self.dsa_picks:
            out["dsa_cells_live"] = sum(
                n * _cells_needed(p, a, self.max_seq) for n in self.dsa_picks.values())
            out["dsa_cells_selected"] = sum(
                n * _cells_needed(p, a, k) for k, n in self.dsa_picks.items())
        if self.rings:
            out["ring_cells_live"] = sum(
                n * _cells_needed(p, a, w) for w, n in self.rings.items())
            out["ring_cells_spanned"] = sum(
                n * self.n_slots * w for w, n in self.rings.items())
        return out

    def totals(self, sums) -> dict:
        """The serving loop's `stats()` keys from `sums`, `step`'s counts
        summed over steps: the running totals of the counts this stack has
        (`expert_picks_total` ... `ring_cells_spanned_total`, 0 before any
        step), `experts_batched_layers` beside the experts', and
        `kernel_layers` and `state_bytes_per_row` always."""
        out = {_TOTALS[name]: sums.get(name, 0) for name in self._totalled}
        if self.has_experts:
            out["experts_batched_layers"] = self.experts_batched_layers
        out.update(kernel_layers=self.kernel_layers,
                   state_bytes_per_row=self.state_bytes)
        return out


def operand_tree(conf: MultiLayerConfiguration, params):
    """`params` as a generation program may take them: every leaf that its
    layer's class declares an operand (`OPERANDS`) held in the layer's
    compute dtype where it is stored wider, so that a program reads the
    weights its products multiply and not the masters they are cast from
    (the products are the same).  Returns (tree, the narrowed leaves in
    layer order).  Every other leaf is the same object, and where nothing
    is wider the tree is `params` itself."""
    out, narrowed = list(params), []
    for i, layer in enumerate(params):
        c = conf.conf(i)
        cd = compute_dtype(c)
        for path in getattr(get_layer(c.layer_type), "OPERANDS", ()):
            *outer, name = (path,) if isinstance(path, str) else path
            node = out[i]
            for key in outer:
                node = node.get(key, {})
            leaf = node.get(name)
            dtype = getattr(leaf, "dtype", None)
            if (dtype is None or not jnp.issubdtype(dtype, jnp.floating)
                    or jnp.dtype(dtype).itemsize <= cd.itemsize):
                continue
            narrow = leaf.astype(cd)
            narrowed.append(narrow)
            out[i] = _replaced(out[i], list(outer) + [name], narrow)
    return (params, []) if not narrowed else (type(params)(out), narrowed)


def _replaced(tree: dict, path: list, leaf) -> dict:
    """A copy of the nested dict `tree` with `leaf` at `path`, sharing every
    other entry."""
    key, *rest = path
    return {**tree, key: _replaced(tree[key], rest, leaf) if rest else leaf}


def _refuse_dense_only(conf, what: str) -> None:
    kinds = dense_only(conf)
    if kinds:
        raise ValueError(
            f"{what} cannot hold the state of layer types {kinds}: it lives "
            f"in the dense slot table only")


def _states(conf, of) -> tuple:
    """One dict a layer: `of(layer conf, layer class)` for the hidden
    layers, {} for the EMBEDDING and the OUTPUT layer."""
    state = [{} for _ in range(conf.n_layers)]
    for i, c, impl in _hidden(conf):
        state[i] = of(c, impl)
    return tuple(state)


def init_state(conf: MultiLayerConfiguration, batch: int, max_seq: int):
    """Fresh decode state for `batch` rows and a `max_seq`-token table."""
    table = positional_bound(conf)
    if table and max_seq > table:
        raise ValueError(
            f"max_seq={max_seq} exceeds the learned positional table "
            f"(max_seq_len={table})")
    return _states(conf, lambda c, impl: impl.init_state(c, batch, max_seq))


def init_paged_state(conf: MultiLayerConfiguration, batch: int,
                     n_pages: int, page_size: int):
    """Fresh paged decode state: recurrent carries stay per-slot
    [batch, H], but each ATTENTION layer's K/V become one shared
    physical pool [n_pages, page_size, n] addressed through the
    per-call page table — memory scales with pages, not
    batch x max_seq."""
    _refuse_dense_only(conf, "a paged K/V pool")
    return _states(conf, lambda c, impl: impl.init_paged_state(
        c, batch, n_pages, page_size))


def zero_row(table):
    """A fresh B=1 row state for one slot of `table`: leaf for leaf what
    `init_state(conf, 1, max_seq)` gives for the table's own conf and
    `max_seq`.  Traced inside a program the zeros are constants, not
    dispatches."""
    return jax.tree_util.tree_map(
        lambda t: jnp.zeros((1,) + t.shape[1:], t.dtype), table)


def write_row(table, row, slot):
    """Write a B=1 row state into row `slot` of a table state, leaf by
    leaf along axis 0 (`h`/`c` carries and `k`/`v` tables alike), and
    return the table.  `slot` is a traced int32 scalar, so one program
    serves every slot; with the table donated the write is in place and
    no other row is touched."""
    def put(t, r):
        at = (slot,) + (jnp.zeros_like(slot),) * (t.ndim - 1)
        return jax.lax.dynamic_update_slice(t, r.astype(t.dtype), at)

    with scope("row_write"):
        return jax.tree_util.tree_map(put, table, row)


def token_embed(conf: MultiLayerConfiguration, params, tok, pos):
    """Embed one token id per row: EMBEDDING stacks gather W[tok]
    (+ P[pos] rowwise when a positional table exists — NOT
    EmbeddingLayer.forward, whose P[:s] convention would misread a [B]
    id vector as a length-B sequence); one-hot stacks build the same
    f32 rows the eager sampler feeds (`eye[cid]`)."""
    c0 = conf.conf(0)
    if LayerType(str(c0.layer_type)) == LayerType.EMBEDDING:
        with layer_scope(0, c0), scope("embed"):
            e = params[0]["W"][tok]
            if "P" in params[0]:
                e = e + params[0]["P"][pos]
            return e
    with scope("embed"):
        return jax.nn.one_hot(tok, c0.n_in, dtype=jnp.float32)


def _head_logp(conf: MultiLayerConfiguration, params, x):
    """log(clip(probs)) of the OUTPUT layer over hidden rows x [rows, n],
    under the layer's scope."""
    last = conf.n_layers - 1
    with layer_scope(last, conf.conf(last)):
        probs = OutputLayer.forward(OutputLayer.head_params(conf, params),
                                    conf.conf(last), x)
        return jnp.log(jnp.clip(probs, 1e-9, 1.0))


def step(conf: MultiLayerConfiguration, params, state, tok, pos,
         page_table=None):
    """One token a row through every layer: (logp, state, counts).  A
    `page_table` is handed on to the layers, of which one whose state lives
    in the shared page pool reads and writes it there (a class that cannot
    page takes no such argument and never meets one: `init_paged_state`
    refused it).  `counts` is None unless the stack has layers that count
    their expert picks (`has_experts`), else their `[picks on held experts,
    distinct held experts hit]` summed over the layers."""
    x = token_embed(conf, params, tok, pos)
    pages = () if page_table is None else (page_table,)
    new_state = [{} for _ in range(conf.n_layers)]
    counts = None
    for i, c, impl in _hidden(conf):
        with layer_scope(i, c):
            if hasattr(impl, "counted_step"):
                x, new_state[i], n = impl.counted_step(
                    params[i], c, x, state[i], pos, *pages)
                counts = n if counts is None else counts + n
            else:
                x, new_state[i] = impl.decode_step(
                    params[i], c, x, state[i], pos, *pages)
    return _head_logp(conf, params, x), tuple(new_state), counts


def decode_step(conf: MultiLayerConfiguration, params, state, tok, pos,
                page_table=None):
    """Advance every row one token: tok [B] int32 (the row's current
    token), pos [B] int32 (the sequence position that token occupies).
    Returns (logp [B, vocab] — log(clip(probs)) for the NEXT token —
    and the updated state tuple); `step` is the same with the expert
    layers' counts beside them, and says what a `page_table`
    [B, pages_per_slot] int32 over a paged state does."""
    logp, state, _ = step(conf, params, state, tok, pos, page_table)
    return logp, state


def verify_chunk(conf: MultiLayerConfiguration, params, state, toks, pos,
                 page_table=None):
    """Speculative verification over a dense or (with `page_table`) a paged
    decode state: advance every row K tokens in one pass and return
    per-position log-probs.

    toks [B, K] int32 — toks[:, 0] is the row's current token, the rest
    are draft continuations; pos [B] int32 is the position of
    toks[:, 0].  Returns (logp [B, K, vocab], new_state, carries):
    logp[:, i] is the next-token distribution AFTER consuming
    toks[:, :i+1], exactly what `decode_step` would return on the i-th
    of K sequential calls.  `carries` holds, per layer whose state is a
    carry, the INTERMEDIATE carries (for an LSTM {"h"/"c": [B, K, hidden]})
    after each of the K steps ({} for every other layer): attention state
    self-heals on mis-speculation (rejected positions are rewritten before
    they are read) but a recurrent carry does not, so the caller must roll
    the returned final state back to carry index e-1 when it accepts only
    e < K tokens.
    """
    _refuse_dense_only(conf, "a verify chunk")
    b, kk = toks.shape
    idx = pos[:, None] + jnp.arange(kk)[None, :]
    x = token_embed(conf, params, toks, idx)  # [B, K, n]
    pages = () if page_table is None else (page_table,)
    new_state = [{} for _ in range(conf.n_layers)]
    carries = [{} for _ in range(conf.n_layers)]
    for i, c, impl in _hidden(conf):
        with layer_scope(i, c):
            x, new_state[i], carries[i] = impl.verify_chunk(
                params[i], c, x, state[i], pos, *pages)
    logp = _head_logp(conf, params, x.reshape(b * kk, -1)).reshape(b, kk, -1)
    return logp, tuple(new_state), tuple(carries)


def prefill(conf: MultiLayerConfiguration, params, state, prompt, length):
    """Fill the decode state from a prompt bucket: prompt [B, T] int32
    (zero-padded past each row's true `length`), length [B] int32 >= 1.
    Returns (logp [B, vocab] at each row's LAST real prompt position —
    what the first generated token samples from — and the filled state).

    Padding is inert by construction: LSTM carries freeze at
    t >= length (as a KDA layer's state does), attention's causal mask
    hides later positions from every real one, and `decode_step`
    overwrites cache position `pos` before attending to it."""
    layers = _hidden(conf)
    c0 = conf.conf(0)
    if layers[0][0] == 1:       # an EMBEDDING layer stands before them
        with layer_scope(0, c0), scope("embed"):
            x = get_layer(c0.layer_type).forward(params[0], c0, prompt)
    else:
        with scope("embed"):
            x = jax.nn.one_hot(prompt, c0.n_in, dtype=jnp.float32)
    new_state = [{} for _ in range(conf.n_layers)]
    for i, c, impl in layers:
        with layer_scope(i, c):
            x, new_state[i] = impl.prefill(params[i], c, x, state[i], length)
    b = prompt.shape[0]
    with layer_scope(conf.n_layers - 1, conf.conf(conf.n_layers - 1)):
        last = x[jnp.arange(b), length - 1]
    return _head_logp(conf, params, last), tuple(new_state)
