"""Cache-aware autoregressive decoding over a stacked network.

The serving generation path (ISSUE 14) splits a generative forward into
two compiled programs instead of re-running the whole prefix every token:

  prefill      run the prompt once through the normal sequence forward,
               recording attention K/V rows into pre-allocated
               [B, max_S, n] caches (LSTM carries (h, c) the same way),
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
  ATTENTION         {"k": [B, max_S, n] compute_dtype, "v": same}
  KDA               {"S": [B, H, dk, dv] f32, "conv": [B, K-1, 3 H dk]}
  MLA               {"c": [B, max_S, rank], "kr": [.., rope]}, or side by
                    side {"ckr": [B, max_S or window, rank + rope]} and
                    with an indexer also {"ki": [B, max_S, index dim]}
  GQA               {"k": [B, G, max_S or window, h] compute_dtype, "v": same}
  everything else   {}
each made by its layer class's `init_state`; `CARRY` on the class says
whether the state advances with every token (so that `decode_block` must
hold a finished row's still) or is a table written at `pos`.  `zero_row`
and `write_row` treat every kind alike, leaf by leaf along axis 0.  A type
whose class has no `init_paged_state` and `verify_chunk` lives in the dense
table only: the paged pool and the verify chunk refuse it (`dense_only`).
The tuple-of-dicts shape makes the whole state one donatable jit
argument whose leaves keep their shapes/dtypes across steps, so the
compiled step can alias its cache buffers in place.

Paged variant (ISSUE 16): `init_paged_state` replaces each ATTENTION
layer's dense [B, max_S, n] table with a shared physical page pool
  ATTENTION         {"k": [n_pages, page_size, n], "v": same}
addressed through a per-call `page_table` [B, pages_per_slot] int32 of
physical page ids — cache memory scales with LIVE pages, not
slots x max_seq.  `decode_step`, `decode_block` and `verify_chunk` take the
table as `page_table=None` and hand it to the layers; how attention writes
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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import LayerType, MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.layers.output import OutputLayer
from deeplearning4j_tpu.utils.profiling import layer_scope, scope

#: hidden layer types the decode path knows how to step one token at a time
GENERATIVE_HIDDEN = (LayerType.LSTM, LayerType.GRAVES_LSTM,
                     LayerType.ATTENTION, LayerType.TRANSFORMER_FFN,
                     LayerType.KDA, LayerType.MLA, LayerType.GQA,
                     LayerType.SWIGLU, LayerType.MOE)

#: token emitted by `decode_block` for scan steps a row sat frozen
#: (its `rem` budget exhausted mid-block) — never a valid token id
BLOCK_SENTINEL = -1


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
    cells a step needs of a row at `max_seq`, and how many its decode step
    reads (`kv_cells_read`)."""
    out = []
    for _, c, impl in _hidden(conf):
        if hasattr(impl, "kv_cells"):
            out += zip(np.atleast_1d(impl.kv_cells(c, max_seq)).tolist(),
                       np.atleast_1d(impl.kv_cells_read(c, max_seq)).tolist())
    return out


def selected_cells(conf: MultiLayerConfiguration, max_seq: int) -> list:
    """For every layer whose decode step picks the cached positions it
    attends to (`selects`), how many it picks for a row at `max_seq`."""
    picks = [impl.selects(c, max_seq) for _, c, impl in _hidden(conf)
             if hasattr(impl, "selects")]
    return [k for k in picks if k]


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
        probs = OutputLayer.forward(params[last], conf.conf(last), x)
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


def decode_block(conf: MultiLayerConfiguration, params, state, tok, pos,
                 keys, temps, rem, k: int, sample, page_table=None):
    """Fused multi-step decode (ISSUE 19): advance every row up to `k`
    tokens in ONE program — a `lax.scan` whose body is exactly
    `decode_step` (over the paged state when `page_table` is given)
    followed by the injected `sample(logp, keys, temps) -> (tok, keys)`
    on-device sampler.  One host dispatch per K tokens instead of per
    token; the token trajectory is bitwise-identical to K sequential
    one-step calls for any K.

    rem [B] int32 is each row's remaining token budget.  A row whose
    budget hits 0 mid-block FREEZES: its tok/pos/key and recurrent
    carries stop advancing (cheap [B]-shaped `where`s — no full-cache
    select), and its scan outputs turn into `BLOCK_SENTINEL`.  Its K/V
    cache needs no mask at all: with tok and pos frozen, the step
    rewrites the SAME cache cell with bitwise-identical values
    (deterministic math over identical inputs), so "stops mutating"
    holds value-for-value, and for released paged rows the host's
    page table already points every write at the inert scratch page.

    The key-split discipline matches the one-step path exactly: the
    sampler runs over the full batch every scan step, but a frozen
    row's advanced key is discarded, so its key splits precisely once
    per token it actually emitted — the same count K=1 decoding burns.

    Returns (toks [k, B] int32 scan outputs, tok [B] (last real token
    per row), keys [B, 2], state) — state LAST, the donation/TP
    contract every decode-family program shares.  Where the stack has
    layers that count (`has_experts`), their counts summed over the k steps,
    [2] int32, come before the state."""
    carried = [i for i, _, impl in _hidden(conf) if impl.CARRY]

    def hold(active, new, old):
        """`new` for the rows still going, `old` for the finished ones."""
        return jnp.where(active.reshape((-1,) + (1,) * (new.ndim - 1)),
                         new, old)

    def body(carry, _):
        st, t, p, ks, r = carry
        active = r > 0
        logp, st2, counts = step(conf, params, st, t, p, page_table)
        t2, ks2 = sample(logp, ks, temps)
        frozen = list(st2)
        for i in carried:
            frozen[i] = {name: hold(active, st2[i][name], st[i][name])
                         for name in st2[i]}
        out = jnp.where(active, t2, jnp.int32(BLOCK_SENTINEL))
        t3 = jnp.where(active, t2, t)
        ks3 = jnp.where(active[:, None], ks2, ks)
        p3 = jnp.where(active, p + 1, p)
        r3 = jnp.where(active, r - 1, r)
        return (tuple(frozen), t3, p3, ks3, r3), (
            out if counts is None else (out, counts))

    carry, outs = jax.lax.scan(
        body, (state, tok, pos, keys, rem), xs=None, length=int(k))
    state, tok, _, keys, _ = carry
    if has_experts(conf):
        toks, counts = outs
        return toks, tok, keys, jnp.sum(counts, axis=0), state
    return outs, tok, keys, state


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
