"""Mellum2's language model through `ContinuousBatcher`, at tiny widths on
the CPU: greedy streams of short and long prompts against the plain reference
of `benchmark/families/mellum`, a slot reused after a long request, the
options that cannot hold the state, scopes, the K/V cell counts on spans, in
`stats()` and on `/metrics`, the typed settings' round trip, the plan, and
what the change left as it was for the confs that were there.  The layers
are in `test_mellum_layers.py`."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.conf import (GQASpec, MoESpec, MultiLayerConfiguration,
                                        NeuralNetConfiguration)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from deeplearning4j_tpu.utils import profiling
from mellum_model import Model, f32      # noqa: F401  (f32: a fixture)

MAX_SEQ = 64
PROMPTS = (5, 30, 9, 3, 21)     # the window is 8: two prompts are past it
NEW = (6, 14, 6, 12, 6)         # and two answers wrap a ring they started in


def assert_greedy(m: Model, prompt, tokens):
    """Every served token is the reference's first at its position, in one
    teacher-forced pass over prompt and answer."""
    ids = np.concatenate([prompt, tokens]).astype(np.int32)[None]
    best = np.argmax(m.logp(ids)[0], axis=-1)
    n = len(prompt)
    assert list(tokens) == list(best[n - 1: n - 1 + len(tokens)])


@pytest.fixture(scope="module")
def served(f32):
    """Five requests through two slots (so slots are reused, once after the
    longest request), K=1."""
    net = MultiLayerNetwork(f32.conf)
    net.params = f32.params
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, f32.sizes["vocab"], n).astype(np.int32) for n in PROMPTS]
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=2, max_seq=MAX_SEQ,
                                prompt_buckets=(8, 16, 32), steps_per_dispatch=1).start()
    streams = [batcher.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEW)]
    tokens = [list(s.tokens(timeout=120)) for s in streams]
    stats = batcher.stats()
    batcher.stop()
    return prompts, tokens, stats, profiling.spans(), net


@pytest.mark.parametrize("request_no", range(5))
def test_a_greedy_stream_is_the_references(f32, served, request_no):
    """Short and long prompts in one queue.  Requests 2 to 4 run in slots
    that an earlier request used, request 3 (3 tokens in, 12 out) where the
    longest left a full ring: the stale cells are masked, not cleared."""
    prompts, tokens, *_ = served
    assert len(tokens[request_no]) == NEW[request_no]
    assert_greedy(f32, prompts[request_no], tokens[request_no])


def test_a_window_layers_state_is_the_window_whatever_max_seq(f32):
    for max_seq in (MAX_SEQ, 4096):
        state = jax.eval_shape(lambda: decode.init_state(f32.conf, 3, max_seq))
        for kind, lay in zip(f32.kinds, state):
            if kind in ("window", "full"):
                cells = 8 if kind == "window" else max_seq
                assert lay["k"].shape == lay["v"].shape == (3, 2, cells, 16), kind
            else:
                assert lay == {}
    assert decode.kv_cells(f32.conf, 4096) == [(8, 8)] * 3 + [(4096, 4096)]
    assert decode.kv_cells(f32.conf, 4) == [(4, 4)] * 4


@pytest.mark.parametrize("kind", ["window", "full"])
def test_the_cells_a_layer_says_it_reads_are_its_programs_keys(f32, kind):
    """`kv_cells_read` is the denominator of `attn.kv_live_share`: it has to
    be the key axis of the scores that `decode_step` computes."""
    from deeplearning4j_tpu.nn.layers.gqa import GQALayer

    i = f32.kinds.index(kind)
    conf, params = f32.conf.confs[i], f32.params[i]
    spec, rows = conf.layer_spec, 3
    state = GQALayer.init_state(conf, rows, MAX_SEQ)
    jaxpr = jax.make_jaxpr(lambda x, st, pos: GQALayer.decode_step(
        params, conf, x, st, pos))(
        np.zeros((rows, conf.n_in), np.float32), state, np.zeros((rows,), np.int32))
    per_group = spec.n_heads // spec.n_kv_heads
    keys = {e.outvars[0].aval.shape[-1] for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "dot_general"
            and e.outvars[0].aval.shape[:3] == (rows, spec.n_kv_heads, per_group)
            and e.outvars[0].aval.shape[-1] != spec.head_dim}
    assert keys == {GQALayer.kv_cells_read(conf, MAX_SEQ)}


def test_fused_blocks_serve_the_same_tokens_and_count_the_same_cells(f32, served):
    prompts, tokens, stats, spans, _ = served
    net = MultiLayerNetwork(f32.conf)
    net.params = f32.params
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=2, max_seq=MAX_SEQ,
                                prompt_buckets=(8, 16, 32), steps_per_dispatch=4).start()
    streams = [batcher.submit(p, max_new_tokens=n) for p, n in zip(prompts[:2], NEW[:2])]
    got = [list(s.tokens(timeout=120)) for s in streams]
    blocks = batcher.stats()
    batcher.stop()
    assert got == tokens[:2]
    # a row of n prompt tokens that is served m: its steps sit at positions
    # n .. n + m - 2 (the first token is the prefill's), each needing
    # min(position + 1, cells) cells a layer
    want = sum(3 * min(q + 1, 8) + min(q + 1, MAX_SEQ)
               for n, m in zip(PROMPTS[:2], NEW[:2]) for q in range(n, n + m - 1))
    assert blocks["kv_cells_live_total"] == want
    assert blocks["kv_cells_spanned_total"] % (2 * (3 * 8 + MAX_SEQ)) == 0


def test_enough_slots_take_the_batched_form_and_serve_the_same_tokens(f32, served):
    """8 experts, top 2: 16 rows are expected to hit 99 % of them, so the
    decode step of 16 slots computes its expert layers in the batched form
    (the 2 slots of `served`: 44 %, the sorted form).  The streams are the
    reference's greedy ones all the same, token for token."""
    from deeplearning4j_tpu.nn.layers.experts import MoELayer

    layers = f32.kinds.count("moe")
    moe = f32.conf.conf(f32.layer("moe"))
    assert [MoELayer.product_form(moe, rows) for rows in (2, 4, 16)] == [
        "sorted", "sorted", "batched"]
    assert decode.experts_batched_layers(f32.conf, 16) == layers
    net = MultiLayerNetwork(f32.conf)
    net.params = f32.params
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, f32.sizes["vocab"], n).astype(np.int32) for n in (5, 8, 3)]
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=16, max_seq=32, prompt_buckets=(8,),
                                steps_per_dispatch=1).start()
    streams = [batcher.submit(p, max_new_tokens=10) for p in prompts]
    tokens = [list(s.tokens(timeout=120)) for s in streams]
    stats = batcher.stats()
    batcher.stop()
    for prompt, got in zip(prompts, tokens):
        assert len(got) == 10
        assert_greedy(f32, prompt, got)
    steps = [s for s in profiling.spans() if s.name == "decode" and "picks_here" in s.attrs]
    # static a program: `stats()` says it once, no span repeats it a step
    assert steps and not any("experts_batched_layers" in s.attrs for s in steps)
    assert stats["experts_batched_layers"] == layers
    # the two slots of `served` stay with the sorted form
    assert served[2]["experts_batched_layers"] == 0


# ---------------------------------------------------------------- refusals

@pytest.mark.parametrize("option", [
    {"page_size": 8}, {"prefix_cache": True}, {"spec_k": 2, "draft_net": "lstm"}])
def test_options_that_cannot_hold_the_state_refuse_the_conf(f32, option):
    if option.get("draft_net"):
        from deeplearning4j_tpu.models.zoo import char_lstm
        option = {**option, "draft_net": MultiLayerNetwork(
            char_lstm(f32.sizes["vocab"], hidden=8)).init()}
    net = MultiLayerNetwork(f32.conf)
    net.params = f32.params
    with pytest.raises(ValueError, match=r"\['gqa'\].*a ring of a window's positions"):
        ContinuousBatcher(net, n_slots=2, max_seq=32, **option)
    assert decode.dense_only(f32.conf) == ["gqa"]
    with pytest.raises(ValueError, match="dense slot table"):
        decode.init_paged_state(f32.conf, 2, 4, 8)


# ----------------------------------------------------------------- tracing

SCOPES = {
    "window": ["ln", "qkv", "rope", "kv_write", "kv_read", "scores", "attend", "proj"],
    "full": ["ln", "qkv", "rope", "kv_write", "kv_read", "scores", "attend", "proj"],
    "moe": ["ln", "router", "dispatch", "experts", "combine"],
}
NAMES = {"window": "gqa_window", "full": "gqa_full", "moe": "moe"}


@pytest.mark.parametrize("entry", ["decode", "prefill"])
def test_the_scopes_are_in_the_lowered_programs(f32, entry):
    conf, params = f32.conf, f32.params
    if entry == "decode":
        lowered = jax.jit(lambda p, s, t, q: decode.decode_step(conf, p, s, t, q)).lower(
            params, decode.init_state(conf, 2, 16), np.zeros((2,), np.int32),
            np.zeros((2,), np.int32))
    else:
        lowered = jax.jit(lambda p, s, t, n: decode.prefill(conf, p, s, t, n)).lower(
            params, decode.init_state(conf, 1, 16), np.zeros((1, 8), np.int32),
            np.ones((1,), np.int32))
    text = lowered.as_text(debug_info=True)
    absent = {"prefill": ("kv_read",)}.get(entry, ())    # a prefill reads no cache
    for i, kind in enumerate(f32.kinds):
        for name in SCOPES.get(kind, ()):
            if name not in absent:
                assert f"L{i}.{NAMES[kind]}/{name}" in text, (entry, i, kind, name)
    assert "/shared" not in text and "L1.gqa/" not in text


def test_decode_spans_carry_the_cells_and_the_experts_counts(f32, served):
    _, tokens, stats, spans, _ = served
    steps = [s for s in spans if s.name == "decode" and "kv_cells_live" in s.attrs]
    assert steps and all("experts_hit" in s.attrs for s in steps)
    per_row = 3 * 8 + MAX_SEQ
    for s in steps:
        assert s.attrs["steps"] == 1 and 1 <= s.attrs["live"] <= 2
        assert s.attrs["kv_cells_spanned"] == 2 * per_row      # both slots' state
        assert 4 * s.attrs["live"] <= s.attrs["kv_cells_live"] <= s.attrs["live"] * per_row
        assert s.attrs["picks_here"] == 2 * 2 * 4      # both slots' rows, top 2, 4 layers
    assert stats["kv_cells_live_total"] == sum(s.attrs["kv_cells_live"] for s in steps)
    assert stats["kv_cells_spanned_total"] == sum(s.attrs["kv_cells_spanned"] for s in steps)
    want = sum(3 * min(q + 1, 8) + min(q + 1, MAX_SEQ)
               for n, m in zip(PROMPTS, NEW) for q in range(n, n + m - 1))
    assert stats["kv_cells_live_total"] == want
    admits = [s for s in spans if s.name == "admit"]
    assert sorted(s.attrs["bucket"] for s in admits) == [8, 8, 16, 32, 32]
    assert sorted(s.attrs["prompt_tokens"] for s in admits) == sorted(PROMPTS)


def test_the_counters_are_exported(served):
    from deeplearning4j_tpu.serving import metrics

    stats = served[2]
    page = metrics.replica_metrics({"generation": stats})
    assert f"dl4j_serving_kv_cells_live_total {stats['kv_cells_live_total']}" in page
    assert f"dl4j_serving_kv_cells_spanned_total {stats['kv_cells_spanned_total']}" in page
    without = {k: v for k, v in stats.items() if not k.startswith("kv_cells")}
    assert "kv_cells" not in metrics.replica_metrics({"generation": without})


def test_a_stack_without_such_layers_counts_no_cells():
    from deeplearning4j_tpu.models.zoo import char_transformer

    net = MultiLayerNetwork(char_transformer(32, d_model=16, n_blocks=1, n_heads=2,
                                             max_seq_len=16)).init()
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=1, max_seq=16, prompt_buckets=(8,)).start()
    list(batcher.submit(np.arange(3, dtype=np.int32), max_new_tokens=3).tokens(timeout=120))
    stats = batcher.stats()
    batcher.stop()
    assert not any(k.startswith("kv_cells") for k in stats)
    assert not any("kv_cells_live" in s.attrs for s in profiling.spans())


# ------------------------------------------------------- conf, plan, old confs

def test_the_typed_settings_round_trip(f32):
    again = MultiLayerConfiguration.from_json(f32.conf.to_json())
    assert again == f32.conf and hash(again) == hash(f32.conf)
    kinds = {type(c.layer_spec).__name__ for c in f32.conf.confs if c.layer_spec}
    assert kinds == {"GQASpec", "MoESpec", "HeadSpec"}
    full = again.conf(f32.layer("full")).layer_spec
    assert isinstance(full.yarn, tuple) and full.yarn[-1] == 1.2772588722239782
    flat = {f.name for f in dataclasses.fields(NeuralNetConfiguration)}
    assert not flat & {"n_kv_heads", "window", "yarn", "score"}
    # the two fields MoESpec gained are written only where they are not the
    # default: a conf from before them serialises as it did
    old = MoESpec(n_routed=8, n_held=4, hidden=8, shared_hidden=8)
    as_json = NeuralNetConfiguration(layer_type="moe", layer_spec=old).to_dict()["layer_spec"]
    assert "score" not in as_json and "router_bias" not in as_json
    new = f32.conf.conf(f32.layer("moe")).to_dict()["layer_spec"]
    assert (new["score"], new["router_bias"]) == ("softmax", False)
    assert GQASpec(n_heads=4, n_kv_heads=2, head_dim=8).scope_kind == "gqa_full"


def test_the_plan_keeps_the_new_leaves_whole(f32):
    from jax.sharding import Mesh, PartitionSpec as P

    from deeplearning4j_tpu.parallel.plan import ShardPlan

    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    plan = ShardPlan(Mesh(devs, ("batch", "model")))
    state = jax.eval_shape(lambda: decode.init_state(f32.conf, 4, 16))
    specs = plan.state_pspecs(state)
    seen = 0
    for lay, spec in zip(state, specs):
        for name in lay:
            assert name in ("k", "v") and spec[name] == P()
            seen += 1
    assert seen == 8
    table = {"k": jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)}    # GPT-2's [B, S, n]
    assert plan.state_pspecs((table,))[0]["k"] == P(None, None, "model")


# Lowered at the parent commit (6a2c6f5) with this file's own recipe: the
# tiny Ling-3.0 stack's decode step and prefill, and a tiny GPT-2 block's.
# Equal texts: the change reached no program of the confs that were there.
PARENT_TEXTS = {
    "ling3:decode": "0343380428ba6d8a", "ling3:prefill": "39a4806081d65b43",
    "gpt2:decode": "5501944fbe9980c9", "gpt2:prefill": "f09c30c3315d46bf"}


def lowered_texts() -> dict:
    from ling3_model import Model as Ling

    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn.multilayer import init_params

    confs = {"ling3": Ling("float32").conf,
             "gpt2": char_transformer(64, d_model=32, n_blocks=1, n_heads=2, max_seq_len=16)}
    out = {}
    for name, conf in confs.items():
        params = jax.eval_shape(lambda k: init_params(conf, k), jax.random.PRNGKey(0))
        table = lambda rows: jax.eval_shape(lambda: decode.init_state(conf, rows, 16))  # noqa: E731
        ids = jax.ShapeDtypeStruct((2,), jnp.int32)
        out[f"{name}:decode"] = jax.jit(
            lambda p, s, t, q: decode.decode_step(conf, p, s, t, q)).lower(
                params, table(2), ids, ids).as_text()
        out[f"{name}:prefill"] = jax.jit(
            lambda p, s, t, n: decode.prefill(conf, p, s, t, n)).lower(
                params, table(1), jax.ShapeDtypeStruct((1, 8), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in out.items()}


def test_the_older_confs_lower_to_the_parents_texts():
    assert lowered_texts() == PARENT_TEXTS
