"""dots3-note-prev's language model through `ContinuousBatcher`, at tiny
widths on the CPU: greedy streams of prompts past `index_topk` and the
window against the plain reference of `benchmark/families/dots3_note`, a slot
reused after a long request, the options that cannot hold the state, scopes,
the cells and the selection counted on spans, in `stats()` and on
`/metrics` against a hand count, what the layers say their steps read
against the steps' own scores, the typed settings' round trip, the plan, and
Ling's `mla` programs as they were.  The layers are in
`test_dots3_layers.py`."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.conf import (LayerType, MLASpec, MultiLayerConfiguration,
                                        NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers.mla import MLALayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from deeplearning4j_tpu.utils import profiling
from dots3_model import Model, f32      # noqa: F401  (f32: a fixture)

MAX_SEQ = 64
PROMPTS = (5, 30, 9, 3, 21)     # index_topk is 8, the window 5: four are past both
NEW = (6, 14, 6, 12, 6)


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
    """Requests 2 to 4 run in slots that an earlier request used, request 3
    (3 tokens in, 12 out) where the longest left full rings and 43 index
    keys: the stale cells are masked, never cleared, and never picked."""
    prompts, tokens, *_ = served
    assert len(tokens[request_no]) == NEW[request_no]
    assert_greedy(f32, prompts[request_no], tokens[request_no])


def test_the_state_is_two_tables_and_an_index_or_a_ring(f32):
    for max_seq in (MAX_SEQ, 4096):
        state = jax.eval_shape(lambda: decode.init_state(f32.conf, 3, max_seq))
        for kind, lay in zip(f32.kinds, state):
            shapes = {k: v.shape for k, v in lay.items()}
            if kind == "full":
                assert shapes == {"ckr": (3, max_seq, 128), "ki": (3, max_seq, 16)}
            elif kind == "window":
                assert shapes == {"ckr": (3, 5, 128)}
            else:
                assert lay == {}
    # a full layer: the picked latents, and every index key; a ring: its cells
    assert decode.kv_cells(f32.conf, 4096) == [(8, 8), (4096, 4096)] * 2 + [(5, 5)]
    assert decode.selected_cells(f32.conf, 4096) == [8, 8]
    # a table no longer than what would be picked: read whole, nothing picked
    assert decode.kv_cells(f32.conf, 4) == [(4, 4)] * 3
    assert decode.selected_cells(f32.conf, 8) == []


@pytest.mark.parametrize("kind", ["full", "window"])
def test_the_cells_a_layer_says_it_reads_are_its_programs_keys(f32, kind):
    """`kv_cells_read` is the denominator of `attn.kv_live_share`: it has to
    be the key axis of the scores that `decode_step` computes, the latents'
    [rows, H, cells] and, in an indexed layer, the index scores' [rows, J,
    cells]."""
    i = f32.layer(kind, 1 if kind == "full" else 0)
    conf, params = f32.conf.confs[i], f32.params[i]
    spec, rows = conf.layer_spec, 3
    state = MLALayer.init_state(conf, rows, MAX_SEQ)
    jaxpr = jax.make_jaxpr(lambda x, st, pos: MLALayer.decode_step(
        params, conf, x, st, pos))(
        np.zeros((rows, conf.n_in), np.float32), state, np.zeros((rows,), np.int32))
    dots = [e.outvars[0].aval.shape for e in _equations(jaxpr.jaxpr)
            if e.primitive.name == "dot_general"]
    widths = {spec.kv_lora_rank, spec.v_head_dim, spec.qk_nope_head_dim,
              128}
    latents = {s[-1] for s in dots if s[:2] == (rows, spec.n_heads) and len(s) == 3
               and s[-1] not in widths}
    read = MLALayer.kv_cells_read(conf, MAX_SEQ)
    if kind == "full":
        index = {s[-1] for s in dots if s[:2] == (rows, spec.index_n_heads)
                 and len(s) == 3 and s[-1] not in widths and s[-1] != spec.index_topk}
        assert (latents, index) == ({read[0]}, {read[1]}) == ({8}, {MAX_SEQ})
    else:
        assert latents == {read[0]} == {5}


def _equations(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _equations(sub)


def hand_count(prompts, new, most):
    """A row of n prompt tokens that is served m: its steps sit at positions
    n .. n + m - 2 (the first token is the prefill's), each needing
    min(position + 1, most) cells."""
    return sum(min(q + 1, most) for n, m in zip(prompts, new) for q in range(n, n + m - 1))


def test_decode_spans_carry_the_cells_and_the_selection(f32, served):
    _, tokens, stats, spans, _ = served
    steps = [s for s in spans if s.name == "decode" and "kv_cells_live" in s.attrs]
    assert steps and all("experts_hit" in s.attrs and "dsa_cells_live" in s.attrs
                         for s in steps)
    per_row = 2 * (8 + MAX_SEQ) + 5
    for s in steps:
        assert s.attrs["steps"] == 1 and 1 <= s.attrs["live"] <= 2
        assert s.attrs["kv_cells_spanned"] == 2 * per_row      # both slots' state
        assert s.attrs["dsa_cells_selected"] <= s.attrs["dsa_cells_live"]
    for name in ("kv_cells_live", "kv_cells_spanned", "dsa_cells_live", "dsa_cells_selected"):
        assert stats[name + "_total"] == sum(s.attrs[name] for s in steps)
    # two indexed layers: every position at or before the row's, and the 8 picked
    assert stats["dsa_cells_live_total"] == 2 * hand_count(PROMPTS, NEW, MAX_SEQ)
    assert stats["dsa_cells_selected_total"] == 2 * hand_count(PROMPTS, NEW, 8)
    # the cells read: picked latents and all index keys of two layers, one ring
    assert stats["kv_cells_live_total"] == (
        stats["dsa_cells_live_total"] + stats["dsa_cells_selected_total"]
        + hand_count(PROMPTS, NEW, 5))
    assert 0 < stats["dsa_cells_selected_total"] < stats["dsa_cells_live_total"]


def test_fused_blocks_serve_the_same_tokens_and_count_the_same(f32, served):
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
    assert blocks["dsa_cells_live_total"] == 2 * hand_count(PROMPTS[:2], NEW[:2], MAX_SEQ)
    assert blocks["dsa_cells_selected_total"] == 2 * hand_count(PROMPTS[:2], NEW[:2], 8)


def test_the_counters_are_exported(served):
    from deeplearning4j_tpu.serving import metrics

    stats = served[2]
    page = metrics.replica_metrics({"generation": stats})
    for name in ("kv_cells_live", "kv_cells_spanned", "dsa_cells_live", "dsa_cells_selected"):
        assert f"dl4j_serving_{name}_total {stats[name + '_total']}" in page
    without = {k: v for k, v in stats.items() if not k.startswith("dsa_cells")}
    assert "dsa_cells" not in metrics.replica_metrics({"generation": without})


def test_a_stack_that_picks_nothing_counts_no_selection():
    """Ling's plain `mla` layer counts its table's cells and no selection."""
    from ling3_model import Model as Ling

    ling = Ling("float32")
    cells = decode.kv_cells(ling.conf, 32)
    assert cells == [(32, 32)] and decode.selected_cells(ling.conf, 32) == []
    net = MultiLayerNetwork(ling.conf)
    net.params = ling.params
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=1, max_seq=32, prompt_buckets=(8,)).start()
    list(batcher.submit(np.arange(3, dtype=np.int32), max_new_tokens=3).tokens(timeout=120))
    stats = batcher.stats()
    batcher.stop()
    assert stats["kv_cells_live_total"] == 4 + 5 and stats["kv_cells_spanned_total"] == 64
    assert not any(k.startswith("dsa_cells") for k in stats)
    assert not any("dsa_cells_live" in s.attrs for s in profiling.spans())


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
    with pytest.raises(ValueError, match=r"\['mla'\].*a latent cache or a ring"):
        ContinuousBatcher(net, n_slots=2, max_seq=32, **option)
    assert decode.dense_only(f32.conf) == ["mla"]
    with pytest.raises(ValueError, match="dense slot table"):
        decode.init_paged_state(f32.conf, 2, 4, 8)


# ----------------------------------------------------------------- tracing

SCOPES = {
    "full": ["ln", "qkv", "rope", "latent_write", "index_write", "index_scores",
             "select", "gather", "absorb", "scores", "attend", "gate", "proj"],
    "window": ["ln", "qkv", "rope", "latent_write", "absorb", "scores", "attend",
               "gate", "proj"],
    "swiglu": ["ln", "ffn"],
    "moe": ["ln", "router", "dispatch", "experts", "shared", "combine"],
}
NAMES = {"full": "mla_full", "window": "mla_window", "swiglu": "swiglu", "moe": "moe"}


@pytest.mark.parametrize("entry", ["decode", "prefill"])
def test_the_scopes_are_in_the_lowered_programs(f32, entry, monkeypatch):
    from deeplearning4j_tpu.nn.layers import mla

    conf, params = f32.conf, f32.params
    if entry == "decode":
        lowered = jax.jit(lambda p, s, t, q: decode.decode_step(conf, p, s, t, q)).lower(
            params, decode.init_state(conf, 2, 16), np.zeros((2,), np.int32),
            np.zeros((2,), np.int32))
    else:
        monkeypatch.setattr(mla, "SCORE_CELLS", 32)     # two blocks, one past index_topk
        lowered = jax.jit(lambda p, s, t, n: decode.prefill(conf, p, s, t, n)).lower(
            params, decode.init_state(conf, 1, 32), np.zeros((1, 16), np.int32),
            np.ones((1,), np.int32))
    text = lowered.as_text(debug_info=True)
    # a prefill gathers and absorbs nothing: the keys are materialised
    absent = {"prefill": ("gather", "absorb")}.get(entry, ())
    for i, kind in enumerate(f32.kinds):
        for name in SCOPES.get(kind, ()):
            if name not in absent:
                assert f"L{i}.{NAMES[kind]}/{name}" in text, (entry, i, kind, name)
    assert "L1.mla/" not in text and "L5.mla_window/select" not in text


# ------------------------------------------------------- conf, plan, old confs

def test_the_typed_settings_round_trip(f32):
    again = MultiLayerConfiguration.from_json(f32.conf.to_json())
    assert again == f32.conf and hash(again) == hash(f32.conf)
    kinds = {type(c.layer_spec).__name__ for c in f32.conf.confs if c.layer_spec}
    assert kinds == {"MLASpec", "SwiGLUSpec", "MoESpec", "HeadSpec"}
    full = again.conf(f32.layer("full")).layer_spec
    assert (full.index_n_heads, full.index_head_dim, full.index_topk) == (2, 16, 8)
    assert (full.scope_kind, again.conf(f32.layer("window")).layer_spec.scope_kind) == (
        "mla_full", "mla_window")
    flat = {f.name for f in dataclasses.fields(NeuralNetConfiguration)}
    assert not flat & {"q_lora_rank", "window", "index_topk", "gate"}
    # the seven fields MLASpec gained are written only where they are not
    # the default: Ling's conf serialises as it did, and its scope is `mla`
    plain = MLASpec(n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16)
    as_json = NeuralNetConfiguration(layer_type="mla", layer_spec=plain).to_dict()["layer_spec"]
    assert sorted(as_json) == ["eps", "kind", "kv_lora_rank", "n_heads", "qk_nope_head_dim",
                               "qk_rope_head_dim", "rope_theta", "v_head_dim"]
    assert plain.scope_kind is None
    new = f32.conf.conf(f32.layer("window")).to_dict()["layer_spec"]
    assert (new["window"], new["gate"], new["lora_rescale"], new["q_lora_rank"]) == (
        5, True, True, 32)
    assert "index_topk" not in new


def test_the_plan_keeps_the_new_leaves_whole(f32):
    from jax.sharding import Mesh, PartitionSpec as P

    from deeplearning4j_tpu.parallel.plan import ShardPlan

    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    plan = ShardPlan(Mesh(devs, ("batch", "model")))
    state = jax.eval_shape(lambda: decode.init_state(f32.conf, 4, 16))
    specs = plan.state_pspecs(state)
    seen = []
    for lay, spec in zip(state, specs):
        for name in lay:
            assert spec[name] == P(), name
            seen.append(name)
    assert sorted(seen) == sorted(["ckr", "ki"] * 2 + ["ckr"])


# Lowered at the parent commit (5db4232) with this file's own recipe: Ling's
# `mla` layer alone at its published widths (32 heads, rank 512, 128 + 64,
# 128; d 2560, bfloat16), the decode step of 64 rows over 2,048 cells and
# the prefill of its two prompt buckets.  Equal texts: the options reached
# no program of the layer without them.
PARENT_TEXTS = {"decode": "b4d3785b01762d83", "prefill:512": "e9e35eae87bfe666",
                "prefill:1024": "6805533c85785ec5"}


def lowered_texts() -> dict:
    conf = NeuralNetConfiguration(
        layer_type=LayerType.MLA, n_in=2560, n_out=2560, dtype="bfloat16",
        compute_dtype="bfloat16", layer_spec=MLASpec(
            n_heads=32, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, rope_theta=6e6))
    params = jax.eval_shape(lambda k: MLALayer.init(k, conf), jax.random.PRNGKey(0))
    state = lambda rows: jax.eval_shape(lambda: MLALayer.init_state(conf, rows, 2048))  # noqa: E731
    out = {"decode": jax.jit(lambda p, x, s, q: MLALayer.decode_step(p, conf, x, s, q)).lower(
        params, jax.ShapeDtypeStruct((64, 2560), jnp.float32), state(64),
        jax.ShapeDtypeStruct((64,), jnp.int32)).as_text()}
    for bucket in (512, 1024):
        out[f"prefill:{bucket}"] = jax.jit(
            lambda p, x, s, n: MLALayer.prefill(p, conf, x, s, n)).lower(
                params, jax.ShapeDtypeStruct((1, bucket, 2560), jnp.float32), state(1),
                jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in out.items()}


def test_lings_mla_programs_lower_to_the_parents_texts():
    assert lowered_texts() == PARENT_TEXTS
