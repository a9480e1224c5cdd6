"""Ling-3.0-flash's language model through `ContinuousBatcher`, at tiny
widths on the CPU: greedy streams against the plain reference of
`benchmark/families/ling3`, the options that cannot hold its
state, its scopes, spans and counters, and what its typed settings left as it
was.  The layers are in `test_ling3_layers.py` and `test_ling3_experts.py`,
the slot table in `test_ling3_slot_table.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration, NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from deeplearning4j_tpu.utils import profiling
from generate_helpers import spans_sum_to_totals
from ling3_model import Model, f32      # noqa: F401  (f32: a fixture)


# --------------------------------------------------------------- (e) batcher

def reference_greedy(m: Model, prompt, n_new: int, width: int = 24):
    """The reference's own greedy continuation; the ids are padded to one
    width (a causal model's logits do not see what follows), so that the
    reference compiles once."""
    ids = list(prompt)
    for _ in range(n_new):
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(ids)] = ids
        ids.append(int(np.argmax(m.logp(padded)[0, len(ids) - 1])))
    return ids[len(prompt):]


@pytest.fixture(scope="module")
def served(f32):
    """Five requests through two slots (so slots are reused)."""
    net = MultiLayerNetwork(f32.conf)
    net.params = f32.params
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, f32.sizes["vocab"], n).astype(np.int32)
               for n in (5, 16, 9, 3, 12)]
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=2, max_seq=48, prompt_buckets=(8, 16)).start()
    streams = [batcher.submit(p, max_new_tokens=6) for p in prompts]
    tokens = [list(s.tokens(timeout=120)) for s in streams]
    stats = batcher.stats()
    batcher.stop()
    return prompts, tokens, stats, profiling.spans(), net


@pytest.mark.parametrize("request_no", range(5))
def test_a_greedy_stream_is_the_references(f32, served, request_no):
    """Requests 2 to 4 run in slots that an earlier request used: they are
    the reference's only if the slot started from `zero_row`."""
    prompts, tokens, *_ = served
    assert tokens[request_no] == reference_greedy(f32, prompts[request_no], 6)


def test_more_requests_than_slots_are_the_references_and_the_spans_sum_up(f32, served):
    """Five requests through two slots: every stream is the reference's greedy
    one (a teacher-forced pass over prompt and answer, padded to one width),
    and the counts on the `decode` spans sum to the totals of `stats()`."""
    prompts, tokens, stats, spans, _ = served
    assert len(prompts) > stats["slots"]["width"]
    for prompt, got in zip(prompts, tokens):
        ids = np.zeros((1, 24), np.int32)
        ids[0, :len(prompt) + len(got)] = np.concatenate([prompt, got])
        best = np.argmax(f32.logp(ids)[0], axis=-1)
        assert got == list(best[len(prompt) - 1: len(prompt) - 1 + len(got)])
    assert spans_sum_to_totals(spans, stats) == [
        "picks_here", "experts_hit", "kv_cells_live", "kv_cells_spanned"]
    assert stats["expert_picks_total"] >= stats["experts_hit_total"] > 0


# ---------------------------------------------------------------- (f) refusals

@pytest.mark.parametrize("option", [
    {"page_size": 8}, {"prefix_cache": True}, {"spec_k": 2, "draft_net": "lstm"}])
def test_options_that_cannot_hold_the_new_state_refuse_the_conf(f32, option):
    if option.get("draft_net"):
        from deeplearning4j_tpu.models.zoo import char_lstm
        option = {**option, "draft_net": MultiLayerNetwork(
            char_lstm(f32.sizes["vocab"], hidden=8)).init()}
    net = MultiLayerNetwork(f32.conf)
    net.params = f32.params
    with pytest.raises(ValueError, match=r"kda.*mla|dense slot table"):
        ContinuousBatcher(net, n_slots=2, max_seq=32, **option)


def test_the_paged_pool_and_the_verify_chunk_refuse_the_state(f32):
    with pytest.raises(ValueError, match="dense slot table"):
        decode.init_paged_state(f32.conf, 2, 4, 8)
    with pytest.raises(ValueError, match="dense slot table"):
        decode.verify_chunk(f32.conf, f32.params, decode.init_state(f32.conf, 1, 8),
                            np.zeros((1, 2), np.int32), np.zeros((1,), np.int32))
    assert decode.dense_only(f32.conf) == ["kda", "mla"]


# ----------------------------------------------------------------- (g) tracing

SCOPES = {
    "kda": ["ln", "qkv", "conv", "gate", "state_update", "state_read", "proj"],
    "mla": ["ln", "qkv", "rope", "latent_write", "absorb", "scores", "attend", "proj"],
    "moe": ["ln", "router", "dispatch", "experts", "shared", "combine"],
    "swiglu": ["ln", "ffn"],
}


@pytest.mark.parametrize("entry", ["decode", "prefill"])
def test_the_new_scopes_are_in_the_lowered_programs(f32, entry):
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
    # the prefill reads the state inside its chunked update and absorbs
    # nothing: it materialises keys and values
    absent = {"prefill": ("state_read", "absorb")}.get(entry, ())
    for i, kind in enumerate(f32.kinds):
        for name in SCOPES.get(kind, ()):
            if name not in absent:
                assert f"L{i}.{kind}/{name}" in text, (entry, i, kind, name)


def test_decode_spans_carry_the_experts_counts(f32, served):
    _, tokens, stats, spans, _ = served
    steps = [s for s in spans if s.name == "decode" and "picks_here" in s.attrs]
    assert steps
    layers = f32.kinds.count("moe")
    held = f32.sizes["experts_held"]
    for s in steps:
        assert s.attrs["k"] == 1 and s.attrs["steps"] == 1 and "live" in s.attrs
        assert 0 < s.attrs["experts_hit"] <= min(s.attrs["picks_here"], held * layers)
        assert s.attrs["picks_here"] <= 2 * f32.sizes["top_k"] * layers
        assert "experts_batched_layers" not in s.attrs  # static: `stats()` has it
    assert stats["experts_batched_layers"] == 0     # 2 rows: the sorted form
    assert stats["expert_picks_total"] == sum(s.attrs["picks_here"] for s in steps)
    assert stats["experts_hit_total"] == sum(s.attrs["experts_hit"] for s in steps)


def test_the_counters_are_exported(served):
    from deeplearning4j_tpu.serving import metrics

    stats = served[2]
    page = metrics.replica_metrics({"generation": stats})
    assert f"dl4j_serving_expert_picks_total {stats['expert_picks_total']}" in page
    assert f"dl4j_serving_experts_hit_total {stats['experts_hit_total']}" in page
    without = {k: v for k, v in stats.items() if not k.startswith("expert")}
    assert "expert" not in metrics.replica_metrics({"generation": without})


# ------------------------------------------------------- conf, plan, old confs

def test_a_conf_without_a_spec_serialises_as_it_did():
    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.optimize.step_cache import conf_fingerprint

    conf = char_transformer(64, d_model=32, n_blocks=1, n_heads=2, max_seq_len=16)
    assert "layer_spec" not in conf.to_json()
    assert MultiLayerConfiguration.from_json(conf.to_json()) == conf
    # the fingerprint of this conf at the parent commit (33ed9ad)
    assert conf_fingerprint(conf) == PARENT_FINGERPRINT
    assert not decode.has_experts(conf) and decode.dense_only(conf) == []


PARENT_FINGERPRINT = "5d39faac43218b01"


def test_the_typed_settings_round_trip(f32):
    again = MultiLayerConfiguration.from_json(f32.conf.to_json())
    assert again == f32.conf and hash(again) == hash(f32.conf)
    kinds = {type(c.layer_spec).__name__ for c in f32.conf.confs if c.layer_spec}
    assert kinds == {"KDASpec", "MLASpec", "SwiGLUSpec", "MoESpec", "HeadSpec"}
    flat = {f.name for f in dataclasses.fields(NeuralNetConfiguration)}
    assert not flat & {"kv_lora_rank", "n_routed", "head_dim", "top_k"}


def test_the_plan_names_the_new_leaves_and_keeps_them_whole(f32):
    from jax.sharding import Mesh, PartitionSpec as P

    from deeplearning4j_tpu.parallel.plan import ShardPlan

    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    plan = ShardPlan(Mesh(devs, ("batch", "model")))
    state = jax.eval_shape(lambda: decode.init_state(f32.conf, 4, 16))
    specs = plan.state_pspecs(state)
    for lay, spec in zip(state, specs):
        for name in lay:
            assert name in ("S", "conv", "c", "kr") and spec[name] == P()
    lstm = {"h": jax.ShapeDtypeStruct((4, 8), jnp.float32),
            "c": jax.ShapeDtypeStruct((4, 8), jnp.float32)}
    assert plan.state_pspecs((lstm,))[0]["c"] == P(None, "model")
