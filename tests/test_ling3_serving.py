"""The layer types that Ling-3.0-flash's language model brought (`kda`, `mla`,
`swiglu`, `moe`, the head under a `HeadSpec`) against the plain reference of
`benchmark/families/ling3`, at tiny widths on the CPU: layer by layer, the
whole model through prefill and the slot table, and through
`ContinuousBatcher`."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, program as bench_program, reference as bench_reference
from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.conf import (LayerType, MultiLayerConfiguration,
                                        NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.layers import experts as experts_mod
from deeplearning4j_tpu.nn.layers.kda import chunked_delta_rule
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, init_params
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from deeplearning4j_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483659
# float32 compute: what is left is the order of float32 sums (the chunked
# KDA prefill against the token scan, absorbed against materialised MLA)
TIGHT = 2e-5
# bfloat16 operands: every matmul rounds its operands to 8 bits of mantissa
# (relative 2^-9); over 14 layers of a residual stream the log-probabilities
# of this tiny model move by some 1e-2.  An int8 path moves them ten times
# further, a dropped layer by more than 1.
LOOSE = 6e-2


def tiny(dtype: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-ep4.json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg["rehearse"],
            "flags": {"param_dtype": dtype, "compute_dtype": dtype}}


class Model:
    def __init__(self, dtype: str):
        self.cfg = tiny(dtype)
        self.fam = families.of(self.cfg)
        self.ref = self.fam.reference
        self.sizes = self.ref.sizes(self.cfg)
        self.conf = self.fam.program.build_conf(self.cfg)
        self.kinds = self.ref.layer_kinds(self.cfg)
        self.weights = jax.jit(self.ref.model_weights, static_argnums=0)(
            bench_reference.Frozen(self.cfg), bench_reference.base_key(SEED))
        self.params = bench_program.program_weights(self.cfg, SEED)

    def logp(self, ids):
        """The reference's log-probabilities [B, S, V] of ids [B, S]."""
        logits = self.ref.teacher_forced_logits(self.cfg, SEED, ids)["f32"]
        return np.asarray(jax.nn.log_softmax(logits, axis=-1))

    def layer(self, kind: str) -> int:
        return self.kinds.index(kind)


@pytest.fixture(scope="module")
def f32():
    return Model("float32")


@pytest.fixture(scope="module")
def bf16():
    return Model("bfloat16")


def rows(shape, seed=0, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# ------------------------------------------------------------ (a) the layers

@pytest.mark.parametrize("kind", ["kda", "mla", "swiglu", "moe", "head"])
def test_a_layer_is_the_references(f32, kind):
    i = f32.layer(kind)
    x = rows((2, 24, f32.sizes["d"]), seed=i)
    want = f32.ref.apply_layer(kind, f32.weights[i], x,
                               bench_reference.Frozen(f32.cfg))
    got = get_layer(f32.conf.conf(i).layer_type).forward(
        f32.params[i], f32.conf.conf(i), x)
    if kind == "head":
        want = jax.nn.softmax(want, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=TIGHT)


def test_the_program_keeps_the_references_numbers(bf16):
    """bfloat16 parameters are rounded once, in the reference's
    `model_weights`: the program's copy is the same numbers, leaf for leaf,
    and has the shapes and types its own `init` gives."""
    back = bf16.fam.program.from_program(bf16.params)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(bf16.weights)):
        assert a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    made = jax.eval_shape(lambda k: init_params(bf16.conf, k), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(made)
            == jax.tree_util.tree_structure(bf16.params))
    for a, b in zip(jax.tree_util.tree_leaves(made),
                    jax.tree_util.tree_leaves(bf16.params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def serve_through_the_table(m: Model, ids, lengths, bucket: int, max_seq: int):
    """Each row of `ids` through its own admission (`prefill_slot`: a padded
    bucket into a zero row, written into the slots-wide table) and then the
    table's decode steps, teacher-forced; returns the log-probabilities that
    came out at every position from `length - 1` on, a list a row."""
    net = MultiLayerNetwork(m.conf)
    net.params = m.params
    ic = net.infer_cache
    n = len(lengths)
    table = ic.init_decode_state(m.conf, n, max_seq)
    out = [[] for _ in range(n)]
    for slot, length in enumerate(lengths):     # logits, not tokens: B=1 rows
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :length] = ids[slot, :length]
        row = decode.init_state(m.conf, 1, max_seq)
        logp, row = jax.jit(lambda p, s, pr, ln: decode.prefill(m.conf, p, s, pr, ln))(
            m.params, row, prompt, np.asarray([length], np.int32))
        out[slot].append(np.asarray(logp[0]))
        table = ic.write_row(m.conf, table, row, slot)
    step = jax.jit(lambda p, s, t, q: decode.decode_step(m.conf, p, s, t, q))
    pos = np.asarray(lengths, np.int32)
    total = ids.shape[1]
    while (pos < total).any():
        live = pos < total
        tok = np.where(live, ids[np.arange(n), np.minimum(pos, total - 1)], 0)
        logp, table = step(m.params, table, tok.astype(np.int32), pos)
        for r in range(n):
            if live[r]:
                out[r].append(np.asarray(logp[r]))
        pos = np.where(live, pos + 1, pos).astype(np.int32)
    return out


@pytest.mark.parametrize("which, tolerance", [("f32", TIGHT), ("bf16", LOOSE)])
def test_prefill_then_decode_through_the_table_is_the_full_forward_pass(
        request, which, tolerance):
    m = request.getfixturevalue(which)
    rng = np.random.default_rng(5)
    lengths = [16, 11, 3]
    ids = rng.integers(0, m.sizes["vocab"], (3, 28)).astype(np.int32)
    want = m.logp(ids)
    got = serve_through_the_table(m, ids, lengths, bucket=16, max_seq=40)
    for r, length in enumerate(lengths):
        assert len(got[r]) == 28 - length + 1
        for j, logp in enumerate(got[r][:-1]):      # the last has no successor
            np.testing.assert_allclose(logp, want[r, length - 1 + j],
                                       atol=tolerance, rtol=0)


# ------------------------------------------------------------------- (b) KDA

def kda_case(m: Model, t: int = 24, b: int = 2):
    i = m.layer("kda")
    x = rows((b, t, m.sizes["d"]), seed=3)
    w = {k: v.astype(jnp.float32) for k, v in m.weights[i].items()}
    q, k, v, g, beta, _ = m.ref.kda_inputs(w, x, m.sizes)
    return i, x, (q, k, v, g, beta)


def test_kda_state_after_a_prefill_is_the_recurrences(f32):
    i, x, inputs = kda_case(f32)
    impl, c = get_layer(LayerType.KDA), f32.conf.conf(i)
    zero = jnp.zeros((2, f32.sizes["heads"], f32.sizes["kda_dim"],
                      f32.sizes["kda_dim"]), jnp.float32)
    want_o, want_s = f32.ref.kda_recurrence(*inputs, zero)
    _, state = impl.prefill(f32.params[i], c, x, impl.init_state(c, 2, 0),
                            jnp.asarray([24, 24], jnp.int32))
    np.testing.assert_allclose(np.asarray(state["S"]), np.asarray(want_s), atol=TIGHT)
    # and from a state that is not zero, a chunk that does not divide the length
    start = rows(zero.shape, seed=9, scale=0.1)
    want_o, want_s = f32.ref.kda_recurrence(*inputs, start)
    got_o, got_s = chunked_delta_rule(*inputs, start, chunk=16)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=TIGHT)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=TIGHT)


def test_kda_decays_that_underflow_stay_finite(f32):
    """Every channel at the lower bound for a whole chunk: e^{-5 * 64} is 0
    in float32, and the chunked form may not divide by it."""
    _, _, (q, k, v, g, beta) = kda_case(f32, t=64, b=1)
    g = jnp.full_like(g, f32.sizes["kda_lower_bound"])
    zero = jnp.zeros((1,) + q.shape[2:] + q.shape[-1:], jnp.float32)
    want_o, want_s = f32.ref.kda_recurrence(q, k, v, g, beta, zero)
    got_o, got_s = chunked_delta_rule(q, k, v, g, beta, zero)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=TIGHT)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=TIGHT)


def test_kda_padding_is_inert(f32):
    i, x, _ = kda_case(f32)
    impl, c = get_layer(LayerType.KDA), f32.conf.conf(i)
    short = jnp.asarray([13, 2], jnp.int32)
    padded = x.at[0, 13:].set(7.0).at[1, 2:].set(-7.0)     # junk past the length
    _, got = impl.prefill(f32.params[i], c, padded, impl.init_state(c, 2, 0), short)
    for r, n in enumerate([13, 2]):
        _, want = impl.prefill(f32.params[i], c, x[r:r + 1, :n],
                               impl.init_state(c, 1, 0), jnp.asarray([n], jnp.int32))
        np.testing.assert_allclose(np.asarray(got["S"][r]), np.asarray(want["S"][0]),
                                   atol=TIGHT)
        np.testing.assert_allclose(np.asarray(got["conv"][r]),
                                   np.asarray(want["conv"][0]), atol=1e-6)


def test_a_frozen_row_in_decode_block_does_not_advance(f32):
    conf, params = f32.conf, f32.params
    state = decode.init_state(conf, 2, 32)
    prompt = np.asarray([[5, 9, 2, 7], [1, 3, 0, 0]], np.int32)
    _, state = decode.prefill(conf, params, state, prompt, np.asarray([4, 2], np.int32))

    def greedy(logp, keys, temps):
        return jnp.argmax(logp, axis=-1).astype(jnp.int32), keys

    tok, pos = np.asarray([11, 4], np.int32), np.asarray([4, 2], np.int32)
    keys, temps = np.zeros((2, 2), np.uint32), np.zeros((2,), np.float32)
    toks, _, _, counts, after = decode.decode_block(
        conf, params, state, tok, pos, keys, temps,
        np.asarray([3, 0], np.int32), 3, greedy)
    assert (np.asarray(toks)[:, 1] == decode.BLOCK_SENTINEL).all()
    assert (np.asarray(toks)[:, 0] != decode.BLOCK_SENTINEL).all()
    assert counts.shape == (2,)
    for before, now, c in zip(state, after, conf.confs):
        if str(c.layer_type) != "kda":
            continue
        for leaf in ("S", "conv"):      # row 1 frozen, row 0 moved on
            np.testing.assert_array_equal(np.asarray(now[leaf][1]),
                                          np.asarray(before[leaf][1]))
            assert not np.array_equal(np.asarray(now[leaf][0]),
                                      np.asarray(before[leaf][0]))


# ------------------------------------------------------------------- (c) MLA

def test_mla_absorbed_decode_is_the_materialised_prefill(f32):
    i = f32.layer("mla")
    impl, c = get_layer(LayerType.MLA), f32.conf.conf(i)
    x = rows((2, 12, f32.sizes["d"]), seed=4)
    full, _ = impl.prefill(f32.params[i], c, x, impl.init_state(c, 2, 16), None)
    _, state = impl.prefill(f32.params[i], c, x[:, :8], impl.init_state(c, 2, 16), None)
    for t in range(8, 12):              # rows at different positions, too
        got, state = impl.decode_step(f32.params[i], c, x[:, t], state,
                                      jnp.asarray([t, t], jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, t]), atol=TIGHT)
    assert state["c"].shape == (2, 16, f32.sizes["kv_rank"])
    assert state["kr"].shape == (2, 16, f32.sizes["rope"])


# --------------------------------------------------------------- (d) experts

def test_the_four_chips_parts_add_up_to_the_uncut_layer(f32):
    """Each rank's routed part, the shared expert counted once, against the
    reference holding every expert."""
    i = f32.layer("moe")
    u = rows((40, f32.sizes["d"]), seed=6)
    whole_cfg = {**f32.cfg, "num_experts": f32.sizes["experts_routed"]}
    key = bench_reference.base_key(SEED)

    def parts(cfg):
        w = f32.ref.layer_weights(cfg, key, i, "moe")
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return f32.ref.moe_parts(w, u, f32.ref.sizes(cfg))

    whole, shared = parts(whole_cfg)
    total = shared
    for rank in range(4):
        cfg = {**f32.cfg, "deployment": {"rank": rank}}
        routed, also_shared = parts(cfg)
        np.testing.assert_array_equal(np.asarray(also_shared), np.asarray(shared))
        total = total + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole + shared),
                               atol=TIGHT)


@pytest.mark.parametrize("rank", [0, 3])
@pytest.mark.parametrize("n_rows", [1, 1024])
def test_every_pick_of_a_held_expert_is_computed(f32, n_rows, rank):
    i = f32.layer("moe")
    cfg = {**f32.cfg, "deployment": {"rank": rank}}
    conf = f32.fam.program.build_conf(cfg).conf(i)
    weights = f32.ref.layer_weights(cfg, bench_reference.base_key(SEED), i, "moe")
    params = f32.fam.program.to_program([weights])[0]
    x = rows((n_rows, f32.sizes["d"]), seed=8)
    got, counts = jax.jit(lambda p, v: experts_mod.MoELayer.apply(p, conf, v))(params, x)
    want = f32.ref.moe(weights, x[None], f32.ref.sizes(cfg))[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TIGHT)
    spec = conf.layer_spec
    u = f32.ref.rms_norm(x, weights["ln"], spec.eps)
    ids, _ = f32.ref.route(jax.nn.sigmoid(u @ weights["Wr"]), weights["b"],
                           f32.ref.sizes(cfg))
    mine = (np.asarray(ids) >= spec.first_held) & (
        np.asarray(ids) < spec.first_held + spec.n_held)
    assert int(counts[0]) == mine.sum()                 # none dropped
    assert int(counts[1]) == len(set(np.asarray(ids)[mine].tolist()))


def test_uneven_routing_takes_the_wide_branch_and_drops_nothing(f32):
    """All the picks on this rank's experts: more than the 3/8 of the rows
    that the narrow branch holds."""
    i = f32.layer("moe")
    spec = f32.conf.conf(i).layer_spec
    u = rows((64, f32.sizes["d"]), seed=2)
    ids = jnp.tile(jnp.arange(spec.top_k, dtype=jnp.int32), (64, 1))
    w = jnp.full((64, spec.top_k), 0.125, jnp.float32)
    got, counts = experts_mod.held_experts(f32.params[i], spec, jnp.float32, u, ids, w)
    p = f32.params[i]
    want = sum(0.125 * experts_mod.swiglu(u, p["Wgu"][e], p["Wd"][e], jnp.float32)
               for e in range(spec.top_k))
    assert int(counts[0]) == 64 * spec.top_k
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TIGHT)


def test_at_most_topk_group_groups_are_chosen(f32):
    spec = f32.conf.conf(f32.layer("moe")).layer_spec
    scores = jax.nn.sigmoid(rows((256, spec.n_routed), seed=1))
    ids, w = experts_mod.route(scores, jnp.zeros((spec.n_routed,)), spec)
    groups = np.asarray(ids) // (spec.n_routed // spec.n_group)
    assert max(len(set(g)) for g in groups.tolist()) <= spec.topk_group
    assert all(len(set(r)) == spec.top_k for r in np.asarray(ids).tolist())
    np.testing.assert_allclose(np.asarray(w).sum(-1), spec.routed_scaling, rtol=1e-5)


def test_the_routers_bias_moves_the_choice_and_not_the_weights(f32):
    spec = f32.conf.conf(f32.layer("moe")).layer_spec
    scores = jax.nn.sigmoid(rows((64, spec.n_routed), seed=2))
    plain, _ = experts_mod.route(scores, jnp.zeros((spec.n_routed,)), spec)
    bias = jnp.zeros((spec.n_routed,)).at[5].set(10.0)
    ids, w = experts_mod.route(scores, bias, spec)
    assert (np.asarray(ids) == 5).any(axis=1).all()          # chosen everywhere
    assert not (np.asarray(plain) == 5).any(axis=1).all()
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), axis=1)
    np.testing.assert_allclose(                             # weights: scores alone
        np.asarray(w), spec.routed_scaling * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)


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
    """Five requests through two slots (so slots are reused), K=1."""
    net = MultiLayerNetwork(f32.conf)
    net.params = f32.params
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, f32.sizes["vocab"], n).astype(np.int32)
               for n in (5, 16, 9, 3, 12)]
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=2, max_seq=48, prompt_buckets=(8, 16),
                                steps_per_dispatch=1).start()
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


def test_fused_blocks_serve_the_same_tokens(f32, served):
    prompts, tokens, *_ = served
    net = MultiLayerNetwork(f32.conf)
    net.params = f32.params
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=2, max_seq=48, prompt_buckets=(8, 16),
                                steps_per_dispatch=4).start()
    streams = [batcher.submit(p, max_new_tokens=6) for p in prompts[:2]]
    got = [list(s.tokens(timeout=120)) for s in streams]
    stats = batcher.stats()
    batcher.stop()
    assert got == tokens[:2]
    blocks = [s for s in profiling.spans() if s.name == "decode" and "steps" in s.attrs]
    assert blocks and all(s.attrs["picks_here"] >= s.attrs["experts_hit"] > 0
                          for s in blocks)
    assert stats["expert_picks_total"] == sum(s.attrs["picks_here"] for s in blocks)


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
