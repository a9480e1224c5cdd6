"""Mellum2's layer types at tiny widths on the CPU against the plain
reference of `benchmark/families/mellum`: grouped-heads attention over a
window and full (YaRN), its blocks of queries, the softmax router over
experts that are all held, and the whole tiny model through `prefill` and
`decode_step`.  The batcher, the slot table, spans and counters are in
`test_mellum_serving.py`."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.layers import experts, gqa, rms
from deeplearning4j_tpu.nn.layers.experts import MoELayer
from deeplearning4j_tpu.nn.layers.gqa import GQALayer
from mellum_model import LOOSE, SEED, TIGHT, Model, bf16, f32, rows     # noqa: F401

YARN = (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 8 queries, so that a sequence of 24 is three blocks and a
    window layer's block meets a band of at most 16 keys."""
    monkeypatch.setattr(gqa, "q_block", lambda n_heads: 8)


@pytest.mark.parametrize("kind", ["window", "full"])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_an_attention_layer_is_the_references(request, small_blocks, dtype, kind):
    m = request.getfixturevalue(dtype)
    i = m.layer(kind)
    x = rows((2, 24, m.sizes["d"]), seed=3)
    with jax.default_matmul_precision("highest"):
        want = m.ref.attention({k: v.astype(jnp.float32) for k, v in m.weights[i].items()},
                               x, m.sizes, kind)
    got = jax.jit(lambda p, v: GQALayer.forward(p, m.conf.conf(i), v))(m.params[i], x)
    assert float(jnp.max(jnp.abs(got - want))) < (TIGHT if dtype == "f32" else 2e-2)
    assert m.conf.conf(i).layer_spec.window == (8 if kind == "window" else 0)


def test_blocks_of_queries_change_nothing(f32, monkeypatch):
    x = rows((1, 24, f32.sizes["d"]), seed=4)
    for kind in ("window", "full"):
        i = f32.layer(kind)
        whole = GQALayer.forward(f32.params[i], f32.conf.conf(i), x)
        monkeypatch.setattr(gqa, "q_block", lambda n_heads: 8)
        np.testing.assert_allclose(GQALayer.forward(f32.params[i], f32.conf.conf(i), x),
                                   whole, atol=TIGHT)
        monkeypatch.undo()


def test_yarn_frequencies_and_factor_are_the_formulas():
    """At the published numbers: 64 frequencies of a head of 128, theta
    500000, factor 16 over 8192 positions, beta_fast 32, beta_slow 1."""
    inv, factor = rms.yarn_frequencies(128, 500000.0, YARN)
    dim = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000.0))  # noqa: E731
    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), 127)
    assert (low, high) == (18, 35)
    i = np.arange(64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    plain = 500000.0 ** (-2.0 * i / 128)
    np.testing.assert_allclose(inv, (1 - ramp) * plain + ramp * plain / 16, rtol=1e-6)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert factor == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)
    # the rotation itself: cos and sin of position x frequency, both scaled
    x = rows((5, 128), seed=1)
    pos = jnp.asarray([0, 1, 100, 5000, 9215])
    ang = np.asarray(pos, np.float64)[:, None] * np.asarray(inv, np.float64)
    cos, sin = np.cos(ang) * factor, np.sin(ang) * factor
    x1, x2 = np.asarray(x[:, :64], np.float64), np.asarray(x[:, 64:], np.float64)
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    np.testing.assert_allclose(rms.rope(x, pos, 500000.0, YARN), want, atol=2e-3)


def test_window_layers_are_untouched_by_yarn(f32):
    x = rows((4, 16), seed=2)
    pos = jnp.asarray([0, 3, 17, 40])
    inv = 500000.0 ** (-np.arange(0, 16, 2) / 16)
    ang = np.asarray(pos)[:, None] * inv
    x1, x2 = np.asarray(x[:, :8]), np.asarray(x[:, 8:])
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], axis=-1)
    np.testing.assert_allclose(rms.rope(x, pos, 500000.0), want, atol=1e-5)
    specs = [c.layer_spec for c in f32.conf.confs if hasattr(c.layer_spec, "window")]
    assert [s.yarn is None for s in specs] == [True, True, True, False]
    assert [s.scope_kind for s in specs] == ["gqa_window"] * 3 + ["gqa_full"]
    assert specs[3].yarn[0] == 16.0 and specs[3].yarn[-1] == 1.2772588722239782


def test_grouped_heads_are_the_model_with_keys_and_values_repeated(f32):
    """4 query heads over 2 K/V heads give what 4 over 4 give when each K/V
    head's columns stand twice."""
    i = f32.layer("full")
    conf, p = f32.conf.conf(i), f32.params[i]
    s = conf.layer_spec
    r = s.n_heads // s.n_kv_heads
    wide = conf.replace(layer_spec=dataclasses.replace(s, n_kv_heads=s.n_heads))

    def repeat(w):
        cols = w.reshape(w.shape[0], s.n_kv_heads, s.head_dim)
        return jnp.repeat(cols, r, axis=1).reshape(w.shape[0], -1)

    x = rows((2, 12, f32.sizes["d"]), seed=6)
    got = GQALayer.forward(p, conf, x)
    want = GQALayer.forward({**p, "Wk": repeat(p["Wk"]), "Wv": repeat(p["Wv"])}, wide, x)
    np.testing.assert_allclose(got, want, atol=TIGHT)
    assert r == 2 and GQALayer.init_state(conf, 3, 40)["k"].shape == (3, 2, 40, 16)
    assert GQALayer.init_state(wide, 3, 40)["k"].shape == (3, 4, 40, 16)


def test_softmax_weights_sum_to_one_and_the_layer_is_the_dense_sum(f32):
    i = f32.layer("moe")
    conf, p, s = f32.conf.conf(i), f32.params[i], f32.sizes
    spec = conf.layer_spec
    assert (spec.score, spec.router_bias, spec.shared_hidden) == ("softmax", False, 0)
    assert spec.n_held == spec.n_routed == s["experts_held"]
    assert sorted(p) == ["Wd", "Wgu", "Wr", "ln"]       # no bias, no shared expert
    assert sorted(MoELayer.init(jax.random.PRNGKey(0), conf)) == sorted(p)
    x = rows((1, 40, s["d"]), seed=7)
    u = rms.rms_norm(x, p["ln"], spec.eps).reshape(40, -1)
    scores = jax.nn.softmax(u @ p["Wr"], axis=-1)
    ids, weights = experts.route(scores, None, spec)
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 1.0, atol=1e-6)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True), atol=1e-6)
    assert set(np.asarray(ids).ravel()) <= set(range(s["experts_held"]))
    # every expert over every row, weighted (0 where not picked): the reference's
    dense = f32.ref.dense_weights(ids, weights, s["experts_held"])
    f = spec.hidden
    want = x[0]
    for e in range(s["experts_held"]):
        h = u @ p["Wgu"][e]
        want = want + dense[:, e, None] * ((jax.nn.silu(h[:, :f]) * h[:, f:]) @ p["Wd"][e])
    got, counts = MoELayer.apply(p, conf, x)
    np.testing.assert_allclose(got[0], want, atol=TIGHT)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(got, f32.ref.moe(f32.weights[i], x, s), atol=TIGHT)
    assert int(counts[0]) == 40 * spec.top_k            # every pick lands here


def test_a_layer_that_holds_every_expert_builds_no_choice_of_rows(f32):
    from deeplearning4j_tpu.nn.conf import MoESpec

    i = f32.layer("moe")
    conf = f32.conf.conf(i)
    # rows past `experts.BATCHED_ROWS_MOST`: the sorted form, as an admission's
    x = jnp.zeros((136, f32.sizes["d"]), jnp.float32)
    assert MoELayer.product_form(conf, 136) == "sorted"
    all_held = str(jax.make_jaxpr(lambda p, v: MoELayer.apply(p, conf, v))(f32.params[i], x))
    assert "cond[" not in all_held and "ragged_dot" in all_held
    half = conf.replace(layer_spec=dataclasses.replace(
        conf.layer_spec, n_held=4, shared_hidden=8, router_bias=True, score="sigmoid"))
    assert isinstance(half.layer_spec, MoESpec)
    params = MoELayer.init(jax.random.PRNGKey(0), half)
    assert "cond[" in str(jax.make_jaxpr(lambda p, v: MoELayer.apply(p, half, v))(params, x))


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_prefill_past_the_window_then_decoding_through_the_ring(request, dtype):
    """A prompt of 20 into a window of 8 (bucket 24: rows of 20 and 13 real
    tokens), then 20 tokens one at a time: the ring wraps five times, and
    every step's log-probabilities are those of the reference's one banded
    pass over all 40."""
    m = request.getfixturevalue(dtype)
    tol = TIGHT if dtype == "f32" else LOOSE
    ids = np.random.default_rng(0).integers(0, m.sizes["vocab"], (2, 40)).astype(np.int32)
    want = m.logp(ids)
    prompt = np.zeros((2, 24), np.int32)
    prompt[:, :20] = ids[:, :20]
    length = jnp.asarray([20, 13], jnp.int32)
    fill = jax.jit(lambda p, s, t, n: decode.prefill(m.conf, p, s, t, n))
    logp, state = fill(m.params, decode.init_state(m.conf, 2, 64), prompt, length)
    assert abs(np.asarray(logp)[0] - want[0, 19]).max() < tol
    assert abs(np.asarray(logp)[1] - want[1, 12]).max() < tol
    # padding is inert: other ids past a row's length change nothing a row keeps
    other = prompt.copy()
    other[0, 20:], other[1, 13:] = 7, 9
    logp2, state2 = fill(m.params, decode.init_state(m.conf, 2, 64), other, length)
    np.testing.assert_array_equal(logp, logp2)
    ring, ring2 = state[1], state2[1]
    for name in ("k", "v"):
        np.testing.assert_array_equal(ring[name], ring2[name])       # the last 8 real
        np.testing.assert_array_equal(state[7][name][1, :, :13], state2[7][name][1, :, :13])
    step = jax.jit(lambda p, s, t, q: decode.decode_step(m.conf, p, s, t, q))
    pos = np.asarray([20, 13])
    for _ in range(20):
        logp, state = step(m.params, state, jnp.asarray(ids[np.arange(2), pos]),
                           jnp.asarray(pos, jnp.int32))
        for r in range(2):
            assert abs(np.asarray(logp)[r] - want[r, pos[r]]).max() < tol
        pos = pos + 1
    assert state[1]["k"].shape == (2, 2, 8, 16) and state[7]["k"].shape == (2, 2, 64, 16)
