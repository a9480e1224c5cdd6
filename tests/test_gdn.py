"""The Gated DeltaNet layer (`nn/layers/gdn.py`) at toy widths on the CPU,
in float32: its chunked prefill against the token recurrence at chunk edges
and with padding, a prefill then decode against the whole forward, the
mapping of key heads to the value heads they serve, and the padding's
identity (g = 0, beta = 0).  Against the plain reference's layer
(`benchmark/families/qwen3_next/reference.py::gdn`) it is held in the whole
model (`benchmark/tests/test_family_qwen3next.py`).  The tolerances are
float32's: what is left is the order of sums (a chunk's products against
the token loop)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import GDNSpec, LayerType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import kda
from deeplearning4j_tpu.nn.layers.gdn import GDNLayer

N = 32
SPEC = GDNSpec(n_k_heads=2, n_v_heads=4, k_dim=8, v_dim=8, conv_kernel=4)
CONF = NeuralNetConfiguration(layer_type=LayerType.GDN, n_in=N, n_out=N,
                              weight_init="normalized", layer_spec=SPEC)
# float32 sums in another order: a chunk's products against the token loop
TIGHT = 2e-5


def params(seed: int = 0):
    """The layer's leaves with decays and gates that do something: A_log
    and dt_bias drawn, norm weights off 1, convolution taps small."""
    p = GDNLayer.init(jax.random.PRNGKey(seed), CONF)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    return dict(p, A_log=0.3 * jax.random.normal(ks[0], (4,)),
                dt_bias=-2.0 + jax.random.normal(ks[1], (4,)),
                o_norm=1.0 + 0.1 * jax.random.normal(ks[2], (8,)),
                conv=0.3 * jax.random.normal(ks[3], (4, 64)))


def inputs(b: int, t: int, seed: int = 2):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, N), jnp.float32)


@jax.jit
def recurrence(q, k, v, g, beta, state):
    """`kda.delta_step` a token at a time: the rule's own definition."""
    def one(s, xs):
        o, s = kda.delta_step(*xs[:3], xs[3], xs[4], s)
        return s, o

    state, o = jax.lax.scan(one, state, tuple(jnp.moveaxis(a, 1, 0)
                                              for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


@pytest.mark.parametrize("t,chunk", [(16, 16), (17, 16), (31, 8), (5, 8)],
                         ids=["one-whole-chunk", "one-past-a-chunk", "ragged-chunks",
                              "shorter-than-a-chunk"])
def test_the_chunked_rule_with_a_decay_a_head_is_the_token_recurrence(t, chunk):
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    b, h, dk = 2, 4, 8
    q = kda.l2norm(jax.random.normal(ks[0], (b, t, h, dk)))
    k = kda.l2norm(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dk))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h, 1)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, dk, dk))
    o, s = jax.jit(lambda *a: kda.chunked_delta_rule(*a, chunk=chunk))(q, k, v, g, beta, s0)
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o, want_o, atol=TIGHT)
    np.testing.assert_allclose(s, want_s, atol=TIGHT)


def test_a_padded_position_leaves_the_state_as_it_was():
    """g = 0 and beta = 0 is the identity: one token of it, and a chunk of
    it after real tokens, return the state they were given."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    s0 = jax.random.normal(ks[0], (2, 4, 8, 8))
    q, k, v = (jax.random.normal(ks[i], (2, 4, 8)) for i in (1, 2, 3))
    zero_g, zero_b = jnp.zeros((2, 4, 1)), jnp.zeros((2, 4))
    _, s = kda.delta_step(q, kda.l2norm(k), v, zero_g, zero_b, s0)
    np.testing.assert_array_equal(s, s0)
    rows = lambda a: jnp.repeat(a[:, None], 5, axis=1)       # noqa: E731
    _, s = kda.chunked_delta_rule(rows(q), rows(kda.l2norm(k)), rows(v),
                                  rows(zero_g), rows(zero_b), s0, chunk=4)
    np.testing.assert_allclose(s, s0, atol=TIGHT)


def test_a_prefill_then_decode_is_the_whole_forward(monkeypatch):
    """A prompt of 11 in a bucket of 16 (padded past its length, in chunks
    of 4), then 5 tokens decoded one at a time, against the forward pass
    over all 16 tokens; a second row of another length in the same call."""
    monkeypatch.setattr(kda, "CHUNK", 4)
    p, x = params(), inputs(2, 16)
    whole = jax.jit(lambda p, x: GDNLayer.forward(p, CONF, x))(p, x)
    length = jnp.asarray([11, 7], jnp.int32)
    padded = jnp.where((jnp.arange(16)[None, :] < length[:, None])[..., None], x, 0.0)
    hidden, state = jax.jit(lambda p, x, s, n: GDNLayer.prefill(p, CONF, x, s, n))(
        p, padded, GDNLayer.init_state(CONF, 2, 16), length)
    np.testing.assert_allclose(hidden[0, :11], whole[0, :11], atol=TIGHT)
    np.testing.assert_allclose(hidden[1, :7], whole[1, :7], atol=TIGHT)
    assert state["S"].dtype == jnp.float32 and state["S"].shape == (2, 4, 8, 8)
    assert state["conv"].shape == (2, 3, 64)
    step = jax.jit(lambda p, x, s, q: GDNLayer.decode_step(p, CONF, x, s, q))
    at = np.asarray([11, 7])
    for _ in range(5):
        h, state = step(p, x[jnp.arange(2), at], state, jnp.asarray(at, jnp.int32))
        for r in range(2):
            np.testing.assert_allclose(h[r], whole[r, at[r]], atol=TIGHT)
        at = at + 1


def test_a_key_head_serves_its_value_heads_and_no_others():
    """Value heads 2j and 2j + 1 read key head j: the layer's q and k of a
    value head are its key head's, l2-normed; and moving one key head's
    input moves the output of its two value heads alone."""
    y = jax.random.normal(jax.random.PRNGKey(4), (3, 64))
    q, k, v = GDNLayer._heads(CONF, y)
    assert q.shape == k.shape == (3, 4, 8) and v.shape == (3, 4, 8)
    keys = y[:, 16:32].reshape(3, 2, 8)
    for j in range(2):
        for i in (2 * j, 2 * j + 1):
            np.testing.assert_allclose(k[:, i], kda.l2norm(keys[:, j]), atol=1e-6)
    np.testing.assert_array_equal(v, y[:, 32:].reshape(3, 4, 8))

    s0 = jnp.zeros((3, 4, 8, 8))
    ones = jnp.ones((3, 4))
    o0, _ = kda.delta_step(q, k, v, jnp.zeros((3, 4, 1)), ones, s0)
    moved = y.at[:, 16:24].add(1.0)                 # key head 0's input
    q1, k1, v1 = GDNLayer._heads(CONF, moved)
    o1, _ = kda.delta_step(q1, k1, v1, jnp.zeros((3, 4, 1)), ones, s0)
    changed = np.abs(np.asarray(o1 - o0)).max(axis=(0, 2)) > 1e-6
    assert changed.tolist() == [True, True, False, False]
