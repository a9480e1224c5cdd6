"""The attention layer types that Ling-3.0-flash's language model brought
(`kda`, `mla`) and its FFNs and head, each against the plain reference of
`benchmark/families/ling3`, at tiny widths on the CPU: a layer's forward
pass, the KDA state a prefill leaves, MLA's absorbed decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference as bench_reference
from deeplearning4j_tpu.nn.conf import LayerType
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.layers.kda import chunked_delta_rule
from ling3_model import TIGHT, Model, f32, rows     # noqa: F401  (f32: a fixture)


# ------------------------------------------------------------ (a) the layers

@pytest.mark.parametrize("kind", ["kda", "mla", "swiglu", "moe", "head"])
def test_a_layer_is_the_references(f32, kind):
    i = f32.layer(kind)
    x = rows((2, 24, f32.sizes["d"]), seed=i)
    frozen, c = bench_reference.Frozen(f32.cfg), f32.conf.conf(i)
    want = jax.jit(lambda w, v: f32.ref.apply_layer(kind, w, v, frozen))(
        f32.weights[i], x)
    got = jax.jit(lambda p, v: get_layer(c.layer_type).forward(p, c, v))(
        f32.params[i], x)
    if kind == "head":
        want = jax.nn.softmax(want, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=TIGHT)

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
    prefill = jax.jit(lambda p, v, s, n: impl.prefill(p, c, v, s, n))
    _, got = prefill(f32.params[i], padded, impl.init_state(c, 2, 0), short)
    for r, n in enumerate([13, 2]):
        _, want = prefill(f32.params[i], x[r:r + 1, :n], impl.init_state(c, 1, 0),
                          jnp.asarray([n], jnp.int32))
        np.testing.assert_allclose(np.asarray(got["S"][r]), np.asarray(want["S"][0]),
                                   atol=TIGHT)
        np.testing.assert_allclose(np.asarray(got["conv"][r]),
                                   np.asarray(want["conv"][0]), atol=1e-6)

# ------------------------------------------------------------------- (c) MLA

def test_mla_absorbed_decode_is_the_materialised_prefill(f32):
    i = f32.layer("mla")
    impl, c = get_layer(LayerType.MLA), f32.conf.conf(i)
    x = rows((2, 12, f32.sizes["d"]), seed=4)
    prefill = jax.jit(lambda p, v, s: impl.prefill(p, c, v, s, None))
    step = jax.jit(lambda p, v, s, q: impl.decode_step(p, c, v, s, q))
    full, _ = prefill(f32.params[i], x, impl.init_state(c, 2, 16))
    _, state = prefill(f32.params[i], x[:, :8], impl.init_state(c, 2, 16))
    for t in range(8, 12):              # rows at different positions, too
        got, state = step(f32.params[i], x[:, t], state,
                          jnp.asarray([t, t], jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, t]), atol=TIGHT)
    assert state["c"].shape == (2, 16, f32.sizes["kv_rank"])
    assert state["kr"].shape == (2, 16, f32.sizes["rope"])
