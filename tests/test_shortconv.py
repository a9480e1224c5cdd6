"""The gated short convolution (`nn/layers/shortconv.py`) at tiny widths on
the CPU: its prefill against the plain reference's loop over taps, a padded
bucket then decode steps against one whole pass, a finished row's tail, the
state through `nn/decode.py` (which names no layer type), and the one
causal convolution that the KDA layer shares with it, bit for bit against
the code KDA had inline before."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.lfm2_moe import reference as ref
from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.conf import (Activation, LayerType, LossFunction,
                                        MultiLayerConfiguration,
                                        NeuralNetConfiguration, ShortConvSpec)
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.layers.rms import causal_conv, conv_tail
from deeplearning4j_tpu.nn.layers.shortconv import ShortConvLayer
from deeplearning4j_tpu.nn.multilayer import init_params

N = 32
TIGHT = 2e-5        # float32 throughout: the order of the taps' sums alone


def conf(dtype="float32", kernel=3):
    return NeuralNetConfiguration(layer_type=LayerType.SHORT_CONV, n_in=N, n_out=N,
                                  dtype=dtype, compute_dtype=dtype,
                                  weight_init="normalized",
                                  layer_spec=ShortConvSpec(kernel=kernel, eps=1e-5))


def params(c, seed=1):
    p = ShortConvLayer.init(jax.random.PRNGKey(seed), c)
    # taps of 0.3: the convolution's part of the output is of the stream's
    # order, so a tap out of place shows
    return {**p, "conv": 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                                 p["conv"].shape).astype(p["conv"].dtype)}


def rows(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize("kernel", [2, 3, 4])
def test_a_prompt_is_the_references_loop_over_taps(kernel):
    c, x = conf(kernel=kernel), rows((2, 11, N))
    p = params(c)
    s = {"conv_k": kernel, "eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref.short_conv(
            {k: v.astype(jnp.float32) for k, v in p.items()}, x, s))(p, x)
    forward = jax.jit(lambda p, x: ShortConvLayer.forward(p, c, x))
    np.testing.assert_allclose(forward(p, x), want, atol=TIGHT)
    # each tap matters: with the taps moved one token along the mixer's
    # output is off by a large share of itself
    moved = {**p, "conv": jnp.roll(p["conv"], 1, axis=0)}
    off = forward(moved, x) - want
    assert float(jnp.linalg.norm(off)) > 0.3 * float(jnp.linalg.norm(want - x))


def test_a_padded_bucket_then_steps_is_one_whole_pass():
    """Rows of 5 and 2 real tokens in a bucket of 8 (the padding is not
    zero: it must not reach the tail), then a token at a time: every output
    is the whole pass's at its position."""
    c, x = conf(), rows((2, 9, N), seed=3)
    p = params(c)
    whole = jax.jit(lambda p, x: ShortConvLayer.forward(p, c, x))(p, x)
    lengths = jnp.asarray([5, 2], jnp.int32)
    padded = jnp.where(jnp.arange(8)[None, :, None] < lengths[:, None, None],
                       x[:, :8], 7.0)
    hidden, state = jax.jit(lambda p, v, s, n: ShortConvLayer.prefill(p, c, v, s, n))(
        p, padded, ShortConvLayer.init_state(c, 2, 16), lengths)
    for r, n in enumerate((5, 2)):
        np.testing.assert_allclose(hidden[r, :n], whole[r, :n], atol=TIGHT)
    assert state["conv"].shape == (2, 2, N)
    step = jax.jit(lambda p, v, s, q: ShortConvLayer.decode_step(p, c, v, s, q))
    at = np.asarray(lengths)
    for _ in range(3):
        h, state = step(p, x[jnp.arange(2), at], state, jnp.asarray(at))
        for r in range(2):
            np.testing.assert_allclose(h[r], whole[r, at[r]], atol=TIGHT)
        at = at + 1


def stack(kernel=3):
    """One-hot ids, one convolution layer, the head."""
    base = NeuralNetConfiguration(n_in=N, n_out=N, weight_init="normalized",
                                  activation=Activation.TANH)
    return MultiLayerConfiguration(confs=(
        conf(kernel=kernel),
        base.replace(layer_type=LayerType.OUTPUT, activation=Activation.SOFTMAX,
                     loss_function=LossFunction.MCXENT)))


def test_the_tail_goes_through_the_walkers_that_never_name_it():
    net_conf = stack()
    p = init_params(net_conf, jax.random.PRNGKey(5))
    p = (params(net_conf.conf(0)), p[1])
    ids = jnp.asarray([[3, 1, 4, 1, 5, 9, 2], [6, 5, 3, 5, 8, 9, 7]], jnp.int32)
    whole = jax.nn.one_hot(ids, N, dtype=jnp.float32)
    head = get_layer(LayerType.OUTPUT)
    want = jax.jit(lambda p, v: jnp.log(jnp.clip(head.forward(
        p[1], net_conf.conf(1),
        ShortConvLayer.forward(p[0], net_conf.conf(0), v).reshape(14, N)),
        1e-9, 1.0)).reshape(2, 7, N))(p, whole)
    lengths = jnp.asarray([4, 2], jnp.int32)
    logp, state = jax.jit(lambda p, s, t, n: decode.prefill(net_conf, p, s, t, n))(
        p, decode.init_state(net_conf, 2, 16), ids[:, :4], lengths)
    step = jax.jit(lambda p, s, t, q: decode.decode_step(net_conf, p, s, t, q))
    np.testing.assert_allclose(logp, want[jnp.arange(2), lengths - 1], atol=1e-5)
    assert sorted(state[0]) == ["conv"] and state[1] == {}
    at = lengths
    for _ in range(3):
        logp, state = step(p, state, ids[jnp.arange(2), at], at)
        np.testing.assert_allclose(logp, want[jnp.arange(2), at], atol=1e-5)
        at = at + 1
    # the table is dense-only, and the decode path asks no type what it is
    assert decode.dense_only(net_conf) == ["shortconv"]
    assert decode.state_bytes_read(net_conf, 16) == {"table": 0, "carry": 2 * N * 4}
    from deeplearning4j_tpu.optimize import infer_cache
    from deeplearning4j_tpu.serving import batcher
    for module in (decode, infer_cache, batcher):
        text = inspect.getsource(module)
        assert "SHORT_CONV" not in text and "shortconv" not in text.lower(), module


def test_an_admission_replaces_a_finished_rows_tail():
    """The table steps every row, a finished one too, so a free slot's tail
    moves on past its stream's end; the next admission's write puts back
    the whole row, and its tail is what a fresh prefill leaves."""
    net_conf = stack()
    p = init_params(net_conf, jax.random.PRNGKey(6))
    tok, pos = jnp.asarray([1, 2], jnp.int32), jnp.zeros((2,), jnp.int32)
    table = decode.init_state(net_conf, 2, 8)
    for _ in range(3):
        _, table = decode.decode_step(net_conf, p, table, tok, pos)
        pos = pos + 1
    prompt, length = jnp.asarray([[3, 1, 4, 0]], jnp.int32), jnp.asarray([3], jnp.int32)
    _, fresh = decode.prefill(net_conf, p, decode.init_state(net_conf, 1, 8), prompt, length)
    assert not np.array_equal(table[0]["conv"][1], fresh[0]["conv"][0])
    table = decode.write_row(table, fresh, jnp.int32(1))
    np.testing.assert_array_equal(table[0]["conv"][1], fresh[0]["conv"][0])
    assert not np.array_equal(table[0]["conv"][0], fresh[0]["conv"][0])


# ------------------------------------- the convolution KDA shares, bit for bit

def parents_prefill(tail, qkv, w, length):
    """The KDA layer's prefill convolution as it stood inline before."""
    kk, t = w.shape[0], qkv.shape[1]
    seen = jnp.concatenate([tail, qkv], axis=1)
    w = w.astype(jnp.float32)
    y = jax.nn.silu(sum(seen[:, j: j + t].astype(jnp.float32) * w[j] for j in range(kk)))
    conv = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
        row, n, kk - 1, axis=0))(seen, length)
    return y, conv


def parents_step(tail, qkv, w):
    seen = jnp.concatenate([tail, qkv[:, None]], axis=1)
    w = w.astype(jnp.float32)
    return jax.nn.silu(jnp.sum(seen.astype(jnp.float32) * w[None], axis=1)), seen[:, 1:]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_shared_convolution_is_kdas_old_one_bit_for_bit(dtype):
    k, c = 4, 48
    tail = rows((3, k - 1, c), 1).astype(dtype)
    prompt = rows((3, 10, c), 2).astype(dtype)
    token = rows((3, c), 3).astype(dtype)
    w = rows((k, c), 4).astype(dtype)
    length = jnp.asarray([10, 4, 1], jnp.int32)

    def new_prefill(tail, x, w, length):
        y, seen = causal_conv(tail, x, w)
        return jax.nn.silu(y), conv_tail(seen, k, length)

    def new_step(tail, x, w):
        y, seen = causal_conv(tail, x, w)
        return jax.nn.silu(y), conv_tail(seen, k)

    for old, new, args in ((parents_prefill, new_prefill, (tail, prompt, w, length)),
                           (parents_step, new_step, (tail, token, w))):
        for a, b in zip(jax.jit(old)(*args), jax.jit(new)(*args)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
