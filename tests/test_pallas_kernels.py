"""Pallas kernels vs jax-level references (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nd.attention import full_attention
from deeplearning4j_tpu.nd.pallas_kernels import (flash_attention,
                                                  fused_lstm_step,
                                                  scatter_add_rows)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_full(causal):
    k = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(k, 3)
    B, S, H, D = 2, 32, 2, 8
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    kk_ = jax.random.normal(kk, (B, S, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H, D), jnp.float32)
    ref = full_attention(q, kk_, v, causal=causal)
    out = flash_attention(q, kk_, v, causal, 8, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grads():
    k = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(k, 3)
    B, S, H, D = 1, 16, 2, 4
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    kk_ = jax.random.normal(kk, (B, S, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H, D), jnp.float32)

    g_fl = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True, 8, 8) ** 2), argnums=(0, 1, 2)))(
        q, kk_, v)
    g_ref = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        full_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2)))(
        q, kk_, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_fused_lstm_step_matches_reference():
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 6)
    B, I, H = 4, 8, 16
    x = jax.random.normal(ks[0], (B, I))
    h = jax.random.normal(ks[1], (B, H))
    c = jax.random.normal(ks[2], (B, H))
    wx = jax.random.normal(ks[3], (I, 4 * H)) * 0.1
    wh = jax.random.normal(ks[4], (H, 4 * H)) * 0.1
    b = jax.random.normal(ks[5], (4 * H,)) * 0.1

    h_new, c_new = fused_lstm_step(x, h, c, wx, wh, b)

    z = x @ wx + h @ wh + b
    i, f, o, g = (jax.nn.sigmoid(z[:, :H]), jax.nn.sigmoid(z[:, H:2 * H]),
                  jax.nn.sigmoid(z[:, 2 * H:3 * H]), jnp.tanh(z[:, 3 * H:]))
    c_ref = f * c + i * g
    h_ref = o * jnp.tanh(c_ref)
    np.testing.assert_allclose(np.asarray(h_new), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_new), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-5)


def test_scatter_add_rows_with_duplicates():
    table = jnp.zeros((10, 4), jnp.float32)
    idx = jnp.array([1, 3, 1, 7], jnp.int32)
    upd = jnp.arange(16, dtype=jnp.float32).reshape(4, 4)
    out = scatter_add_rows(table, idx, upd)
    ref = np.zeros((10, 4), np.float32)
    for i, r in zip([1, 3, 1, 7], np.asarray(upd)):
        ref[i] += r
    np.testing.assert_allclose(np.asarray(out), ref)


def test_scatter_add_rows_ragged_padding():
    table = jnp.ones((6, 4), jnp.float32)
    idx = jnp.array([5, 0, 5], jnp.int32)  # 3 rows -> padded to 8 internally
    upd = jnp.ones((3, 4), jnp.float32)
    out = scatter_add_rows(table, idx, upd)
    ref = np.ones((6, 4), np.float32)
    ref[5] += 2.0
    ref[0] += 1.0
    np.testing.assert_allclose(np.asarray(out), ref)


def test_attention_layer_flash_impl():
    from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import get_layer

    conf = NeuralNetConfiguration(layer_type=LayerType.ATTENTION, n_in=16,
                                  n_out=16, n_heads=4, causal=True,
                                  attention_block_size=8,
                                  attention_impl="flash")
    layer = get_layer(conf.layer_type)
    params = layer.init(jax.random.PRNGKey(0), conf)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    y = layer.forward(params, conf, x)
    conf_full = conf.replace(attention_impl="full")
    y_ref = layer.forward(params, conf_full, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_lstm_layer_fused_matches_scan():
    from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import get_layer

    conf = NeuralNetConfiguration(layer_type=LayerType.LSTM, n_in=8,
                                  n_out=16, lstm_impl="scan")
    layer = get_layer(conf.layer_type)
    params = layer.init(jax.random.PRNGKey(0), conf)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, 8))
    y_scan = layer.forward(params, conf, x)
    y_fused = layer.forward(params, conf.replace(lstm_impl="fused"), x)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_scan),
                               rtol=1e-5, atol=1e-5)


def test_lstm_layer_fused_grads_match_scan():
    from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import get_layer

    conf = NeuralNetConfiguration(layer_type=LayerType.LSTM, n_in=4,
                                  n_out=8, lstm_impl="scan")
    layer = get_layer(conf.layer_type)
    params = layer.init(jax.random.PRNGKey(2), conf)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 4))

    def loss(p, c):
        return jnp.sum(layer.forward(p, c, x) ** 2)

    g_scan = jax.jit(jax.grad(loss), static_argnums=1)(params, conf)
    g_fused = jax.jit(jax.grad(loss), static_argnums=1)(
        params, conf.replace(lstm_impl="fused"))
    for k in g_scan:
        np.testing.assert_allclose(np.asarray(g_fused[k]),
                                   np.asarray(g_scan[k]),
                                   rtol=1e-4, atol=1e-5)
