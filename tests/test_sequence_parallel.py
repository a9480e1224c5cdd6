"""Sequence/context parallelism: blockwise, ring, Ulysses attention.

Distributed cases run on the 8-device virtual CPU mesh from conftest (the
analog of the reference's in-JVM rig, `BaseTestDistributed.java:34-98`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.parallel.sequence import (
    blockwise_attention, full_attention, make_context_parallel_attention,
    ring_attention, ulysses_attention)

B, S, H, D = 2, 32, 4, 8


def _qkv(seed=0):
    k = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(k, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    kk_ = jax.random.normal(kk, (B, S, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H, D), jnp.float32)
    return q, kk_, v


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_full(causal):
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, block_size=8, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_ragged_tail_exact(causal):
    q, k, v = _qkv()
    out = blockwise_attention(q, k, v, block_size=5, causal=causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_attention_layer_rejects_bad_n_out():
    from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import get_layer

    conf = NeuralNetConfiguration(layer_type=LayerType.ATTENTION, n_in=16,
                                  n_out=32, n_heads=4)
    with pytest.raises(ValueError, match="residual"):
        get_layer(conf.layer_type).init(jax.random.PRNGKey(0), conf)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(1)
    ref = full_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, axis="sp", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    mesh = make_mesh({"sp": 4})  # heads=4 must be divisible by axis
    q, k, v = _qkv(2)
    ref = full_attention(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh, axis="sp", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_flow():
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(3)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring))(q, k, v)
    g_full = jax.jit(jax.grad(loss_full))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               rtol=1e-3, atol=1e-3)


def test_make_context_parallel_attention_jits():
    mesh = make_mesh({"sp": 8})
    fn = make_context_parallel_attention(mesh, kind="ring", causal=True)
    q, k, v = _qkv(4)
    out = fn(q, k, v)
    assert out.shape == (B, S, H, D)
    assert np.isfinite(np.asarray(out)).all()


def test_attention_layer_in_network():
    from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import get_layer

    conf = NeuralNetConfiguration(layer_type=LayerType.ATTENTION, n_in=16,
                                  n_out=16, n_heads=4, causal=True,
                                  attention_block_size=8)
    layer = get_layer(conf.layer_type)
    params = layer.init(jax.random.PRNGKey(0), conf)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    y = jax.jit(lambda p, x: layer.forward(p, conf, x))(params, x)
    assert y.shape == x.shape
    # conf round-trips through JSON with the new fields
    conf2 = NeuralNetConfiguration.from_json(conf.to_json())
    assert conf2.n_heads == 4 and conf2.causal and conf2.attention_block_size == 8


def test_char_transformer_lm_learns():
    """Flagship transformer LM: learns a deterministic char pattern."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    vocab, seq, batch = 5, 16, 8
    rng = np.random.RandomState(0)
    # cyclic pattern: next char = (char + 1) % vocab
    starts = rng.randint(0, vocab, batch)
    seqs = (starts[:, None] + np.arange(seq + 1)) % vocab
    x = jnp.asarray(seqs[:, :-1])
    y = jax.nn.one_hot(jnp.asarray(seqs[:, 1:]).reshape(-1), vocab)

    conf = char_transformer(vocab, d_model=32, n_blocks=1, n_heads=4,
                            max_seq_len=seq, lr=0.01, iterations=150)
    net = MultiLayerNetwork(conf, seed=0).init()
    net.fit(x, y)
    out = np.asarray(net.output(x)).reshape(batch, seq, vocab)
    pred = out.argmax(-1)
    acc = (pred == np.asarray(seqs[:, 1:])).mean()
    assert acc > 0.95, f"transformer LM failed to learn cycle: acc={acc}"
