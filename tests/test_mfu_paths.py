"""Parity tests for the MFU-campaign hot paths.

Each optimized path is gated by a conf flag and claims BITWISE f32
identity (sparse labels) or reference-tolerance identity (flash
block-skip) with the path it replaces — these tests are the claim's
enforcement.  The updater chain has one layout since PR 31 (the
`fused_updater` flag is inert), so its cases check the mathematics against
NumPy; what the flag no longer does is held in `test_updater_one_layout.py`.  Flag combinations are also exercised end-to-end
through `MultiLayerNetwork.finetune` (the compiled step-cache program),
so the parity holds through tracing, donation and the solver scan, not
just at the op level (`test_mfu_end_to_end.py`); the fused flash backward is in
`test_mfu_fused_bwd.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nd import losses
from deeplearning4j_tpu.nd.attention import full_attention
from deeplearning4j_tpu.nd.pallas_kernels import (flash_attention,
                                                  pick_attention_blocks)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.optimize.updater import (adjust_gradient,
                                                 init_updater, update_params)
from mfu_helpers import _assert_tree_bitwise, _f64, _numpy_chain


# -- sparse-label loss path --------------------------------------------------

def _softmax_rows(key, rows, vocab):
    logits = jax.random.normal(key, (rows, vocab), jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


def test_sparse_mcxent_bitwise_value_and_grad():
    key = jax.random.PRNGKey(0)
    rows, vocab = 40, 13
    p = _softmax_rows(key, rows, vocab)
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows,), 0, vocab)
    one_hot = jax.nn.one_hot(ids, vocab, dtype=jnp.float32)

    dense = losses.mcxent_rows(one_hot, p)
    sparse = losses.mcxent_rows(ids.astype(jnp.int32), p)
    _assert_tree_bitwise(dense, sparse, "mcxent rows")

    g_dense = jax.grad(lambda o: jnp.mean(losses.mcxent_rows(one_hot, o)))(p)
    g_sparse = jax.grad(lambda o: jnp.mean(losses.mcxent_rows(ids, o)))(p)
    _assert_tree_bitwise(g_dense, g_sparse, "mcxent grad")


def test_sparse_mcxent_padded_tail_weighted_bitwise():
    """Pad rows carry class id 0 and weight 0.0 (`pad_batch` convention):
    the weighted loss and its gradient must match the one-hot path's
    all-zero pad rows bit for bit."""
    key = jax.random.PRNGKey(2)
    real, pad, vocab = 24, 8, 11
    p = _softmax_rows(key, real + pad, vocab)
    ids = np.zeros(real + pad, np.int32)
    ids[:real] = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (real,), 0, vocab))
    one_hot = np.zeros((real + pad, vocab), np.float32)
    one_hot[np.arange(real), ids[:real]] = 1.0  # pad rows stay all-zero
    w = jnp.asarray(np.r_[np.ones(real), np.zeros(pad)].astype(np.float32))

    def weighted(labels, o):
        return jnp.dot(losses.mcxent_rows(labels, o), w) / jnp.sum(w)

    v_dense = weighted(jnp.asarray(one_hot), p)
    v_sparse = weighted(jnp.asarray(ids), p)
    _assert_tree_bitwise(v_dense, v_sparse, "weighted loss")
    g_dense = jax.grad(lambda o: weighted(jnp.asarray(one_hot), o))(p)
    g_sparse = jax.grad(lambda o: weighted(jnp.asarray(ids), o))(p)
    _assert_tree_bitwise(g_dense, g_sparse, "weighted grad")


def test_sparse_labels_rejected_outside_mcxent_family():
    ids = jnp.zeros(4, jnp.int32)
    out = jnp.ones((4, 3), jnp.float32) / 3.0
    for fn in ("mse", "xent", "squared_loss"):
        with pytest.raises(TypeError, match="sparse"):
            losses.get_rowwise(fn)(ids, out)
        with pytest.raises(TypeError, match="sparse"):
            losses.get_loss(fn)(ids, out)
    # the mcxent family accepts them
    losses.get_rowwise("mcxent")(ids, out)
    losses.get_loss("negativeloglikelihood")(ids, out)


# -- the updater chain -------------------------------------------------------

def _param_tree(key):
    """Odd, MXU-unfriendly shapes on purpose, and leaves of several sizes:
    the two global norms run over all of them."""
    ks = jax.random.split(key, 4)
    return {"blk": {"W": jax.random.normal(ks[0], (13, 7), jnp.float32),
                    "b": jax.random.normal(ks[1], (7,), jnp.float32)},
            "out": {"W": jax.random.normal(ks[2], (7, 5), jnp.float32),
                    "b": jax.random.normal(ks[3], (5,), jnp.float32)}}


_UPDATER_OPTIONS = [
    {},
    {"gradient_clip_norm": 0.05},          # binding clip: norms on the path
    {"constrain_gradient_to_unit_norm": True},
    {"use_regularization": True, "l2": 1e-3},
    {"use_adagrad": True, "adagrad_reset_iterations": 2},
]


@pytest.mark.parametrize("which", ["", "sgd", "adagrad", "nesterov",
                                   "adam", "rmsprop"])
@pytest.mark.parametrize("opts", _UPDATER_OPTIONS,
                         ids=[",".join(o) or "plain"
                              for o in _UPDATER_OPTIONS])
def test_updater_chain_matches_numpy(which, opts):
    """Three iterations of `adjust_gradient` under `jit`, state carried,
    against the NumPy float64 statement of each algorithm: clip, unit
    norm, l2 and the AdaGrad reset (iteration 0 and 2) included."""
    conf = NeuralNetConfiguration(lr=0.05, momentum=0.9, updater=which,
                                  **opts)
    params = _param_tree(jax.random.PRNGKey(10))
    state = init_updater(params)
    chain = jax.jit(lambda it, g, s: adjust_gradient(conf, it, g, params, s))
    hist, vel = _f64(state.adagrad_hist), _f64(state.velocity)
    for it in range(3):
        grads = jax.tree_util.tree_map(
            lambda p: jax.random.normal(jax.random.fold_in(
                jax.random.PRNGKey(20), it), p.shape, p.dtype) * 0.1, params)
        step, state = chain(jnp.asarray(it), grads, state)
        want, hist, vel = _numpy_chain(conf, it, _f64(grads), _f64(params),
                                       hist, vel)
        for name, got, ref in [("step", step, want),
                               ("hist", state.adagrad_hist, hist),
                               ("velocity", state.velocity, vel)]:
            for i, (x, y) in enumerate(zip(_f64(got), ref)):
                np.testing.assert_allclose(
                    x, y, rtol=2e-5, atol=1e-7,
                    err_msg=f"{which or 'legacy'} {name} leaf {i} it={it}")


@pytest.mark.parametrize("which", ["", "sgd", "adagrad", "nesterov",
                                   "adam", "rmsprop"])
def test_update_params_is_params_minus_the_step(which):
    """What the train steps call: `update_params` lands where subtracting
    `adjust_gradient`'s step does, with the same new state (two programs,
    so a fused multiply-add may round a last bit differently)."""
    conf = NeuralNetConfiguration(lr=0.05, momentum=0.9, updater=which,
                                  gradient_clip_norm=0.05)
    params = _param_tree(jax.random.PRNGKey(13))
    grads = jax.tree_util.tree_map(lambda p: 0.3 * p + 0.01, params)
    state = init_updater(params)
    step, want_state = jax.jit(
        lambda g, p, s: adjust_gradient(conf, 1, g, p, s))(
        grads, params, state)
    got, got_state = jax.jit(
        lambda g, p, s: update_params(conf, 1, g, p, s))(grads, params, state)
    want = jax.tree_util.tree_map(lambda p, a: p - a, params, step)
    for x, y in zip(_f64((got, got_state)), _f64((want, want_state))):
        np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-7)


# -- causal flash block-skip -------------------------------------------------

@pytest.mark.parametrize("seq,blocks", [(64, (16, 16)), (96, (32, 16)),
                                        (128, (32, 32))])
def test_block_skip_bitwise_vs_masked_flash(seq, blocks):
    """Skipping the mask on fully-unmasked tiles replaces a `where` by its
    identity branch — forward AND backward must be bitwise-identical to
    the all-masked kernel, at ragged (S, block) combinations where full
    and partial tiles mix."""
    bq, bk = blocks
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(4), 3)
    B, H, D = 2, 2, 8
    q = jax.random.normal(kq, (B, seq, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, seq, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, seq, H, D), jnp.float32)

    base = flash_attention(q, k, v, True, bq, bk, block_skip=False)
    skip = flash_attention(q, k, v, True, bq, bk, block_skip=True)
    _assert_tree_bitwise(base, skip, f"fwd S={seq}")

    g_base = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, True, bq, bk, block_skip=False) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_skip = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, True, bq, bk, block_skip=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    _assert_tree_bitwise(g_base, g_skip, f"bwd S={seq}")


def test_block_skip_matches_full_attention():
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    B, S, H, D = 2, 64, 2, 8
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H, D), jnp.float32)
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, 16, 16, block_skip=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pick_attention_blocks_table_and_fallback():
    assert pick_attention_blocks(256, 32) == (128, 128)   # table hit
    assert pick_attention_blocks(2048, 128) == (256, 256)
    bq, bk = pick_attention_blocks(192, 48)               # fallback: divides
    assert 192 % bq == 0 and 192 % bk == 0
    assert pick_attention_blocks(100, 64) == (128, 128)   # indivisible S
    # bwd-aware picks: table hits return the (bwd_q, bwd_k) half, the
    # fallback caps one notch lower (more live VMEM per backward tile)
    assert pick_attention_blocks(256, 32, bwd=True) == (128, 128)
    assert pick_attention_blocks(4096, 128, bwd=True) == (128, 256)
    bq, bk = pick_attention_blocks(192, 48, bwd=True)
    assert 192 % bq == 0 and 192 % bk == 0 and bq <= 128 and bk <= 256
    assert pick_attention_blocks(100, 64, bwd=True) == (128, 128)
