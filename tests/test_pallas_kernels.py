"""Pallas kernels vs jax-level references (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nd import pallas_kernels
from deeplearning4j_tpu.nd.attention import full_attention
from deeplearning4j_tpu.nd.pallas_kernels import (flash_attention,
                                                  fused_lstm_step,
                                                  scatter_add_rows)
from deeplearning4j_tpu.nn.layers.mla import select_top


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


# -------------------------------------- sparse latent attention (decode)

CASES = {   # a call's rows' positions (the table holds 64 cells, blocks of 16)
    "at position 0": [0, 0, 0],
    "under topk": [3, 11, 7],            # every live cell is picked
    "ties": [40, 63, 29],                # the k-th value is shared
    "ragged": [20, 37, 50],              # live lengths that are not whole blocks
    "mixed": [0, 5, 12, 16, 47, 63],     # one call, rows at every kind of place
    "dead slot": [33, 64, 25],           # a stale row past the table, and one
}                                        # whose choice is empty


def masked_read(q, table, picked, scale):
    """The oracle: the whole table under the choice, as the step reads it off
    the chip."""
    hi = jax.lax.Precision.HIGHEST
    sc = jnp.einsum("bhc,bsc->bhs", q, table, precision=hi,
                    preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(picked[:, None], sc, -1e30), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p.astype(table.dtype), table, precision=hi,
                      preferred_element_type=jnp.float32)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dsa_decode_is_the_masked_read(dtype, case, monkeypatch):
    """`dl4j_dsa_decode` against the whole table under the choice (what an
    indexed layer's decode step reads off the chip), the mask
    `mla.select_top`'s."""
    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 16)
    pos = jnp.asarray(CASES[case], jnp.int32)
    n, cells, k = len(CASES[case]), 64, 12
    key = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(key[0], (n, 4, 128), jnp.float32).astype(dtype)
    table = jax.random.normal(key[1], (n, cells, 128), jnp.float32).astype(dtype)
    scores = jax.random.normal(key[2], (n, cells), jnp.float32)
    if case == "ties":
        scores = jnp.round(scores)
    picked = select_top(scores, jnp.arange(cells)[None] <= pos[:, None], k)
    if case == "dead slot":
        picked = picked.at[2].set(False)
    got = pallas_kernels.dsa_decode(q, table, picked, pos, 0.125, interpret=True)
    want = masked_read(q, table, picked, 0.125)
    has = np.asarray(picked.any(-1))
    # bfloat16: the kernel rounds the softmax's weights before it divides by
    # their sum, the read after; either way 8 bits of a weight
    tol = 1e-5 if dtype == "float32" else 1.5e-2
    np.testing.assert_allclose(np.asarray(got)[has], np.asarray(want)[has], atol=tol)
    assert (np.asarray(got)[~has] == 0).all() and has.sum() == n - (case == "dead slot")


def test_dsa_decode_waits_for_every_copy_it_starts(monkeypatch, capsys):
    """Under the TPU interpreter's happens-before race detector, with copies
    that land as they start, fresh buffers full of NaN and reads off a
    buffer's end raising: the copies a row starts for itself and for the
    next row are each waited once (a copy left unwaited leaves its
    semaphore counting at the kernel's exit, which the interpreter prints),
    none races a read of its buffer, and nothing unwritten is summed."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 16)
    pos = jnp.asarray([64, 0, 12, 16, 47, 63], jnp.int32)   # stale, first, ragged
    key = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(key[0], (6, 4, 128), jnp.float32)
    table = jax.random.normal(key[1], (6, 64, 128), jnp.float32)
    picked = select_top(jax.random.normal(key[2], (6, 64), jnp.float32),
                        jnp.arange(64)[None] <= pos[:, None], 12)
    got = pallas_kernels.dsa_decode(q, table, picked, pos, 0.125, interpret=pltpu.InterpretParams(
        detect_races=True, dma_execution_mode="eager"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        masked_read(q, table, picked, 0.125)), atol=1e-5)
    assert not interpret_pallas_call.races.races_found
    assert "non-zero count" not in capsys.readouterr().out


# ------------------------------------- grouped-query attention (decode)

GQA_ROWS = {    # a call's rows' positions, blocks of 16 (the chip's 512 scaled):
    # the first cell, either side of a block's edge, the table's last cell
    # and finished slots past it; then rows anywhere
    "edges": lambda cells: [0, 15, 16, 17, cells - 1, cells, cells + 3],
    "row mix": lambda cells: np.random.default_rng(cells).integers(0, cells, 6).tolist(),
}


@pytest.mark.parametrize("rows", list(GQA_ROWS))
@pytest.mark.parametrize("cells", [64, 288], ids=["4-blocks", "18-blocks"])
@pytest.mark.parametrize("heads", [(4, 8, 128), (8, 4, 64), (16, 1, 128)],
                         ids=["G4-R8-h128", "G8-R4-h64", "G16-R1-h128"])
def test_gqa_decode_is_the_masked_read(heads, cells, rows, monkeypatch, capsys):
    """`dl4j_gqa_decode` against the whole table under `seen`, as a grouped
    layer's decode step reads it off the chip (`GQALayer._attend`), in float32;
    heads of 64 handed positions innermost, as the chip lays their table out.
    Every cell past a row's last block is NaN, so a block read past a row's
    position would poison its output.  Under the TPU interpreter's race
    detector with copies that land as they start: every copy is waited once
    and none races a read of its buffer."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.nn.conf import GQASpec, LayerType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.gqa import GQALayer

    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 16)
    g, r, h = heads
    pos = jnp.asarray(GQA_ROWS[rows](cells), jnp.int32)
    n = len(pos)
    key = jax.random.split(jax.random.PRNGKey(14), 3)
    q = jax.random.normal(key[0], (n, g, r, h), jnp.float32) / np.sqrt(h)
    k, v = (jax.random.normal(kk, (n, g, cells, h), jnp.float32) for kk in key[1:])
    walked = (np.clip(np.asarray(pos), -1, cells - 1) // 16 + 1) * 16
    past = jnp.asarray(np.arange(cells)[None] >= walked[:, None])[:, None, :, None]
    conf = NeuralNetConfiguration(layer_type=LayerType.GQA, n_in=g * r * h, dtype="float32",
                                  layer_spec=GQASpec(n_heads=g * r, n_kv_heads=g, head_dim=h))
    seen = jnp.arange(cells)[None, :] <= pos[:, None]
    want = GQALayer._attend(conf, q, k, v, seen[:, None, None, :])
    k, v = (jnp.where(past, jnp.nan, t) for t in (k, v))
    last = h % 128 != 0
    if last:
        k, v = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
    got = pallas_kernels.gqa_decode(q, k, v, pos, cells_last=last,
                                    interpret=pltpu.InterpretParams(
                                        detect_races=True, dma_execution_mode="eager"))
    np.testing.assert_allclose(np.asarray(got).reshape(n, -1), np.asarray(want), atol=1e-5)
    assert not interpret_pallas_call.races.races_found
    assert "non-zero count" not in capsys.readouterr().out


@pytest.mark.parametrize("kernel", ["gqa", "dsa"])
def test_a_decode_kernel_on_a_model_axis_is_the_call_on_one_chip(kernel, monkeypatch):
    """Interpreted inside a program partitioned over a `model` axis of 4 of
    the virtual devices (the queries split by head, the tables whole, as the
    serving plan lays them): each chip runs the whole call, and the result
    is the single-chip call's, bit for bit."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 16)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4), ("batch", "model"))
    pos = jnp.asarray([0, 15, 16, 40, 63, 64], jnp.int32)
    key = jax.random.split(jax.random.PRNGKey(15), 3)
    if kernel == "gqa":
        q = jax.random.normal(key[0], (6, 4, 2, 128), jnp.float32)
        k, v = (jax.random.normal(kk, (6, 4, 64, 128), jnp.float32) for kk in key[1:])
        call = lambda q, k, v, pos: pallas_kernels.gqa_decode(q, k, v, pos)  # noqa: E731
        args = (q, k, v, pos)
    else:
        q = jax.random.normal(key[0], (6, 4, 128), jnp.float32)
        table = jax.random.normal(key[1], (6, 64, 128), jnp.float32)
        picked = jnp.arange(64)[None] <= pos[:, None]
        call = lambda q, t, m, pos: pallas_kernels.dsa_decode(q, t, m, pos, 0.125)  # noqa: E731
        args = (q, table, picked, pos)
    shardings = (NamedSharding(mesh, P(None, "model")),) + (NamedSharding(mesh, P()),) * 3
    placed = [jax.device_put(a, s) for a, s in zip(args, shardings)]
    got = jax.jit(call)(*placed)
    assert len(got.sharding.device_set) == 4
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jax.jit(call)(*args)))
