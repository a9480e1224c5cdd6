"""Grouped attention with heads 64 wide (`nn/layers/gqa.py`) at tiny widths
on the CPU: decode through the slot table against the plain reference and
against the whole forward pass, the slot table at 2,048 bytes a position a
layer at the published widths, and every layer that counts its state in
cells declaring, in `kv_cell_bytes`, the bytes its state's leaves hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.lfm2_moe import reference as ref
from deeplearning4j_tpu.nn.conf import (GQASpec, LayerType, MLASpec,
                                        NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttentionLayer
from deeplearning4j_tpu.nn.layers.gqa import GQALayer
from deeplearning4j_tpu.nn.layers.mla import MLALayer

TIGHT = 2e-5        # float32: the order of the sums


def conf(n_in=64, heads=8, kv=4, head_dim=64, window=0, dtype="float32"):
    return NeuralNetConfiguration(
        layer_type=LayerType.GQA, n_in=n_in, n_out=n_in, dtype=dtype,
        compute_dtype=dtype, weight_init="normalized",
        layer_spec=GQASpec(n_heads=heads, n_kv_heads=kv, head_dim=head_dim,
                           window=window, rope_theta=1e6, qk_norm=True, eps=1e-5))


def params(c, seed=1):
    p = GQALayer.init(jax.random.PRNGKey(seed), c)
    # norm weights about 1 but not 1: a head's norm applied to another
    # head's dimensions shows
    k = jax.random.PRNGKey(seed + 7)
    return {**p, "q_norm": 1 + 0.3 * jax.random.normal(k, p["q_norm"].shape),
            "k_norm": 1 - 0.3 * jax.random.normal(k, p["k_norm"].shape)}


def rows(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def prefill_then_steps(c, p, x, prompt: int, max_seq: int):
    """The outputs of a prefill of `prompt` tokens and then a decode step a
    token, [B, T, n], and the state."""
    b, t, _ = x.shape
    out, state = jax.jit(lambda p, v, s, n: GQALayer.prefill(p, c, v, s, n))(
        p, x[:, :prompt], GQALayer.init_state(c, b, max_seq),
        jnp.full((b,), prompt, jnp.int32))
    outs = [out]
    step = jax.jit(lambda p, v, s, q: GQALayer.decode_step(p, c, v, s, q))
    for at in range(prompt, t):
        h, state = step(p, x[:, at], state, jnp.full((b,), at, jnp.int32))
        outs.append(h[:, None])
    return jnp.concatenate(outs, axis=1), state


@pytest.mark.parametrize("kv", [4, 2], ids=["four-kv-heads", "two-kv-heads"])
def test_a_64_wide_decode_is_the_references_attention(kv):
    c = conf(kv=kv)
    p, x = params(c), rows((2, 12, 64))
    s = {"kv_heads": kv, "head_dim": 64, "heads": 8, "eps": 1e-5, "theta": 1e6}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref.attention(
            {k: v.astype(jnp.float32) for k, v in p.items()}, x, s))(p, x)
    got, state = prefill_then_steps(c, p, x, 5, 16)
    assert state["k"].shape == (2, kv, 16, 64)
    np.testing.assert_allclose(got, want, atol=TIGHT)


@pytest.mark.parametrize("window", [0, 4], ids=["table", "ring"])
def test_a_64_wide_decode_is_the_whole_forward_pass(window):
    """A ring of 4 cells wraps over the 10 positions as a table does not."""
    c = conf(window=window)
    p, x = params(c), rows((3, 10, 64), seed=4)
    got, state = prefill_then_steps(c, p, x, 6, 12)
    assert state["k"].shape == (3, 4, window or 12, 64)
    whole = jax.jit(lambda p, x: GQALayer.forward(p, c, x))(p, x)
    np.testing.assert_allclose(got, whole, atol=TIGHT)


def test_the_slot_table_takes_2048_bytes_a_position_a_layer():
    """At the published widths: 8 K/V heads of 64, K and V, bfloat16."""
    c = conf(n_in=2048, heads=32, kv=8, dtype="bfloat16")
    assert GQALayer.kv_cell_bytes(c, 6144) == 2048
    state = jax.eval_shape(lambda: GQALayer.init_state(c, 64, 6144))
    assert state["k"].shape == (64, 8, 6144, 64)
    assert sum(a.size * a.dtype.itemsize for a in state.values()) == 64 * 6144 * 2048


def mla(**options):
    return NeuralNetConfiguration(
        layer_type=LayerType.MLA, n_in=32, n_out=32, dtype="bfloat16",
        compute_dtype="bfloat16",
        layer_spec=MLASpec(n_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
                           qk_rope_head_dim=8, v_head_dim=8, **options))


@pytest.mark.parametrize("impl,c", [
    (GQALayer, conf(head_dim=64)),
    (GQALayer, conf(head_dim=64, kv=3, heads=6, window=5)),
    (GQALayer, conf(head_dim=128)),             # Mellum's heads
    (GQALayer, conf(head_dim=96, window=5)),
    (MLALayer, mla()),                          # latent and rotary key apart
    (MLALayer, mla(window=5)),                  # side by side in whole 128s
    (MLALayer, mla(q_lora_rank=16, index_n_heads=2, index_head_dim=8,
                   index_topk=4)),              # and the indexer's keys
    (MultiHeadAttentionLayer, NeuralNetConfiguration(   # GPT-2's [rows, H, cells, hd]
        layer_type=LayerType.ATTENTION, n_in=32, n_out=32, n_heads=4,
        dtype="bfloat16", compute_dtype="bfloat16")),
], ids=["gqa64", "gqa64-ring", "gqa128", "gqa96-ring", "mla", "mla-ring",
        "mla-indexed", "mha"])
def test_a_declared_cell_is_the_states_own_bytes(impl, c):
    """What `decode.state_bytes_read` counts of a table, `kv_cells_read` x
    `kv_cell_bytes`, is every byte of a row's state where the step reads it
    whole, as every such layer's does; a GQA state keeps [rows, G, cells, h]
    at every head width, and GPT-2's [rows, H, cells, hd]."""
    max_seq = 12
    state = jax.eval_shape(lambda: impl.init_state(c, 1, max_seq))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(state))
    declared = np.dot(np.atleast_1d(impl.kv_cells_read(c, max_seq)),
                      np.atleast_1d(impl.kv_cell_bytes(c, max_seq)))
    assert declared == held
    if impl is GQALayer:
        s = c.layer_spec
        assert state["k"].shape == (1, s.n_kv_heads, s.window or max_seq, s.head_dim)
    if impl is MultiHeadAttentionLayer:
        assert state["k"].shape == (1, c.n_heads, max_seq, c.n_in // c.n_heads)


# ------------------------------------- the walk of a row's live blocks

@pytest.mark.parametrize("head_dim,window,chip,max_seq,engages", [
    (64, 0, True, 6144, True),          # LFM2's tables, positions innermost
    (128, 0, True, 9216, True),         # Mellum's full layers
    (128, 1024, True, 9216, False),     # its rings: all live from position 1,023
    (96, 0, True, 4096, False),         # a table the kernel would make the chip copy
    (64, 0, False, 6144, False),        # off the chip: `seen` over the table, the oracle
    (128, 0, True, 200, False),         # positions not whole 128s
], ids=["h64-table", "h128-table", "h128-ring", "h96-table", "off-the-chip", "ragged-table"])
def test_the_kernel_engages_on_tables_it_reads_where_they_lie(monkeypatch, head_dim, window,
                                                              chip, max_seq, engages):
    from deeplearning4j_tpu.nn.layers import gqa

    monkeypatch.setattr(gqa, "is_tpu", lambda: chip)
    c = conf(head_dim=head_dim, window=window)
    assert GQALayer.decode_kernel(c, max_seq) == engages
    assert GQALayer.kv_read_block(c, max_seq) == (512 if engages else 0)
    # what it reads at the most does not change: the whole table, or the ring
    assert GQALayer.kv_cells_read(c, max_seq) == (min(window, max_seq) if window else max_seq)


def test_a_decode_step_through_the_kernel_is_the_masked_read(monkeypatch):
    """The chip's path, the kernel interpreted: the same outputs and the same
    state as the step off the chip, for rows at the first cell, inside and at
    the edge of a block, at the table's last cell and past it (a finished
    slot at `max_seq`).  Heads of 64, whose table the kernel is handed
    positions innermost (`test_pallas_kernels.py` holds it to the masked
    read at heads of 128 too)."""
    head_dim = 64
    from deeplearning4j_tpu.nd import pallas_kernels
    from deeplearning4j_tpu.nn.layers import gqa

    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 32)
    c = conf(head_dim=head_dim)
    p, max_seq = params(c), 128
    pos = jnp.asarray([0, 31, 32, 77, 127, 128], jnp.int32)
    k, v = (rows((6, 4, max_seq, head_dim), seed=s) for s in (5, 6))
    x = rows((6, 64), seed=7)
    step = lambda: GQALayer.decode_step(p, c, x, {"k": k, "v": v}, pos)  # noqa: E731
    want, want_state = step()
    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    assert "pallas_call" in str(jax.make_jaxpr(step)())
    got, got_state = step()
    np.testing.assert_allclose(got, want, atol=TIGHT)
    for name in ("k", "v"):
        np.testing.assert_array_equal(got_state[name], want_state[name])


#: (positions, advances) of five slots, and the cells the full layer's walks
#: in blocks of 32 cover
WALKS = {
    # slot 4 is free at the table's end
    "one-step": ([0, 31, 32, 127, 128], [1, 1, 1, 1, 0], 32 + 32 + 64 + 128 + 128),
    # rows either side of a block's edge, and one at the table's last position
    "across-a-block-edge": ([63, 64, 95, 96, 127], [1, 1, 0, 1, 1],
                            64 + 96 + 96 + 128 + 128),
}


@pytest.mark.parametrize("case", sorted(WALKS))
def test_a_walked_table_counts_the_blocks_its_rows_read(monkeypatch, case):
    """On the chip's path a full layer's `kv_cells_spanned` and its bytes in
    `state_bytes_read` are the blocks of 32 cells each slot's step walks at
    the position handed to the program, live or not (a finished slot at
    `max_seq` walks all 4); the rings of 8 still count whole.  The tiny
    Mellum with heads of 64: three window layers, one full."""
    from mellum_model import Model

    from deeplearning4j_tpu.nd import pallas_kernels
    from deeplearning4j_tpu.nn import decode
    from deeplearning4j_tpu.nn.layers import gqa

    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 32)
    m = Model("float32", head_dim=64)
    counts = decode.StepCounts(m.conf, 5, 128)
    assert counts.totals({})["kernel_layers"] == decode.kernel_layers(m.conf, 128) == 1
    full, ring = (GQALayer.kv_cell_bytes(m.conf.conf(m.layer(kind)), 128)
                  for kind in ("full", "window"))
    pos, adv, walked = WALKS[case]
    got = counts.step(np.asarray(pos), np.asarray(adv))
    rings = 3 * 5 * 8
    assert got["kv_cells_spanned"] == walked + rings
    assert got["state_bytes_read"] == walked * full + rings * ring
    monkeypatch.setattr(gqa, "is_tpu", lambda: False)      # off the chip: the whole table
    whole = decode.StepCounts(m.conf, 5, 128)
    assert whole.step(np.asarray(pos), np.asarray(adv))["kv_cells_spanned"] == 5 * 128 + rings
    assert whole.totals({})["kernel_layers"] == 0
