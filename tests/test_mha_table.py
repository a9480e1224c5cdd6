"""Multi-head attention's decode table (`nn/layers/attention.py`), heads
before positions (`[B, H, max_S, hd]`), at tiny widths on the CPU: greedy
decode through it is the eager forward token for token, dense and paged,
with and without a verify chunk, and through `dl4j_gqa_decode` (interpreted)
where the chip's path is taken; the layer's step through the kernel is its
masked read at 16 heads of 128; and the batcher counts the blocks the
kernel walks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import char_transformer
from deeplearning4j_tpu.nd import pallas_kernels
from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import gqa
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttentionLayer as MHA
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from generate_helpers import (VOCAB, _clean_faults, _draft_net, _drain,  # noqa: F401
                              _eager_transformer_tokens, transformer_net)

PROMPTS = ([1, 2, 3], [4, 5, 6, 7])


def walked_net():
    """One block whose heads are 64 wide and whose positions are whole 128s:
    a table the kernel walks where the chip's path is taken."""
    return MultiLayerNetwork(char_transformer(VOCAB, d_model=128, n_blocks=1, n_heads=2,
                                              max_seq_len=128), seed=3).init()


@pytest.mark.parametrize("page_size,spec,walked", [
    (0, False, False), (4, False, False), (0, True, False), (4, True, False),
    (0, False, True)], ids=["dense", "paged", "dense-verify", "paged-verify", "dense-walked"])
def test_a_greedy_decode_is_the_eager_forward(transformer_net, monkeypatch, page_size, spec,
                                              walked):
    """The batcher's compiled programs over the heads-first table (or over
    the page pool, whose pages a prefilled row is copied into position by
    position) emit the eager forward's greedy tokens; under a (recurrent,
    disagreeing) draft each verify chunk writes and reads its K positions
    in one pass and rewrites the rejected ones in the next.
    `dense-walked`: the chip's path, the kernel interpreted, blocks of 32
    cells."""
    net, max_seq = transformer_net, 16
    if walked:
        monkeypatch.setattr(gqa, "is_tpu", lambda: True)
        monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 32)
        net, max_seq = walked_net(), 128
        assert decode.kernel_layers(net.conf, max_seq) == 1
    refs = [_eager_transformer_tokens(net, p, 8) for p in PROMPTS]
    cb = ContinuousBatcher(net, n_slots=2, max_seq=max_seq, prompt_buckets=(8,),
                           page_size=page_size,
                           draft_net=_draft_net() if spec else None,
                           spec_k=3 if spec else 0)
    try:
        assert _drain([cb.submit(p, max_new_tokens=8) for p in PROMPTS]) == refs
        if spec:
            assert cb.stats()["speculative"]["rounds"] >= 1
    finally:
        cb.stop()


def test_a_step_through_the_kernel_is_the_masked_read(monkeypatch):
    """At GPT-2 1.3B's heads (16 of 128), in float32, whose blocks the tile
    rule halves to 128 cells: the step's output and state through the
    kernel are the masked read's, for rows in the first block, either side
    of a block's edge, at the table's last cell and past it.  Every cell past
    a row's last block is NaN when the kernel reads, so a block read past a
    row's position would poison its output."""
    c = NeuralNetConfiguration(layer_type=LayerType.ATTENTION, n_in=16 * 128, n_out=16 * 128,
                               n_heads=16, dtype="float32", compute_dtype="float32")
    cells = 256
    assert MHA.kv_read_block(c, cells) == 0             # off the chip
    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    assert MHA.kv_read_block(c, cells) == 128
    monkeypatch.setattr(gqa, "is_tpu", lambda: False)
    pos = jnp.asarray([0, 5, 127, 128, 255, 256], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(42), 4)
    p = MHA.init(keys[0], c)
    x = jax.random.normal(keys[1], (6, c.n_in), jnp.float32)
    k, v = (jax.random.normal(kk, (6, 16, cells, 128), jnp.float32) for kk in keys[2:])
    step = jax.jit(lambda st: MHA.decode_step(p, c, x, st, pos))
    want, want_state = step({"k": k, "v": v})
    walked = (np.clip(np.asarray(pos), -1, cells - 1) // 128 + 1) * 128
    past = jnp.asarray(np.arange(cells)[None] >= walked[:, None])[:, None, :, None]
    k, v = (jnp.where(past, jnp.nan, t) for t in (k, v))
    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    step = jax.jit(lambda st: MHA.decode_step(p, c, x, st, pos))
    assert "pallas_call" in str(jax.make_jaxpr(step)({"k": k, "v": v}))
    got, got_state = step({"k": k, "v": v})
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name in ("k", "v"):
        live = ~np.broadcast_to(np.asarray(past), got_state[name].shape)
        np.testing.assert_array_equal(np.asarray(got_state[name])[live],
                                      np.asarray(want_state[name])[live])


def test_the_batcher_counts_the_blocks_a_table_is_walked_in(monkeypatch):
    """On the chip's path GPT-2's table is one of `kernel_layers`, and its
    `kv_cells_spanned` and `state_bytes_read` in the batcher's `StepCounts`
    are the blocks of 32 cells each slot's step walks at the position handed
    to the program (a free slot at `max_seq` walks all 4); off it the table
    counts whole."""
    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 32)
    net = walked_net()
    cell = MHA.kv_cell_bytes(net.conf.conf(1), 128)
    pos, adv = np.asarray([0, 31, 32, 128]), np.asarray([1, 1, 1, 0])
    counted = {}
    for chip in (True, False):
        monkeypatch.setattr(gqa, "is_tpu", lambda: chip)
        counts = decode.StepCounts(net.conf, 4, 128)
        step = counts.step(pos, adv)
        counted[chip] = (counts.totals({})["kernel_layers"], step["kv_cells_spanned"],
                         step["state_bytes_read"], step["kv_cells_live"])
    walked = 32 + 32 + 64 + 128
    assert counted[True] == (1, walked, walked * cell, 1 + 32 + 33)
    assert counted[False] == (0, 4 * 128, 4 * 128 * cell, 1 + 32 + 33)


def test_under_a_model_axis_the_table_splits_by_head_and_is_read_masked(monkeypatch):
    """Under a plan with a `model` axis over 4 of the virtual devices
    GPT-2's table lies split by head (`SPLIT_HEADS`), and the step takes
    the masked read there even on the chip's path: no kernel, which would
    need the table whole on every chip, and the single chip's outputs."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.parallel.plan import ShardPlan

    net = walked_net()
    conf, c, p = net.conf, net.conf.conf(1), net.params[1]
    plan = ShardPlan(Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("batch", "model")))
    state = decode.init_state(conf, 4, 128)
    assert plan.state_pspecs(state, conf)[1] == {"k": P(None, "model", None, None),
                                                 "v": P(None, "model", None, None)}
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    table = {name: jax.random.normal(kk, state[1][name].shape, jnp.float32)
             for name, kk in zip(("k", "v"), keys[1:])}
    x = jax.random.normal(keys[0], (4, c.n_in), jnp.float32)
    pos = jnp.asarray([0, 31, 32, 127], jnp.int32)
    step = jax.jit(lambda st: MHA.decode_step(p, c, x, st, pos))
    want, want_state = step(table)
    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    placed = jax.tree_util.tree_map(jax.device_put, table,
                                    plan.state_shardings(state, conf)[1])
    step = jax.jit(lambda st: MHA.decode_step(p, c, x, st, pos))
    assert "pallas_call" not in str(jax.make_jaxpr(step)(placed))
    assert "pallas_call" in str(jax.make_jaxpr(step)(table))
    got, got_state = step(placed)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name in ("k", "v"):
        np.testing.assert_array_equal(got_state[name], want_state[name])
