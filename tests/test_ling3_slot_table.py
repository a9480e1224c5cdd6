"""Ling-3.0-flash's language model whole, at tiny widths on the CPU, through
prefill and the slot table against the plain reference of
`benchmark/families/ling3`: the program holds the reference's numbers, an
admission and the table's decode steps are the full forward pass in float32
and in bfloat16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, init_params
from ling3_model import LOOSE, TIGHT, Model, bf16, f32      # noqa: F401  (fixtures)


def test_the_program_keeps_the_references_numbers(bf16):
    """bfloat16 parameters are rounded once, in the reference's
    `model_weights`: the program's copy is the same numbers, leaf for leaf,
    and has the shapes and types its own `init` gives."""
    back = bf16.fam.program.from_program(bf16.params)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(bf16.weights)):
        assert a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    made = jax.eval_shape(lambda k: init_params(bf16.conf, k), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(made)
            == jax.tree_util.tree_structure(bf16.params))
    for a, b in zip(jax.tree_util.tree_leaves(made),
                    jax.tree_util.tree_leaves(bf16.params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def serve_through_the_table(m: Model, ids, lengths, bucket: int, max_seq: int):
    """Each row of `ids` through its own admission (`prefill_slot`: a padded
    bucket into a zero row, written into the slots-wide table) and then the
    table's decode steps, teacher-forced; returns the log-probabilities that
    came out at every position from `length - 1` on, a list a row."""
    net = MultiLayerNetwork(m.conf)
    net.params = m.params
    ic = net.infer_cache
    n = len(lengths)
    table = ic.init_decode_state(m.conf, n, max_seq)
    out = [[] for _ in range(n)]
    prefill = jax.jit(lambda p, s, pr, ln: decode.prefill(m.conf, p, s, pr, ln))
    for slot, length in enumerate(lengths):     # logits, not tokens: B=1 rows
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :length] = ids[slot, :length]
        row = decode.init_state(m.conf, 1, max_seq)
        logp, row = prefill(m.params, row, prompt, np.asarray([length], np.int32))
        out[slot].append(np.asarray(logp[0]))
        table = ic.write_row(m.conf, table, row, slot)
    step = jax.jit(lambda p, s, t, q: decode.decode_step(m.conf, p, s, t, q))
    pos = np.asarray(lengths, np.int32)
    total = ids.shape[1]
    while (pos < total).any():
        live = pos < total
        tok = np.where(live, ids[np.arange(n), np.minimum(pos, total - 1)], 0)
        logp, table = step(m.params, table, tok.astype(np.int32), pos)
        for r in range(n):
            if live[r]:
                out[r].append(np.asarray(logp[r]))
        pos = np.where(live, pos + 1, pos).astype(np.int32)
    return out


@pytest.mark.parametrize("which, tolerance", [("f32", TIGHT), ("bf16", LOOSE)])
def test_prefill_then_decode_through_the_table_is_the_full_forward_pass(
        request, which, tolerance):
    m = request.getfixturevalue(which)
    rng = np.random.default_rng(5)
    lengths = [16, 11, 3]
    ids = rng.integers(0, m.sizes["vocab"], (3, 28)).astype(np.int32)
    want = m.logp(ids)
    got = serve_through_the_table(m, ids, lengths, bucket=16, max_seq=40)
    for r, length in enumerate(lengths):
        assert len(got[r]) == 28 - length + 1
        for j, logp in enumerate(got[r][:-1]):      # the last has no successor
            np.testing.assert_allclose(logp, want[r, length - 1 + j],
                                       atol=tolerance, rtol=0)
