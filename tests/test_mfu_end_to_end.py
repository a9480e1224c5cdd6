"""The MFU campaign's flags end to end: through `MultiLayerNetwork.finetune`
(the compiled step-cache program) and the 8-way data-parallel step, so that the
parity of `test_mfu_paths.py` and `test_mfu_fused_bwd.py` holds through
tracing, donation and the solver scan, not just at the op level."""

import jax
import jax.numpy as jnp
import numpy as np

from mfu_helpers import _assert_tree_bitwise


# -- end-to-end through the compiled train step ------------------------------

def _char_batch(vocab, batch, seq, sparse):
    rng = np.random.RandomState(7)
    ids = rng.randint(0, vocab, (batch, seq + 1))
    x = jnp.asarray(ids[:, :-1].astype(np.int32))
    if sparse:
        return x, jnp.asarray(ids[:, 1:].reshape(-1).astype(np.int32))
    return x, jnp.asarray(
        np.eye(vocab, dtype=np.float32)[ids[:, 1:].reshape(-1)])


def test_end_to_end_flag_combos_bitwise():
    """char-transformer `finetune` through the step cache: every flag
    combination must land on bitwise-identical parameters after the
    solver scan (donation, bucketing and fingerprinting included).
    `fused_updater` is inert since PR 31 (the updater has one layout): its
    arms hold that the flag is still accepted and changes no bit."""
    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    vocab, batch, seq = 17, 4, 16

    def train(fused, sparse):
        conf = char_transformer(vocab, d_model=32, n_blocks=1, n_heads=2,
                                max_seq_len=seq, iterations=2,
                                fused_updater=fused, sparse_labels=sparse)
        net = MultiLayerNetwork(conf, seed=42).init()
        net.finetune(*_char_batch(vocab, batch, seq, sparse))
        return net.params

    ref = train(False, False)
    for combo in [(True, False), (False, True), (True, True)]:
        _assert_tree_bitwise(ref, train(*combo), f"combo {combo}")

def _dp_train(vocab, batch, seq, steps, sparse, fused):
    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    conf = char_transformer(vocab, d_model=32, n_blocks=1, n_heads=2,
                            max_seq_len=seq, sparse_labels=sparse,
                            fused_updater=fused)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, size=(steps, batch, seq)).astype(np.int32)
    net = MultiLayerNetwork(conf).init()
    tr = DataParallelTrainer(net, mesh=make_mesh({"dp": 8}))
    batches = []
    for i in range(steps):
        flat = ids[i].reshape(batch * seq)
        y = (jnp.asarray(flat, jnp.int32) if sparse
             else jnp.asarray(np.eye(vocab, dtype=np.float32)[flat]))
        batches.append((jnp.asarray(ids[i]), y))
    score = tr.fit(batches)
    return jax.device_get(tr.state.params), score


def test_dp_step_sparse_labels_bitwise():
    """8-way dp train, 3 batches: `sparse_labels` is fully bitwise in the
    dp step too — params AND reported score."""
    ref, ref_score = _dp_train(17, 16, 16, 3, sparse=False, fused=False)
    sp, sp_score = _dp_train(17, 16, 16, 3, sparse=True, fused=False)
    _assert_tree_bitwise(ref, sp, "sparse_labels dp")
    assert sp_score == ref_score


def test_dp_step_fused_updater_single_step_bitwise():
    """One 8-way dp step with `fused_updater` on lands on bitwise-identical
    params.  The flag is inert since PR 31 (one updater layout, so both
    confs lower to the same program: `test_updater_one_layout.py`); the
    case stays to hold that it is accepted and changes no bit."""
    ref, ref_score = _dp_train(17, 16, 16, 1, sparse=False, fused=False)
    for sparse, fused in [(False, True), (True, True)]:
        got, score = _dp_train(17, 16, 16, 1, sparse=sparse, fused=fused)
        _assert_tree_bitwise(ref, got, f"dp 1-step combo {(sparse, fused)}")
        # the score is a mean over bitwise-identical per-row losses, but
        # the scalar reduce can fuse in a different summation order in a
        # reshaped program — a reporting value, not training state
        np.testing.assert_allclose(score, ref_score, rtol=1e-6,
                                   err_msg=f"combo {(sparse, fused)}")


def test_dp_step_fused_updater_iterated_close():
    """Three iterated 8-way dp steps with `fused_updater` on stay at the
    plain conf's parameters.  The flag is inert since PR 31: there is one
    updater layout, so the two programs are the same text and the
    step-scale tolerance that two separately compiled layouts once needed
    (Adam amplifies a last-ulp seed where a moment sits near zero) is now
    met with room; the case stays to hold that the flag is accepted."""
    ref, _ = _dp_train(17, 16, 16, 3, sparse=False, fused=False)
    for sparse, fused in [(False, True), (True, True)]:
        got, _ = _dp_train(17, 16, 16, 3, sparse=sparse, fused=fused)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4,
                err_msg=f"dp 3-step combo {(sparse, fused)}")


def test_end_to_end_fused_bwd_through_step_cache():
    """char-transformer finetune through the compiled step cache with
    attention_impl pinned to flash and the fused-bwd flag flipped: params
    must agree at tight tolerance (the fused backward is allclose, not
    bitwise, by contract; on CPU the auto-interpret gate makes both runs
    take the recompute fallback, where agreement is exact)."""
    from deeplearning4j_tpu.models.zoo import char_transformer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    vocab, batch, seq = 17, 4, 16

    def train(fused):
        conf = char_transformer(vocab, d_model=32, n_blocks=1, n_heads=2,
                                max_seq_len=seq, iterations=2,
                                attention_fused_bwd=fused)
        conf = conf.replace(confs=tuple(
            c.replace(attention_impl="flash", attention_block_size=8)
            for c in conf.confs))
        net = MultiLayerNetwork(conf, seed=42).init()
        net.finetune(*_char_batch(vocab, batch, seq, False))
        return net.params

    ref, got = train(False), train(True)
    for i, (a, b) in enumerate(zip(jax.tree_util.tree_leaves(ref),
                                   jax.tree_util.tree_leaves(got))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"leaf {i}")
