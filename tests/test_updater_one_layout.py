"""The updater has one layout (PR 31): the train steps run the chain leaf by
leaf over the trees they hold, `conf.fused_updater` is accepted and read by
nothing, and a donated state is updated in place.  These cases read the
lowered and the compiled step of a small `char_transformer`, and hold three
data-parallel Adam steps to a NumPy statement of Adam."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import char_transformer
from deeplearning4j_tpu.nn.conf import (NeuralNetConfiguration,
                                        OptimizationAlgorithm)
from deeplearning4j_tpu.nn.multilayer import (MultiLayerNetwork,
                                              network_regularization,
                                              network_rowwise_loss)
from deeplearning4j_tpu.optimize import solver
from deeplearning4j_tpu.parallel.data_parallel import (init_train_state,
                                                       make_dp_train_step)
from deeplearning4j_tpu.parallel.mesh import make_mesh
from mfu_helpers import _f64, _numpy_chain

VOCAB, SEQ, BATCH = 17, 16, 4


def _conf(fused):
    return char_transformer(VOCAB, d_model=32, n_blocks=1, n_heads=2,
                            max_seq_len=SEQ, sparse_labels=True,
                            fused_updater=fused)


def _batch(i=0):
    ids = np.random.RandomState(i).randint(0, VOCAB, (BATCH, SEQ + 1))
    return (jnp.asarray(ids[:, :-1], jnp.int32),
            jnp.asarray(ids[:, 1:].reshape(-1), jnp.int32))


def _dp_step(fused):
    """The jitted dp step over two devices, the arguments of one call (a
    fresh state, which a call donates) and the step lowered for them."""
    conf = _conf(fused)
    state = init_train_state(MultiLayerNetwork(conf, seed=3).init())
    step = make_dp_train_step(conf, make_mesh({"dp": 2}))
    args = (state, *_batch(), jax.random.PRNGKey(0))
    return conf, step, args, step.lower(*args)


@pytest.fixture(scope="module")
def fused_step():
    return _dp_step(True)


def test_dp_step_holds_no_flat_buffer(fused_step):
    """No array of the summed parameter count, and nothing concatenated
    under the `updater` scope, with the flag on."""
    _, _, args, lowered = fused_step
    total = sum(leaf.size for leaf in jax.tree_util.tree_leaves(args[0].params))
    text = lowered.as_text(debug_info=True)
    assert "module @jit_dl4j_train_step" in text
    assert re.search(r'[/("]updater[/)]', text)
    assert f"tensor<{total}x" not in text
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    for line in text.splitlines():
        if "stablehlo.concatenate" in line:
            where = named[re.search(r"loc\((#loc\d+)\)", line).group(1)]
            assert "updater" not in where, where


def test_dp_step_aliases_every_state_leaf(fused_step):
    """Every parameter and moment leaf of the donated state (and its step
    counter) is an input the compiled step writes its output over."""
    _, _, args, lowered = fused_step
    leaves = len(jax.tree_util.tree_leaves(args[0]))
    hlo = lowered.compile().as_text()
    header = hlo[:hlo.index("\n")]
    aliased = {int(i) for i in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    assert set(range(leaves)) <= aliased, sorted(
        set(range(leaves)) - aliased)


def test_fused_updater_flag_changes_no_program(fused_step):
    """`fused_updater=True` and `False` lower to the same text, in the dp
    step and in `solver._sgd`."""
    assert fused_step[3].as_text() == _dp_step(False)[3].as_text()

    objective = solver.from_loss(
        lambda p, key: jnp.sum((p["A"] @ p["x"] - 1.0) ** 2))
    params = {"A": jnp.eye(3) * 2.0, "x": jnp.zeros(3)}

    def sgd_text(fused):
        conf = NeuralNetConfiguration(
            optimization_algo=OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT,
            updater="adam", num_iterations=3, gradient_clip_norm=1.0,
            fused_updater=fused)
        return jax.jit(lambda p, k: solver._sgd(objective, p, conf, k)).lower(
            params, jax.random.PRNGKey(0)).as_text()

    assert sgd_text(True) == sgd_text(False)


def test_three_dp_adam_steps_match_numpy_adam(fused_step):
    """Three steps of the dp step (gradients averaged over two devices,
    state donated and handed on) against Adam written in NumPy float64 over
    gradients of the same loss taken on one device.  Held where a gradient
    is a number and not rounding noise: Adam's first steps are lr * sign(g),
    and the key bias's gradient (a third of `bqkv`) is zero in exact
    arithmetic, so its sign is the summation order's."""
    conf, step, args, _ = fused_step
    # a copy: the step donates its state, and the fixture's is shared
    state, key = jax.tree_util.tree_map(jnp.copy, args[0]), args[3]
    out = conf.conf(conf.n_layers - 1)

    @jax.jit
    def grad(p, x, y):
        return jax.grad(lambda q: jnp.mean(network_rowwise_loss(
            conf, q, x, y, key, training=True))
            + network_regularization(conf, q))(p)

    treedef = jax.tree_util.tree_structure(state.params)
    first = want = _f64(state.params)
    hist, vel = _f64(state.updater.adagrad_hist), _f64(state.updater.velocity)
    sure = [np.ones(w.shape, bool) for w in want]
    for t in range(3):
        x, y = _batch(t)
        g = _f64(grad(jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(w, jnp.float32) for w in want]), x, y))
        sure = [a & (np.abs(b) > 1e-6) for a, b in zip(sure, g)]
        adj, hist, vel = _numpy_chain(out, t, g, want, hist, vel)
        want = [w - a for w, a in zip(want, adj)]
        state, _ = step(state, x, y, key)
    assert int(state.step) == 3
    assert sum(a.sum() for a in sure) > 0.9 * sum(a.size for a in sure)
    moved = 0.0
    for i, (got, ref, was, ok) in enumerate(zip(
            _f64(state.params), want, first, sure)):
        # a change of up to 3 lr a coordinate, held to a hundredth of lr
        np.testing.assert_allclose(got[ok], ref[ok], rtol=0,
                                   atol=out.lr * 1e-2, err_msg=f"leaf {i}")
        moved = max(moved, float(np.max(np.abs(ref - was))))
    assert moved > 2 * out.lr
    for got, ref in [(state.updater.velocity, vel),
                     (state.updater.adagrad_hist, hist)]:
        for a, b in zip(_f64(got), ref):
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=1e-3 * np.max(np.abs(b)))
