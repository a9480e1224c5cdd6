"""What the `test_mfu_*.py` files share.  No test file: nothing here is
collected."""

import jax
import numpy as np


def _assert_tree_bitwise(a, b, where=""):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, f"tree structure mismatch {where}"
    for i, (x, y) in enumerate(zip(la, lb)):
        assert x.dtype == y.dtype and x.shape == y.shape, \
            f"leaf {i} meta mismatch {where}"
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"leaf {i} bits differ {where}"


def _numpy_chain(conf, it, grads, params, hist, vel):
    """The updater chain as plain NumPy float64 over lists of leaves: what
    `optimize/updater.py` has to compute, written down a second time."""
    eps, lr, which = 1e-8, conf.lr, conf.updater
    if conf.use_regularization and conf.l2:
        grads = [g + conf.l2 * p for g, p in zip(grads, params)]
    if which == "adam":
        b1, b2 = conf.adam_beta1, conf.adam_beta2
        vel = [b1 * m + (1 - b1) * g for m, g in zip(vel, grads)]
        hist = [b2 * v + (1 - b2) * g * g for v, g in zip(hist, grads)]
        c1, c2 = 1 - b1 ** (it + 1), 1 - b2 ** (it + 1)
        step = [lr * (m / c1) / (np.sqrt(v / c2) + conf.adam_eps)
                for m, v in zip(vel, hist)]
    elif which == "rmsprop":
        rho = conf.rmsprop_decay
        hist = [rho * h + (1 - rho) * g * g for h, g in zip(hist, grads)]
        step = [lr * g / (np.sqrt(h) + eps) for g, h in zip(grads, hist)]
    elif which == "nesterov":
        vel = [conf.momentum * v + g for v, g in zip(vel, grads)]
        step = [lr * (g + conf.momentum * v) for g, v in zip(grads, vel)]
    else:
        adagrad = conf.use_adagrad if which == "" else which == "adagrad"
        scaled = [lr * g for g in grads]
        if adagrad:
            reset = (conf.adagrad_reset_iterations > 0
                     and it % conf.adagrad_reset_iterations == 0)
            hist = [g * g if reset else h + g * g
                    for h, g in zip(hist, grads)]
            scaled = [lr * g / (np.sqrt(h) + eps)
                      for g, h in zip(grads, hist)]
        vel = [conf.momentum * v + s for v, s in zip(vel, scaled)]
        step = vel

    def norm(leaves):
        return np.sqrt(sum(np.sum(np.square(x)) for x in leaves))

    if conf.gradient_clip_norm > 0.0:
        scale = min(1.0, conf.gradient_clip_norm / (norm(step) + eps))
        step = [x * scale for x in step]
    if conf.constrain_gradient_to_unit_norm:
        gn = norm(step)
        step = [x / (gn + eps) for x in step]
    return step, hist, vel


def _f64(tree):
    return [np.asarray(x, np.float64)
            for x in jax.tree_util.tree_leaves(tree)]
