"""Gradient adjustment (the updater chain).

Parity: reference `optimize/GradientAdjustment.java:159-226` — per-variable
AdaGrad with optional periodic reset, else plain lr scaling; momentum with a
scheduled `momentumAfter` map; L2 weight decay; unit-norm constraint.
(The reference also divides by batch size; here losses are already batch
means, so that scaling is built into the gradient itself.)

TPU-native design: a pure `(conf, iteration, grads, params, state) ->
(adjusted, state)` transform over pytrees — the functional equivalent of
optax transforms, kept self-contained so the solver loop can live entirely
inside one XLA program.

The chain has one layout: it maps over the trees leaf by leaf, as the
state is kept between steps (`UpdaterState`: two trees shaped like the
params), and pins nothing with `optimization_barrier`.  The flat-buffer
layout of PR 6 (every tree concatenated before the chain and sliced after)
went in PR 31 with the barriers that kept its bits equal to this one's: on
the chip the training cell's step spent 94 of its 165 ms in the chain and
the copies round it, and spends about 7 of 80 ms now (`PERF.md` 6, PR 31).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.utils.profiling import scope


class UpdaterState(NamedTuple):
    adagrad_hist: object   # pytree like params
    velocity: object       # pytree like params


def init_updater(params) -> UpdaterState:
    # two distinct zero trees: sharing one would alias buffers, which
    # breaks donation (same buffer donated twice) in jitted train steps
    return UpdaterState(
        adagrad_hist=jax.tree_util.tree_map(jnp.zeros_like, params),
        velocity=jax.tree_util.tree_map(jnp.zeros_like, params))


def _momentum_at(conf, iteration):
    """Scheduled momentum (parity: `momentumAfter` map)."""
    m = jnp.asarray(conf.momentum, jnp.float32)
    for it, mom in conf.momentum_after:
        m = jnp.where(iteration >= it, jnp.asarray(mom, jnp.float32), m)
    return m


def tree_norm(t):
    """sqrt of the summed per-leaf squared f32 norms (solver's norm form)."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(t)))


def adjust_gradient(conf, iteration, grads, params, state: UpdaterState):
    """Apply the updater chain; returns (step_direction, new_state).

    The returned value is the *scaled step* (lr folded in), to be subtracted
    from params — matching how `GradientAdjustment` rewrites the raw gradient
    in place before the step function applies it.

    `conf.updater` selects the algorithm; "" keeps the reference chain
    (AdaGrad flag + scheduled momentum, `GradientAdjustment.java:159-226`),
    while adam / nesterov / rmsprop are parity-plus (the 2015 reference
    predates them).  Adam reuses the two state trees: velocity = first
    moment, adagrad_hist = second moment.

    One layout, leaf by leaf: every op is elementwise over the trees the
    caller already holds, except the two global norms (`tree_norm`).
    Nothing between the gradient and the returned step is pinned, so XLA
    makes the chain one fusion a leaf that reads g, m, v (and p under l2)
    once and, in a step whose state is donated, writes the moments in
    place.  `conf.fused_updater` is accepted and read by nothing.
    """
    with scope("updater"):
        return _chain(conf, iteration, grads, params, state)


def update_params(conf, iteration, grads, params, state: UpdaterState):
    """One updater step applied: `(params - step, new_state)`, the chain and
    the subtraction in one `updater` scope, so that a leaf's moments and
    its parameter are updated by one fusion."""
    with scope("updater"):
        step, state = _chain(conf, iteration, grads, params, state)
        return jax.tree_util.tree_map(
            lambda p, a: p - a.astype(p.dtype), params, step), state


def _chain(conf, iteration, grads, params, state: UpdaterState):
    eps = 1e-8
    lr = conf.lr
    which = (getattr(conf, "updater", "") or "").lower()

    # L2 weight decay on the raw gradient (before adaptive scaling)
    if conf.use_regularization and conf.l2:
        grads = jax.tree_util.tree_map(
            lambda g, p: g + conf.l2 * p.astype(g.dtype), grads, params)

    hist = state.adagrad_hist
    vel = state.velocity
    if which == "adam":
        b1, b2 = conf.adam_beta1, conf.adam_beta2
        t = jnp.asarray(iteration, jnp.float32) + 1.0
        vel = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, vel, grads)
        hist = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, hist, grads)
        c1 = 1.0 - jnp.power(b1, t)
        c2 = 1.0 - jnp.power(b2, t)
        step = jax.tree_util.tree_map(
            lambda m, v: lr * (m / c1.astype(m.dtype))
            / (jnp.sqrt(v / c2.astype(v.dtype)) + conf.adam_eps),
            vel, hist)
    elif which == "rmsprop":
        rho = conf.rmsprop_decay
        hist = jax.tree_util.tree_map(
            lambda h, g: rho * h + (1 - rho) * g * g, hist, grads)
        step = jax.tree_util.tree_map(
            lambda g, h: lr * g / (jnp.sqrt(h) + eps), grads, hist)
    elif which == "nesterov":
        mom = _momentum_at(conf, iteration)
        vel = jax.tree_util.tree_map(
            lambda v, g: mom.astype(g.dtype) * v + g, vel, grads)
        # look-ahead step: lr * (g + mu * v_new)
        step = jax.tree_util.tree_map(
            lambda g, v: lr * (g + mom.astype(g.dtype) * v), grads, vel)
    elif which in ("", "sgd", "adagrad"):
        # legacy reference chain; "sgd"/"adagrad" force the flag either way
        use_adagrad = (conf.use_adagrad if which == ""
                       else which == "adagrad")
        if use_adagrad:
            new_hist = jax.tree_util.tree_map(lambda h, g: h + g * g, hist,
                                              grads)
            if conf.adagrad_reset_iterations > 0:
                resetting = (iteration % conf.adagrad_reset_iterations) == 0
                new_hist = jax.tree_util.tree_map(
                    lambda h, g: jnp.where(resetting, g * g, h), new_hist,
                    grads)
            scaled = jax.tree_util.tree_map(
                lambda g, h: lr * g / (jnp.sqrt(h) + eps), grads, new_hist)
            hist = new_hist
        else:
            scaled = jax.tree_util.tree_map(lambda g: lr * g, grads)

        mom = _momentum_at(conf, iteration)
        vel = jax.tree_util.tree_map(
            lambda v, s: mom.astype(s.dtype) * v + s, vel, scaled)
        step = vel
    else:
        raise ValueError(
            f"unknown updater {which!r}: expected one of "
            "'' | sgd | adagrad | nesterov | adam | rmsprop")

    if conf.gradient_clip_norm > 0.0:
        gn = tree_norm(step)
        scale = jnp.minimum(1.0, conf.gradient_clip_norm / (gn + eps))
        step = jax.tree_util.tree_map(lambda x: x * scale.astype(x.dtype), step)

    if conf.constrain_gradient_to_unit_norm:
        gn = tree_norm(step)
        step = jax.tree_util.tree_map(
            lambda x: x / (gn + eps).astype(x.dtype), step)

    return step, UpdaterState(adagrad_hist=hist, velocity=vel)
