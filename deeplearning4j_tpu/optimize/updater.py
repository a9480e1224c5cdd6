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


# -- flat-buffer (fused) layout ---------------------------------------------
#
# The tree_map chain above launches O(leaves x ops) small kernels per step
# (~30 tree_maps for a 2-block transformer).  `conf.fused_updater` runs the
# same chain over a few contiguous same-dtype buffers instead: every updater
# op is elementwise, so concatenating the leaves changes kernel *count*, not
# any computed bit.  The two global norms are the only reductions — those are
# computed per original leaf (slice + reshape to the leaf's shape) so the
# f32 reduction shapes and summation order match the tree path bitwise.

class FlatSpec(NamedTuple):
    treedef: object      # tree structure of the param pytree
    shapes: tuple        # per leaf, original shape
    leaf_slices: tuple   # per leaf: (group index, offset, size)
    group_dtypes: tuple  # per dtype group
    group_sizes: tuple


def make_flat_spec(params) -> FlatSpec:
    """Group param leaves by dtype into contiguous 1-D buffer layouts."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    group_of = {}   # dtype -> group index, first-seen order
    offsets = []
    slices = []
    for leaf in leaves:
        dt = jnp.asarray(leaf).dtype
        if dt not in group_of:
            group_of[dt] = len(group_of)
            offsets.append(0)
        g = group_of[dt]
        size = int(leaf.size)
        slices.append((g, offsets[g], size))
        offsets[g] += size
    return FlatSpec(treedef=treedef,
                    shapes=tuple(leaf.shape for leaf in leaves),
                    leaf_slices=tuple(slices),
                    group_dtypes=tuple(group_of),
                    group_sizes=tuple(offsets))


def flat_ravel(spec: FlatSpec, tree):
    """Pytree -> tuple of contiguous 1-D buffers (one per dtype group).

    Each leaf enters the buffer through an `optimization_barrier`: without
    it XLA fuses the reshape+concatenate into the leaf's PRODUCER, which
    re-vectorizes that producer over the flat iteration space — and
    vectorized transcendentals (sin/exp/tanh in a backward pass) are only
    ulp-reproducible within one loop shape, so raveled gradients would
    differ in their last bit from the tree path's (observed on CPU: a
    handful of boundary elements per leaf).  Barriered, the producer
    keeps the leaf-shaped loop the tree path compiles, and only the
    already-materialized bits are copied."""
    leaves = jax.tree_util.tree_leaves(tree)
    parts = [[] for _ in spec.group_sizes]
    for leaf, (g, _, _) in zip(leaves, spec.leaf_slices):
        parts[g].append(jnp.reshape(jax.lax.optimization_barrier(leaf),
                                    (-1,)))
    return tuple(p[0] if len(p) == 1 else jnp.concatenate(p)
                 for p in parts)


def flat_unravel(spec: FlatSpec, bufs):
    """Inverse of `flat_ravel` — slices are views XLA fuses into consumers."""
    leaves = [bufs[g][o:o + n].reshape(shape)
              for (g, o, n), shape in zip(spec.leaf_slices, spec.shapes)]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def flat_norm(spec: FlatSpec, bufs):
    """sqrt of the global squared norm, reduced per ORIGINAL leaf shape so
    the result is bitwise-identical to the tree path's
    `sqrt(sum(jnp.sum(square(leaf)) for leaf in tree_leaves(t)))`.

    The optimization_barrier matters: without it XLA fuses the slice +
    reshape into the reduction and emits a strided accumulation whose f32
    summation order differs from a reduction over a materialized leaf by
    a few ulps (observed on CPU).  Barriered, the reduce sees the same
    contiguous leaf-shaped input as the tree path and the bits match."""
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(
            jax.lax.optimization_barrier(bufs[g][o:o + n].reshape(shape))
            .astype(jnp.float32)))
        for (g, o, n), shape in zip(spec.leaf_slices, spec.shapes)))


def tree_norm(t):
    """sqrt of the summed per-leaf squared f32 norms (solver's norm form)."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(t)))


def adjust_gradient(conf, iteration, grads, params, state: UpdaterState,
                    _norm_fn=tree_norm):
    """Apply the updater chain; returns (step_direction, new_state).

    The returned value is the *scaled step* (lr folded in), to be subtracted
    from params — matching how `GradientAdjustment` rewrites the raw gradient
    in place before the step function applies it.

    `conf.updater` selects the algorithm; "" keeps the reference chain
    (AdaGrad flag + scheduled momentum, `GradientAdjustment.java:159-226`),
    while adam / nesterov / rmsprop are parity-plus (the 2015 reference
    predates them).  Adam reuses the two state trees: velocity = first
    moment, adagrad_hist = second moment.

    Every op in the chain is elementwise over the pytree except the two
    global norms, so the same code body serves the fused flat-buffer path
    (`adjust_gradient_flat`), which only swaps `_norm_fn`.

    The entry barrier pins WHICH gradient bits the chain consumes: when a
    gradient has a cheap fused producer (elementwise tail of a backward
    pass), XLA likes to duplicate that producer into each updater
    consumer, and a duplicated transcendental re-vectorized over a
    different loop shape returns ulp-different values — so the chain
    would see gradient bits that differ from (and between!) its
    consumers.  The same goes for the mid-chain barriers on the updated
    moments and the exit barrier on the returned step.  Caveat: XLA is
    still free to drop a barrier late in its pipeline and re-duplicate
    (observed on CPU, where the flat-layout step fusion recomputes the
    moments inline), so across two *separately compiled* programs of
    different layouts the barriers reduce drift to isolated last-ulp
    elements rather than guaranteeing zero — see `adjust_gradient_auto`
    for how the parity claims are scoped per train path.
    """
    with scope("updater"):
        return _chain(conf, iteration, grads, params, state, _norm_fn)


def _chain(conf, iteration, grads, params, state: UpdaterState, _norm_fn):
    eps = 1e-8
    lr = conf.lr
    which = (getattr(conf, "updater", "") or "").lower()
    grads = jax.tree_util.tree_map(jax.lax.optimization_barrier, grads)

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
        # pin the moment bits: vel/hist are both outputs and step inputs,
        # and an unpinned multiply-add would be duplicated into the step
        # fusion where contraction (FMA) can round differently per layout
        vel, hist = jax.lax.optimization_barrier((vel, hist))
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
        hist = jax.lax.optimization_barrier(hist)
        step = jax.tree_util.tree_map(
            lambda g, h: lr * g / (jnp.sqrt(h) + eps), grads, hist)
    elif which == "nesterov":
        mom = _momentum_at(conf, iteration)
        vel = jax.tree_util.tree_map(
            lambda v, g: mom.astype(g.dtype) * v + g, vel, grads)
        vel = jax.lax.optimization_barrier(vel)
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
            new_hist = jax.lax.optimization_barrier(new_hist)
            scaled = jax.tree_util.tree_map(
                lambda g, h: lr * g / (jnp.sqrt(h) + eps), grads, new_hist)
            hist = new_hist
        else:
            scaled = jax.tree_util.tree_map(lambda g: lr * g, grads)

        mom = _momentum_at(conf, iteration)
        vel = jax.tree_util.tree_map(
            lambda v, s: mom.astype(s.dtype) * v + s, vel, scaled)
        vel = jax.lax.optimization_barrier(vel)
        step = vel
    else:
        raise ValueError(
            f"unknown updater {which!r}: expected one of "
            "'' | sgd | adagrad | nesterov | adam | rmsprop")

    if conf.gradient_clip_norm > 0.0:
        gn = _norm_fn(step)
        scale = jnp.minimum(1.0, conf.gradient_clip_norm / (gn + eps))
        step = jax.tree_util.tree_map(lambda x: x * scale.astype(x.dtype), step)

    if conf.constrain_gradient_to_unit_norm:
        gn = _norm_fn(step)
        step = jax.tree_util.tree_map(
            lambda x: x / (gn + eps).astype(x.dtype), step)

    # exit barrier, same reason as the entry one: unbarriered, the chain's
    # trailing multiply fuses into the caller's `params - step` and may
    # contract to an FMA there (rounding once) while the other layout
    # rounds twice
    step = jax.tree_util.tree_map(jax.lax.optimization_barrier, step)
    return step, UpdaterState(adagrad_hist=hist, velocity=vel)


def adjust_gradient_flat(conf, iteration, grad_bufs, param_bufs,
                         state: UpdaterState, spec: FlatSpec):
    """Fused updater chain over `flat_ravel`ed buffers.

    `grad_bufs`/`param_bufs` and the state fields are tuples of contiguous
    same-dtype 1-D buffers; the whole chain then runs as a handful of
    full-width kernels instead of O(leaves x ops) small ones.  Elementwise
    math on a concatenation is bitwise-identical per element, and the norms
    reduce per original leaf via `flat_norm`, so the result unravels to
    exactly the tree path's bits (parity-tested for all five algorithms).
    """
    return adjust_gradient(conf, iteration, grad_bufs, param_bufs, state,
                           _norm_fn=lambda t: flat_norm(spec, t))


def adjust_gradient_auto(conf, iteration, grads, params,
                         state: UpdaterState):
    """`adjust_gradient` that honours `conf.fused_updater`, keeping the
    tree-shaped calling convention.

    When the flag is set, grads/params/state are flat-raveled at the
    boundary, the chain runs fused, and the step + new state unravel
    back to trees, so train-step code (the dp / sharded steps) can stay
    layout-agnostic.  Parity scope: within one compiled program the two
    layouts are bitwise-identical (`test_fused_updater_bitwise`), and so
    is the whole single-device solver path end to end
    (`test_end_to_end_flag_combos_bitwise`).  Across *separately
    compiled* tree- vs flat-layout programs — the dp train step — XLA
    may duplicate a producer into a consumer fusion with different FMA
    contraction, leaving isolated last-ulp differences the barriers in
    `adjust_gradient` cannot pin; the dp parity test therefore asserts
    ≤1-ulp closeness there, not equality.  NOTE: callers whose updater
    state is mesh-sharded (ZeRO-1, local-SGD) keep the tree path —
    raveling would regather the shards."""
    if not getattr(conf, "fused_updater", False):
        return adjust_gradient(conf, iteration, grads, params, state)
    spec = make_flat_spec(params)
    with scope("updater"):      # the ravel and the unravel are the chain's
        fstate = UpdaterState(
            adagrad_hist=flat_ravel(spec, state.adagrad_hist),
            velocity=flat_ravel(spec, state.velocity))
        adj, new = _chain(conf, iteration, flat_ravel(spec, grads),
                          flat_ravel(spec, params), fstate,
                          lambda t: flat_norm(spec, t))
        return (flat_unravel(spec, adj),
                UpdaterState(
                    adagrad_hist=flat_unravel(spec, new.adagrad_hist),
                    velocity=flat_unravel(spec, new.velocity)))
