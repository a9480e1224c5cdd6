"""Data-parallel training on a device mesh.

What the reference built out of Akka actors + Hazelcast state
(`MasterActor.java:61`, `IterateAndUpdateImpl.java:34`: workers fit on their
shard, ship whole parameter vectors, master averages, re-broadcasts) and out
of Spark (`SparkDl4jMultiLayer.java:157-210`: broadcast -> mapPartitions ->
fold/Add -> divide) collapses here into ONE compiled XLA program:

  fast path   — per-step gradient all-reduce: `shard_map` over the `dp`
                axis, `lax.pmean` on gradients over ICI, updater-chain step.
                This is the mathematically-synchronous version of what
                parameter averaging approximates.
  parity path — `fit_averaging`: each dp shard runs k *local* solver
                iterations then parameters are `pmean`-averaged — the exact
                BSP IterativeReduce semantics (`IterativeReduceWorkRouter.
                java:48-59`), one round = one XLA program.

Gradients/parameters never touch the host between steps; the "network
boundary" of the reference (Hazelcast job slots) becomes ICI collectives.
"""

from __future__ import annotations

import signal
import threading
import time
from typing import Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.multilayer import (MultiLayerNetwork, has_batchnorm,
                                              network_regularization,
                                              network_rowwise_loss,
                                              update_bn_ema_from_stats)
from deeplearning4j_tpu.optimize.updater import (UpdaterState, adjust_gradient,
                                                 init_updater, update_params)
from deeplearning4j_tpu.parallel.mesh import shard_batch
from deeplearning4j_tpu.parallel.sequence import _as_varying, _shard_map
from deeplearning4j_tpu.reliability import TrainingInterrupted, faults
from deeplearning4j_tpu.utils import profiling

import logging

log = logging.getLogger(__name__)


class TrainState(NamedTuple):
    """Carried training state — params + updater state + step counter.

    The analog of what the reference scattered across `BaseOptimizer`'s
    string-keyed searchState map and `GradientAdjustment`'s per-variable
    AdaGrad caches."""

    params: object
    updater: UpdaterState
    step: jnp.ndarray


def init_train_state(net: MultiLayerNetwork) -> TrainState:
    if net.params is None:
        net.init()
    # copy: train steps donate the state's buffers, and donating the
    # network's own params would leave net.output()/score() holding
    # deleted arrays mid-fit on TPU
    params = jax.tree_util.tree_map(jnp.copy, net.params)
    return TrainState(params=params, updater=init_updater(params),
                      step=jnp.asarray(0, jnp.int32))


def _jit_step(fn, entry: str):
    """`jax.jit` of a train step (state donated) under its name in the
    trace, `dl4j_<entry>`: the name joins no key of `track_jit`."""
    return jax.jit(profiling.named(fn, entry), donate_argnums=(0,))


def _feature_row_weights(w, x):
    """Per-feature-row weights from a per-label-row mask (label rows may be
    a multiple of feature rows, e.g. B*T for sequence models)."""
    ratio = w.shape[0] // x.shape[0]
    return w.reshape(x.shape[0], ratio)[:, 0]


def make_dp_train_step(conf: MultiLayerConfiguration, mesh: Mesh,
                       axis: str = "dp", masked: bool = False,
                       grad_accum: int = 1, cache=None):
    """Compile one data-parallel training step.

    Unmasked (default): `step(state, x, y, key) -> (state, mean_score)`,
    x/y sharded over `axis` on their leading dim, params replicated,
    gradients pmean'd over ICI.

    masked=True adds a per-label-row weight vector `w` — the
    remainder-batch path: tail batches are zero-padded to a dp-divisible
    shape and pad rows carry weight 0, so every real sample contributes to
    the gradient exactly once (VERDICT r1: the old path silently dropped up
    to dp-1 samples per batch).  Global loss = psum(sum_local(w * rows)) /
    psum(sum(w)) + regularization; gradients via psum of per-shard
    contributions (exact global weighted mean).  BATCH_NORM statistics are
    weighted the same way (pad rows don't skew the normalization).

    cache: optional `optimize.step_cache.CompiledProgramCache` — the
    step's per-shape AOT compiles are then timed/counted in its stats
    (`track_jit`), so multi-chip compiles are as observable as the
    single-chip train/infer caches.

    grad_accum=k splits each shard's batch into k microbatches, runs the
    forward/backward per microbatch under `lax.scan` (peak activation
    memory drops ~k-fold) and applies ONE update from the averaged
    gradients — for dropout-free networks numerically the plain step's
    gradient exactly (mean of equal-size microbatch means; dropout draws
    a fresh key per microbatch, so masks differ from the one-key plain
    step).  Only the unmasked, batchnorm-free path supports it (BN would
    see microbatch statistics); the per-shard batch must be divisible by
    k (checked at trace time).
    """
    out_conf = conf.conf(conf.n_layers - 1)
    n_shards = mesh.shape[axis]
    collect_bn = has_batchnorm(conf)
    if grad_accum > 1 and (masked or collect_bn):
        raise ValueError("grad_accum requires the unmasked path on a "
                         "batchnorm-free network")

    def local_step(state: TrainState, x, y, w, key):
        # distinct per-shard dropout keys, same param update everywhere
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        # differentiate w.r.t. a VARYING view of the replicated params:
        # under check_vma, the cotangent of an invariant input gets an
        # implicit psum inserted by the transpose (grads arrive already
        # summed over dp), which would make the explicit pmean/psum
        # below scale the update by n_dp. Marking params varying keeps
        # the cotangents per-shard so OUR collective does the reduction
        # (exposed by plain-SGD configs; adagrad's sign-like first step
        # masked it).
        var_params = jax.tree_util.tree_map(
            lambda p: _as_varying(p, axis), state.params)
        wx = None if w is None else _feature_row_weights(w, x)
        if w is not None:
            den = jnp.maximum(jax.lax.psum(jnp.sum(w), axis), 1.0)

        def loss_fn(p, k):
            out = network_rowwise_loss(conf, p, x, y, k, training=True,
                                       row_weights=wx,
                                       return_bn_stats=collect_bn)
            rows, stats = out if collect_bn else (out, ())
            with profiling.scope("loss"):
                if w is None:
                    loss = jnp.mean(rows) + network_regularization(conf, p)
                else:
                    # regularization / n_shards: the psum below re-sums it
                    loss = (jnp.sum(rows * w) / den
                            + network_regularization(conf, p) / n_shards)
            return loss, stats

        if grad_accum > 1:
            # microbatch scan: one fwd/bwd per slice, gradients averaged
            if x.shape[0] % grad_accum or y.shape[0] % grad_accum:
                raise ValueError(
                    f"per-shard batch {x.shape[0]} (labels {y.shape[0]}) "
                    f"not divisible by grad_accum={grad_accum}")
            xs = x.reshape(grad_accum, x.shape[0] // grad_accum,
                           *x.shape[1:])
            # label rows may be a multiple of feature rows (B*T for
            # sequence models); row order is batch-major so block
            # splitting stays aligned with x's microbatches
            ys = y.reshape(grad_accum, y.shape[0] // grad_accum,
                           *y.shape[1:])

            def micro_loss(p, k, xm, ym):
                rows = network_rowwise_loss(conf, p, xm, ym, k,
                                            training=True)
                return jnp.mean(rows) + network_regularization(conf, p)

            def micro(carry, inp):
                g_acc, s_acc, k = carry
                xm, ym = inp
                k, sub = jax.random.split(k)
                s, g = jax.value_and_grad(micro_loss)(var_params, sub,
                                                      xm, ym)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, s_acc + s, k), None

            g0 = jax.tree_util.tree_map(
                lambda p: _as_varying(jnp.zeros_like(p), axis),
                state.params)
            s0 = _as_varying(jnp.zeros((), jnp.float32), axis)
            (grads, score, _), _ = jax.lax.scan(micro, (g0, s0, key),
                                                (xs, ys))
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            score = score / grad_accum
        else:
            (score, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(var_params, key)
        # the all-reduce: what Hazelcast/Spark moved as whole param vectors
        reduce = jax.lax.pmean if w is None else jax.lax.psum
        with profiling.scope("allreduce"):
            grads = reduce(grads, axis)
            score = reduce(score, axis)
        params, upd = update_params(out_conf, state.step, grads,
                                    state.params, state.updater)
        if collect_bn:
            # running inference stats from GLOBAL-batch statistics, reusing
            # the moments the loss forward already computed (no 2nd pass)
            params = update_bn_ema_from_stats(conf, params, stats, axis=axis)
        return TrainState(params, upd, state.step + 1), score

    rep = P()
    if masked:
        fn, in_specs = local_step, (rep, P(axis), P(axis), P(axis), rep)
    else:
        def fn(state, x, y, key):
            return local_step(state, x, y, None, key)
        in_specs = (rep, P(axis), P(axis), rep)
    sharded = _shard_map(fn, mesh, in_specs, (rep, rep))
    name = "train_step" + ("_masked" if masked else "") + (
        f"_accum{grad_accum}" if grad_accum > 1 else "")
    jitted = _jit_step(sharded, name)
    if cache is not None:
        return cache.track_jit(
            ("dp_step", axis, masked, grad_accum), jitted)
    return jitted


def make_masked_dp_train_step(conf: MultiLayerConfiguration, mesh: Mesh,
                              axis: str = "dp", cache=None):
    return make_dp_train_step(conf, mesh, axis, masked=True, cache=cache)


def make_sharded_train_step(conf: MultiLayerConfiguration, mesh: Mesh,
                            cache=None):
    """Compiler-partitioned (pjit-style) training step for meshes with
    tensor-parallel axes: params get `tp` shardings via `param_pspecs`,
    batch is sharded over `dp`, and XLA inserts the collectives (psum for
    grads over dp, all-gather/reduce-scatter for tp) automatically."""
    out_conf = conf.conf(conf.n_layers - 1)

    collect_bn = has_batchnorm(conf)

    def step_fn(state: TrainState, x, y, key):
        def loss_fn(p, k):
            out = network_rowwise_loss(conf, p, x, y, k, training=True,
                                       return_bn_stats=collect_bn)
            rows, stats = out if collect_bn else (out, ())
            return jnp.mean(rows) + network_regularization(conf, p), stats

        (score, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, key)
        params, upd = update_params(out_conf, state.step, grads,
                                    state.params, state.updater)
        if collect_bn:
            params = update_bn_ema_from_stats(conf, params, stats)
        return TrainState(params, upd, state.step + 1), score

    jitted = _jit_step(step_fn, "sharded_step")
    if cache is not None:
        return cache.track_jit(("sharded_step",), jitted)
    return jitted


def zero1_pspecs(tree, mesh: Mesh, axis: str = "dp"):
    """ZeRO-1 PartitionSpecs for an updater-state pytree: each leaf
    shards its first dp-divisible dimension over `axis`; indivisible or
    scalar leaves replicate.  (New scope beyond the reference — ZeRO is
    a 2020s memory optimization; the 2015 reference replicates
    everything.)"""
    size = mesh.shape[axis]

    def spec(x):
        for d in range(getattr(x, "ndim", 0)):
            if x.shape[d] % size == 0 and x.shape[d] >= size:
                return P(*([None] * d + [axis]))
        return P()

    return jax.tree_util.tree_map(spec, tree)


def make_zero1_train_step(conf: MultiLayerConfiguration, mesh: Mesh,
                          axis: str = "dp", masked: bool = False,
                          cache=None):
    """Data-parallel step with ZeRO-1 optimizer-state sharding, built on
    GSPMD sharding annotations instead of manual collectives: the batch
    is dp-sharded, params stay replicated, and the AdaGrad/momentum (or
    adam m/v) state lives SHARDED over the dp axis — 1/n_dp of the
    optimizer memory per chip.  `with_sharding_constraint` on the
    gradients entering the updater makes XLA lower the dp grad reduction
    as a reduce-scatter, the elementwise updater math runs shard-local,
    and the parameter update all-gathers the adjusted step — the ZeRO-1
    communication schedule, derived by the partitioner from layout
    constraints rather than hand-written ppermutes.

    Use with `zero1_shard_state(state, mesh)`; step signature matches
    `make_dp_train_step` (state, x, y, key) -> (state, score).

    masked=True is the pad-and-mask remainder-batch variant (ISSUE 17
    closing PR 10's guard): signature (state, x, y, w, key), per-label-
    row weights, loss = dot(rows, w) / max(sum(w), 1) + reg.  Because
    this is the GSPMD path the weighted mean is one whole-array
    contraction (no per-shard psum), so a zero-padded tail batch scores
    and steps on exactly the real rows — divisible batches never route
    here and stay bitwise-identical to the unmasked step."""
    out_conf = conf.conf(conf.n_layers - 1)
    collect_bn = has_batchnorm(conf)
    if collect_bn:
        raise ValueError("zero1 step does not support BatchNorm nets "
                         "(per-batch stats need the shard_map path)")

    def step_fn(state: TrainState, x, y, *rest):
        (w, key) = rest if masked else (None, rest[0])

        def loss_fn(p, k):
            wx = None if w is None else _feature_row_weights(w, x)
            rows = network_rowwise_loss(conf, p, x, y, k, training=True,
                                        row_weights=wx)
            if w is None:
                return jnp.mean(rows) + network_regularization(conf, p)
            den = jnp.maximum(jnp.sum(w), 1.0)
            return (jnp.dot(rows, w) / den
                    + network_regularization(conf, p))

        score, grads = jax.value_and_grad(loss_fn)(state.params, key)
        # pin the gradient layout to the updater's sharded layout: the
        # dp-mean above then lowers as reduce-scatter(+partial sums)
        # instead of a full all-reduce
        gspecs = zero1_pspecs(grads, mesh, axis)
        grads = jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, s)), grads, gspecs)
        params, upd = update_params(out_conf, state.step, grads,
                                    state.params, state.updater)
        # params come back replicated (all-gather of the sharded step)
        params = jax.tree_util.tree_map(
            lambda p: jax.lax.with_sharding_constraint(
                p, NamedSharding(mesh, P())), params)
        return TrainState(params, upd, state.step + 1), score

    jitted = _jit_step(step_fn,
                       "zero1_step" + ("_masked" if masked else ""))
    if cache is not None:
        return cache.track_jit(("zero1_step", axis, masked), jitted)
    return jitted


def zero1_shard_state(state: TrainState, mesh: Mesh, axis: str = "dp"):
    """Place a TrainState for the ZeRO-1 step: params replicated, updater
    state sharded over `axis` (its per-chip footprint drops n_dp-fold)."""
    rep = NamedSharding(mesh, P())

    def put_rep(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, rep), tree)

    def put_sharded(tree):
        specs = zero1_pspecs(tree, mesh, axis)
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            tree, specs)

    return TrainState(params=put_rep(state.params),
                      updater=UpdaterState(
                          adagrad_hist=put_sharded(state.updater.adagrad_hist),
                          velocity=put_sharded(state.updater.velocity)),
                      step=jax.device_put(state.step, rep))


def make_plan_train_step(conf: MultiLayerConfiguration, plan,
                         masked: bool = False, zero1: bool = False,
                         cache=None):
    """GSPMD training step driven by a `parallel.plan.ShardPlan` with a
    `model` axis (ISSUE 17): params tensor-shard per the plan's
    per-leaf specs (QKV/FFN-up/embedding column-split, Wo/FFN-down
    row-split), the batch shards over the plan's batch axis, and jit
    inserts the collectives — the all-reduce after every row-split
    matmul AND the dp gradient reduction come out of one partitioner
    pass.  Updater moments follow the params' model split; zero1=True
    additionally shards their first batch-divisible dim over the batch
    axis (`plan.zero1_pspecs` — both axes on one leaf where divisible).
    masked=True is the pad-and-mask remainder variant ((state, x, y, w,
    key), weight-0 pad rows, dot-form weighted mean).

    Use with `plan_shard_state`; signatures match
    `make_zero1_train_step`."""
    out_conf = conf.conf(conf.n_layers - 1)
    if has_batchnorm(conf):
        raise ValueError("plan step does not support BatchNorm nets "
                         "(per-batch stats need the shard_map path)")
    mesh = plan.mesh
    batch_spec = P(plan.batch_axis if plan.batch_axis in mesh.axis_names
                   else None)

    def pin(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, s: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, s)), tree, specs)

    def step_fn(state: TrainState, x, y, *rest):
        (w, key) = rest if masked else (None, rest[0])
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, batch_spec))
        params = pin(state.params, plan.param_pspecs(state.params))

        def loss_fn(p, k):
            wx = None if w is None else _feature_row_weights(w, x)
            rows = network_rowwise_loss(conf, p, x, y, k, training=True,
                                        row_weights=wx)
            if w is None:
                return jnp.mean(rows) + network_regularization(conf, p)
            den = jnp.maximum(jnp.sum(w), 1.0)
            return (jnp.dot(rows, w) / den
                    + network_regularization(conf, p))

        score, grads = jax.value_and_grad(loss_fn)(params, key)
        gspec_fn = plan.zero1_pspecs if zero1 else plan.param_pspecs
        grads = pin(grads, gspec_fn(grads))
        new_params, upd = update_params(out_conf, state.step, grads,
                                        params, state.updater)
        # params stay model-sharded across steps (never gathered); only
        # the zero1 batch-axis split of the step all-gathers back
        new_params = pin(new_params, plan.param_pspecs(new_params))
        return TrainState(new_params, upd, state.step + 1), score

    jitted = _jit_step(step_fn, "plan_step" + ("_masked" if masked else "")
                       + ("_zero1" if zero1 else ""))
    if cache is not None:
        return cache.track_jit(
            ("plan_step", plan.sharding_tag(), masked, zero1), jitted)
    return jitted


def plan_shard_state(state: TrainState, plan, zero1: bool = False
                     ) -> TrainState:
    """Place a TrainState per a model-axis ShardPlan: params and updater
    moments tensor-sharded per leaf (zero1 composes the batch axis into
    the moments), step replicated — no leaf lives at global size on any
    one chip."""
    mesh = plan.mesh

    def put(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            tree, specs)

    uspec_fn = plan.zero1_pspecs if zero1 else plan.param_pspecs
    return TrainState(
        params=put(state.params, plan.param_pspecs(state.params)),
        updater=UpdaterState(
            adagrad_hist=put(state.updater.adagrad_hist,
                             uspec_fn(state.updater.adagrad_hist)),
            velocity=put(state.updater.velocity,
                         uspec_fn(state.updater.velocity))),
        step=jax.device_put(state.step, NamedSharding(mesh, P())))


def param_pspecs(params, mesh: Mesh, tp_axis: str = "tp"):
    """Tensor-parallel PartitionSpecs for a params pytree: 2-D weight
    matrices shard their output dim over `tp_axis` when divisible; 4-D conv
    filters shard output feature maps; everything else replicates.  (New
    scope beyond the reference — its only strategy was DP, SURVEY §2.)"""
    if tp_axis not in mesh.axis_names:
        return jax.tree_util.tree_map(lambda _: P(), params)
    size = mesh.shape[tp_axis]

    def spec(x):
        if x.ndim == 2 and x.shape[1] % size == 0:
            return P(None, tp_axis)
        if x.ndim == 4 and x.shape[-1] % size == 0:
            return P(None, None, None, tp_axis)
        return P()

    return jax.tree_util.tree_map(spec, params)


def shard_train_state(state: TrainState, mesh: Mesh, tp_axis: str = "tp"):
    """Place a TrainState on the mesh with tp-sharded params (updater state
    follows params' sharding; step replicated)."""
    pspecs = param_pspecs(state.params, mesh, tp_axis)

    def put(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            tree, specs)

    return TrainState(
        params=put(state.params, pspecs),
        updater=UpdaterState(
            adagrad_hist=put(state.updater.adagrad_hist, pspecs),
            velocity=put(state.updater.velocity, pspecs)),
        step=jax.device_put(state.step, NamedSharding(mesh, P())),
    )


def make_averaging_round(conf: MultiLayerConfiguration, mesh: Mesh,
                         local_steps: int, axis: str = "dp",
                         masked: bool = False, cache=None):
    """Compile one BSP IterativeReduce round: every dp shard takes
    `local_steps` independent updater-chain steps on its own data, then
    parameters are averaged (`pmean`) — exact reference semantics
    (worker fit -> addUpdate -> IterateAndUpdateImpl average), minus the
    disk spills.  HogWild (async, no gate) corresponds to running shards
    un-averaged and calling this with local_steps=k, average every round
    being optional — see `AveragingTrainer.hogwild`.

    masked=True (remainder batches): local losses are weighted means over
    each shard's real rows, and the final average weights each shard's
    parameters by its real-row count — a shard holding only pad rows
    contributes nothing (the reference analog: an idle worker submits no
    update)."""
    out_conf = conf.conf(conf.n_layers - 1)
    collect_bn = has_batchnorm(conf)

    def round_fn(state: TrainState, x, y, w, key):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        wx = None if w is None else _feature_row_weights(w, x)
        if w is not None:
            local_den = jnp.sum(w)
            safe_den = jnp.maximum(local_den, 1.0)
            has_data = (local_den > 0).astype(jnp.float32)

        def one(carry, it):
            params, upd, k = carry
            k, sub = jax.random.split(k)

            def loss_fn(p, kk):
                out = network_rowwise_loss(conf, p, x, y, kk, training=True,
                                           row_weights=wx,
                                           return_bn_stats=collect_bn)
                rows, stats = out if collect_bn else (out, ())
                if w is None:
                    loss = jnp.mean(rows) + network_regularization(conf, p)
                else:
                    loss = (jnp.sum(rows * w) / safe_den
                            + network_regularization(conf, p))
                return loss, stats

            (score, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, sub)
            adj, upd = adjust_gradient(out_conf, state.step + it, grads,
                                       params, upd)
            gate = 1.0 if w is None else has_data
            params = jax.tree_util.tree_map(
                lambda p, a: p - gate * a.astype(p.dtype), params, adj)
            if collect_bn:
                # local stats (no psum): the round's aggregation averages
                # the ema entries along with every other parameter
                params = update_bn_ema_from_stats(conf, params, stats)
            return (params, upd, k), score

        # the carry becomes dp-varying after one step (per-shard RNG fold,
        # masked gates); mark the invariant inits as varying so the
        # check_vma pass can type the scan with checking ON
        vary = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: _as_varying(a, axis), t)
        (params, upd, _), scores = jax.lax.scan(
            one, (vary(state.params), vary(state.updater), key),
            jnp.arange(local_steps))

        # the aggregation step: IterateAndUpdateImpl.accumulate -> average
        if w is None:
            return (TrainState(jax.lax.pmean(params, axis),
                               jax.lax.pmean(upd, axis),
                               state.step + local_steps),
                    jax.lax.pmean(scores[-1], axis))

        total = jnp.maximum(jax.lax.psum(local_den, axis), 1.0)

        def wavg(tree):
            return jax.tree_util.tree_map(
                lambda p: jax.lax.psum(p * (local_den / total).astype(p.dtype),
                                       axis), tree)

        return (TrainState(wavg(params), wavg(upd),
                           state.step + local_steps),
                jax.lax.psum(scores[-1] * local_den, axis) / total)

    rep = P()
    if masked:
        fn, in_specs = round_fn, (rep, P(axis), P(axis), P(axis), rep)
    else:
        def fn(state, x, y, key):
            return round_fn(state, x, y, None, key)
        in_specs = (rep, P(axis), P(axis), rep)
    sharded = _shard_map(fn, mesh, in_specs, (rep, rep))
    jitted = _jit_step(sharded,
                       "averaging_round" + ("_masked" if masked else ""))
    if cache is not None:
        return cache.track_jit(
            ("dp_averaging", axis, masked, local_steps), jitted)
    return jitted


def make_masked_averaging_round(conf: MultiLayerConfiguration, mesh: Mesh,
                                local_steps: int, axis: str = "dp",
                                cache=None):
    return make_averaging_round(conf, mesh, local_steps, axis, masked=True,
                                cache=cache)


class DataParallelTrainer:
    """Drives a MultiLayerNetwork over a mesh — the role of
    `DeepLearning4jDistributed` + `SparkDl4jMultiLayer`, minus the cluster
    plumbing XLA now does.

    mode="sync"      per-step gradient all-reduce (fast path)
    mode="averaging" BSP local-steps-then-average (reference parity)
    zero1=True       sync mode with ZeRO-1 updater-state sharding: the
                     adagrad/momentum moments live 1/n_dp per chip
                     (`make_zero1_train_step`); checkpoints gather them
                     to full shape on save and re-shard on load, so the
                     same elastic resume covers them
    plan=ShardPlan   a `parallel.plan.ShardPlan` with a `model` axis
                     switches to the tensor-parallel GSPMD step
                     (`make_plan_train_step`): params + updater moments
                     shard per-leaf, batches over the plan's batch axis
                     (zero1 composes), and checkpoints write the SHARDED
                     layout — no global leaf ever materializes
    """

    def __init__(self, net: MultiLayerNetwork, mesh: Optional[Mesh] = None,
                 mode: str = "sync", local_steps: int = 5,
                 axis: str = "dp", listeners=(), grad_accum: int = 1,
                 zero1: bool = False, plan=None):
        self.plan = plan
        self._plan_tp = bool(plan is not None
                             and getattr(plan, "has_model_axis", False))
        if self._plan_tp:
            mesh = plan.mesh
            axis = plan.batch_axis
        elif mesh is None:
            if plan is not None:
                mesh = plan.mesh  # 1-D plan: the plain dp path
                axis = plan.batch_axis
            else:
                raise ValueError("pass mesh= or plan=")
        self.net = net
        self.mesh = mesh
        self.axis = axis
        self.mode = mode
        self.zero1 = bool(zero1)
        self.listeners = list(listeners)
        if net.params is None:
            net.init()
        # multi-chip compile observability: every step variant's AOT
        # compile is timed/counted here, like the single-chip caches
        from deeplearning4j_tpu.optimize.step_cache import (
            CompiledProgramCache)

        self.compile_cache = CompiledProgramCache()
        self.compile_cache.kind = "dp-step-cache"
        if self._plan_tp:
            if mode != "sync":
                raise ValueError("a model-axis plan requires mode='sync'")
            if grad_accum > 1:
                raise ValueError("a model-axis plan does not compose "
                                 "with grad_accum yet")
            self._step = make_plan_train_step(net.conf, plan, zero1=zero1,
                                              cache=self.compile_cache)
        elif zero1:
            if mode != "sync":
                raise ValueError("zero1=True requires mode='sync' (the "
                                 "averaging round replicates its carry)")
            if grad_accum > 1:
                raise ValueError("zero1=True does not compose with "
                                 "grad_accum yet")
            self._step = make_zero1_train_step(net.conf, mesh, axis,
                                               cache=self.compile_cache)
        elif mode == "sync":
            self._step = make_dp_train_step(net.conf, mesh, axis,
                                            grad_accum=grad_accum,
                                            cache=self.compile_cache)
        elif mode == "averaging":
            if grad_accum > 1:
                raise ValueError(
                    "grad_accum is only supported in mode='sync'")
            self._step = make_averaging_round(net.conf, mesh, local_steps,
                                              axis, cache=self.compile_cache)
        else:
            raise ValueError(f"unknown mode {mode!r}")
        self._local_steps = local_steps
        self._grad_accum = grad_accum
        self._masked_step = None  # built lazily on first remainder batch
        self.state = init_train_state(net)
        if self._plan_tp:
            self.state = plan_shard_state(self.state, plan, zero1)
        elif zero1:
            self.state = zero1_shard_state(self.state, mesh, axis)
        self._key = jax.random.PRNGKey(net.conf.confs[0].seed or 0)
        # crash-safety bookkeeping (fit(checkpoint_dir=...)): SIGTERM flag
        # checked between batches, resume provenance, write-cost accounting
        self._stop_training = threading.Event()
        self.resumed_from_step: Optional[int] = None
        self.checkpoint_write_seconds = 0.0
        self.checkpoints_written = 0
        self._fit_calls = 0     # the rid of a `fit` call's spans

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- checkpoint / elastic resume ----------------------------------------
    def mesh_meta(self) -> dict:
        """Topology stamp recorded in every checkpoint this trainer
        writes: enough for a loader to detect (not guess) an N->M or
        zero1-flag change on resume."""
        return {"axis_names": list(self.mesh.axis_names),
                "shape": {a: int(self.mesh.shape[a])
                          for a in self.mesh.axis_names},
                "zero1": self.zero1}

    def _check_mesh_meta(self, meta: dict) -> None:
        """Compare the checkpoint's recorded topology with THIS mesh and
        log every difference — elastic resume handles them all (leaves
        are saved gathered), but silently is how divergence hides."""
        ck = meta.get("mesh") or {}
        if not ck:
            return  # pre-elastic checkpoint: nothing recorded to compare
        ck_axes = list(ck.get("axis_names") or [])
        cur_axes = list(self.mesh.axis_names)
        if ck_axes != cur_axes:
            log.warning("checkpoint mesh axes %s != current %s; leaves "
                        "re-place on the current mesh", ck_axes, cur_axes)
        ck_shape = {k: int(v) for k, v in (ck.get("shape") or {}).items()}
        cur_shape = {a: int(self.mesh.shape[a]) for a in cur_axes}
        if ck_shape != cur_shape:
            log.info("elastic resume: checkpoint written on mesh %s, "
                     "resuming on %s", ck_shape, cur_shape)
        if bool(ck.get("zero1", False)) != self.zero1:
            log.info("checkpoint zero1=%s, trainer zero1=%s: updater "
                     "state re-places per the current mode",
                     bool(ck.get("zero1", False)), self.zero1)

    def _place_state(self, state: TrainState) -> TrainState:
        """Re-place a host-materialized TrainState on THIS trainer's mesh
        — the elastic half of resume (`get_sharding_tree` pattern): a
        sharding tree for the NEW mesh re-places every leaf, so a
        checkpoint written on N chips trains on M.  Params and step
        replicate; updater state replicates too, or re-shards over the
        dp axis in zero1 mode; a model-axis plan re-shards everything
        per its per-leaf specs."""
        if self._plan_tp:
            return plan_shard_state(
                TrainState(params=state.params, updater=state.updater,
                           step=jnp.asarray(state.step, jnp.int32)),
                self.plan, self.zero1)
        if self.zero1:
            return zero1_shard_state(
                TrainState(params=state.params, updater=state.updater,
                           step=jnp.asarray(state.step, jnp.int32)),
                self.mesh, self.axis)
        rep = NamedSharding(self.mesh, P())

        def put(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(jnp.asarray(a), rep), tree)

        return TrainState(params=put(state.params),
                          updater=put(state.updater),
                          step=jax.device_put(
                              jnp.asarray(state.step, jnp.int32), rep))

    def _apply_restored(self, params, updater, meta: dict) -> None:
        self._check_mesh_meta(meta)
        step = int(meta.get("step", 0))
        self.state = self._place_state(TrainState(
            params=params, updater=updater,
            step=jnp.asarray(step, jnp.int32)))
        self.net.params = jax.tree_util.tree_map(jnp.asarray, params)
        rng = (meta.get("metadata") or {}).get("rng_key")
        if rng is not None:
            # without the key a "resumed" run draws a fresh dropout/shuffle
            # stream and silently diverges from the uninterrupted one
            self._key = jnp.asarray(np.asarray(rng, dtype=np.uint32))
        self.resumed_from_step = step

    def restore(self, directory: str) -> int:
        """Resume from a checkpoint: params, updater state, step counter,
        AND the host RNG key land back in the trainer, re-placed on THIS
        trainer's mesh (elastic: the writing mesh may have had a
        different device count).  Returns the restored step."""
        from deeplearning4j_tpu.parallel import checkpoint

        params, updater, meta = checkpoint.load(
            directory, like_params=self.state.params,
            like_updater=self.state.updater)
        self._apply_restored(params, updater, meta)
        return int(meta["step"])

    def _save_checkpoint(self, directory: str, batches_done: int) -> None:
        """Synchronous atomic checkpoint of the COMPLETE cross-batch
        state: params + updater moments (zero1 shards gather to full
        shape via device_get) + step + host RNG key + data cursor."""
        from deeplearning4j_tpu.parallel import checkpoint as ckpt

        t0 = time.perf_counter()
        # a model-axis plan writes the SHARDED layout (one piece per
        # unique shard — no global leaf on host); `load`/`load_resilient`
        # read both layouts, so resume is unchanged
        writer = ckpt.save_sharded if self._plan_tp else ckpt.save
        writer(directory, self.state.params, self.state.updater,
               conf=self.net.conf, step=int(self.state.step),
               data_cursor={"batches_done": int(batches_done)},
               metadata={"rng_key": np.asarray(
                   jax.device_get(self._key)).tolist()},
               mesh=self.mesh_meta())
        self.checkpoint_write_seconds += time.perf_counter() - t0
        self.checkpoints_written += 1

    def request_stop_training(self) -> None:
        """Ask a running `fit(checkpoint_dir=...)` to checkpoint and
        raise `TrainingInterrupted` after the current batch (what the
        installed SIGTERM handler calls)."""
        self._stop_training.set()

    def _step_padded(self, x, y):
        """Zero-pad a remainder batch to a dp-divisible shape and run the
        masked step (pad rows carry weight 0).  Label rows may be a multiple
        of feature rows (e.g. B*T for sequence models) — the mask follows
        the label rows."""
        n_dp = self.mesh.shape[self.axis]
        b = x.shape[0]
        pad = n_dp - b % n_dp
        ratio = max(1, y.shape[0] // max(1, b))
        if self._masked_step is None:
            if self._grad_accum > 1:
                # the masked path has no accumulation: the tail batch runs
                # one full fwd/bwd — warn, since accumulation is usually
                # chosen for activation-memory headroom
                log.warning(
                    "remainder batch of %d runs the masked step WITHOUT "
                    "grad_accum=%d (single fwd/bwd)", b, self._grad_accum)
            if self._plan_tp:
                self._masked_step = make_plan_train_step(
                    self.net.conf, self.plan, masked=True,
                    zero1=self.zero1, cache=self.compile_cache)
            elif self.zero1:
                self._masked_step = make_zero1_train_step(
                    self.net.conf, self.mesh, self.axis, masked=True,
                    cache=self.compile_cache)
            elif self.mode == "sync":
                self._masked_step = make_masked_dp_train_step(
                    self.net.conf, self.mesh, self.axis,
                    cache=self.compile_cache)
            else:
                self._masked_step = make_masked_averaging_round(
                    self.net.conf, self.mesh, self._local_steps, self.axis,
                    cache=self.compile_cache)
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        y = jnp.concatenate(
            [y, jnp.zeros((pad * ratio,) + y.shape[1:], y.dtype)])
        w = jnp.concatenate([jnp.ones(b * ratio, jnp.float32),
                             jnp.zeros(pad * ratio, jnp.float32)])
        x, y, w = shard_batch(self.mesh, (x, y, w), self.axis)
        return self._masked_step(self.state, x, y, w, self._next_key())

    def fit(self, data: Iterable, epochs: int = 1, *,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every_n_batches: int = 0,
            auto_resume: bool = True) -> float:
        """data yields (features, labels) or DataSet; leading dim must be
        divisible by the dp axis size (remainder batches pad-and-mask —
        in every mode, including zero1 and plan steps).

        With `checkpoint_dir` the run is crash-safe AND elastic (ISSUE
        10): the complete cross-batch state — params, updater moments,
        step, host RNG key, batch cursor — is checkpointed atomically
        every `checkpoint_every_n_batches` batches (and at the end), a
        SIGTERM checkpoints-then-raises `TrainingInterrupted`, and a
        rerun with the same `checkpoint_dir` and the same batch stream
        auto-resumes at the saved cursor — on ANY device count: the
        checkpoint holds gathered host arrays, and resume re-places them
        on this trainer's mesh (same-topology resume is bit-identical;
        N->M changes only the f32 reduction grouping of the collectives).
        The batch cursor counts across epochs, so resume lands mid-epoch
        correctly."""
        start_batch = 0
        if checkpoint_dir is not None and auto_resume:
            start_batch = self._try_resume(checkpoint_dir)
        if checkpoint_dir is None:
            return self._fit_loop(data, epochs, None, 0, 0)
        self._stop_training.clear()
        prev_handler, installed = None, False
        if threading.current_thread() is threading.main_thread():
            try:
                prev_handler = signal.signal(
                    signal.SIGTERM,
                    lambda signum, frame: self._stop_training.set())
                installed = True
            except ValueError:
                pass  # exotic embedding: no handler, explicit stop only
        try:
            return self._fit_loop(data, epochs, checkpoint_dir,
                                  int(checkpoint_every_n_batches),
                                  start_batch)
        finally:
            if installed:
                signal.signal(signal.SIGTERM, prev_handler)

    def _try_resume(self, directory: str) -> int:
        """Restore the newest valid checkpoint under `directory` (or its
        .bak) into this trainer; returns the batch cursor to skip to (0 =
        nothing to resume)."""
        from deeplearning4j_tpu.parallel import checkpoint

        restored = checkpoint.load_resilient(
            directory, like_params=self.state.params,
            like_updater=self.state.updater)
        if restored is None:
            return 0
        params, updater, meta = restored
        self._apply_restored(params, updater, meta)
        cursor = int((meta.get("data_cursor") or {}).get("batches_done", 0))
        log.info("mesh fit: auto-resumed %s at batch %d (step %d, mesh %s)",
                 directory, cursor, self.resumed_from_step,
                 (meta.get("mesh") or {}).get("shape"))
        return cursor

    def _fit_loop(self, data, epochs: int, checkpoint_dir: Optional[str],
                  every_n: int, start_batch: int) -> float:
        """The loop's thread is tiled by its spans, all keyed by the number
        of this `fit` call: `fit.next` (the iterator), `fit.step` (the step
        call: placing the batch and dispatching the program, which blocks
        once the device's queue is full) and `fit.sync` (each host read)."""
        score = float("nan")
        n_dp = self.mesh.shape[self.axis]
        n_done = 0
        self._fit_calls += 1
        rid = self._fit_calls
        for _ in range(epochs):
            if hasattr(data, "reset"):
                data.reset()
            batches = iter(data)
            while True:
                with profiling.span("fit.next", rid=rid):
                    try:
                        batch = next(batches)
                    except StopIteration:
                        break
                n_done += 1
                if n_done <= start_batch:
                    # replaying the resumed prefix of the stream: the data
                    # order is deterministic, so skipping (not re-training)
                    # these batches reproduces the dead run's position; no
                    # RNG keys are consumed (the restored key already
                    # accounts for them)
                    continue
                with profiling.span("fit.step", rid=rid, step=n_done):
                    faults.fire("trainer.step", batch=n_done)
                    x, y = ((batch.features, batch.labels)
                            if hasattr(batch, "features") else batch)
                    x, y = jnp.asarray(x), jnp.asarray(y)
                    if x.shape[0] % n_dp:
                        # pad-and-mask: every real sample still contributes
                        # exactly once (no silent remainder drop; zero1 and
                        # plan modes route through their masked variants)
                        self.state, s = self._step_padded(x, y)
                    else:
                        x, y = shard_batch(self.mesh, (x, y), self.axis)
                        self.state, s = self._step(self.state, x, y,
                                                   self._next_key())
                score = s
                if self.listeners:
                    # only a listener forces the host sync; otherwise steps
                    # stay async so dispatch pipelines ahead of the device
                    with profiling.span("fit.sync", rid=rid):
                        step, s = int(self.state.step), float(s)
                    for li in self.listeners:
                        li.iteration_done(self, step, s)
                if checkpoint_dir is not None:
                    if self._stop_training.is_set():
                        self._save_checkpoint(checkpoint_dir, n_done)
                        raise TrainingInterrupted(
                            f"stop requested: checkpointed {checkpoint_dir}"
                            f" at batch {n_done}")
                    if every_n > 0 and n_done % every_n == 0:
                        self._save_checkpoint(checkpoint_dir, n_done)
        if checkpoint_dir is not None and n_done > start_batch:
            self._save_checkpoint(checkpoint_dir, n_done)
        if self._plan_tp:
            # keep the tensor-sharded placement: gathering a model the
            # plan exists to fit across chips would defeat it.  Copy so
            # a later fit's donated steps can't delete the net's view;
            # serving re-places per its own plan (`set_serve_mesh`).
            self.net.params = jax.tree_util.tree_map(
                jnp.copy, self.state.params)
        else:
            # hand the net a single-device copy: the serve/train-path AOT
            # programs compile for single-chip layouts, and an
            # already-compiled executable can't reshard a mesh-replicated
            # NamedSharding leaf the way plain jit would.  Replicated
            # params make this a local device copy (async, no host
            # roundtrip).
            self.net.params = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, self.mesh.devices.flat[0]),
                self.state.params)
        with profiling.span("fit.sync", rid=rid):
            return float(score) if score is not None else float("nan")
