"""MultiLayerNetwork — the stacked-network model container.

Parity: reference `nn/multilayer/MultiLayerNetwork.java:59-1530`:
  fit(iter)            -> pretrain (layer-wise) + finetune/backprop   (:928-992)
  feedForward/output   -> per-layer activate with InputPreProcessors  (:488-518, :1159)
  predict              -> row argmax                                   (:1069-1078)
  score                -> output-layer loss                            (OutputLayer.java:77-90)
  params()/setParams   -> flat parameter vector pack/unpack
  merge                -> parameter averaging (see parallel/averaging.py)

TPU-native design: the network is a frozen config + a params pytree (tuple of
per-layer dicts).  Training compiles ONE XLA program per (config, batch
shape): the configured solver (optimize.solver) runs its whole iteration
loop on-device.  Backprop is `jax.grad` through the stacked forward — there
is no hand-written `backWard`/delta algebra to maintain.  Layer-wise
pretraining drives each pretrainable layer's `pretrain_grad_and_score`
through the same solver machinery (`pretrain` flag parity).
"""

from __future__ import annotations

import logging
import os
import signal
import threading
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.nn.conf import (LayerType, MultiLayerConfiguration,
                                        OptimizationAlgorithm)
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.layers.preprocessor import apply_preprocessor
from deeplearning4j_tpu.optimize import solver as solver_mod
from deeplearning4j_tpu.optimize.infer_cache import InferCache
from deeplearning4j_tpu.optimize.listeners import dispatch as dispatch_listeners
from deeplearning4j_tpu.optimize.step_cache import TrainStepCache
from deeplearning4j_tpu.reliability import TrainingInterrupted
from deeplearning4j_tpu.utils import profiling

log = logging.getLogger("deeplearning4j_tpu")

_PRETRAINABLE = {LayerType.RBM, LayerType.AUTOENCODER,
                 LayerType.RECURSIVE_AUTOENCODER}


def init_params(conf: MultiLayerConfiguration, key) -> tuple:
    """Initialize every layer's params (ParamInitializer dispatch parity)."""
    keys = jax.random.split(key, max(1, conf.n_layers))
    return tuple(
        get_layer(c.layer_type).init(keys[i], c)
        for i, c in enumerate(conf.confs)
    )


def _layer_forward(impl, c, params, h, key, training, index):
    """One layer's forward under its scope `L<index>.<layer_type>` (so every
    device operation of the layer, forward and backward, says whose it is),
    optionally under jax.checkpoint (conf.remat):
    activations inside the layer are recomputed during backward instead of
    stored, trading ~1/3 extra FLOPs for HBM capacity — the standard TPU
    trick for fitting larger batches (SURVEY §7 / scaling-book recipe).

    Training forwards go through jax.checkpoint with BOTH remat settings
    (remat=False saves every residual, so nothing is recomputed): the
    checkpoint boundary fixes the layer's backward to one
    linearize-then-transpose structure, whose input-cotangent summation
    order differs from plain trace-through autodiff by float noise.  One
    shared structure means flipping conf.remat changes memory, never a
    single grad bit."""
    with profiling.layer_scope(index, c):
        if training:
            policy = (None if c.remat
                      else jax.checkpoint_policies.everything_saveable)
            return jax.checkpoint(
                lambda p, hh, kk: impl.forward(p, c, hh, kk, training),
                policy=policy)(params, h, key)
        return impl.forward(params, c, h, key, training)


def feed_forward(conf: MultiLayerConfiguration, params, x, key=None,
                 training=False, up_to: Optional[int] = None):
    """Activations after each layer (MultiLayerNetwork.feedForward parity).

    Returns the list of post-layer activations; `up_to` stops early (used by
    layer-wise pretraining to build a layer's input).
    """
    n = conf.n_layers if up_to is None else up_to
    acts = []
    keys = (jax.random.split(key, max(1, n)) if key is not None
            else [None] * max(1, n))
    from deeplearning4j_tpu.nn.layers.output import OutputLayer

    for i in range(n):
        c = conf.conf(i)
        x = apply_preprocessor(conf.preprocessor(i), x)
        p = (OutputLayer.head_params(conf, params) if i == conf.n_layers - 1
             else params[i])
        x = _layer_forward(get_layer(c.layer_type), c, p, x, keys[i],
                           training, i)
        acts.append(x)
    return acts


def network_output(conf, params, x, key=None, training=False):
    acts = feed_forward(conf, params, x, key, training)
    return acts[-1] if acts else x


def network_loss(conf: MultiLayerConfiguration, params, x, labels, key=None,
                 training=True):
    """End-to-end loss: hidden forward + OutputLayer loss (+ L2 across layers)."""
    from deeplearning4j_tpu.nn.layers.output import OutputLayer

    n = conf.n_layers
    keys = (jax.random.split(key, n) if key is not None else [None] * n)
    h = x
    for i in range(n - 1):
        c = conf.conf(i)
        h = apply_preprocessor(conf.preprocessor(i), h)
        h = _layer_forward(get_layer(c.layer_type), c, params[i], h,
                           keys[i], training, i)
    out_conf = conf.conf(n - 1)
    h = apply_preprocessor(conf.preprocessor(n - 1), h)
    with profiling.layer_scope(n - 1, out_conf):
        loss = OutputLayer.loss(OutputLayer.head_params(conf, params), out_conf,
                                h, labels, keys[n - 1], training)
    if out_conf.use_regularization and out_conf.l2:
        for i in range(n - 1):
            if "W" in params[i]:
                loss = loss + 0.5 * out_conf.l2 * jnp.sum(
                    params[i]["W"].astype(jnp.float32) ** 2)
    return loss


def network_rowwise_loss(conf: MultiLayerConfiguration, params, x, labels,
                         key=None, training=True, row_weights=None,
                         return_bn_stats=False):
    """Per-label-row loss vector, no regularization (see
    `network_regularization` for that half).  Row count follows `labels`'
    leading dim — e.g. B*T rows for a char-LSTM whose rnn_to_ff stage
    flattens time into the batch.

    row_weights (per feature row, pad rows = 0) keeps BATCH_NORM training
    statistics over real rows only — zero padding must neither skew the
    normalization nor the loss.

    return_bn_stats=True additionally returns the raw BN moments
    ((s1, s2, cnt) per BATCH_NORM layer, in layer order) computed during
    THIS forward, so train steps can maintain running inference stats
    without a second forward pass (`update_bn_ema_from_stats`)."""
    from deeplearning4j_tpu.nn.layers.base import BatchNormLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer

    n = conf.n_layers
    keys = (jax.random.split(key, n) if key is not None else [None] * n)
    h = x
    stats = []
    for i in range(n - 1):
        c = conf.conf(i)
        h = apply_preprocessor(conf.preprocessor(i), h)
        impl = get_layer(c.layer_type)
        is_bn = LayerType(str(c.layer_type)) == LayerType.BATCH_NORM
        if is_bn and training and (row_weights is not None
                                   or return_bn_stats):
            with profiling.layer_scope(i, c):
                s1, s2, cnt = BatchNormLayer.moments(h, row_weights)
                if return_bn_stats:
                    stats.append((s1, s2, cnt))
                mean, var = BatchNormLayer.stats_of(s1, s2, cnt)
                h = BatchNormLayer.apply_stats(params[i], h,
                                               mean.astype(h.dtype),
                                               var.astype(h.dtype))
        else:
            h = _layer_forward(impl, c, params[i], h, keys[i], training, i)
    out_conf = conf.conf(n - 1)
    h = apply_preprocessor(conf.preprocessor(n - 1), h)
    with profiling.layer_scope(n - 1, out_conf):
        rows = OutputLayer.rowwise_loss(OutputLayer.head_params(conf, params),
                                        out_conf, h, labels, keys[n - 1],
                                        training)
    if return_bn_stats:
        return rows, tuple(stats)
    return rows


def has_batchnorm(conf: MultiLayerConfiguration) -> bool:
    return any(LayerType(str(c.layer_type)) == LayerType.BATCH_NORM
               for c in conf.confs)


def _bn_ema_apply(c, p, mean, var):
    """One layer's EMA advance: ema = m*ema + (1-m)*batch, plus the total
    EMA weight used for bias correction at inference."""
    m = c.batch_norm_momentum
    p = dict(p)
    p["ema_mean"] = (m * p["ema_mean"].astype(jnp.float32)
                     + (1 - m) * mean).astype(p["ema_mean"].dtype)
    p["ema_var"] = (m * p["ema_var"].astype(jnp.float32)
                    + (1 - m) * var).astype(p["ema_var"].dtype)
    if "ema_w" in p:
        p["ema_w"] = (m * p["ema_w"].astype(jnp.float32)
                      + (1 - m)).astype(p["ema_w"].dtype)
    return p


def update_bn_ema_from_stats(conf: MultiLayerConfiguration, params, stats,
                             axis=None):
    """Advance every BATCH_NORM layer's running stats from the raw moments
    the loss forward already computed (`network_rowwise_loss(...,
    return_bn_stats=True)`) — no second forward pass.

    axis: shard_map collective axis — moments are psum'd across dp shards
    so every shard records GLOBAL-batch statistics.
    """
    from deeplearning4j_tpu.nn.layers.base import BatchNormLayer

    bn_idx = [i for i, c in enumerate(conf.confs)
              if LayerType(str(c.layer_type)) == LayerType.BATCH_NORM]
    new = list(params)
    for (s1, s2, cnt), i in zip(stats, bn_idx):
        if axis is not None:
            s1 = jax.lax.psum(s1, axis)
            s2 = jax.lax.psum(s2, axis)
            cnt = jax.lax.psum(cnt, axis)
        mean, var = BatchNormLayer.stats_of(s1, s2, cnt)
        new[i] = _bn_ema_apply(conf.conf(i), new[i], mean, var)
    return tuple(new)


def update_bn_ema(conf: MultiLayerConfiguration, params, x, axis=None,
                  row_weights=None):
    """Running-EMA update of every BATCH_NORM layer's inference stats from
    one training batch via a (partial) forward pass — for host-side training
    loops that can't thread the stats out of their loss forward (MLN.fit's
    solver scans).  Compiled train steps should prefer
    `update_bn_ema_from_stats` (zero extra forwards).

    axis:        shard_map collective axis name — batch stats are psum'd
                 across dp shards so every shard sees GLOBAL-batch stats.
    row_weights: optional per-feature-row weights (pad rows of a masked
                 remainder batch carry 0 — excluded from the stats AND from
                 the propagated activations' normalization).
    """
    if not has_batchnorm(conf):
        return params
    from deeplearning4j_tpu.nn.layers.base import BatchNormLayer

    last_bn = max(i for i, c in enumerate(conf.confs)
                  if LayerType(str(c.layer_type)) == LayerType.BATCH_NORM)
    new = list(params)
    h = x
    for i in range(last_bn + 1):
        c = conf.conf(i)
        h = apply_preprocessor(conf.preprocessor(i), h)
        is_bn = LayerType(str(c.layer_type)) == LayerType.BATCH_NORM
        if is_bn:
            s1, s2, cnt = BatchNormLayer.moments(h, row_weights)
            if axis is not None:
                s1 = jax.lax.psum(s1, axis)
                s2 = jax.lax.psum(s2, axis)
                cnt = jax.lax.psum(cnt, axis)
            mean, var = BatchNormLayer.stats_of(s1, s2, cnt)
            new[i] = _bn_ema_apply(c, new[i], mean, var)
        if i < last_bn:
            # propagate with batch stats (training=True) — downstream BN
            # layers must see the inputs training actually produces
            # (row-weighted so pad rows don't skew the propagation either)
            if is_bn:
                h = BatchNormLayer.forward(params[i], c, h, None,
                                           training=True,
                                           row_weights=row_weights)
            else:
                h = get_layer(c.layer_type).forward(params[i], c, h, None,
                                                    training=True)
    return tuple(new)


def make_finetune_loss(conf: MultiLayerConfiguration, collect_bn: bool = False):
    """Batched finetune loss `(params, x, y, w, key) -> (loss, stats)`.

    Loss = row-weighted mean of `network_rowwise_loss` over the real rows
    (w is the per-LABEL-row weight vector; pad rows carry 0) plus
    `network_regularization`.  This is the ONE loss definition shared by
    the compiled step-cache programs and the uncached comparison path, so
    cached and uncached training match bit-for-bit; a full batch is just
    w = ones.  stats is () unless collect_bn (then the raw BatchNorm
    moments of this forward, for `update_bn_ema_from_stats`)."""

    def loss_fn(params, x, y, w, key):
        # feature-row weights from label-row weights (label rows may be
        # B*T for sequence models)
        ratio = w.shape[0] // x.shape[0]
        wx = w.reshape(x.shape[0], ratio)[:, 0]
        out = network_rowwise_loss(conf, params, x, y, key, training=True,
                                   row_weights=wx,
                                   return_bn_stats=collect_bn)
        rows, stats = out if collect_bn else (out, ())
        # dot, not sum(rows * w): a gemm contraction over the batch dim is
        # bit-invariant to trailing zero-weight pad rows, while reduce_sum's
        # pairwise split is shape-dependent (see layers.base.rows_broadcast)
        loss = (jnp.dot(rows, w) / jnp.maximum(jnp.dot(w, jnp.ones_like(w)),
                                               1.0)
                + network_regularization(conf, params))
        return loss, stats

    return loss_fn


def network_regularization(conf: MultiLayerConfiguration, params):
    """The regularization half of `network_loss` (L2 across layers + the
    output layer's L2/L1), as one scalar counted once per step."""
    out_conf = conf.conf(conf.n_layers - 1)
    reg = jnp.asarray(0.0, jnp.float32)
    if not out_conf.use_regularization:
        return reg
    if out_conf.l2:
        for i in range(conf.n_layers):
            if "W" in params[i]:
                reg = reg + 0.5 * out_conf.l2 * jnp.sum(
                    params[i]["W"].astype(jnp.float32) ** 2)
    if out_conf.l1:
        reg = reg + out_conf.l1 * jnp.sum(
            jnp.abs(params[conf.n_layers - 1]["W"].astype(jnp.float32)))
    return reg


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration, seed: Optional[int] = None):
        self.conf = conf
        if seed is None:
            seed = conf.confs[0].seed if conf.confs else 123
        self._key = jax.random.PRNGKey(seed)
        self._params: Optional[tuple] = None
        self.listeners: List = []
        self._bn_ema_fn = None
        # compiled train-step cache: one AOT-compiled solver program per
        # (conf, algo, batch shape), reused across every fit batch.
        # use_step_cache=False restores the legacy retrace-per-batch path.
        self.step_cache = TrainStepCache()
        self.use_step_cache = True
        # serve-path sibling: one AOT-compiled program per (conf, entry
        # point, shape bucket) for output/score/feed_forward — repeated
        # serving calls at a seen shape never re-trace.
        # use_infer_cache=False restores the legacy retrace-per-call path.
        self.infer_cache = InferCache()
        self.use_infer_cache = True
        self._bn_in_step = False  # did the last finetune advance BN EMA?
        # SIGTERM/preemption flag: `fit(checkpoint_dir=...)` checks it
        # between batches and checkpoints-then-exits when set
        self._stop_training = threading.Event()
        # crash-resume bookkeeping, reported by the CLI train JSON
        self.resumed_from_batch: Optional[int] = None
        self.checkpoint_write_seconds = 0.0
        self.checkpoints_written = 0
        # persistent compile cache: DL4J_COMPILE_CACHE=<dir> attaches the
        # on-disk program store to every network in the process, so
        # restarts skip recompiles (the CLI's --compile-cache flag sets
        # the same thing explicitly)
        cache_dir = os.environ.get("DL4J_COMPILE_CACHE")
        if cache_dir:
            self.set_compile_cache(cache_dir)
        # serve-precision policy report (set_serve_precision): policy
        # name + calibration facts + measured accuracy delta — serving
        # has no labels, so the delta is measured here, once, and the
        # batcher/server/router surface it read-only
        self._serve_precision_report: dict = {"policy": "f32"}

    @property
    def params(self) -> Optional[tuple]:
        return self._params

    @params.setter
    def params(self, params: Optional[tuple]) -> None:
        # a replaced or dropped tree takes what serving derived from it along
        self._params = params
        self.infer_cache.forget_params()

    # -- lifecycle ---------------------------------------------------------
    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def init(self) -> "MultiLayerNetwork":
        self.params = init_params(self.conf, self._next_key())
        return self

    def set_listeners(self, listeners) -> None:
        self.listeners = list(listeners)

    # -- persistent compile cache -------------------------------------------
    def set_compile_cache(self, directory, max_bytes=None):
        """Attach a persistent on-disk program store at `directory` to
        both the train-step and serve-path caches (shared store, one key
        schema): memory misses check disk before compiling, and fresh
        compiles write back, so a restarted process skips every compile
        a previous run already paid for.  Returns the store."""
        from deeplearning4j_tpu.optimize.persist import PersistentProgramStore

        kw = {} if max_bytes is None else {"max_bytes": max_bytes}
        store = PersistentProgramStore(directory, **kw)
        self.step_cache.set_persist(store)
        self.infer_cache.set_persist(store)
        # tuned-table inheritance (ISSUE 18): a table `cli tune` persisted
        # for this (conf fingerprint, device kind) installs process-wide
        # here, so replicas and future sessions serve with the tuned
        # constants and fresh_tunes == 0.  Missing/corrupt/wrong-kind
        # tables degrade to registry defaults inside load_and_install.
        from deeplearning4j_tpu.optimize import tunables
        from deeplearning4j_tpu.optimize.step_cache import conf_fingerprint

        if tunables.active() is None:
            tunables.load_and_install(store, conf_fingerprint(self.conf))
        return store

    def set_serve_mesh(self, mesh=None, spec=None):
        """Shard the serve path across a mesh.  With no arguments this
        is the 1-D pattern: `Mesh(('batch',))` over every visible
        device, rows split, params replicated, collectives inserted by
        jit (GSPMD).  `spec` takes a `--mesh`-style string instead
        ("batch=2,model=4", parsed by `parallel.plan.parse_mesh_spec`;
        "all" or "" = the 1-D default): a `model` axis tensor-shards
        params, activations, and decode KV state per the ShardPlan, so
        one model can exceed one chip's HBM.  Sharding is a cache-KEY
        dimension — single-chip, 1-D, and 2-D programs coexist in
        memory and on disk, and 1-D keys are byte-identical to their
        pre-plan form.  Returns the mesh."""
        from deeplearning4j_tpu.parallel.mesh import serve_mesh
        from deeplearning4j_tpu.parallel.plan import (parse_mesh_spec,
                                                      plan_mesh)

        if mesh is not None and spec is not None:
            raise ValueError("pass mesh= or spec=, not both")
        if spec is not None:
            mesh = plan_mesh(parse_mesh_spec(spec))
        elif mesh is None:
            mesh = serve_mesh()
        self.infer_cache.set_mesh(mesh)
        return mesh

    @property
    def serve_precision(self) -> str:
        """Active serve-path precision policy ("f32" until changed)."""
        return self.infer_cache.policy

    @property
    def serve_precision_report(self) -> dict:
        """The report `set_serve_precision` produced for the active
        policy (calibration facts + measured accuracy delta)."""
        return self._serve_precision_report

    def set_serve_precision(self, policy: str = "f32", calibration=None,
                            measure: bool = True) -> dict:
        """Serve every subsequent `output`/`feed_forward`/`score` call —
        and every program `warmup()` compiles from here on — under a
        precision policy (optimize/quantize.py): "f32" (default,
        bitwise-unchanged), "bf16" (params cast on load, bf16 compute),
        or "int8" (per-channel symmetric weight quantization, scales
        calibrated on `calibration` — a held-out batch; None builds a
        deterministic synthetic one shaped for the conf).

        The policy is a cache-KEY dimension like the serve mesh, so
        per-policy programs coexist in memory and in the disk store.
        With a persistent store attached, the int8 quantized weights are
        themselves persisted (checksummed, LRU'd) keyed by (conf
        fingerprint, params digest) — a restarted process reloads the
        exact same scales instead of recalibrating.  int8 quantizes a
        SNAPSHOT of the current params; after further training, call
        this again to requantize.

        Returns (and retains, see `serve_precision_report`) a report
        with the measured accuracy delta vs f32 on a held-out batch
        (`measure=False` skips the measurement forwards)."""
        from deeplearning4j_tpu.optimize import quantize

        quantize.validate_policy(policy)
        if self.params is None:
            self.init()
        qparams = cal_report = None
        if policy == "int8":
            if calibration is None:
                calibration = quantize.default_calibration(self.conf)
            calibration = jnp.asarray(calibration)
            store = self.infer_cache.persist
            art_key = quantize.quantize_artifact_key(
                self.infer_cache._fingerprint(self.conf),
                quantize.params_digest(self.params))
            blob = store.load_bytes(art_key) if store is not None else None
            if blob is not None:
                try:
                    qparams, cal_report = quantize.unpack_quantized(blob)
                except Exception:  # noqa: BLE001 — recalibrate instead
                    qparams = None
            if qparams is None:
                qparams, cal_report = quantize.calibrate_int8(
                    self.conf, self.params, calibration)
                if store is not None:
                    store.store_bytes(
                        art_key, quantize.pack_quantized(qparams, cal_report))
        self.infer_cache.set_policy(policy, qparams=qparams)
        report = {"policy": policy}
        if cal_report:
            report["calibration"] = cal_report
        if measure and policy != "f32":
            # held out from the calibration batch when that defaulted
            batch = (calibration if calibration is not None
                     else quantize.default_calibration(self.conf, seed=1))
            report["accuracy_delta"] = quantize.accuracy_delta(
                self.conf, self.params, jnp.asarray(batch), policy,
                qparams=qparams)
        self._serve_precision_report = report
        return report

    def warmup(self, shapes, entries=("output",), train=False):
        """Precompile the serve/train programs for the given batch shapes
        ahead of traffic, so the first real request is a cache hit.

        `shapes`: iterable of batch sizes (int → (b, n_in)), full input
        shapes (tuple), or example arrays.  `entries` picks the serve
        entry points ("output", "feed_forward", "loss"); `train=True`
        additionally compiles the train step for each shape.  With a
        persistent store attached (`set_compile_cache`), warmup populates
        the disk cache for every future process too.  Returns a summary
        dict with the per-cache stats."""
        if self.params is None:
            self.init()
        compiled = []
        for spec in shapes:
            if isinstance(spec, int):
                x = jnp.zeros((spec, self.conf.confs[0].n_in), jnp.float32)
            elif isinstance(spec, tuple):
                x = jnp.zeros(spec, jnp.float32)
            else:
                x = jnp.asarray(spec)
            y = None
            if train or "loss" in entries:
                out = jax.eval_shape(
                    lambda p, xx: network_output(self.conf, p, xx, key=None,
                                                 training=False),
                    self.params, x)
                y = jnp.zeros(out.shape, out.dtype)
            for entry in entries:
                if entry == "output":
                    self.infer_cache.output(self.conf, self.params, x,
                                            compile_only=True)
                elif entry == "feed_forward":
                    self.infer_cache.feed_forward(self.conf, self.params, x,
                                                  compile_only=True)
                elif entry == "loss":
                    self.infer_cache.loss(self.conf, self.params, x, y,
                                          compile_only=True)
                else:
                    raise ValueError(f"unknown warmup entry {entry!r}")
            if train:
                self.step_cache.finetune(self.conf, self.params, x, y,
                                         self._key, compile_only=True)
            compiled.append(tuple(x.shape))
        return {
            "shapes": compiled,
            "entries": list(entries),
            "train": bool(train),
            "step_cache": self.step_cache.stats.as_dict(),
            "infer_cache": self.infer_cache.stats.as_dict(),
        }

    def warmup_generate(self, slots: Optional[int] = None, max_seq: int = 64,
                        prompt_buckets: Sequence[int] = (8,),
                        page_size: Optional[int] = None, n_pages: int = 0,
                        prefix_cache: bool = False, draft_net=None,
                        spec_k: int = 0):
        """Precompile the autoregressive generation programs (ISSUE 14)
        ahead of traffic: ONE decode step over the `slots`-wide table
        plus one admission program per prompt bucket, `prefill_slot`:
        compiled against the same slots-wide table, it prefills one row,
        samples the first token and writes the row into its slot (the
        B=1 `prefill` is the single-stream callers' and is not warmed
        here).  The optional decode accelerators (ISSUE 16) each swap or
        add programs, and the warmup mirrors the serving batcher exactly
        so `fresh_compiles == 0` holds for ANY flag combination:
        `page_size > 0` warms the paged decode step over the shared
        page pool instead of the dense one, and the B=1 `prefill` whose
        row the batcher copies into pages; `prefix_cache` warms the
        logp-returning `prefill_logp_slot` the prefix cache records
        instead of the sampling one, and `write_row` for its hits;
        `draft_net` + `spec_k` warm the batched verify step plus the
        draft model's own decode and `prefill_slot` programs.
        With a persistent store attached the programs land on disk like
        every other warmup — a restarted serve process starts
        generating with `fresh_compiles == 0`.  Returns a summary with
        the cache stats."""
        if self.params is None:
            self.init()
        # None -> tunable-governed geometry, resolved exactly like
        # ContinuousBatcher's own defaults so warmup and serving compile
        # the same programs under a tuned table
        from deeplearning4j_tpu.optimize import tunables

        slots = int(tunables.resolve("decode.slots")
                    if slots is None else slots)
        page_size = (tunables.resolve("decode.page_size")
                     if page_size is None else page_size)
        ic = self.infer_cache
        tok = jnp.zeros((slots,), jnp.int32)
        pos = jnp.zeros((slots,), jnp.int32)
        keys = jnp.zeros((slots, 2), jnp.uint32)
        temps = jnp.zeros((slots,), jnp.float32)
        page_size = int(page_size)
        page_table = None
        if page_size > 0:
            # identical pool geometry to ContinuousBatcher: physical
            # page 0 is the scratch page, so the pool holds n_pages + 1
            pages_per_slot = -(-int(max_seq) // page_size)
            pool_pages = int(n_pages) or int(slots) * pages_per_slot
            state = ic.init_paged_decode_state(
                self.conf, slots, pool_pages + 1, page_size)
            page_table = jnp.zeros((slots, pages_per_slot), jnp.int32)
        else:
            state = ic.init_decode_state(self.conf, slots, max_seq)
        ic.decode(self.conf, self.params, state, tok, pos, keys, temps,
                  page_table=page_table, compile_only=True)
        if draft_net is not None:
            if int(spec_k) < 2:
                raise ValueError("draft_net requires spec_k >= 2")
            toks = jnp.zeros((slots, int(spec_k)), jnp.int32)
            ic.verify(self.conf, self.params, state, toks, pos, keys, temps,
                      page_table=page_table, compile_only=True)
            dic = draft_net.infer_cache
            dstate = dic.init_decode_state(draft_net.conf, slots, max_seq)
            dic.decode(draft_net.conf, draft_net.params, dstate, tok,
                       pos, keys, temps, compile_only=True)
        # admissions: a dense table takes each stream in through ONE
        # program a bucket, compiled against the slots-wide table (the
        # prefill, the first token and the row's write); a paged pool
        # keeps the B=1 prefill and writes the row's pages apart
        paged = page_size > 0
        row = (ic.init_decode_state(self.conf, 1, max_seq)
               if paged or prefix_cache else None)
        if prefix_cache and not paged:
            ic.write_row(self.conf, state, row, 0, compile_only=True)
        buckets = sorted(int(b) for b in prompt_buckets)
        for tb in buckets:
            if tb > max_seq:
                raise ValueError(f"prompt bucket {tb} exceeds "
                                 f"max_seq={max_seq}")
            prompt = jnp.zeros((1, tb), jnp.int32)
            length = jnp.ones((1,), jnp.int32)
            if paged and prefix_cache:
                ic.prefill_logp(self.conf, self.params, row, prompt,
                                length, compile_only=True)
            elif paged:
                ic.prefill(self.conf, self.params, row, prompt, length,
                           keys[:1], temps[:1], compile_only=True)
            elif prefix_cache:
                ic.prefill_logp_slot(self.conf, self.params, state, 0,
                                     prompt, length, compile_only=True)
            else:
                ic.prefill_slot(self.conf, self.params, state, 0, prompt,
                                length, keys[:1], temps[:1],
                                compile_only=True)
            if draft_net is not None:
                draft_net.infer_cache.prefill_slot(
                    draft_net.conf, draft_net.params, dstate, 0, prompt,
                    length, keys[:1], temps[:1], compile_only=True)
        return {
            "slots": int(slots),
            "max_seq": int(max_seq),
            "prompt_buckets": buckets,
            "page_size": page_size,
            "prefix_cache": bool(prefix_cache),
            "spec_k": int(spec_k) if draft_net is not None else 0,
            "infer_cache": ic.stats.as_dict(),
        }

    # -- serving ------------------------------------------------------------
    def serve(self, host: str = "127.0.0.1", port: int = 0,
              max_delay_ms: Optional[float] = None, max_pending: int = 1024,
              max_batch_rows=None, batching: bool = True,
              request_timeout_s: float = 30.0,
              drain_timeout_s: float = 10.0,
              default_deadline_ms=None, breaker=None,
              generate: bool = False, gen_slots: Optional[int] = None,
              gen_max_seq: int = 64, gen_prompt_buckets=(8,),
              gen_max_pending: int = 64, gen_page_size: Optional[int] = None,
              gen_pages: int = 0, gen_prefix_cache: bool = False,
              gen_prefix_match: str = "exact", gen_draft=None,
              gen_spec_k: int = 0):
        """Start the micro-batching HTTP gateway over this network
        (`serving.ModelServer`): POST /v1/predict coalesces concurrent
        requests into one bucketed infer-cache call per flush, GET
        /v1/stats reports queue depth / batch histogram / latency
        percentiles / fresh-compile count / breaker state, GET
        /healthz + /readyz report liveness/readiness.  Call `warmup()`
        (or attach a warmed `set_compile_cache` dir) first so the first
        request is served without a fresh compile.  `generate=True`
        additionally runs the continuous-batching decode loop behind
        POST /v1/generate (call `warmup_generate()` with matching
        gen_* arguments first for the same zero-compile start).
        Returns the started server; `server.stop()` drains gracefully
        and shuts it down."""
        from deeplearning4j_tpu.serving.server import ModelServer

        if self.params is None:
            self.init()
        return ModelServer(self, host=host, port=port,
                           max_delay_ms=max_delay_ms,
                           max_pending=max_pending,
                           max_batch_rows=max_batch_rows,
                           batching=batching,
                           request_timeout_s=request_timeout_s,
                           drain_timeout_s=drain_timeout_s,
                           default_deadline_ms=default_deadline_ms,
                           breaker=breaker, generate=generate,
                           gen_slots=gen_slots, gen_max_seq=gen_max_seq,
                           gen_prompt_buckets=gen_prompt_buckets,
                           gen_max_pending=gen_max_pending,
                           gen_page_size=gen_page_size,
                           gen_pages=gen_pages,
                           gen_prefix_cache=gen_prefix_cache,
                           gen_prefix_match=gen_prefix_match,
                           gen_draft=gen_draft,
                           gen_spec_k=gen_spec_k).start()

    # -- inference ---------------------------------------------------------
    def _serve_cached(self, x) -> bool:
        """Serve-path cache eligibility: batched input (axis 0 = rows is
        what bucketing pads) and the cache switched on."""
        return self.use_infer_cache and getattr(x, "ndim", 0) >= 2

    def feed_forward(self, x):
        x = jnp.asarray(x)
        if self._serve_cached(x):
            return self.infer_cache.feed_forward(self.conf, self.params, x)
        return feed_forward(self.conf, self.params, x)

    def output(self, x):
        x = jnp.asarray(x)
        if self._serve_cached(x):
            return self.infer_cache.output(self.conf, self.params, x)
        return network_output(self.conf, self.params, x)

    def predict(self, x):
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    def score(self, x, labels) -> float:
        x, labels = jnp.asarray(x), jnp.asarray(labels)
        if self._serve_cached(x):
            return float(self.infer_cache.loss(self.conf, self.params, x,
                                               labels))
        return float(network_loss(self.conf, self.params, x, labels,
                                  key=None, training=False))

    def f1_score(self, x, labels) -> float:
        """Classification F1 on (x, labels) — the reference's
        `OutputLayer.score(examples, labels)` (OutputLayer.java:183-188),
        surfaced at network level: higher is better, 0..1."""
        from deeplearning4j_tpu.evaluation import Evaluation

        ev = Evaluation()
        ev.eval(jnp.asarray(labels), self.output(x))
        return float(ev.f1())

    def evaluate(self, data, labels=None, batch_size: int = 0,
                 prefetch: bool = True):
        """Bucketed, prefetched evaluation — see `evaluation.evaluate`."""
        from deeplearning4j_tpu.evaluation import evaluate

        if labels is not None:
            from deeplearning4j_tpu.datasets.dataset import DataSet

            data = DataSet(np.asarray(data), np.asarray(labels))
        return evaluate(self, data, batch_size=batch_size, prefetch=prefetch)

    # -- training ----------------------------------------------------------
    def _finetune_objective(self, x, labels):
        conf = self.conf

        def loss(params, key):
            return network_loss(conf, params, x, labels, key, training=True)

        objective = solver_mod.from_loss(loss)
        out_conf = conf.conf(conf.n_layers - 1)
        if OptimizationAlgorithm(str(out_conf.optimization_algo)) == \
                OptimizationAlgorithm.HESSIAN_FREE:
            # factor as predict+loss so HF gets Gauss-Newton products
            # (reference: computeDeltasR/feedForwardR R-op machinery,
            # MultiLayerNetwork.java:554-627,1407-1479)
            from deeplearning4j_tpu.nd.losses import get_loss
            loss_fn = get_loss(out_conf.loss_function)

            def predict(params, key):
                return network_output(conf, params, x)

            objective = objective._replace(
                gnvp=solver_mod.from_predict_loss(
                    predict, lambda z: loss_fn(labels, z)).gnvp)
        return objective

    def pretrain_layer(self, i: int, x) -> None:
        """Optimize layer i's unsupervised objective on its own inputs."""
        c = self.conf.conf(i)
        impl = get_layer(c.layer_type)
        x = jnp.asarray(x)

        def gs(p, key):
            return impl.pretrain_grad_and_score(p, c, x, key)

        def sc(p, key):
            return impl.pretrain_score(p, c, x, key)

        if self.use_step_cache:
            new_p, scores = self.step_cache.pretrain(
                c, i, impl, self.params[i], x, self._next_key())
        else:
            objective = solver_mod.Objective(grad_and_score=gs, score=sc)
            new_p, scores = solver_mod.optimize(objective, self.params[i],
                                                c, self._next_key())
        params = list(self.params)
        params[i] = new_p
        self.params = tuple(params)
        dispatch_listeners(self.listeners, self, scores)

    def pretrain(self, data) -> None:
        """Layer-wise pretraining (MultiLayerNetwork.pretrain :149-190)."""
        for batch in _as_batches(data):
            x = jnp.asarray(batch[0] if isinstance(batch, tuple) else batch)
            for i in range(self.conf.n_layers - 1):
                c = self.conf.conf(i)
                if LayerType(str(c.layer_type)) not in _PRETRAINABLE:
                    continue
                acts = feed_forward(self.conf, self.params, x, up_to=i)
                layer_in = acts[-1] if acts else x
                layer_in = apply_preprocessor(self.conf.preprocessor(i), layer_in)
                self.pretrain_layer(i, layer_in)

    def finetune(self, x, labels) -> None:
        """Supervised end-to-end optimization (finetune/backprop parity).

        Default path: the compiled step cache — batch data enters the
        solver program as jit arguments, so a (conf, batch-shape) pair
        compiles once and every further batch is a cache hit.  BatchNorm
        EMA advances inside the compiled step.  Hessian-free rides the
        same cache: its Gauss-Newton product threads the pad-row weight
        mask through the loss-of-outputs half
        (`solver.weighted_predict_loss`), so HF programs share the
        bucketed padding too."""
        x, labels = jnp.asarray(x), jnp.asarray(labels)
        out_conf = self.conf.conf(self.conf.n_layers - 1)
        if self.use_step_cache:
            self.params, scores = self.step_cache.finetune(
                self.conf, self.params, x, labels, self._next_key())
            self._bn_in_step = has_batchnorm(self.conf)
        else:
            objective = self._finetune_objective(x, labels)
            self.params, scores = solver_mod.optimize(
                objective, self.params, out_conf, self._next_key())
            self._bn_in_step = False
        dispatch_listeners(self.listeners, self, scores)

    def _fit_batch(self, x, y) -> None:
        """One fit step: pretrain/finetune/BN-EMA for a single batch."""
        self._bn_in_step = False
        if self.conf.pretrain:
            self.pretrain(jnp.asarray(x))
        if self.conf.backprop:
            self.finetune(x, y)
        if has_batchnorm(self.conf) and not self._bn_in_step:
            # legacy host path (cache disabled / backprop off): true
            # running EMA across every fit batch via an extra partial
            # forward.  The cached finetune already folded this into
            # the compiled step from the solver's own forward.
            if self._bn_ema_fn is None:
                self._bn_ema_fn = jax.jit(partial(update_bn_ema, self.conf))
            self.params = self._bn_ema_fn(self.params, jnp.asarray(x))

    def fit(self, data, labels=None, *, checkpoint_dir: Optional[str] = None,
            checkpoint_every_n_batches: int = 0,
            auto_resume: bool = True) -> None:
        """fit(DataSet/ndarray pair/iterator) — MultiLayerNetwork.fit parity.

        With `checkpoint_dir` the run is crash-safe (ISSUE 5): params +
        RNG key + batch cursor are checkpointed atomically every
        `checkpoint_every_n_batches` batches (and at the end), a SIGTERM
        checkpoints-then-raises `TrainingInterrupted`, and a rerun with
        the same `checkpoint_dir` and the same batch stream auto-resumes
        at the saved cursor — reaching bit-identical params to an
        uninterrupted run at the same total batch count.  (The compiled
        solver re-initializes its updater inside every per-batch
        program, so cross-batch training state is exactly params + RNG
        key; nothing else needs saving.)"""
        if self.params is None:
            self.init()
        if labels is not None:
            batches = [(data, labels)]
        else:
            batches = _as_batches(data)
        if checkpoint_dir is None:
            for batch in batches:
                x, y = batch if isinstance(batch, tuple) else (
                    batch.features, batch.labels)
                self._fit_batch(x, y)
            return
        self._fit_checkpointed(batches, checkpoint_dir,
                               int(checkpoint_every_n_batches), auto_resume)

    def request_stop_training(self) -> None:
        """Ask a running `fit(checkpoint_dir=...)` to checkpoint and
        raise `TrainingInterrupted` after the current batch (what the
        installed SIGTERM handler calls)."""
        self._stop_training.set()

    def _save_checkpoint(self, directory: str, batches_done: int) -> None:
        import time as _time

        from deeplearning4j_tpu.parallel import checkpoint as ckpt

        t0 = _time.perf_counter()
        ckpt.save(directory, self.params, conf=self.conf,
                  step=batches_done,
                  data_cursor={"batches_done": int(batches_done)},
                  metadata={"rng_key": np.asarray(
                      jax.device_get(self._key)).tolist()})
        self.checkpoint_write_seconds += _time.perf_counter() - t0
        self.checkpoints_written += 1

    def _fit_checkpointed(self, batches, checkpoint_dir: str,
                          every_n: int, auto_resume: bool) -> None:
        from deeplearning4j_tpu.parallel import checkpoint as ckpt

        start_batch = 0
        if auto_resume:
            restored = ckpt.load_resilient(checkpoint_dir,
                                           like_params=self.params)
            if restored is not None:
                params, _, meta = restored
                self.params = params
                start_batch = int(
                    (meta.get("data_cursor") or {}).get("batches_done", 0))
                rng = (meta.get("metadata") or {}).get("rng_key")
                if rng is not None:
                    self._key = jnp.asarray(np.asarray(rng, dtype=np.uint32))
                self.resumed_from_batch = start_batch
                log.info("fit: auto-resumed %s at batch %d",
                         checkpoint_dir, start_batch)
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
        n_done = 0
        try:
            for batch in batches:
                n_done += 1
                if n_done <= start_batch:
                    continue  # replaying the resumed prefix of the stream
                x, y = batch if isinstance(batch, tuple) else (
                    batch.features, batch.labels)
                self._fit_batch(x, y)
                if self._stop_training.is_set():
                    self._save_checkpoint(checkpoint_dir, n_done)
                    raise TrainingInterrupted(
                        f"stop requested: checkpointed {checkpoint_dir} "
                        f"at batch {n_done}")
                if every_n > 0 and n_done % every_n == 0:
                    self._save_checkpoint(checkpoint_dir, n_done)
            self._save_checkpoint(checkpoint_dir, n_done)
        finally:
            if installed:
                signal.signal(signal.SIGTERM, prev_handler)

    # -- parameter vector (distributed/averaging contract) -----------------
    def params_flat(self) -> jnp.ndarray:
        """Flat parameter vector (parity: `MultiLayerNetwork.params()`)."""
        flat, _ = ravel_pytree(self.params)
        return flat

    def set_params_flat(self, flat) -> None:
        _, unravel = ravel_pytree(self.params)
        self.params = unravel(jnp.asarray(flat))

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(self.conf)
        net.params = self.params
        return net


def _as_batches(data):
    """Normalize fit() inputs: iterator of DataSets, single DataSet, array."""
    if hasattr(data, "features") and hasattr(data, "labels"):
        return [(data.features, data.labels)]
    if hasattr(data, "__next__") or hasattr(data, "reset"):
        return ((d.features, d.labels) for d in data)
    if isinstance(data, (list,)):
        return [(d.features, d.labels) if hasattr(d, "features") else d
                for d in data]
    return [data]
