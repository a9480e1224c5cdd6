"""Compiled train-step cache — compile once, execute many.

The core lesson of both the TPU paper (Jouppi et al.) and TensorFlow's
dataflow design (Abadi et al.) is that each (config, batch-shape) pair
should lower to ONE XLA program reused for the whole run.  Before this
module the single-chip path violated that: `MultiLayerNetwork.finetune`
closed a fresh loss over each batch's arrays and handed it to
`solver_mod.optimize`, so the entire solver `lax.scan` was re-traced and
re-compiled per batch with the batch data baked in as constants.

Design:

  key schema    (kind, conf-fingerprint, algorithm, arg shapes/dtypes,
                 pretrain-layer index) -> AOT-compiled XLA executable.
                 The fingerprint is a sha1 of the frozen config's
                 canonical JSON, so config edits can never alias a stale
                 program.
  batch args    batch data (x, labels, row weights) are explicit jit
                 ARGUMENTS of the compiled program (see
                 `solver.BatchedObjective`), never closure constants.
  donation      params are donated to the step (`donate_argnums=(0,)`) on
                 accelerator backends, so the single-chip path stops
                 double-buffering parameters in HBM.  Donation is skipped
                 on CPU, where XLA would only warn.  Caveat: a donated
                 params buffer is dead after the call — `clone()`d
                 networks sharing params with a training net must copy
                 first on TPU (`parallel.data_parallel.init_train_state`
                 already does).
  bucketing     remainder batches are zero-padded up to the smallest
                 already-known bucket that fits (buckets grow on demand
                 from the full-batch sizes actually seen), and pad rows
                 carry row-weight 0 through the existing
                 `network_rowwise_loss(..., row_weights=...)` machinery —
                 masked out of the loss, the gradients AND the BatchNorm
                 batch statistics.  A full epoch therefore compiles at
                 most n_buckets programs instead of one per tail shape.
  observability `cache.stats` tracks hits, misses, steps executed and
                 per-key compile seconds; every miss is logged so
                 retraces are observable instead of silent.

Hessian-free finetune joins the cache too: its Gauss-Newton product is
built from `solver.weighted_predict_loss`, which threads the pad-row
weight mask through the loss-of-outputs half of the product — pad rows
carry exact-zero curvature cotangents, so HF programs share the bucketed
padding (and its bit-exactness guarantee) with every other algorithm.

The serve-path sibling of this module is `optimize/infer_cache.py`
(`InferCache`): it reuses the `CompiledProgramCache` machinery below for
the inference entry points (output / loss / feed_forward).
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nd.platform import default_backend
from deeplearning4j_tpu.optimize import solver as solver_mod
from deeplearning4j_tpu.reliability import faults
from deeplearning4j_tpu.utils import profiling

log = logging.getLogger("deeplearning4j_tpu")


def conf_fingerprint(conf) -> str:
    """Stable fingerprint of a frozen config: sha1 of its canonical JSON
    (sorted keys), truncated — collision-safe far beyond any realistic
    number of configs per process."""
    return hashlib.sha1(conf.to_json().encode("utf-8")).hexdigest()[:16]


def arg_signature(*arrays) -> Tuple:
    """(shape, dtype) tuple per array — the shape part of the cache key."""
    return tuple(
        None if a is None else (tuple(a.shape), str(jnp.asarray(a).dtype))
        for a in arrays)


class StepCacheStats:
    """Counters exposed on the cache object (ISSUE: observability).

    The memory-vs-disk-vs-compile split: `hits` are in-memory program
    reuses, `disk_hits` are programs restored from the persistent store
    (trace/lower skipped, deserialize+compile paid — see
    `deserialize_seconds`), `misses` are fresh trace+compiles
    (`compile_seconds`); `disk_write_seconds` is the write-back cost of
    persisting fresh compiles."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.steps = 0                      # compiled-step executions
        self.compile_seconds: Dict[Tuple, float] = {}  # key -> seconds
        self.disk_hits = 0
        self.disk_write_seconds = 0.0
        self.deserialize_seconds = 0.0
        self.io_errors = 0  # disk faults downgraded to misses (persist)
        self.fetch_hits = 0     # entries warmed over the wire (persist)
        self.fetch_corrupt = 0  # fetched bytes failing re-validation

    @property
    def total_compile_seconds(self) -> float:
        return float(sum(self.compile_seconds.values()))

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "steps": self.steps, "entries": len(self.compile_seconds),
                "compile_seconds": round(self.total_compile_seconds, 3),
                "disk_hits": self.disk_hits,
                "disk_write_seconds": round(self.disk_write_seconds, 3),
                "deserialize_seconds": round(self.deserialize_seconds, 3),
                "io_errors": self.io_errors,
                "fetch_hits": self.fetch_hits,
                "fetch_corrupt": self.fetch_corrupt}

    def __repr__(self):
        return f"StepCacheStats({self.as_dict()})"


class CompiledProgramCache:
    """Shared compile-once machinery: keyed AOT programs, grow-on-demand
    shape buckets, and hit/miss/compile-seconds stats.

    `TrainStepCache` (below) and the serve-path `InferCache`
    (`optimize/infer_cache.py`) are both thin entry-point layers over
    this class — same key schema, same bucket policy, same
    observability, different programs.

    donate: None = donate params on accelerator backends only (CPU XLA
    ignores donation with a warning); True/False force it.
    buckets: optional fixed iterable of allowed batch-row buckets; by
    default buckets grow on demand from the batch sizes seen (full
    batches come first in practice, tails then pad up into them).
    persist: optional `optimize.persist.PersistentProgramStore` — memory
    misses check the on-disk store before compiling (disk hit: the
    trace/lower cost is skipped, `stats.disk_hits`/`deserialize_seconds`
    grow), and fresh compiles write back (`stats.disk_write_seconds`).
    """

    #: label used in miss logs so train/infer retraces are distinguishable
    kind = "program-cache"

    def __init__(self, donate: Optional[bool] = None,
                 buckets: Optional[Tuple[int, ...]] = None,
                 persist=None):
        self._programs: Dict[Tuple, Callable] = {}
        self._fingerprints: Dict[int, str] = {}  # id(conf) memo
        self._buckets: List[int] = sorted(buckets) if buckets else []
        self._fixed_buckets = buckets is not None
        self._donate = donate
        self._persist = persist
        # per-key audit records (builder, abstract args, donation) so the
        # program auditor (analysis/program_audit.py) can re-trace and
        # inspect every program this cache ever compiled
        self._audit_records: Dict[Tuple, dict] = {}
        self.stats = StepCacheStats()
        # the serving gateway (and its batching-off control arm) reaches
        # this cache from many threads at once: lookup, bucket growth and
        # stats mutate under one lock (program EXECUTION does not — jax
        # dispatch is thread-safe and must overlap)
        self._lock = threading.RLock()

    # -- persistence --------------------------------------------------------
    @property
    def persist(self):
        return self._persist

    def set_persist(self, store) -> None:
        """Attach (or detach with None) a `PersistentProgramStore` —
        already-compiled in-memory programs stay valid either way."""
        with self._lock:
            self._persist = store

    # -- bucket policy ------------------------------------------------------
    def bucket_rows(self, n: int) -> int:
        """Smallest known bucket >= n; otherwise n becomes a new bucket
        (fixed bucket sets never grow — an oversize batch runs unpadded
        as its own bucket, logged).  A tuned `infer.bucket_ladder`
        (optimize/tunables.py) pre-seeds the grow-on-demand list; the
        registry default is the empty ladder, which leaves this loop
        byte-identical to the pre-registry behavior."""
        from deeplearning4j_tpu.optimize import tunables

        with self._lock:
            if not self._fixed_buckets:
                for b in tunables.resolve("infer.bucket_ladder"):
                    if int(b) not in self._buckets:
                        self._buckets.append(int(b))
                        self._buckets.sort()
            for b in self._buckets:
                if b >= n:
                    return b
            if self._fixed_buckets and self._buckets:
                log.info("%s: batch of %d rows exceeds the fixed "
                         "buckets %s; running unpadded", self.kind, n,
                         self._buckets)
            else:
                self._buckets.append(n)
                self._buckets.sort()
            return n

    @property
    def buckets(self) -> Tuple[int, ...]:
        return tuple(self._buckets)

    # -- program lookup -----------------------------------------------------
    def _fingerprint(self, conf) -> str:
        with self._lock:
            fp = self._fingerprints.get(id(conf))
            if fp is None:
                fp = conf_fingerprint(conf)
                self._fingerprints[id(conf)] = fp
            return fp

    def _donate_argnums(self) -> Tuple[int, ...]:
        donate = self._donate
        if donate is None:
            donate = default_backend() != "cpu"
        return (0,) if donate else ()

    def audit_records(self) -> List[dict]:
        """Snapshot of the per-program audit records (one per compiled
        or disk-restored key): {key, kind, build, abstract,
        donate_argnums, mesh, shardings}.
        `analysis.program_audit.audit_cache` re-traces each builder
        against its abstract args to inspect the jaxpr without
        executing anything; the `shardings` entry (per-arg Sharding or
        per-leaf pytree, None single-chip) feeds the
        replicated-large-leaf rule."""
        with self._lock:
            return list(self._audit_records.values())

    def program_memory(self) -> List[dict]:
        """Per-program per-device argument-memory estimate, one row per
        audit record: `per_device_argument_bytes` sums each abstract
        leaf's shard size under its recorded sharding (the bytes ONE
        chip holds), `replicated_argument_bytes` the unsharded total —
        the pair that proves a tensor-parallel plan fits where a
        replicated one cannot.  When the backend exposes it, the
        compiled executable's `memory_analysis()` is attached verbatim
        under `memory_analysis` (argument/output/temp/generated-code
        sizes); backends without it (CPU) leave it None, which is why
        the estimate is computed from the avals and always present."""
        import numpy as np

        with self._lock:
            recs = list(self._audit_records.values())
            programs = dict(self._programs)
        rows = []
        for rec in recs:
            per_dev = total = 0
            for leaf in jax.tree_util.tree_leaves(rec["abstract"]):
                shape = tuple(getattr(leaf, "shape", ()) or ())
                nbytes = int(np.prod(shape, dtype=np.int64)
                             * np.dtype(leaf.dtype).itemsize)
                total += nbytes
                s = getattr(leaf, "sharding", None)
                if s is not None:
                    shard = tuple(s.shard_shape(shape))
                    per_dev += int(np.prod(shard, dtype=np.int64)
                                   * np.dtype(leaf.dtype).itemsize)
                else:
                    per_dev += nbytes
            analysis = None
            fn = programs.get(rec["key"])
            try:
                mem = fn.memory_analysis() if fn is not None else None
                if mem is not None:
                    analysis = {
                        k: int(getattr(mem, k))
                        for k in ("argument_size_in_bytes",
                                  "output_size_in_bytes",
                                  "temp_size_in_bytes",
                                  "generated_code_size_in_bytes")
                        if hasattr(mem, k)}
            except Exception:  # noqa: BLE001 — backend without analysis
                analysis = None
            rows.append({"key": rec["key"],
                         "entry": rec["key"][0] if rec["key"] else None,
                         "per_device_argument_bytes": int(per_dev),
                         "replicated_argument_bytes": int(total),
                         "memory_analysis": analysis})
        return rows

    def _get(self, key: Tuple, build: Callable[[], Callable], args: Tuple,
             shardings: Optional[Tuple] = None,
             donate: Optional[Tuple[int, ...]] = None):
        """Return the compiled executable for `key`: memory hit, else
        disk hit (persistent store attached), else a timed fresh
        trace+compile with disk write-back.  Serialized under the cache
        lock: two threads racing a miss would otherwise compile (and
        persist) the same program twice.

        shardings: optional per-arg shardings (None = default
        single-device placement).  Each entry is either ONE
        `jax.sharding.Sharding` applied to every leaf of the matching
        arg subtree (replicated params, row-sharded batch — the 1-D
        serve pattern), or a PYTREE of shardings matching the arg
        leaf-for-leaf (tensor-parallel plans place each param / KV
        leaf differently).  Either way the program compiles with
        jit-inserted collectives — the caller must fold the sharding
        into `key`.

        donate: optional per-program donate_argnums override (None =
        the cache-wide `_donate_argnums()` policy).  Lets an entry with
        a different aliasing contract — e.g. the KV-cache decode step,
        which donates its state buffers but never its params — coexist
        with the cache's default entries."""
        with self._lock:
            return self._get_locked(key, build, args, shardings, donate)

    def _get_locked(self, key: Tuple, build: Callable[[], Callable],
                    args: Tuple, shardings: Optional[Tuple] = None,
                    donate: Optional[Tuple[int, ...]] = None):
        fn = self._programs.get(key)
        if fn is not None:
            self.stats.hits += 1
            return fn
        if shardings is None:
            abstract = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                               jnp.asarray(a).dtype), args)
        else:
            def _abs(a, _s):
                return jax.ShapeDtypeStruct(jnp.shape(a),
                                            jnp.asarray(a).dtype,
                                            sharding=_s)

            abstract = tuple(
                jax.tree_util.tree_map(lambda a, _s=s: _abs(a, _s), arg)
                if isinstance(s, jax.sharding.Sharding)
                else jax.tree_util.tree_map(_abs, arg, s)
                for arg, s in zip(args, shardings))
        donate = self._donate_argnums() if donate is None else tuple(donate)
        self._audit_records[key] = {
            "key": key, "kind": self.kind, "build": build,
            "abstract": abstract, "donate_argnums": donate,
            "mesh": shardings is not None, "shardings": shardings}
        if self._persist is not None:
            fn = self._load_from_disk(key, abstract, donate)
            self._sync_persist_counters()
            if fn is not None:
                return fn
        # armed 'compile' faults fire here: the one place every fresh
        # trace+compile (train or infer) funnels through
        faults.fire("compile", kind=self.kind, key=repr(key))
        self.stats.misses += 1
        t0 = time.perf_counter()
        exported = None
        if self._persist is not None:
            # fresh compiles ALSO route through jax.export: the executed
            # module is the exact module a later disk hit restores, so
            # cold and warm-disk runs match bit-for-bit — and the trace
            # happens once (export), never again for this artifact
            from jax import export as jax_export

            try:
                exported = jax_export.export(jax.jit(build()))(*abstract)
            except Exception as e:  # noqa: BLE001 — non-exportable program
                log.warning("%s: program %s is not exportable (%s); "
                            "compiling without persistence", self.kind, key, e)
        # outside the try: what the compiler refuses is raised once, as
        # itself, not relabelled as an export problem and compiled twice
        program = build() if exported is None else exported.call
        # the name is the trace's, made from the entry kind; it joins no
        # key, and a disk hit gives the restored program the same one
        fn = jax.jit(profiling.named(program, key[0]),
                     donate_argnums=donate).lower(*abstract).compile()
        dt = time.perf_counter() - t0
        self.stats.compile_seconds[key] = dt
        log.info("%s miss: compiled %s in %.2fs (entry %d)",
                 self.kind, key, dt, len(self._programs) + 1)
        if exported is not None:
            tw = time.perf_counter()
            self._persist.store(key, exported)
            self.stats.disk_write_seconds += time.perf_counter() - tw
            self._sync_persist_counters()
        self._programs[key] = fn
        return fn

    def _sync_persist_counters(self) -> None:
        """Mirror the store's entry-health counters onto the stats the
        serving surfaces expose (stores can be shared across caches, so
        the store owns the truth and the cache snapshots it)."""
        self.stats.io_errors = self._persist.io_errors
        self.stats.fetch_hits = getattr(self._persist, "fetch_hits", 0)
        self.stats.fetch_corrupt = getattr(self._persist,
                                           "fetch_corrupt", 0)

    def _load_from_disk(self, key: Tuple, abstract, donate):
        """Disk half of `_get`: deserialize + AOT-compile a persisted
        program.  Any failure (corrupt entry already evicted by the
        store, platform drift the fingerprint missed) returns None and
        the caller recompiles."""
        t0 = time.perf_counter()
        exported = self._persist.load(key)
        if exported is None:
            return None
        try:
            fn = jax.jit(profiling.named(exported.call, key[0]),
                         donate_argnums=donate).lower(*abstract).compile()
        except Exception as e:  # noqa: BLE001 — treat as corrupt: evict
            log.warning("%s: persisted entry for %s failed to compile "
                        "(%s); evicting and recompiling", self.kind, key, e)
            self._persist.evict(key)
            return None
        dt = time.perf_counter() - t0
        self.stats.disk_hits += 1
        self.stats.deserialize_seconds += dt
        log.info("%s disk hit: restored %s in %.2fs (entry %d)",
                 self.kind, key, dt, len(self._programs) + 1)
        self._programs[key] = fn
        return fn

    def track_jit(self, base_key: Tuple, jitted) -> Callable:
        """Wrap an already-jitted program (e.g. a shard_map'd dp train
        step) so its per-shape AOT compiles are timed and counted in
        this cache's stats like every single-chip program.  lower() runs
        on the REAL args of the triggering call, so GSPMD/mesh shardings
        are preserved; entries are keyed by `base_key` + the flattened
        arg signature + the arg SHARDINGS — a compiled executable only
        accepts the exact layouts it was built for, and dp params really
        do change layout once (host-resident at step 0, mesh-replicated
        after), which is a genuine second program, not a re-trace.  No
        disk persistence (multi-device layouts are process-topology-
        bound; the platform fingerprint would thrash)."""

        def wrapped(*args):
            leaves = jax.tree_util.tree_leaves(args)
            shards = tuple(str(getattr(l, "sharding", None))
                           for l in leaves)
            key = tuple(base_key) + (arg_signature(*leaves), shards)
            fn = self._programs.get(key)
            if fn is None:
                self.stats.misses += 1
                t0 = time.perf_counter()
                fn = jitted.lower(*args).compile()
                dt = time.perf_counter() - t0
                self.stats.compile_seconds[key] = dt
                log.info("%s miss: compiled %s in %.2fs (entry %d)",
                         self.kind, key, dt, len(self._programs) + 1)
                self._programs[key] = fn
            else:
                self.stats.hits += 1
            self.stats.steps += 1
            return fn(*args)

        # callers that AOT-compile explicitly reach through
        wrapped.lower = jitted.lower
        wrapped.__wrapped__ = jitted
        return wrapped

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            self._audit_records.clear()
            self._buckets = (sorted(self._buckets) if self._fixed_buckets
                             else [])
            self.stats = StepCacheStats()

    def __len__(self):
        return len(self._programs)

    # -- padding ------------------------------------------------------------
    @staticmethod
    def pad_batch(x, y, bucket: int):
        """Zero-pad (x, y) up to `bucket` feature rows and build the
        per-label-row weight vector (pad rows weigh 0).  Label rows may
        be a multiple of feature rows (B*T for sequence models)."""
        b = x.shape[0]
        ratio = max(1, y.shape[0] // max(1, b))
        pad = bucket - b
        w = jnp.concatenate([jnp.ones(b * ratio, jnp.float32),
                             jnp.zeros(pad * ratio, jnp.float32)])
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            y = jnp.concatenate(
                [y, jnp.zeros((pad * ratio,) + y.shape[1:], y.dtype)])
        return x, y, w


class TrainStepCache(CompiledProgramCache):
    """Memoizes AOT-compiled solver programs (the training entry points
    over `CompiledProgramCache`)."""

    kind = "step-cache"

    # -- network train steps ------------------------------------------------
    def finetune(self, conf, params, x, y, key, compile_only: bool = False):
        """One cached supervised solver run (`MultiLayerNetwork.finetune`
        body): pads (x, y) to the bucket, fetches/compiles the program
        for (conf, algo, shapes) and executes it.

        Returns (new_params, per-iteration scores).  BatchNorm running
        stats are advanced INSIDE the program from the last solver
        iteration's batch moments (`update_bn_ema_from_stats`) — no
        second forward pass.

        compile_only=True (warmup) registers the bucket and compiles —
        or disk-restores — the program without executing a step; params
        are untouched and None is returned."""
        from deeplearning4j_tpu.nn.multilayer import has_batchnorm

        out_conf = conf.conf(conf.n_layers - 1)
        bucket = self.bucket_rows(int(x.shape[0]))
        x, y, w = self.pad_batch(x, y, bucket)
        collect_bn = has_batchnorm(conf)
        cache_key = ("finetune", self._fingerprint(conf),
                     str(out_conf.optimization_algo),
                     arg_signature(x, y, w))
        args = (params, x, y, w, key)
        fn = self._get(cache_key,
                       lambda: _finetune_program(conf, collect_bn), args)
        if compile_only:
            return None
        self.stats.steps += 1
        return fn(*args)

    def pretrain(self, layer_conf, layer_idx: int, impl, layer_params, x,
                 key):
        """One cached layer-wise pretraining solver run
        (`MultiLayerNetwork.pretrain_layer` body).  Pretraining
        objectives take no row weights, so batches are NOT bucketed —
        each distinct input shape compiles its own program (keyed by the
        pretrain-layer index)."""
        cache_key = ("pretrain", layer_idx, self._fingerprint(layer_conf),
                     str(layer_conf.optimization_algo), arg_signature(x))
        args = (layer_params, x, key)
        fn = self._get(cache_key,
                       lambda: _pretrain_program(layer_conf, impl), args)
        self.stats.steps += 1
        return fn(*args)


def _finetune_program(conf, collect_bn: bool) -> Callable:
    """Build the (uncompiled) finetune step: run the configured solver
    over explicit batch args, then fold the BatchNorm EMA advance into
    the same program.  Hessian-free additionally gets a Gauss-Newton
    product with the pad-row weight mask threaded through its
    loss-of-outputs half (`solver.weighted_predict_loss`), so HF shares
    the bucketed padding instead of the legacy closure path."""
    # local import: nn.multilayer imports this module at top level
    from deeplearning4j_tpu.nn.conf import OptimizationAlgorithm
    from deeplearning4j_tpu.nn.multilayer import (make_finetune_loss,
                                                  network_output,
                                                  update_bn_ema_from_stats)

    out_conf = conf.conf(conf.n_layers - 1)
    loss_and_stats = make_finetune_loss(conf, collect_bn=collect_bn)
    is_hf = (OptimizationAlgorithm(str(out_conf.optimization_algo))
             == OptimizationAlgorithm.HESSIAN_FREE)

    def program(params, x, y, w, key):
        if collect_bn:
            def gsa(p, k):
                (s, stats), g = jax.value_and_grad(
                    lambda pp, kk: loss_and_stats(pp, x, y, w, kk),
                    has_aux=True)(p, k)
                return g, s, stats

            objective = solver_mod.Objective(
                grad_and_score=lambda p, k: gsa(p, k)[:2],
                score=lambda p, k: loss_and_stats(p, x, y, w, k)[0],
                grad_score_aux=gsa)
        else:
            objective = solver_mod.from_loss(
                lambda p, k: loss_and_stats(p, x, y, w, k)[0])
        if is_hf:
            # factor as predict+loss for Gauss-Newton products (the
            # reference's computeDeltasR R-op machinery); pad rows enter
            # the product with weight 0 — exact-zero curvature cotangents
            objective = objective._replace(
                gnvp=solver_mod.weighted_predict_loss(
                    lambda p, k: network_output(conf, p, x),
                    _rowwise_output_loss(out_conf), y, w).gnvp)
        new_params, scores, aux = solver_mod.optimize_with_aux(
            objective, params, out_conf, key)
        if collect_bn:
            new_params = update_bn_ema_from_stats(conf, new_params, aux)
        return new_params, scores

    return program


def _rowwise_output_loss(out_conf):
    """The output layer's per-row loss `(labels, outputs) -> [rows]` for
    the Gauss-Newton factorization."""
    from deeplearning4j_tpu.nd.losses import get_rowwise

    return get_rowwise(out_conf.loss_function)


def _pretrain_program(layer_conf, impl) -> Callable:
    """Build the (uncompiled) layer-pretraining step over explicit x."""

    def program(layer_params, x, key):
        objective = solver_mod.Objective(
            grad_and_score=lambda p, k: impl.pretrain_grad_and_score(
                p, layer_conf, x, k),
            score=lambda p, k: impl.pretrain_score(p, layer_conf, x, k))
        return solver_mod.optimize(objective, layer_params, layer_conf, key)

    return program
