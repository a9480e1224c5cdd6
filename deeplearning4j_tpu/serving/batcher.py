"""Dynamic micro-batching: many concurrent requests, one device call.

Every `MultiLayerNetwork.output()` call dispatches its own XLA program,
so concurrent callers serialize on dispatch and run at batch-size-1
arithmetic intensity — the exact regime the TPU datacenter analysis
(Jouppi et al., 2017) shows starves the MXU.  `MicroBatcher` recovers
the batch: requests land on a per-(feature-shape, dtype) FIFO from any
thread, and ONE dispatcher thread drains them into a single
`net.output()` call that the serve-path compile cache
(`optimize/infer_cache.py`) pads into its largest fitting row bucket.

Flush policy (classic dynamic batching under a latency SLO):
  - full bucket: queued rows reach the target batch (the largest known
    `InferCache` row bucket, capped by `max_batch_rows`), or
  - deadline: the OLDEST queued request has waited `max_delay_ms`.

Correctness: inference is row-independent (the property the infer
cache's pad/slice machinery already guarantees bit-exactly — pad rows
never leak), so each caller's rows in a coalesced batch are bitwise the
rows a direct `net.output()` call would have returned.

Backpressure: the queue is bounded (`max_pending` requests); beyond it
`predict()` fails fast with `ServerOverloaded` (HTTP 503 upstream)
instead of growing memory without bound.

Resilience (ISSUE 5):
  - per-request `deadline_ms`, enforced at enqueue AND again after
    coalescing — a request that expires while queued is evicted before
    the batch is padded/executed and answered `DeadlineExceeded`
    (HTTP 504 upstream), so dead rows never waste device time;
  - a `CircuitBreaker` around the cached execute path: after
    `failure_threshold` consecutive failures the breaker opens and the
    gateway degrades to the uncached eager forward pass
    (`network_output`), which shares none of the compile-cache
    machinery with the primary path; half-open probes re-try the
    primary and close the breaker on success.  Degraded batches are
    still row-sliced per request and are numerically identical to an
    eager `net.output()` call.
"""

from __future__ import annotations

import hashlib
import heapq
import io
import itertools
import queue
import statistics
import threading
import time
from collections import Counter, OrderedDict, deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.optimize import tunables
from deeplearning4j_tpu.reliability import CircuitBreaker, DeadlineExceeded, faults
from deeplearning4j_tpu.utils.profiling import span

#: coalescing target when no row bucket is known yet and the caller set
#: no `max_batch_rows` cap — now a registry default
#: (`optimize/tunables.py`, "batcher.target_rows"); kept as a module
#: constant for compat, but `_target_rows` resolves through the tuned
#: table so `cli tune` winners apply without a restart
DEFAULT_TARGET_ROWS = tunables.default("batcher.target_rows")

#: rows/s is reported over this trailing window (seconds)
RATE_WINDOW_S = 10.0

#: request priority classes, highest first.  "interactive" (the default:
#: a user is waiting) preempts "batch" (offline scoring backfill) in the
#: coalescing queue — each queue stays partitioned interactive-prefix /
#: batch-suffix, so when a flush can't take everyone the user-facing
#: rows ride first.
PRIORITIES = ("interactive", "batch")

#: cumulative-histogram bucket bounds (seconds) for per-priority request
#: latency — Prometheus-convention `le` upper bounds, +Inf implied
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

#: histogram bucket bounds for tokens accepted per speculative verify
#: step (`le` upper bounds; a round always accepts >= 1)
ACCEPTED_TOKENS_BOUNDS = (1, 2, 3, 4, 6, 8, 12, 16)

#: in-memory prefix-cache entries kept per batcher (LRU; the disk store,
#: when attached, holds evicted entries too)
PREFIX_CACHE_ENTRIES = 32

#: why the device had nothing queued, a closed set (`ContinuousBatcher.
#: _charge`): the next program was an admission's; a decode step launched
#: from the host's arrays after an admission or an idle wait; a step of a
#: loop that reads every step back before the next (`_runs_ahead` false, a
#: speculative round); no stream was live and none pending
STARVED_CAUSES = ("admit", "restart", "sync", "empty")


class ServerOverloaded(RuntimeError):
    """The gateway's pending queue is full — fail fast (HTTP 503)."""


class PagesExhausted(RuntimeError):
    """The KV page pool has no free page for the request.  At admission
    this queues the stream (pages free as live streams finish); past the
    admission gate — overcommitted pools only — it ends the one stream
    that could not grow, never the table."""


class _PagePool:
    """Host-side free list over the physical K/V page pool.

    Physical page 0 is the scratch page: every released slot's page
    table points there, so junk written for inactive rows lands behind
    the additive mask instead of in anyone's context.  Usable pages are
    1..n_pages; `alloc` traverses the `decode.page_alloc` fault point
    (an armed raise fails the ONE stream being grown) and raises
    `PagesExhausted` when the request exceeds the free list."""

    def __init__(self, n_pages: int):
        self.n_pages = int(n_pages)
        # pop() hands out ascending ids: 1, 2, ...
        self._free = list(range(self.n_pages, 0, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self, n: int, **ctx) -> List[int]:
        faults.fire("decode.page_alloc", requested=n,
                    free=len(self._free), **ctx)
        if n > len(self._free):
            raise PagesExhausted(
                f"{n} KV pages requested, {len(self._free)} free "
                f"(pool={self.n_pages})")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages) -> None:
        for p in pages:
            if int(p):
                self._free.append(int(p))


def _host_sample(logp, key, temperature: float):
    """One row of `InferCache._sample_tokens` on the host: split the
    stream's key once, argmax when temperature <= 0, else
    `categorical(sub, logp / temperature)` — the eager sampler's exact
    discipline (models/char_lstm.py:140), which the compiled programs
    already reproduce bit-for-bit.  This is what lets one cached prefill
    logp serve streams with different keys and temperatures.  Returns
    (token int, advanced key np.uint32[2])."""
    import jax
    import jax.numpy as jnp

    ks = np.asarray(jax.random.split(jnp.asarray(key)))
    new_key, sub = ks[0], ks[1]
    if temperature > 0:
        tok = int(jax.random.categorical(
            jnp.asarray(sub),
            jnp.asarray(logp, jnp.float32) / np.float32(temperature)))
    else:
        tok = int(np.argmax(np.asarray(logp, np.float32)))
    return tok, new_key


def _prng_key(seed: int) -> np.ndarray:
    """`jax.random.PRNGKey(seed)` as a host array, made on the host: under
    the default implementation (threefry2x32) the key is the seed's high and
    low 32 bits, the high ones kept only where 64-bit types are on.  A
    `submit` therefore runs nothing on the device and never queues behind
    the decode step in flight.  Any other implementation keeps the call."""
    import jax

    if jax.config.jax_default_prng_impl != "threefry2x32":
        return np.asarray(jax.random.PRNGKey(seed))
    bits = int(np.int64(seed))      # past 64 bits it raises, as PRNGKey does
    high = (bits >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.asarray([high, bits & 0xFFFFFFFF], np.uint32)


class _Pending:
    """One enqueued request: its rows, completion event, and timing."""

    __slots__ = ("x", "rows", "done", "result", "error", "t_enqueue",
                 "deadline", "priority", "claimed")

    def __init__(self, x, deadline_ms: Optional[float] = None,
                 priority: str = "interactive"):
        self.x = x
        self.rows = int(x.shape[0])
        self.done = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.t_enqueue = time.monotonic()
        self.deadline = (None if deadline_ms is None
                         else self.t_enqueue + float(deadline_ms) / 1000.0)
        self.priority = priority
        # lazy-deletion marker for the dispatcher's heaps: set when the
        # request leaves its queue (dispatched or evicted), so stale
        # heap entries are skipped instead of searched for
        self.claimed = False


class MicroBatcher:
    """Coalesces concurrent predict requests into bucketed device calls.

    net:            the `MultiLayerNetwork` to serve (its `infer_cache`
                    provides the bucketed AOT programs).
    max_delay_ms:   latency budget a request may wait for co-riders
                    before the dispatcher flushes anyway.
    max_pending:    bound on queued (not yet dispatched) requests;
                    beyond it `predict()` raises `ServerOverloaded`.
    max_batch_rows: cap on coalesced rows per device call; defaults to
                    the largest known infer-cache bucket (so a warmed
                    server batches exactly into its warmed program), or
                    `DEFAULT_TARGET_ROWS` when no bucket exists yet.
    breaker:        `CircuitBreaker` guarding the cached execute path;
                    pass your own to tune thresholds (tests inject a
                    fake-clock breaker).
    """

    def __init__(self, net, max_delay_ms: Optional[float] = None,
                 max_pending: int = 1024,
                 max_batch_rows: Optional[int] = None,
                 auto_start: bool = True,
                 breaker: Optional[CircuitBreaker] = None):
        self.net = net
        # None -> the tunable's effective value (tuned table if one is
        # installed, else the registry default of 3.0 ms)
        if max_delay_ms is None:
            max_delay_ms = tunables.resolve("batcher.max_delay_ms")
        self.max_delay_s = float(max_delay_ms) / 1000.0
        self.max_pending = int(max_pending)
        self.max_batch_rows = max_batch_rows
        self.breaker = breaker if breaker is not None else CircuitBreaker()
        self._auto_start = auto_start
        self._cv = threading.Condition()
        # key = (feature shape beyond axis 0, dtype): only requests that
        # concatenate into one well-formed batch share a queue
        self._queues: Dict[Tuple, Deque[_Pending]] = {}
        # min-heaps with lazy deletion (ISSUE 19): every enqueue pushes
        # (t_enqueue, seq, key, req) and, when a deadline exists,
        # (deadline, t_enqueue, seq, key, req).  Requests leaving a
        # queue flip `claimed` and are skipped when they surface at a
        # heap top, so oldest-request / earliest-deadline queries are
        # O(log n) instead of the linear scans they replaced.  `seq`
        # breaks timestamp ties so requests are never compared.
        self._arrival_heap: List[Tuple] = []
        self._deadline_heap: List[Tuple] = []
        self._seq = 0
        self._pending = 0
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # -- stats (guarded by _cv's lock) ---------------------------------
        self._t_start = time.monotonic()
        self._reqs_done = 0
        self._rows_done = 0
        self._batch_hist: Dict[int, int] = {}   # flushed batch rows -> count
        self._latencies: Deque[float] = deque(maxlen=4096)  # seconds
        # (t_done, rows, policy): the precision policy is recorded per
        # flush at execute time, so per-policy rows/s stays honest when
        # the operator flips `set_serve_precision` mid-flight
        self._recent: Deque[Tuple[float, int, str]] = deque()
        self._rows_by_policy: Dict[str, int] = {}   # cumulative rows
        self._deadline_misses = 0   # requests evicted past their deadline
        self._errors = 0            # requests answered with an exception
        self._degraded_batches = 0  # batches served by the eager fallback
        # -- per-priority-class stats (guarded by _cv's lock) --------------
        self._pending_by = {p: 0 for p in PRIORITIES}
        self._reqs_by = {p: 0 for p in PRIORITIES}       # completions
        self._lat_by = {p: deque(maxlen=4096) for p in PRIORITIES}
        # cumulative latency histogram per priority: one count per
        # LATENCY_BUCKETS_S bound (non-cumulative here; exporters sum),
        # +Inf bucket == count
        self._lat_hist = {p: {"counts": [0] * len(LATENCY_BUCKETS_S),
                              "inf": 0, "sum": 0.0, "count": 0}
                          for p in PRIORITIES}

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "MicroBatcher":
        with self._cv:
            if self._thread is not None:
                return self
            self._stop = False
            self._thread = threading.Thread(
                target=self._dispatch_loop, name="dl4j-microbatch",
                daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Stop the dispatcher; queued requests are drained (served)
        before the thread exits."""
        with self._cv:
            self._stop = True
            thread, self._thread = self._thread, None
            self._cv.notify_all()
        if thread is not None:
            thread.join(timeout=timeout)

    # -- request side (any thread) ------------------------------------------
    def predict(self, x, timeout: Optional[float] = None,
                deadline_ms: Optional[float] = None,
                priority: str = "interactive") -> np.ndarray:
        """Enqueue `x` ([rows, ...features]) and block until its output
        activations come back from a coalesced device call.

        `priority` is one of `PRIORITIES`: "interactive" requests are
        inserted ahead of every queued "batch" request (behind earlier
        interactive ones), so batch backfill can never hold a user
        request behind a long tail of queued offline rows.

        Raises `ServerOverloaded` when `max_pending` requests are
        already queued, `DeadlineExceeded` when `deadline_ms` elapses
        before a result exists (checked at enqueue and again after
        coalescing), and `TimeoutError` past `timeout` seconds."""
        x = np.asarray(x)
        if x.ndim < 2:
            raise ValueError(
                f"predict expects batched input [rows, ...features]; "
                f"got shape {x.shape}")
        if priority not in PRIORITIES:
            raise ValueError(
                f"priority must be one of {PRIORITIES}; got {priority!r}")
        if deadline_ms is not None and float(deadline_ms) <= 0.0:
            with self._cv:
                self._deadline_misses += 1
                self._reqs_by[priority] += 1
            raise DeadlineExceeded(
                f"deadline_ms={deadline_ms} already expired at enqueue")
        req = _Pending(x, deadline_ms, priority)
        key = (x.shape[1:], str(x.dtype))
        with self._cv:
            if self._pending >= self.max_pending:
                raise ServerOverloaded(
                    f"{self._pending} requests already pending "
                    f"(max_pending={self.max_pending})")
            q = self._queues.setdefault(key, deque())
            if priority == "batch" or not q or q[-1].priority != "batch":
                q.append(req)
            else:
                # interactive preemption: slot in at the head of the
                # batch-class suffix (queues stay partitioned, so a
                # linear scan for the boundary is the whole cost)
                i = 0
                while i < len(q) and q[i].priority != "batch":
                    i += 1
                q.insert(i, req)
            self._seq += 1
            heapq.heappush(self._arrival_heap,
                           (req.t_enqueue, self._seq, key, req))
            if req.deadline is not None:
                heapq.heappush(
                    self._deadline_heap,
                    (req.deadline, req.t_enqueue, self._seq, key, req))
            self._pending += 1
            self._pending_by[priority] += 1
            self._cv.notify_all()
        if self._thread is None and self._auto_start:
            self.start()
        if not req.done.wait(timeout):
            raise TimeoutError(
                f"no response within {timeout}s (queue depth "
                f"{self.queue_depth()})")
        if req.error is not None:
            raise req.error
        return req.result

    def queue_depth(self) -> int:
        with self._cv:
            return self._pending

    # -- dispatcher (one thread) --------------------------------------------
    def _target_rows(self) -> int:
        """Coalescing target: the largest known infer-cache row bucket
        (so flushed-full batches hit an already-compiled program), capped
        by `max_batch_rows`."""
        buckets = self.net.infer_cache.buckets
        cap = self.max_batch_rows
        fitting = [b for b in buckets if cap is None or b <= cap]
        if fitting:
            return max(fitting)
        if cap is not None:
            return cap
        return int(tunables.resolve("batcher.target_rows"))

    def _oldest_key(self):
        """The queue holding the longest-waiting request (FIFO across
        shapes: no shape can be starved by a busier one).  The arrival
        heap's first live entry IS the global oldest — claimed entries
        pop off lazily, so the former every-entry scan is now
        O(log n) amortized.  Caller holds `_cv`."""
        h = self._arrival_heap
        while h and h[0][3].claimed:
            heapq.heappop(h)
        return h[0][2] if h else None

    def _evict_expired_locked(self, now: float) -> None:
        """Answer every queued request whose deadline has passed with
        `DeadlineExceeded` — before it is coalesced, padded, or allowed
        to hold a batch open.  Eviction order is the deadline heap's:
        (deadline, t_enqueue) — earliest deadline first, FIFO within a
        tie.  Caller holds `_cv`."""
        h = self._deadline_heap
        while h and (h[0][4].claimed or h[0][0] <= now):
            _, _, _, key, r = heapq.heappop(h)
            if r.claimed:
                continue
            r.claimed = True
            self._queues[key].remove(r)
            self._pending -= 1
            self._pending_by[r.priority] -= 1
            self._reqs_by[r.priority] += 1
            self._deadline_misses += 1
            self._errors += 1
            r.error = DeadlineExceeded(
                f"deadline exceeded after "
                f"{(now - r.t_enqueue) * 1e3:.1f}ms in queue")
            r.done.set()

    def _earliest_deadline_locked(self) -> Optional[float]:
        h = self._deadline_heap
        while h and h[0][4].claimed:
            heapq.heappop(h)
        return h[0][0] if h else None

    def _dispatch_loop(self) -> None:
        while True:
            with self._cv:
                now = time.monotonic()
                self._evict_expired_locked(now)
                key = self._oldest_key()
                if key is None:
                    if self._stop:
                        return
                    self._cv.wait()
                    continue
                q = self._queues[key]
                target = self._target_rows()
                queued_rows = sum(r.rows for r in q)
                # `_oldest_key` just cleaned the arrival heap's top, so
                # its timestamp is the oldest live request's — no scan
                flush_at = self._arrival_heap[0][0] + self.max_delay_s
                # stopping: drain immediately rather than wait out SLOs
                if (queued_rows < target and now < flush_at
                        and not self._stop):
                    # wake early if any queued request's deadline lands
                    # before the flush, so eviction is prompt
                    edl = self._earliest_deadline_locked()
                    wake_at = flush_at if edl is None else min(flush_at, edl)
                    self._cv.wait(timeout=max(wake_at - now, 1e-4))
                    continue
                batch = [q.popleft()]
                rows = batch[0].rows
                # head-of-line FIFO: take co-riders while they still fit
                # (interactive preemption already put user-facing rows
                # at the head, so they are the ones guaranteed to ride)
                while q and rows + q[0].rows <= target:
                    batch.append(q.popleft())
                    rows += batch[-1].rows
                self._pending -= len(batch)
                for r in batch:
                    r.claimed = True
                    self._pending_by[r.priority] -= 1
            self._execute(batch)

    # -- execution paths -----------------------------------------------------
    def _primary_output(self, xb: np.ndarray) -> np.ndarray:
        """The cached path: infer-cache bucketed AOT program (or a fresh
        compile on a miss).  Guarded by the circuit breaker."""
        faults.fire("dispatcher.execute", rows=int(xb.shape[0]))
        return np.asarray(self.net.output(xb))

    def _degraded_output(self, xb: np.ndarray) -> np.ndarray:
        """The fallback: uncached eager forward pass, sharing none of
        the compile/persist machinery with the primary path.  Row
        independence still holds, so slicing stays bitwise-correct."""
        from deeplearning4j_tpu.nn.multilayer import network_output
        return np.asarray(network_output(self.net.conf, self.net.params, xb))

    def _execute(self, batch) -> None:
        xs = [r.x for r in batch]
        xb = xs[0] if len(xs) == 1 else np.concatenate(xs, axis=0)
        out, err, degraded = None, None, False
        if self.breaker.allow():
            try:
                out = self._primary_output(xb)
                self.breaker.record_success()
            except BaseException as e:  # noqa: BLE001 — degrade, then report
                self.breaker.record_failure()
                err = e
        else:
            err = RuntimeError("circuit breaker open")
        if out is None:
            try:
                out = self._degraded_output(xb)
                degraded, err = True, None
            except BaseException as e:  # noqa: BLE001 — delivered per request
                # both paths failed (e.g. malformed input): the PRIMARY
                # error is what callers should see when we have one
                err = err if err is not None else e
                out = None
        t_done = time.monotonic()
        policy = self.net.infer_cache.policy
        # output rows per input row: 1 for row-wise models, T for sequence
        # models whose output stage flattens [B, T, V] to [B*T, V]
        k = 1 if out is None else max(1, out.shape[0] // xb.shape[0])
        offset = 0
        for r in batch:
            if err is not None:
                r.error = err
            else:
                r.result = out[offset * k:(offset + r.rows) * k]
                offset += r.rows
            r.done.set()
        with self._cv:
            rows = sum(r.rows for r in batch)
            self._reqs_done += len(batch)
            self._rows_done += rows
            self._rows_by_policy[policy] = (
                self._rows_by_policy.get(policy, 0) + rows)
            self._batch_hist[rows] = self._batch_hist.get(rows, 0) + 1
            self._recent.append((t_done, rows, policy))
            while self._recent and t_done - self._recent[0][0] > RATE_WINDOW_S:
                self._recent.popleft()
            for r in batch:
                lat = t_done - r.t_enqueue
                self._latencies.append(lat)
                self._lat_by[r.priority].append(lat)
                self._reqs_by[r.priority] += 1
                if err is None:
                    h = self._lat_hist[r.priority]
                    h["sum"] += lat
                    h["count"] += 1
                    for i, bound in enumerate(LATENCY_BUCKETS_S):
                        if lat <= bound:
                            h["counts"][i] += 1
                            break
                    else:
                        h["inf"] += 1
            if degraded:
                self._degraded_batches += 1
            if err is not None:
                self._errors += len(batch)

    # -- observability -------------------------------------------------------
    @staticmethod
    def _percentile(sorted_vals, q: float) -> float:
        if not sorted_vals:
            return 0.0
        idx = min(len(sorted_vals) - 1,
                  max(0, int(round(q * (len(sorted_vals) - 1)))))
        return sorted_vals[idx]

    def stats(self) -> dict:
        """Gateway counters for `/v1/stats`: queue depth, batch-size
        histogram, latency percentiles, rows/s, the fresh-compile count
        (infer-cache misses — a warmed server serves with 0), plus the
        resilience block (deadline misses, errors, breaker state,
        `degraded` = currently serving on the eager fallback)."""
        with self._cv:
            lat = sorted(self._latencies)
            now = time.monotonic()
            recent_rows = 0
            recent_by_policy: Dict[str, int] = {}
            for t, r, pol in self._recent:
                if now - t <= RATE_WINDOW_S:
                    recent_rows += r
                    recent_by_policy[pol] = recent_by_policy.get(pol, 0) + r
            window = min(max(now - self._t_start, 1e-9), RATE_WINDOW_S)
            rows_by_policy = dict(self._rows_by_policy)
            depth = self._pending
            reqs, rows = self._reqs_done, self._rows_done
            hist = {str(k): v for k, v in sorted(self._batch_hist.items())}
            deadline_misses = self._deadline_misses
            errors = self._errors
            degraded_batches = self._degraded_batches
            priorities = {}
            for p in PRIORITIES:
                plat = sorted(self._lat_by[p])
                h = self._lat_hist[p]
                priorities[p] = {
                    "queue_depth": self._pending_by[p],
                    "requests": self._reqs_by[p],
                    "latency_ms": {
                        "p50": round(self._percentile(plat, 0.50) * 1e3, 3),
                        "p99": round(self._percentile(plat, 0.99) * 1e3, 3),
                    },
                    "latency_hist_s": {
                        "bounds": list(LATENCY_BUCKETS_S),
                        "counts": list(h["counts"]),
                        "inf": h["inf"],
                        "sum": h["sum"],
                        "count": h["count"],
                    },
                }
        cache = self.net.infer_cache.stats
        breaker = self.breaker.stats()
        return {
            "queue_depth": depth,
            "max_pending": self.max_pending,
            "max_delay_ms": self.max_delay_s * 1000.0,
            "target_rows": self._target_rows(),
            "requests": reqs,
            "rows": rows,
            "rows_per_sec": round(recent_rows / window, 2),
            "batch_rows_hist": hist,
            "latency_ms": {
                "p50": round(self._percentile(lat, 0.50) * 1e3, 3),
                "p95": round(self._percentile(lat, 0.95) * 1e3, 3),
                "p99": round(self._percentile(lat, 0.99) * 1e3, 3),
            },
            "fresh_compiles": cache.misses,
            "cache": cache.as_dict(),
            # active serve-precision policy + per-policy throughput and
            # the accuracy delta measured at set_serve_precision time
            # (serving has no labels — the delta can't be measured here)
            "precision": {
                "policy": self.net.infer_cache.policy,
                "rows_by_policy": rows_by_policy,
                "rows_per_sec_by_policy": {
                    p: round(r / window, 2)
                    for p, r in sorted(recent_by_policy.items())},
                "report": getattr(self.net, "serve_precision_report",
                                  {"policy": "f32"}),
            },
            "deadline_misses": deadline_misses,
            "errors": errors,
            "degraded_batches": degraded_batches,
            "degraded": breaker["state"] != CircuitBreaker.CLOSED,
            "breaker": breaker,
            "priorities": priorities,
            # autotuning state: tuned-table presence + fresh_tunes (a
            # warm process that inherited its table from disk shows 0)
            "tuning": tunables.status(),
        }


# -- continuous batching for autoregressive generation (ISSUE 14) -----------

class GenerationStream:
    """One in-flight generation request: its prompt, sampling knobs, and
    the token queue the HTTP handler (or any caller thread) drains while
    the decode loop keeps producing.

    `tokens()` yields ints as they are generated and raises the stream's
    stored error — after delivering every token that preceded it — when
    generation failed mid-stream."""

    def __init__(self, prompt, max_new_tokens: int, temperature: float,
                 rng_seed: int):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new = int(max_new_tokens)
        self.temperature = float(temperature)
        # per-stream PRNG key, split once per sampled token on-device —
        # the eager sampler's exact key discipline
        self.key = _prng_key(int(rng_seed))
        self.error: Optional[BaseException] = None
        self.tokens_emitted = 0
        #: tokens to swallow on readmission after a page-pool
        #: preemption (the recompute re-derives the delivered prefix)
        self._replay = 0
        self._counted_admit = False
        #: the batcher's number for this request (`submit` gives it):
        #: every span of the stream's way through the loop carries it
        self.rid: Optional[int] = None
        self.t_submit = time.monotonic()
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self._q: "queue.Queue" = queue.Queue()

    # decode-loop side ------------------------------------------------------
    def _emit(self, tok: int, now: float) -> None:
        if self.t_first is None:
            self.t_first = now
        self.tokens_emitted += 1
        self._q.put(int(tok))

    def _deliver(self, tok: int, now: float) -> bool:
        """Emit `tok` unless it replays an already-delivered token
        after a page-pool preemption: decode is deterministic given
        (prompt, key), so a recomputed stream re-derives exactly the
        prefix the consumer already has, and those tokens are swallowed
        rather than duplicated."""
        if self._replay > 0:
            self._replay -= 1
            return False
        self._emit(tok, now)
        return True

    def _finish(self, error: Optional[BaseException] = None) -> None:
        self.error = error
        self.t_done = time.monotonic()
        self._q.put(None)

    # consumer side ---------------------------------------------------------
    def tokens(self, timeout: Optional[float] = None):
        """Yield generated token ids until the stream completes; raises
        the stored error (mid-generation fault) or TimeoutError when no
        token arrives within `timeout` seconds."""
        while True:
            try:
                t = self._q.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError(
                    f"no token within {timeout}s (stream has "
                    f"{self.tokens_emitted} so far)")
            if t is None:
                if self.error is not None:
                    raise self.error
                return
            yield t

    @property
    def ttft_s(self) -> Optional[float]:
        if self.t_first is None:
            return None
        return self.t_first - self.t_submit


class ContinuousBatcher:
    """Fixed-width decode slot table with per-step admission (Orca-style
    continuous batching).

    Every table step is ONE compiled `InferCache.decode` call over all
    `n_slots` rows; a sequence that finishes frees its slot and the next
    queued stream is admitted — prefilled and emitting its first token —
    on the very next step instead of waiting for the longest neighbour
    to finish.  `continuous=False` is the sequential control arm:
    admission only happens when EVERY slot is free,
    so each wave barriers on its longest sequence.

    Correctness: rows are independent (each slot carries its own K/V
    table and LSTM state and its own PRNG key), so slot packing never
    changes a stream's tokens — a greedy stream reproduces the eager
    sampler's trajectory exactly regardless of its neighbours.

    Three conf-gated decode optimizations (ISSUE 16), each
    token-identical to the plain path and OFF by default:

    page_size > 0   paged KV: the dense [slots, max_seq, n] tables
                    become a shared physical page pool + per-slot page
                    tables; memory scales with live tokens, `n_pages`
                    can overcommit `n_slots` (admission queues on a dry
                    pool, it never crashes).
    prefix_cache    prefill keyed by prompt digest: a repeated prompt
                    copies the cached row state and samples its first
                    token from the cached logp on the host — TTFT is
                    one eager sample, not a prefill.  `prefix_match=
                    "longest"` additionally reuses the longest cached
                    strict prefix and feeds the remaining prompt tokens
                    through the decode table.
    draft_net+spec_k speculative decoding: the (recurrent-only) draft
                    proposes spec_k - 1 tokens, one batched verify step
                    chain-samples against them, and the agreeing prefix
                    is accepted — the emitted tokens ARE the target's
                    own chain samples, so trajectories match sequential
                    decode at any temperature.
    """

    def __init__(self, net, n_slots: Optional[int] = None, max_seq: int = 64,
                 prompt_buckets: Tuple[int, ...] = (8,),
                 max_pending: int = 64, continuous: bool = True,
                 auto_start: bool = True, page_size: Optional[int] = None,
                 n_pages: int = 0, prefix_cache: bool = False,
                 prefix_match: str = "exact", draft_net=None,
                 spec_k: int = 0):
        from deeplearning4j_tpu.nn import decode as decode_mod
        from deeplearning4j_tpu.nn.conf import LayerType

        self.net = net
        # None -> tunable-governed geometry ("decode.slots" /
        # "decode.page_size"); explicit arguments always win so warmup
        # and the batcher stay geometry-identical when the caller pins
        if n_slots is None:
            n_slots = tunables.resolve("decode.slots")
        if page_size is None:
            page_size = tunables.resolve("decode.page_size")
        self.n_slots = int(n_slots)
        self.max_seq = int(max_seq)
        self.prompt_buckets = tuple(sorted(
            int(b) for b in prompt_buckets if int(b) <= self.max_seq))
        self.max_pending = int(max_pending)
        self.continuous = bool(continuous)
        self._auto_start = auto_start
        self._layer_types = decode_mod.check_generative(net.conf)
        # a state a layer type defines itself (KDA's matrix, MLA's latents,
        # a window layer's ring beside a full layer's table)
        # lives in the dense slot table only
        dense_only = decode_mod.dense_only(net.conf)
        asked = [name for name, on in (
            ("page_size", int(page_size) > 0),
            ("prefix_cache", prefix_cache),
            ("spec_k", draft_net is not None)) if on]
        if dense_only and asked:
            raise ValueError(
                f"{asked} cannot serve layer types {dense_only} yet: their "
                f"state is a recurrent matrix, a latent cache or a ring of a "
                f"window's positions a slot, which the paged pool has no "
                f"pages for, a cached prefix row is right for only at the "
                f"prompt's end, and a verify chunk cannot roll back")
        # what a completed step read of the layers' state, by the rows'
        # positions: the counts of its `decode` span and of `stats()`
        self._counts = decode_mod.StepCounts(net.conf, self.n_slots,
                                             self.max_seq)
        # silent positional-table overrun fix: `token_embed` gathers
        # P[pos] with no bound check, and jit CLAMPS out-of-range
        # gathers — a stream decoding past the learned table would read
        # the last row forever instead of failing.  The table edge
        # (`submit` clamps max_new to max_seq - n) bounds every pos, so
        # rejecting max_seq > bound here closes the hole for the paged
        # path too, which has no [B, max_seq] dense table to trip the
        # `init_state` check.
        bound = decode_mod.positional_bound(net.conf)
        if bound and self.max_seq > bound:
            raise ValueError(
                f"max_seq={self.max_seq} exceeds the learned positional "
                f"table (max_seq_len={bound}); decoding past it would "
                f"silently clamp P[pos] gathers")
        # -- paged KV (page 0 = scratch; usable pages are 1..n_pages) ------
        self.page_size = int(page_size)
        self.paged = self.page_size > 0
        if self.paged:
            self.pages_per_slot = -(-self.max_seq // self.page_size)
            self.n_pages = int(n_pages) or self.n_slots * self.pages_per_slot
            if self.n_pages < self.pages_per_slot:
                raise ValueError(
                    f"n_pages={self.n_pages} cannot hold even one "
                    f"max_seq={self.max_seq} stream "
                    f"({self.pages_per_slot} pages of {self.page_size})")
            self._pool: Optional[_PagePool] = _PagePool(self.n_pages)
            self._page_table = np.zeros(
                (self.n_slots, self.pages_per_slot), np.int32)
        else:
            self.pages_per_slot = 0
            self.n_pages = 0
            self._pool = None
            self._page_table = None
        # -- prefix cache --------------------------------------------------
        self.prefix_cache_enabled = bool(prefix_cache)
        if prefix_match not in ("exact", "longest"):
            raise ValueError(
                f"prefix_match must be 'exact' or 'longest', "
                f"got {prefix_match!r}")
        self.prefix_match = prefix_match
        self._prefix_lru: "OrderedDict[str, tuple]" = OrderedDict()
        self._prefix_hits = 0
        self._prefix_misses = 0
        # -- speculative decoding ------------------------------------------
        self.draft_net = draft_net
        self.spec_k = int(spec_k) if draft_net is not None else 0
        if draft_net is not None:
            if self.spec_k < 2:
                raise ValueError(
                    "speculative decoding needs spec_k >= 2 (current "
                    "token + at least one draft position per verify)")
            d_types = decode_mod.check_generative(draft_net.conf)
            if any(t == LayerType.ATTENTION for t in d_types):
                raise ValueError(
                    "the draft model must be recurrent-only: rejected "
                    "draft tokens roll its carries back to a retained "
                    "copy, which K/V tables are too large to retain "
                    "per position")
            dbound = decode_mod.positional_bound(draft_net.conf)
            if dbound and self.max_seq > dbound:
                raise ValueError(
                    f"max_seq={self.max_seq} exceeds the DRAFT model's "
                    f"positional table (max_seq_len={dbound})")
        self._draft_state = None  # device tree, B = n_slots (spec only)
        self._cv = threading.Condition()
        self._pending: Deque[GenerationStream] = deque()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # -- slot table (decode-loop thread only) --------------------------
        self._state = None                      # device tree, B = n_slots
        self._slots: List[Optional[GenerationStream]] = [None] * self.n_slots
        self._tok = np.zeros((self.n_slots,), np.int32)
        self._pos = np.zeros((self.n_slots,), np.int32)
        self._keys = np.zeros((self.n_slots, 2), np.uint32)
        self._temps = np.zeros((self.n_slots,), np.float32)
        # prompt tokens still to feed through a longest-prefix-matched
        # slot (decode-loop thread only; empty with the flag off)
        self._feed: List[List[int]] = [[] for _ in range(self.n_slots)]
        self._spec_rounds = 0
        self._accept_hist = {"counts": [0] * len(ACCEPTED_TOKENS_BOUNDS),
                             "inf": 0, "sum": 0.0, "count": 0}
        # the rounds' clock (decode-loop thread only): what the last
        # eight steps ahead took from readback to readback and their
        # dispatches on the host, and the wall and blocked time since the
        # last completion
        self._periods: Deque[float] = deque(maxlen=8)
        self._dispatches: Deque[float] = deque(maxlen=8)
        self._t_mark = 0.0
        self._waited_s = 0.0
        # the account of what is on the device (decode-loop thread only,
        # the one thread that launches programs and reads them back): how
        # many it has launched and not yet seen finish, and the instant
        # (`time.monotonic_ns`) the device last turned: ran dry, or took up
        # the program it is running.  `_starved_ns` adds up, by cause, the
        # intervals it stood dry; `stats()` reads it without a lock (one
        # writer, whole integers, keys that never change)
        self._in_flight = 0
        self._t_turn = time.monotonic_ns()
        self._starved_ns = dict.fromkeys(STARVED_CAUSES, 0)
        # -- stats (guarded by _cv's lock) ---------------------------------
        self._t_start = time.monotonic()
        self._tokens_total = 0
        self._admitted = 0
        self._completed = 0
        self._failed = 0
        self._preempted = 0
        self._active = 0
        self._recent_tokens: Deque[Tuple[float, int]] = deque()
        self._ttfts: Deque[float] = deque(maxlen=4096)
        self._ttft_hist = {"counts": [0] * len(LATENCY_BUCKETS_S),
                           "inf": 0, "sum": 0.0, "count": 0}
        # host-overhead accounting per completed step (guarded by
        # _cv's lock): wall = readback-to-readback span, host = wall
        # minus the time spent blocked in device_get
        self._host_s = 0.0
        self._wall_s = 0.0
        # table steps dispatched, and those of them dispatched while
        # the step before was still in flight
        self._steps = 0
        self._steps_ahead = 0
        # summed from the closed `admit` spans (guarded by _cv's lock):
        # what admissions held the loop for, and what their streams waited
        self._rids = itertools.count(1)
        self._admit_s = 0.0
        self._queue_wait_s = 0.0
        # `self._counts.step`'s counts summed over the completed steps
        self._step_sums: Counter = Counter()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ContinuousBatcher":
        with self._cv:
            if self._thread is not None:
                return self
            self._stop = False
            if self._state is None:
                if self.paged:
                    # pool row 0 is the scratch page — physical pool =
                    # usable pages + 1
                    self._state = self.net.infer_cache.init_paged_decode_state(
                        self.net.conf, self.n_slots, self.n_pages + 1,
                        self.page_size)
                else:
                    self._state = self.net.infer_cache.init_decode_state(
                        self.net.conf, self.n_slots, self.max_seq)
            if self.draft_net is not None and self._draft_state is None:
                self._draft_state = self.draft_net.infer_cache.init_decode_state(
                    self.draft_net.conf, self.n_slots, self.max_seq)
            self._thread = threading.Thread(
                target=self._decode_loop, name="dl4j-decode", daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Stop the decode loop; queued and in-flight streams are run to
        completion first (drain = serve, like the MicroBatcher)."""
        with self._cv:
            self._stop = True
            thread, self._thread = self._thread, None
            self._cv.notify_all()
        if thread is not None:
            thread.join(timeout=timeout)

    # -- request side (any thread) ------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16,
               temperature: float = 0.0,
               rng_seed: int = 0) -> GenerationStream:
        """Queue a generation request; returns its `GenerationStream`
        immediately (tokens arrive on `stream.tokens()`).  Greedy when
        `temperature <= 0`.  Raises `ServerOverloaded` past
        `max_pending` queued streams and ValueError for prompts the
        decode table cannot hold."""
        stream = GenerationStream(prompt, max_new_tokens, temperature,
                                  rng_seed)
        n = int(stream.prompt.shape[0])
        if n < 1:
            raise ValueError("prompt must hold at least one token id")
        if n >= self.max_seq:
            raise ValueError(
                f"prompt of {n} tokens leaves no room to generate in a "
                f"max_seq={self.max_seq} decode table")
        if stream.max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # the table edge bounds the stream, never overruns it
        stream.max_new = min(stream.max_new, self.max_seq - n)
        with self._cv:
            if self._stop and self._thread is None:
                raise ServerOverloaded("generation batcher is stopped")
            if len(self._pending) >= self.max_pending:
                raise ServerOverloaded(
                    f"{len(self._pending)} generation streams already "
                    f"pending (max_pending={self.max_pending})")
            stream.rid = next(self._rids)
            self._pending.append(stream)
            self._cv.notify_all()
        if self._thread is None and self._auto_start:
            self.start()
        return stream

    def generate(self, prompt, max_new_tokens: int = 16,
                 temperature: float = 0.0, rng_seed: int = 0,
                 timeout: Optional[float] = 60.0) -> List[int]:
        """Blocking convenience: submit + drain the whole stream."""
        stream = self.submit(prompt, max_new_tokens, temperature, rng_seed)
        return list(stream.tokens(timeout=timeout))

    # -- decode loop (one thread) -------------------------------------------
    def _prompt_bucket(self, n: int) -> int:
        for b in self.prompt_buckets:
            if b >= n:
                return b
        return n  # oversize prompt: its own bucket (fresh compile, logged)

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    # -- the account of what is on the device -------------------------------
    def _launched(self, sp: span, cause: str) -> None:
        """Called right after the call of a compiled program returns, inside
        the loop's open top-level span `sp`: one more program is on the
        device.  If none was, the device has had nothing queued since
        `_t_turn`, and the interval is charged to `cause` (`_charge`)."""
        if not self._in_flight:
            self._charge(sp, cause)
        self._in_flight += 1

    def _landed(self, now_ns: int) -> None:
        """Called right after a host read has seen a launched program's
        outputs, at `now_ns`: that program is off the device, which at this
        instant either takes up the next one queued or runs dry."""
        self._in_flight -= 1
        self._t_turn = now_ns

    def _charge(self, sp: span, cause: str) -> None:
        """The device stood dry from `_t_turn` until now, for `cause` (one
        of `STARVED_CAUSES`): `sp` gets the interval (`starved_at`, a list
        of `(from_ns, until_ns)` on the record's clock), its length added to
        `starved_ns`, and `starved_cause`; the cause's counter of `stats()`
        the same nanoseconds.  The device turns now: it takes up what was
        just launched, or (`empty`) goes on standing dry from here.  Called
        alone, with nothing in flight, for a program that no host read ever
        observes (a row's write after an admission's read, the draft's
        prefill): charged to here and taken as landed at once, so that the
        count never waits for a read that does not come; its own device
        time then lies inside the next interval charged, small beside it."""
        now = time.monotonic_ns()
        dry = now - self._t_turn
        sp.attrs.setdefault("starved_at", []).append((self._t_turn, now))
        sp.set(starved_ns=sp.attrs.get("starved_ns", 0) + dry,
               starved_cause=cause)
        self._starved_ns[cause] += dry
        self._t_turn = now

    def _admit_one(self, slot: int, stream: GenerationStream) -> None:
        """Admit `stream` into `slot`.  A dense admission is ONE call of
        one compiled program (`InferCache.prefill_slot`): it takes the
        slot table, donated, prefills the prompt into a zero row made
        inside the trace, samples the stream's first token (TTFT = this
        call) and hands the table back with the row written in place.
        Nothing is dispatched outside that program.

        A prefix-cache hit skips the prefill entirely: the cached row
        state is written into the table (`InferCache.write_row`, one
        small program) and (exact match) the first token is sampled
        on the host from the cached logp with the stream's own key, or
        (longest match) the unmatched prompt suffix is queued to feed
        through the decode table.  Either way the token trajectory is
        identical to a cold prefill.  A paged table keeps the B=1
        prefill and `_scatter_row`'s page writes.

        The whole of it is one `admit` span keyed by the stream's rid
        (`queue_wait_ns`: from `submit` to here; `path`: which way in,
        "prefill_slot", "write_row" or "paged"), its parts the children
        `admit.prefill` and `admit.deliver`, with `admit.scatter` between
        them on the two paths that write a row they hold;
        `admit_seconds_total` and `queue_wait_seconds_total` add up the
        same spans."""
        n = int(stream.prompt.shape[0])
        wait_ns = int((time.monotonic() - stream.t_submit) * 1e9)
        sp = span("admit", rid=stream.rid, slot=slot, prompt_tokens=n,
                  queue_wait_ns=wait_ns)
        try:
            with sp:
                self._admit_spanned(slot, stream, n, sp)
        finally:
            with self._cv:
                self._admit_s += sp.seconds
                self._queue_wait_s += wait_ns / 1e9

    def _admit_spanned(self, slot: int, stream: GenerationStream, n: int,
                       sp: span) -> None:
        import jax

        ic, conf, params = self.net.infer_cache, self.net.conf, self.net.params
        faults.fire("generate.admit", slot=slot, prompt_tokens=n)
        hit = (self._prefix_lookup(stream.prompt)
               if self.prefix_cache_enabled else None)
        m = n if hit is None else int(hit[0])
        pages: Optional[List[int]] = None
        if self.paged:
            # allocate the admission pages before any device work, so a
            # dry pool queues the stream instead of wasting a prefill
            pages = self._pool.alloc(-(-m // self.page_size), slot=slot)
        sp.set(path="paged" if self.paged
               else "prefill_slot" if hit is None else "write_row")
        tok0 = key1 = row = None        # a row in hand is still to be written
        if hit is None:
            bucket = self._prompt_bucket(n)
            sp.set(bucket=bucket)
            prompt = np.zeros((1, bucket), np.int32)
            prompt[0, :n] = stream.prompt
            length = np.asarray([n], np.int32)
            with span("admit.prefill"):     # to the program's host read
                with span("admit.launch"):  # until the program's call returns
                    if self.paged:  # B=1 programs; the pages are written below
                        fill, fill_logp = ic.prefill, ic.prefill_logp
                        into = (ic.init_decode_state(conf, 1, self.max_seq),)
                    else:       # one program writes the row into the table
                        fill, fill_logp = ic.prefill_slot, ic.prefill_logp_slot
                        into = (self._state, slot)
                    if self.prefix_cache_enabled:
                        logp, *kept, state = fill_logp(conf, params, *into,
                                                       prompt, length)
                        # the filled row: the paged program's state itself,
                        # handed back beside the table by the slot program
                        kept = kept[0] if kept else state
                    else:
                        t0, keys1, state = fill(
                            conf, params, *into, prompt, length,
                            stream.key[None],
                            np.asarray([stream.temperature], np.float32))
                    self._launched(sp, "admit")
                    if self.paged:
                        row = state
                    else:
                        self._state = state
                # blocked on the device until the first token (or the
                # prompt's last logp) is on the host: with nothing else in
                # flight, the admission program's device time and the transfer
                with span("admit.readback"):
                    try:
                        if self.prefix_cache_enabled:
                            logp = np.asarray(logp, np.float32)[0]
                        else:
                            t0, keys1 = jax.device_get((t0, keys1))
                            tok0, key1 = int(t0[0]), keys1[0]
                    finally:    # read or failed, it is off the account
                        self._landed(time.monotonic_ns())
            if self.prefix_cache_enabled:
                self._prefix_store(stream.prompt, logp, kept)
                tok0, key1 = _host_sample(logp, stream.key,
                                          stream.temperature)
        else:
            row = hit[2]
            if hit[1] is not None:  # exact match: cached prefill logp
                tok0, key1 = _host_sample(hit[1], stream.key,
                                          stream.temperature)
        if row is not None:
            with span("admit.scatter"):
                self._scatter_row(slot, row, pages)
                self._charge(sp, "admit")   # launched, and never read back
        if self.draft_net is not None:
            # the draft consumes exactly the m tokens the target row has
            # consumed, so feed rounds advance both in lockstep
            self._draft_admit(slot, stream.prompt[:m])
            self._charge(sp, "admit")       # so is the draft's prefill
        with span("admit.deliver"):
            self._admit_deliver(slot, stream, n, m, tok0, key1)

    def _admit_deliver(self, slot: int, stream: GenerationStream, n: int,
                       m: int, tok0, key1) -> None:
        """The host half of an admission: the slot's row of the table's
        arguments, the first token to the caller, the counters."""
        self._slots[slot] = stream
        self._temps[slot] = stream.temperature
        now = time.monotonic()
        delivered = False
        if tok0 is not None:
            self._tok[slot] = tok0
            self._pos[slot] = n
            self._keys[slot] = key1
            delivered = stream._deliver(tok0, now)
        else:
            # longest-prefix match: next decode steps consume the
            # unmatched prompt tokens; the stream's key stays unsplit
            # until the first REAL sample (the step that consumes the
            # last prompt token), so tokens match a cold prefill
            self._tok[slot] = int(stream.prompt[m])
            self._pos[slot] = m
            self._keys[slot] = stream.key
            self._feed[slot] = [int(x) for x in stream.prompt[m + 1:]]
        with self._cv:
            if not stream._counted_admit:
                stream._counted_admit = True
                self._admitted += 1
            self._active += 1
            if delivered:
                self._tokens_total += 1
                self._recent_tokens.append((now, 1))
                self._record_ttft_locked(stream)
        if tok0 is not None and stream.tokens_emitted >= stream.max_new:
            self._release_slot(slot, stream)

    def _record_ttft_locked(self, stream: GenerationStream) -> None:
        """TTFT bookkeeping for a stream's FIRST emitted token (caller
        holds `_cv`)."""
        ttft = stream.ttft_s
        self._ttfts.append(ttft)
        h = self._ttft_hist
        h["sum"] += ttft
        h["count"] += 1
        for i, bound in enumerate(LATENCY_BUCKETS_S):
            if ttft <= bound:
                h["counts"][i] += 1
                break
        else:
            h["inf"] += 1

    def _scatter_row(self, slot: int, row, pages: Optional[List[int]]):
        """Write a B=1 row state in hand (device or host tree) into the
        slot table: a prefix-cache hit's row, or a paged admission's.
        Dense: one call of the `write_row` program, the table donated
        and written in place.  Paged: the dense K/V copied into the
        freshly allocated physical pages, recurrent carries per slot,
        in eager `.at[].set` writes as before: pages are a different
        write (per page, per layer), no benchmark cell runs it, and it
        gets a program of its own when a paged cell exists."""
        if not self.paged:
            self._state = self.net.infer_cache.write_row(
                self.net.conf, self._state, row, slot)
            return
        import jax.numpy as jnp

        ps = self.page_size
        new_state = []
        for i, lay in enumerate(self._state):
            if not lay:
                new_state.append(lay)
            elif "h" in lay:
                new_state.append(
                    {"h": lay["h"].at[slot].set(row[i]["h"][0]),
                     "c": lay["c"].at[slot].set(row[i]["c"][0])})
            else:
                # the row's dense [H, max_S, hd] table as the pool's
                # [max_S, n] positions
                k, v = lay["k"], lay["v"]
                rk, rv = (jnp.swapaxes(row[i][name][0], 0, 1).reshape(
                    -1, lay[name].shape[-1]) for name in ("k", "v"))
                for j, phys in enumerate(pages):
                    blk_k = rk[j * ps: (j + 1) * ps]
                    blk_v = rv[j * ps: (j + 1) * ps]
                    k = k.at[phys, : blk_k.shape[0]].set(blk_k)
                    v = v.at[phys, : blk_v.shape[0]].set(blk_v)
                new_state.append({"k": k, "v": v})
        self._state = tuple(new_state)
        self._page_table[slot, :] = 0
        self._page_table[slot, : len(pages)] = pages

    def _draft_admit(self, slot: int, prompt: np.ndarray) -> None:
        """Prefill the draft model's row `slot` over `prompt` (the tokens
        the target row has consumed): the draft's own `prefill_slot`
        program on its (always dense) table.  The draft decodes greedily
        with a dummy key — its proposals only gate acceptance, never
        sampling."""
        dn = self.draft_net
        m = int(prompt.shape[0])
        bucket = self._prompt_bucket(m)
        pb = np.zeros((1, bucket), np.int32)
        pb[0, :m] = prompt
        _, _, self._draft_state = dn.infer_cache.prefill_slot(
            dn.conf, dn.params, self._draft_state, slot, pb,
            np.asarray([m], np.int32), np.zeros((1, 2), np.uint32),
            np.zeros((1,), np.float32))

    # -- prefix cache -------------------------------------------------------
    def _prefix_digest(self, prompt: np.ndarray) -> str:
        """Cache key for a prompt's prefill: prompt tokens + conf
        fingerprint + max_seq (row-state shape) + serve policy — the
        same dimensions that key the prefill program itself.  A plan
        with a `model` axis folds its decode tag in too (sharded rows
        are laid out differently); 1-D/single-chip digests stay
        byte-identical to their pre-plan form."""
        ic = self.net.infer_cache
        h = hashlib.sha256()
        h.update(ic._fingerprint(self.net.conf).encode())
        h.update(repr((self.max_seq, ic.policy)).encode())
        tag = ic._decode_tag()
        if tag != ic.SINGLE:
            h.update(repr(tag).encode())
        h.update(np.ascontiguousarray(prompt, np.int32).tobytes())
        return h.hexdigest()

    def _prefix_store(self, prompt: np.ndarray, logp: np.ndarray,
                      row) -> None:
        """Record a cold prefill: (prompt, logp at its last position,
        host copy of the filled B=1 row state), LRU-capped in memory and
        written through to the program disk store when one is attached."""
        import jax

        host_row = jax.tree_util.tree_map(np.asarray, row)
        digest = self._prefix_digest(prompt)
        entry = (np.asarray(prompt, np.int32).copy(), logp, host_row)
        with self._cv:
            self._prefix_lru[digest] = entry
            self._prefix_lru.move_to_end(digest)
            while len(self._prefix_lru) > PREFIX_CACHE_ENTRIES:
                self._prefix_lru.popitem(last=False)
        persist = self.net.infer_cache.persist
        if persist is not None:
            try:
                arrs = {"prompt": entry[0], "logp": logp}
                for i, lay in enumerate(host_row):
                    for kk, vv in lay.items():
                        arrs[f"L{i}_{kk}"] = vv
                buf = io.BytesIO()
                np.savez(buf, **arrs)
                persist.store_bytes(("prefix", digest), buf.getvalue())
            except BaseException:  # noqa: BLE001 — disk is best-effort
                pass

    def _prefix_disk_load(self, digest: str):
        """Exact-match entry from the disk store, or None.  Corruption
        surfaces as an exception and becomes a counted miss upstream."""
        persist = self.net.infer_cache.persist
        if persist is None:
            return None
        blob = persist.load_bytes(("prefix", digest))
        if blob is None:
            return None
        z = np.load(io.BytesIO(blob))
        row = []
        for i in range(len(self._layer_types)):
            lay = {}
            for kk in ("c", "h", "k", "v"):
                name = f"L{i}_{kk}"
                if name in z:
                    lay[kk] = z[name]
            row.append(lay)
        entry = (np.asarray(z["prompt"], np.int32),
                 np.asarray(z["logp"], np.float32), tuple(row))
        with self._cv:
            self._prefix_lru[digest] = entry
            while len(self._prefix_lru) > PREFIX_CACHE_ENTRIES:
                self._prefix_lru.popitem(last=False)
        return entry

    def _prefix_lookup(self, prompt: np.ndarray):
        """(matched_tokens, logp_or_None, host_row_state) for `prompt`,
        or None on a miss.  logp is set only for an exact match.  ANY
        failure — the armed `generate.prefix_lookup` fault, a corrupt
        disk entry — degrades to a counted miss and a cold prefill; the
        stream never fails here."""
        try:
            faults.fire("generate.prefix_lookup",
                        prompt_tokens=int(prompt.shape[0]))
            digest = self._prefix_digest(prompt)
            with self._cv:
                entry = self._prefix_lru.get(digest)
                if entry is not None:
                    self._prefix_lru.move_to_end(digest)
            if entry is None:
                entry = self._prefix_disk_load(digest)
            if entry is not None:
                with self._cv:
                    self._prefix_hits += 1
                return (int(entry[0].shape[0]), entry[1], entry[2])
            if self.prefix_match == "longest":
                best = None
                with self._cv:
                    candidates = list(self._prefix_lru.values())
                for p2, _, row2 in candidates:
                    m = int(p2.shape[0])
                    if (m < int(prompt.shape[0])
                            and (best is None or m > best[0])
                            and np.array_equal(p2, prompt[:m])):
                        best = (m, None, row2)
                if best is not None:
                    with self._cv:
                        self._prefix_hits += 1
                    return best
        except BaseException:  # noqa: BLE001 — lookup faults degrade
            pass
        with self._cv:
            self._prefix_misses += 1
        return None

    def _release_slot(self, slot: int,
                      stream: GenerationStream,
                      error: Optional[BaseException] = None) -> None:
        stream._finish(error)
        self._slots[slot] = None
        self._temps[slot] = 0.0
        self._feed[slot] = []
        if self.paged:
            # release the slot's pages and point its table rows at the
            # scratch page so later junk writes stay inert
            self._pool.free(self._page_table[slot])
            self._page_table[slot, :] = 0
        with self._cv:
            self._active -= 1
            if error is None:
                self._completed += 1
            else:
                self._failed += 1
            self._cv.notify_all()

    def _preempt_slot(self, slot: int,
                      stream: GenerationStream) -> None:
        """Page-pool preemption (overcommitted pool, mid-decode
        exhaustion): free the slot AND its pages WITHOUT finishing the
        stream, and requeue it at the front for recompute-from-scratch.
        The readmitted stream replays its already-delivered tokens
        silently (see `GenerationStream._deliver`), so the consumer
        sees one uninterrupted, token-identical stream.  Freeing this
        slot's pages is also what guarantees progress: the survivors
        can now grow to full length, and `n_pages >= pages_per_slot`
        (enforced at construction) means a lone stream always fits."""
        stream._replay = stream.tokens_emitted
        self._slots[slot] = None
        self._temps[slot] = 0.0
        self._feed[slot] = []
        if self.paged:
            self._pool.free(self._page_table[slot])
            self._page_table[slot, :] = 0
        with self._cv:
            self._active -= 1
            self._preempted += 1
            self._pending.appendleft(stream)
            self._cv.notify_all()

    def _admit_pending(self) -> None:
        free = self._free_slots()
        if not self.continuous and len(free) != self.n_slots:
            return  # sequential arm: barrier on the slowest slot
        for slot in free:
            with self._cv:
                if not self._pending:
                    return
                stream = self._pending.popleft()
            try:
                self._admit_one(slot, stream)
            except PagesExhausted:
                # genuine pool pressure: queue, don't fail — pages free
                # as live streams complete, and admission re-runs every
                # table step
                with self._cv:
                    self._pending.appendleft(stream)
                return
            except BaseException as e:  # noqa: BLE001 — isolate the stream
                with self._cv:
                    self._failed += 1
                stream._finish(e)

    def _pages(self):
        """The `page_table` argument of a decode-family call: a copy of the
        host's table (the loop goes on writing it while the device reads),
        or None over a dense table, which selects the dense program."""
        return self._page_table.copy() if self.paged else None

    def _lazy_alloc(self, k: int) -> None:
        """Ensure every active slot has physical pages for its next `k`
        positions, allocating from the pool as streams cross page
        boundaries.  Genuine exhaustion past the admission gate
        (overcommit pressure) preempts the ONE stream that could not
        grow — requeued for recompute, never failed; an armed
        `decode.page_alloc` fault ends that stream with the injected
        error.  Either way the table keeps decoding."""
        ps = self.page_size
        for slot, stream in enumerate(self._slots):
            if stream is None:
                continue
            p0 = int(self._pos[slot])
            need = [p for p in range(p0 // ps, (p0 + k - 1) // ps + 1)
                    if p < self.pages_per_slot
                    and self._page_table[slot, p] == 0]
            if not need:
                continue
            try:
                got = self._pool.alloc(len(need), slot=slot, pos=p0)
            except PagesExhausted:
                self._preempt_slot(slot, stream)
                continue
            except BaseException as e:  # noqa: BLE001 — isolate the stream
                self._release_slot(slot, stream, error=e)
                continue
            for p, phys in zip(need, got):
                self._page_table[slot, p] = phys

    def _runs_ahead(self) -> bool:
        """Whether the step after the one just dispatched can be
        dispatched before this one is read back: its inputs must not
        depend on the host having seen this step's tokens.  They do while
        a slot feeds a prompt's rest through the table (the host swaps
        the sampled token for the next prompt token), under a draft
        network (a speculative round needs the accepted depth, a lockstep
        step the host's token), and over the paged pool, which stays
        synchronous: a step in flight would be writing pages that the
        next step's growth may preempt and hand to another slot, and no
        cell runs it."""
        return not (self.draft_net is not None or self.paged
                    or any(self._feed))

    def _admissible(self) -> bool:
        """Whether a pending stream could be admitted now: a slot stands
        free (all of them in the sequential arm)."""
        free = self._slots.count(None)
        return free > 0 and (self.continuous or free == self.n_slots)

    def _has_pending(self) -> bool:
        with self._cv:
            return bool(self._pending)

    def _leaves_rounds(self) -> bool:
        """Whether the rounds should complete what is in flight and hand
        back to `_decode_loop`: an admission is due."""
        return self._admissible() and self._has_pending()

    def _decode_rounds(self) -> None:
        """Table steps, each in a `decode` span of its own, until nothing
        is in flight.

        A step has two halves, `_dispatch` (fault points, the rows'
        schedule, the program's call) and `_complete` (the one
        `device_get`, delivery, releases).  Where the next step's inputs
        need the host to have seen this step's tokens (`_runs_ahead`) a
        span is dispatch then complete, and the rounds end with it: the
        synchronous step.  Everywhere else the loop stays ONE STEP AHEAD:
        step n+1 is dispatched while step n runs, its token and key
        arguments step n's device outputs, so that the device finds its
        next step queued when it finishes one.  A steady span is then
        complete(n), `decode.wait`, dispatch(n+2) while n+1 runs; the two
        spans that start a run hold a dispatch alone, and those that end
        it complete one step each of what is left in flight.  A span
        carries what its completed step counted (`live`, `steps`,
        `picks_here`, `kv_cells_*` ...) and, of its dispatch, `ahead`.

        An admission keeps the wait it had.  While a slot stands free the
        loop holds dispatch(n+2) back (`_wait_for_need`), so that a
        submission arriving meanwhile is admitted behind the one step in
        flight, not behind two: that step is completed, the rounds end
        and `_admit_pending` runs as it always did, from the host's
        arrays."""
        flying: Deque[dict] = deque()
        self._t_mark, self._waited_s = time.monotonic(), 0.0
        leaving = False
        while True:
            with span("decode", k=1) as sp:
                if len(flying) == 2 or leaving:
                    self._complete(flying.popleft(), sp)
                if flying and not leaving:
                    self._wait_for_need()
                    leaving = self._leaves_rounds()
                if not leaving:
                    step = self._dispatch(sp, flying)
                    if step is None:
                        leaving = True
                    elif self._runs_ahead():
                        flying.append(step)
                    else:       # synchronous: nothing was in flight before it
                        self._complete(step, sp)
            if not flying:
                return

    def _wait_for_need(self) -> None:
        """With a step in flight and a slot standing free, wait on `_cv`
        (which `submit` notifies) for a submission, until the device is
        about to need the next step: the running step's start (`_t_turn`:
        the account's stamp of when the device took it up) plus the
        loop's recent readback-to-readback period, less its recent
        `decode.dispatch` time and a margin of as much again.  Both are
        the loop's own observations; before it has any, and with no slot
        free, it does not wait.  The wait is the `decode.wait` span; the
        callers take the GIL for the tokens just delivered meanwhile."""
        if not (self._periods and self._admissible()):
            return
        need = (self._t_turn / 1e9 + statistics.median(self._periods)
                - 2.0 * statistics.median(self._dispatches))
        if need <= time.monotonic():
            return
        with span("decode.wait") as waited:
            with self._cv:
                while not self._pending:
                    left = need - time.monotonic()
                    if left <= 0:
                        break
                    self._cv.wait(left)
        self._waited_s += waited.seconds

    def _dispatch(self, sp: span, flying: Deque[dict]) -> Optional[dict]:
        """The first half of a table step, inside its `decode` span `sp`:
        schedule the rows, fire their fault points (a raise ends THAT
        stream only), then one compiled decode call over all slots.
        Returns the step's record for `_complete`, or None where no row
        was left to advance (or a speculative round took the step).

        With a step in flight (`flying`) the rows are scheduled by
        arithmetic: streams end by count alone, so a row whose budget or
        whose table edge the step in flight uses up is scheduled as a
        free slot is, and the token and key arguments are that step's
        device outputs, passed on without a sync.  With none the host's
        arrays are the arguments."""
        prev = flying[-1] if flying else None
        streams = list(self._slots)
        pos = self._pos.copy()
        adv = np.zeros((self.n_slots,), np.int32)
        for slot, stream in enumerate(streams):
            if stream is None:
                continue
            ran = (int(prev["adv"][slot])
                   if prev is not None and prev["streams"][slot] is stream
                   else 0)
            pos[slot] += ran
            left = stream.max_new - stream.tokens_emitted + stream._replay
            adv[slot] = left > ran and int(pos[slot]) < self.max_seq
        for slot in map(int, np.flatnonzero(adv)):
            try:
                faults.fire("decode.step", slot=slot, pos=int(pos[slot]))
            except BaseException as e:  # noqa: BLE001 — isolate the stream
                # the K=1 count: what is in flight reaches the stream first
                while flying:
                    self._complete(flying.popleft(), sp)
                if self._slots[slot] is streams[slot]:
                    self._release_slot(slot, streams[slot], error=e)
                adv[slot] = 0
        prev = flying[-1] if flying else None
        # a span's counts are its completed step's: `live` is this step's
        # only where the span completed none before it
        sp.attrs.setdefault("live", int(adv.sum()))
        if not adv.any():
            return None
        active = np.flatnonzero(adv)
        if (self.spec_k
                and all(not self._feed[s] for s in active)
                and all(int(pos[s]) + self.spec_k <= self.max_seq
                        for s in active)):
            self._spec_once(sp)
            return None
        ic = self.net.infer_cache
        with span("decode.dispatch") as dispatched:
            if self.paged:
                self._lazy_alloc(1)
                for slot, stream in enumerate(streams):
                    if self._slots[slot] is not stream:
                        adv[slot] = 0   # preempted or failed in page growth
                if not adv.any():
                    return None
            tok, keys = ((self._tok.copy(), self._keys.copy())
                         if prev is None else (prev["tok"], prev["keys"]))
            # counts: a stack with expert layers returns their [picks, hit]
            tok2, keys2, *counts, self._state = ic.decode(
                self.net.conf, self.net.params, self._state, tok, pos, keys,
                self._temps.copy(), page_table=self._pages())
            # dry only with nothing in flight: the first step of a run
            self._launched(sp, "restart" if self._runs_ahead() else "sync")
            if self.draft_net is not None:
                # non-spec rounds (feeds pending, or a slot near the table
                # edge) still advance the draft's carries over the same
                # token, so the draft stays in lockstep with what each
                # slot has consumed (behind the step just launched, and
                # never read: the account leaves it out)
                dn = self.draft_net
                _, _, self._draft_state = dn.infer_cache.decode(
                    dn.conf, dn.params, self._draft_state, self._tok.copy(),
                    self._pos.copy(), np.zeros((self.n_slots, 2), np.uint32),
                    np.zeros((self.n_slots,), np.float32))
        ahead = int(prev is not None)
        sp.set(ahead=ahead)
        self._dispatches.append(dispatched.seconds)
        with self._cv:
            self._steps += 1
            self._steps_ahead += ahead
        return {"streams": streams, "adv": adv, "pos": pos, "tok": tok2,
                "keys": keys2, "counts": counts, "ahead": ahead}

    def _complete(self, step: dict, sp: span) -> None:
        """The second half of a table step: ONE batched device->host
        transfer for the (tokens, keys, counts) triple, then per-slot
        delivery and the release of finished slots.  A token for a slot
        whose stream is no longer the one that was dispatched is
        dropped."""
        import jax

        with span("decode.readback") as readback:
            tok2, keys2, counts = jax.device_get(
                (step["tok"], step["keys"], step["counts"]))
        now_ns = time.monotonic_ns()
        now = now_ns / 1e9      # `time.monotonic()`'s clock
        # a step dispatched ahead started when the one before it was read
        # back (`_t_turn`), and the step behind this one starts now
        if step["ahead"]:
            self._periods.append((now_ns - self._t_turn) / 1e9)
        self._landed(now_ns)
        sp.set(live=int(step["adv"].sum()))
        # added, not set: a span that completes two steps carries their sum
        counted = self._counts.step(step["pos"], step["adv"], *counts)
        sp.set(**{name: sp.attrs.get(name, 0) + n
                  for name, n in counted.items()})
        with self._cv:
            self._step_sums.update(counted)
        emitted = 0
        with span("decode.deliver"):
            for slot, stream in enumerate(step["streams"]):
                if not step["adv"][slot] or self._slots[slot] is not stream:
                    continue
                if self._feed[slot]:
                    # prompt-feed step (longest-prefix admission): the
                    # table consumed one prompt token; the sampled output
                    # and advanced key are discarded so the stream's key
                    # stream stays identical to a cold prefill's
                    self._tok[slot] = self._feed[slot].pop(0)
                    self._pos[slot] += 1
                    continue
                first = stream.tokens_emitted == 0
                self._tok[slot] = tok2[slot]
                self._pos[slot] += 1
                self._keys[slot] = keys2[slot]
                if stream._deliver(int(tok2[slot]), now):
                    emitted += 1
                    if first:
                        with self._cv:
                            self._record_ttft_locked(stream)
                if (stream.tokens_emitted >= stream.max_new
                        or int(self._pos[slot]) >= self.max_seq):
                    self._release_slot(slot, stream)
        t_end = time.monotonic()
        self._note_block(t_end - self._t_mark,
                         self._waited_s + readback.seconds, emitted, now)
        self._t_mark, self._waited_s = t_end, 0.0

    def _note_block(self, wall: float, wait: float, emitted: int,
                    now: float) -> None:
        """Per-step bookkeeping: token totals + trailing rate window and
        the host-overhead split (host = wall minus the time spent blocked
        in device_get or waiting for a submission)."""
        host = max(wall - wait, 0.0)
        with self._cv:
            self._tokens_total += emitted
            self._recent_tokens.append((now, emitted))
            while (self._recent_tokens
                   and now - self._recent_tokens[0][0] > RATE_WINDOW_S):
                self._recent_tokens.popleft()
            self._host_s += host
            self._wall_s += wall

    def _spec_once(self, sp: span) -> None:
        """One speculative round: the draft proposes spec_k - 1 tokens
        per slot, ONE verify program chain-samples spec_k target tokens
        against them, and each slot emits its agreeing prefix (>= 1
        token — position 0 consumes the slot's current token, whose
        sample needs no draft to agree with).

        Parity: emitted tokens are the target's own chain samples, and
        sample i conditioned on exactly the tokens emitted before it —
        the acceptance rule cuts the chain at the first draft
        disagreement, which is precisely where sample i+1's conditioning
        would diverge from the emitted sequence.  The key stream advances
        once per ACCEPTED token (keys_all[:, e-1]), so trajectories
        match sequential decode at any temperature.  Draft carries roll
        back to the retained copy at each slot's accepted depth;
        mis-speculated K/V rows are rewritten before the next read."""
        import jax
        import jax.numpy as jnp

        ic = self.net.infer_cache
        dn = self.draft_net
        k = self.spec_k
        nb = self.n_slots
        dkeys = np.zeros((nb, 2), np.uint32)
        dtemps = np.zeros((nb,), np.float32)
        toks = np.zeros((nb, k), np.int32)
        toks[:, 0] = self._tok
        # draft phase: k - 1 proposals plus one catch-up step (so the
        # retained ladder reaches depth k for fully accepted chunks);
        # each call's input state is copied first because decode donates
        retained = [self._draft_state]
        cur = self._tok.copy()
        for i in range(1, k + 1):
            feed = jax.tree_util.tree_map(jnp.copy, retained[-1])
            nxt, _, out = dn.infer_cache.decode(
                dn.conf, dn.params, feed, cur,
                self._pos + np.int32(i - 1), dkeys, dtemps)
            self._launched(sp, "sync")
            retained.append(out)
            cur = np.asarray(nxt)
            self._landed(time.monotonic_ns())
            if i < k:
                toks[:, i] = cur
        if self.paged:
            self._lazy_alloc(k)
            if not any(s is not None for s in self._slots):
                return
        g, keys_all, self._state = ic.verify(
            self.net.conf, self.net.params, self._state, toks,
            self._pos.copy(), self._keys.copy(), self._temps.copy(),
            page_table=self._pages())
        self._launched(sp, "sync")
        g, keys_all = jax.device_get((g, keys_all))
        now_ns = time.monotonic_ns()
        self._landed(now_ns)
        now = now_ns / 1e9      # `time.monotonic()`'s clock
        e_idx = np.zeros((nb,), np.int32)
        emitted = 0
        accepted: List[int] = []
        for slot, stream in enumerate(self._slots):
            if stream is None:
                continue
            e = 1
            while e < k and toks[slot, e] == g[slot, e - 1]:
                e += 1
            e_idx[slot] = e
            first = stream.tokens_emitted == 0
            sent = 0
            for j in range(e):
                if stream.tokens_emitted >= stream.max_new:
                    break  # surplus accepted tokens past the budget
                if stream._deliver(int(g[slot, j]), now):
                    sent += 1
            emitted += sent
            accepted.append(sent)
            self._tok[slot] = g[slot, e - 1]
            self._keys[slot] = keys_all[slot, e - 1]
            self._pos[slot] += e
            if first and sent:
                with self._cv:
                    self._record_ttft_locked(stream)
            if (stream.tokens_emitted >= stream.max_new
                    or int(self._pos[slot]) >= self.max_seq):
                self._release_slot(slot, stream)
        # roll each draft carry to the retained state at its slot's
        # accepted depth (inactive slots keep depth 0 = unchanged)
        rows = jnp.arange(nb)
        self._draft_state = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves)[e_idx, rows], *retained)
        with self._cv:
            self._spec_rounds += 1
            self._tokens_total += emitted
            self._recent_tokens.append((now, emitted))
            while (self._recent_tokens
                   and now - self._recent_tokens[0][0] > RATE_WINDOW_S):
                self._recent_tokens.popleft()
            for c in accepted:
                h = self._accept_hist
                h["sum"] += c
                h["count"] += 1
                for i, bound in enumerate(ACCEPTED_TOKENS_BOUNDS):
                    if c <= bound:
                        h["counts"][i] += 1
                        break
                else:
                    h["inf"] += 1

    def _decode_loop(self) -> None:
        """The loop's thread is tiled by three top-level spans: `admit`
        (one a stream, in `_admit_pending`), `decode` (a table step of
        `_decode_rounds`, which come back here with nothing in flight
        whenever an admission is due) and `idle` (waiting for work)."""
        self._t_turn = time.monotonic_ns()      # the account opens dry
        while True:
            self._admit_pending()
            if any(s is not None for s in self._slots):
                self._decode_rounds()
                continue
            with self._cv:
                if self._pending:
                    continue
                if self._stop:
                    return
                # nothing to launch: the device's dry time up to the end of
                # this wait is the traffic's, and whoever ends the wait is
                # charged from there
                with span("idle") as sp:
                    self._cv.wait(timeout=0.5)
                    self._charge(sp, "empty")

    # -- observability -------------------------------------------------------
    def stats(self) -> dict:
        """Generation counters for `/v1/stats`: slot occupancy, queue
        depth, tokens/sec over the trailing window, TTFT percentiles +
        histogram, stream outcomes, and the fresh-compile count.

        Host time, by what covers what: `host_overhead_fraction` and
        `decode_host_seconds_total` cover DECODE STEPS ONLY (the decode
        rounds' wall time less what the loop spent blocked in
        `device_get` or waiting for a submission, `_note_block`); an
        admission is in neither.  With the loop a step ahead that is the
        host's BUSY share of a step, not time the device waits: it rises
        as the period falls to the device's step.  `decode_steps_total`
        counts the table steps dispatched and
        `decode_steps_ahead_total` those dispatched while the step before
        was still in flight.  **What the device WAITS for the host is
        `device_starved_seconds_total`**, `{cause: seconds}` over
        `STARVED_CAUSES`: the intervals in which the loop had nothing on
        the device, each measured from the host read that saw the last
        program finish to the return of the next program's call, and named
        by what the loop was doing (`admit`, `restart`, `sync`; `empty` is
        the traffic's: nothing live, nothing pending).  The two are not
        each other's complement: busy time behind a step in flight costs
        nothing, starved time is lost.  The same intervals lie on the
        `admit`, `decode` and `idle` spans (`starved_ns`, `starved_cause`,
        `starved_at`).  `admit_seconds_total` is the `admit` spans' sum and
        `queue_wait_seconds_total` what those streams waited from `submit`
        to their admission."""
        with self._cv:
            now = time.monotonic()
            recent = sum(c for t, c in self._recent_tokens
                         if now - t <= RATE_WINDOW_S)
            ttfts = sorted(self._ttfts)
            h = self._ttft_hist
            active = self._active
            out = {
                "slots": {"width": self.n_slots, "active": active,
                          "free": self.n_slots - active},
                "max_seq": self.max_seq,
                "prompt_buckets": list(self.prompt_buckets),
                "continuous": self.continuous,
                "queue_depth": len(self._pending),
                "streams": {"admitted": self._admitted,
                            "completed": self._completed,
                            "failed": self._failed},
                "tokens": self._tokens_total,
                "tokens_per_sec": round(
                    recent / min(max(now - self._t_start, 1e-9),
                                 RATE_WINDOW_S), 2),
                "ttft_ms": {
                    "p50": round(MicroBatcher._percentile(ttfts, 0.50) * 1e3,
                                 3),
                    "p99": round(MicroBatcher._percentile(ttfts, 0.99) * 1e3,
                                 3),
                },
                "ttft_hist_s": {
                    "bounds": list(LATENCY_BUCKETS_S),
                    "counts": list(h["counts"]),
                    "inf": h["inf"],
                    "sum": h["sum"],
                    "count": h["count"],
                },
                "host_overhead_fraction": (
                    round(self._host_s / self._wall_s, 4)
                    if self._wall_s > 0 else 0.0),
                "decode_host_seconds_total": round(self._host_s, 6),
                "decode_steps_total": self._steps,
                "decode_steps_ahead_total": self._steps_ahead,
                "device_starved_seconds_total": {
                    cause: round(ns / 1e9, 6)
                    for cause, ns in self._starved_ns.items()},
                "admit_seconds_total": round(self._admit_s, 6),
                "queue_wait_seconds_total": round(self._queue_wait_s, 6),
                # the served tree's weight operands held in the compute
                # dtype beside their masters (`InferCache._operand_params`),
                # and the trees cast so far: one a params tree
                "operand_bytes_narrowed": self.net.infer_cache.operand_bytes_narrowed,
                "operand_casts_total": self.net.infer_cache.operand_casts,
            }
            # what the steps read of the layers' state, summed
            out.update(self._counts.totals(self._step_sums))
        if self.paged:
            with self._cv:
                live_tokens = sum(
                    int(self._pos[s]) for s, st in enumerate(self._slots)
                    if st is not None)
                out["kv_pages"] = {
                    "page_size": self.page_size,
                    "total": self.n_pages,
                    "free": self._pool.free_count,
                    "live": self._pool.live_count,
                    "live_tokens": live_tokens,
                    "live_bytes": self._pool.live_count * self._page_bytes(),
                    "preempted_streams": self._preempted,
                }
        if self.prefix_cache_enabled:
            with self._cv:
                out["prefix_cache"] = {
                    "hits": self._prefix_hits,
                    "misses": self._prefix_misses,
                    "entries": len(self._prefix_lru),
                    "match": self.prefix_match,
                }
        if self.spec_k:
            with self._cv:
                h = self._accept_hist
                out["speculative"] = {
                    "k": self.spec_k,
                    "rounds": self._spec_rounds,
                    "accepted_per_step": (round(h["sum"] / h["count"], 3)
                                          if h["count"] else 0.0),
                    "accepted_hist": {
                        "bounds": list(ACCEPTED_TOKENS_BOUNDS),
                        "counts": list(h["counts"]),
                        "inf": h["inf"],
                        "sum": h["sum"],
                        "count": h["count"],
                    },
                }
        out["fresh_compiles"] = self.net.infer_cache.stats.misses
        if self.draft_net is not None:
            # warmed means warmed END TO END: the draft's programs count
            out["fresh_compiles"] += self.draft_net.infer_cache.stats.misses
        return out

    def _page_bytes(self) -> int:
        """Bytes one physical K/V page occupies across every attention
        layer (K and V)."""
        total = 0
        for lay in (self._state or ()):
            if lay and "k" in lay and "h" not in lay:
                total += 2 * self.page_size * int(np.prod(
                    lay["k"].shape[2:])) * lay["k"].dtype.itemsize
        return total
