"""Search-based autotuning over the compiled-program space (ROADMAP 6).

TVM-style flow: for each tunable group, enumerate the registry's declared
search space, prune candidates whose analytic cost (the registry's cost
hints, `optimize/tunables.py`) is >= 2x the incumbent's *before* compiling
anything, then compile and measure the survivors as real programs through
the existing step-cache/infer-cache machinery — warm call outside the
timed region, min-of-rounds with an injectable clock.  Winners beat the
incumbent by a margin (default 2%) or the default stands, so a tuned
table is never slower than stock.

The winning :class:`~deeplearning4j_tpu.optimize.tunables.TunedTable` is
keyed per (conf fingerprint, device kind) and persisted through the disk
compile cache's opaque-payload path, so replicas and future sessions
inherit it at `set_compile_cache` time with ``fresh_tunes == 0``.

Fault points: ``tune.measure`` (per candidate measurement — a failure
skips the candidate, counted, and the search completes) and ``tune.load``
(table read — a failure degrades to registry defaults with one warning;
serving never blocks on tuning).
"""
from __future__ import annotations

import logging
import time
from typing import Optional, Sequence

import numpy as np

from deeplearning4j_tpu.optimize import tunables
from deeplearning4j_tpu.optimize.step_cache import conf_fingerprint
from deeplearning4j_tpu.reliability import faults

log = logging.getLogger(__name__)

#: candidates whose analytic cost is >= this multiple of the incumbent's
#: are never compiled (TVM's "don't measure the obviously bad" pruning)
PRUNE_RATIO = 2.0

#: a challenger must beat the incumbent by this fraction or the default
#: stands — guarantees tuned >= default within noise (ties keep defaults)
MIN_GAIN = 0.02


class _Search:
    """Bookkeeping shared by every group: measured/pruned/failed counts
    plus the winning entries."""

    def __init__(self, rounds: int, clock):
        self.rounds = max(1, int(rounds))
        self.clock = clock
        self.entries = {}
        self.groups = {}
        self.candidates_measured = 0
        self.candidates_pruned = 0
        self.measure_failures = 0

    def measure(self, step) -> Optional[float]:
        """Min-of-rounds seconds for `step()`, or None when the
        measurement faulted (candidate skipped, search continues)."""
        try:
            faults.fire("tune.measure")
            step()  # warm: compile + first dispatch outside the timed region
            best = None
            for _ in range(self.rounds):
                t0 = self.clock()
                step()
                dt = self.clock() - t0
                best = dt if best is None or dt < best else best
            self.candidates_measured += 1
            return best
        except Exception as e:  # noqa: BLE001 — one bad candidate never ends a search
            log.warning("tune: candidate measurement failed: %r", e)
            self.measure_failures += 1
            return None

    def pick(self, group, key, candidates, default_value, run,
             throughput=None):
        """Measure `run(c)` for each candidate; record the winner under
        `key` iff it beats the default by MIN_GAIN.  `candidates` must
        include the default (the incumbent baseline).  `throughput(c)`
        converts each candidate's time to a rows/s-style figure for the
        report (higher is better); without it, lower seconds win."""
        timings = {}
        for cand in candidates:
            t = self.measure(lambda c=cand: run(c))
            if t is None:
                continue
            timings[cand] = t
        report = {"candidates": {repr(c): t for c, t in timings.items()},
                  "default": default_value, "winner": default_value}
        self.groups.setdefault(group, {})[key or group] = report
        if not timings:
            return default_value

        def score(c):
            # higher is better
            return throughput(c) / timings[c] if throughput \
                else 1.0 / timings[c]

        base = score(default_value) if default_value in timings else None
        winner = max(timings, key=score)
        if base is None or score(winner) > base * (1.0 + MIN_GAIN):
            report["winner"] = winner
            if winner != default_value:
                self.entries[key] = winner
        return report["winner"]


def _prune(search, tun, candidates, incumbent, **ctx):
    """Drop candidates whose analytic cost hint is >= PRUNE_RATIO x the
    incumbent's (never compiled); groups without hints keep everything."""
    if tun.cost_hint is None or incumbent is None:
        return list(candidates)
    base = tun.cost_hint(incumbent, **ctx)
    kept = []
    for c in candidates:
        if c != incumbent and tun.cost_hint(c, **ctx) >= PRUNE_RATIO * base:
            search.candidates_pruned += 1
        else:
            kept.append(c)
    return kept


def _attention_shapes(conf):
    """(seq, head_dim) pairs the conf's attention layers run at."""
    from deeplearning4j_tpu.nn.conf import LayerType
    seq = max([int(c.max_seq_len) for c in conf.confs
               if getattr(c, "max_seq_len", 0)] or [0])
    shapes = []
    for c in conf.confs:
        if c.layer_type == LayerType.ATTENTION and seq > 0:
            hd = int(c.n_in) // max(1, int(c.n_heads))
            if (seq, hd) not in shapes:
                shapes.append((seq, hd))
    return shapes


def _tune_attention(net, search, rng):
    """Per-(seq, head_dim) flash block sweep — fwd and bwd tables.

    Measured through the real Pallas entry point (interpret mode off-TPU,
    where candidates tie and the measured defaults stand — the table only
    moves on hardware where blocks genuinely differ)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nd.pallas_kernels import (flash_attention,
                                                      pick_attention_blocks)
    for seq, hd in _attention_shapes(net.conf):
        q, k, v = (jnp.asarray(rng.standard_normal((1, seq, 2, hd)),
                               jnp.float32) for _ in range(3))
        qualifier = "%dx%d" % (seq, hd)
        for name, bwd in (("attention.block_fwd", False),
                          ("attention.block_bwd", True)):
            tun = tunables.REGISTRY[name]
            incumbent = pick_attention_blocks(seq, hd, bwd=bwd)
            cands = [c for c in tun.space
                     if seq % c[0] == 0 and seq % c[1] == 0]
            if incumbent not in cands:
                cands.insert(0, incumbent)
            cands = _prune(search, tun, cands, incumbent,
                           seq=seq, head_dim=hd)

            def run(c, bwd=bwd):
                if bwd:
                    fn = jax.grad(lambda a: flash_attention(
                        a, k, v, True, fused_bwd=True, block_q_bwd=c[0],
                        block_k_bwd=c[1]).sum())
                    jax.block_until_ready(fn(q))
                else:
                    jax.block_until_ready(
                        flash_attention(q, k, v, True, c[0], c[1]))

            search.pick("attention", "%s@%s" % (name, qualifier), cands,
                        incumbent, run)
            tunables.note_fresh()


def _serve_input(conf, rows, rng):
    """A well-formed serve batch for the conf's input layer: int token
    ids [rows, seq] for embedding-first models (seq capped by the
    learned positional table), float features [rows, n_in] otherwise."""
    from deeplearning4j_tpu.nn.conf import LayerType
    c0 = conf.confs[0]
    if c0.layer_type == LayerType.EMBEDDING:
        seq = int(getattr(c0, "max_seq_len", 0)) or 16
        return rng.integers(0, int(c0.n_in),
                            size=(rows, seq)).astype(np.int32)
    return np.asarray(rng.standard_normal((rows, int(c0.n_in))), np.float32)


def _tune_serve(net, search, rng):
    """Row-count sweep through the infer cache: rows/s at each candidate
    target picks `batcher.target_rows`; the measured ladder up to the
    winner becomes `infer.bucket_ladder` so warm processes pre-seed the
    same buckets.  Ascending order so each candidate compiles at its own
    exact bucket (`bucket_rows` grows on demand)."""
    tun = tunables.REGISTRY["batcher.target_rows"]
    incumbent = tun.default
    cands = sorted(set(tun.space) | {incumbent})

    def run(rows):
        np.asarray(net.output(_serve_input(net.conf, rows, rng)))

    winner = search.pick("serve", "batcher.target_rows", cands, incumbent,
                         run, throughput=lambda rows: float(rows))
    tunables.note_fresh()
    measured = search.groups["serve"]["batcher.target_rows"]["candidates"]
    ladder = tuple(c for c in cands if repr(c) in measured and c <= winner)
    if winner != incumbent and ladder:
        search.entries["infer.bucket_ladder"] = ladder


def _tune_decode(net, search, max_seq):
    """Slot-width sweep through the compiled decode step: tokens/s at
    each table width picks `decode.slots` (every live slot yields one
    token per step, so wider tables win until the step time grows
    faster than the width)."""
    from deeplearning4j_tpu.nn import decode as decode_mod
    try:
        decode_mod.check_generative(net.conf)
    except Exception:  # noqa: BLE001 — non-generative conf: nothing to tune
        return
    bound = decode_mod.positional_bound(net.conf)
    if bound:
        max_seq = min(int(max_seq), int(bound))
    if net.params is None:
        net.init()
    ic = net.infer_cache
    tun = tunables.REGISTRY["decode.slots"]
    incumbent = tun.default
    cands = sorted(set(tun.space) | {incumbent})

    def run(slots):
        import jax.numpy as jnp
        state = ic.init_decode_state(net.conf, slots, max_seq)
        tok = jnp.zeros((slots,), jnp.int32)
        pos = jnp.zeros((slots,), jnp.int32)
        keys = jnp.zeros((slots, 2), jnp.uint32)
        temps = jnp.zeros((slots,), jnp.float32)
        # decode donates its state buffers: thread the returned state
        for _ in range(4):
            tok, keys, state = ic.decode(net.conf, net.params, state,
                                         tok, pos, keys, temps)
            pos = pos + 1
        np.asarray(tok)

    search.pick("decode", "decode.slots", cands, incumbent, run,
                throughput=lambda slots: float(slots))
    tunables.note_fresh()
    _tune_decode_steps(net, search, max_seq)


def _tune_decode_steps(net, search, max_seq):
    """K sweep through the fused decode block: tokens/s at each
    steps-per-dispatch picks `decode.steps_per_dispatch` (each dispatch
    advances every slot K tokens and costs ONE host round-trip, so
    bigger K wins until per-step device time dominates the amortised
    host overhead)."""
    import jax
    import jax.numpy as jnp

    ic = net.infer_cache
    slots = 2
    tun = tunables.REGISTRY["decode.steps_per_dispatch"]
    incumbent = tun.default
    cands = sorted(k for k in set(tun.space) | {incumbent}
                   if k <= max_seq)

    def run(k):
        state = ic.init_decode_state(net.conf, slots, max_seq)
        tok = jnp.zeros((slots,), jnp.int32)
        pos = jnp.zeros((slots,), jnp.int32)
        keys = jnp.zeros((slots, 2), jnp.uint32)
        temps = jnp.zeros((slots,), jnp.float32)
        steps = 0
        while steps + k <= max_seq:
            rem = jnp.full((slots,), k, jnp.int32)
            _, tok, keys, state = ic.decode_multi(
                net.conf, net.params, state, tok, pos, keys, temps,
                rem, k)
            pos = pos + k
            steps += k
        jax.device_get(tok)

    # every candidate decodes (about) the same token count, so the
    # tokens-per-run numerator is the actual work done, not K itself
    search.pick("decode", "decode.steps_per_dispatch", cands, incumbent,
                run, throughput=lambda k: float(slots * (max_seq // k) * k))
    tunables.note_fresh()


def tune_model(net, groups: Sequence[str] = ("attention", "serve",
                                             "decode"),
               rounds: int = 3, seed: int = 0, clock=time.perf_counter,
               max_seq: int = 64) -> dict:
    """Search the registry's config space for `net` and return the report
    (winning entries + counters).  Deterministic under a fixed seed and
    an injected clock: candidate order is fixed and data comes from the
    seeded rng."""
    t0 = clock()
    if net.params is None:
        net.init()
    rng = np.random.default_rng(seed)
    search = _Search(rounds, clock)
    if "attention" in groups:
        _tune_attention(net, search, rng)
    if "serve" in groups:
        _tune_serve(net, search, rng)
    if "decode" in groups:
        _tune_decode(net, search, max_seq)
    fp = conf_fingerprint(net.conf)
    report = {
        "fingerprint": fp,
        "groups": search.groups,
        "entries": {k: v for k, v in sorted(search.entries.items())},
        "candidates_measured": search.candidates_measured,
        "candidates_pruned": search.candidates_pruned,
        "measure_failures": search.measure_failures,
        "rounds": search.rounds,
        "seed": int(seed),
        "tune_seconds": clock() - t0,
    }
    return report


def tune_and_store(net, store=None, force: bool = False, **kw) -> dict:
    """The `cli tune` entry point: inherit an existing valid table from
    the store (``fresh_tunes == 0``) unless `force`, else search, persist
    the winners, and install the table process-wide.  Returns the report
    with the `tuning` status block attached."""
    fp = conf_fingerprint(net.conf)
    kind = store.platform.get("device_kind", "none") if store is not None \
        else _device_kind()
    if store is not None and not force:
        existing = tunables.load_table(store, fp, kind)
        if existing is not None:
            tunables.install(existing, source="disk")
            return {
                "fingerprint": fp,
                "device_kind": kind,
                "entries": dict(existing.entries),
                "candidates_measured": 0,
                "candidates_pruned": 0,
                "measure_failures": 0,
                "tune_seconds": 0.0,
                "tuning": tunables.status(),
            }
    report = tune_model(net, **kw)
    table = tunables.TunedTable(report["entries"], device_kind=kind,
                                fingerprint=fp,
                                meta={"rounds": report["rounds"],
                                      "seed": report["seed"]})
    if store is not None:
        tunables.save_table(store, table)
    tunables.install(table, source="fresh")
    report["device_kind"] = kind
    report["tuning"] = tunables.status()
    return report


def _device_kind() -> str:
    from deeplearning4j_tpu.optimize.persist import platform_info
    return platform_info().get("device_kind", "none")
