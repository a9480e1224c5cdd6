"""The decode loop's account of the device's dry time, checked against the
device's own clock.

`ContinuousBatcher` keeps an account of what it has put on the device and
writes every interval in which it had nothing there onto the span that ended
it (`starved_at`, `starved_cause`; `serving/batcher.py::_charge`).  That is
the host's view: an interval runs from the host read that saw the last
program finish to the return of the next program's call.  A profiler trace
(`.xplane.pb`) of the same run holds the device's view: when each compiled
program and each of its operations ran.  This module lays the one over the
other and says how far they agree:

    idle_s                  the traced window less the union of the device's
                            operations (what the benchmark calls idle)
    idle_starved_s          {cause: seconds} of the device's long gaps
                            between programs (`MIN_GAP_NS` and more) that lie
                            inside a starved interval of that cause
    idle_unaccounted_s      the long gaps' seconds inside no starved interval
    idle_launch_gaps_s      the short gaps between programs: the next program
                            was queued, the device took this long to start it
    idle_inside_programs_s  gaps between the operations of one program
    starved_s               {cause: seconds} the account charged in the window
    starved_busy_s          of them, seconds in which a program was running:
                            the account's error (a launched program may start
                            before its call returns; a read returns after the
                            transfer, not when the program ends)
    read_lag_ms_p50         over the intervals: from the end of the last
                            program that ran before one to its start (what
                            the host saw late: the transfer, the wake-up)
    launch_lead_ms_p50      from the start of the first program after one's
                            start to its end (positive: the program ran
                            before its call returned)

Two clocks have to be brought together first.  A span of the record and
its `dl4j:` annotation in the trace are one object stamped twice
(`utils/profiling.py::span`), so every `admit` span, matched by its rid,
gives the offset between `time.monotonic_ns()` and the trace's host clock;
the median over all of them is used.  And the trace's device plane runs
behind its host planes (by 1.3 to 1.5 ms in the traces of PR 36, drifting a
fifth of a millisecond in 5 s: a program "starts" before the host has
enqueued it): the runtime's own `DoEnqueueProgram` events on the host give
that lag, as the median, over the programs launched onto an idle device, of
the enqueue's end less the program's start (`device_clock_lag_ms`; a trace
without such events is read as it stands, and says so with None).

    python3 -m deeplearning4j_tpu.analysis.starved_audit TRACE.xplane.pb SPANS.json
    python3 -m deeplearning4j_tpu.analysis.starved_audit --keep DIR -m <module> [its arguments]

The second form runs `<module>` in this process as `python3 -m` would, keeps
the `.xplane.pb` of every profiler session it opens (a benchmark run deletes
its own) under DIR beside the process's span record, and audits each.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import runpy
import shutil
import statistics
import sys
from typing import Dict, Iterable, List, Sequence, Tuple

from deeplearning4j_tpu.utils import profiling

#: a gap between two programs this long or longer is the device waiting for
#: the host; a shorter one is the device starting a program already queued
MIN_GAP_NS = 300_000

#: the annotation a benchmark run brackets its traced window with
WINDOW = "bench:window"

#: the TPU runtime's host event that hands a program to the device
ENQUEUE = "DoEnqueueProgram"

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """The intervals merged where they touch or overlap, sorted."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What `merged` (disjoint, sorted, inside [lo, hi]) leaves of [lo, hi]."""
    edges = [lo] + [t for ab in merged for t in ab] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def overlap(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Summed length of the intersection of two disjoint, sorted lists."""
    starts = [a for a, _ in ys]
    total = 0.0
    for a, b in xs:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(ys) and ys[i][0] < b:
            total += max(0.0, min(b, ys[i][1]) - max(a, ys[i][0]))
            i += 1
    return total


def audit(programs: Iterable[Interval], ops: Iterable[Interval],
          starved: Iterable[Tuple[float, float, str]], window: Interval,
          min_gap_ns: float = MIN_GAP_NS) -> dict:
    """The overlay, from plain intervals on ONE clock, in nanoseconds:
    `programs` every run of a compiled program on the device, `ops` every
    operation inside them, `starved` the account's `(from, until, cause)`,
    `window` the traced window.  Seconds out (the module's docstring names
    each key)."""
    lo, hi = window
    ran = union(clip(programs, lo, hi))
    worked = union(clip(ops, lo, hi))
    between = gaps(ran, lo, hi)
    long_gaps = [g for g in between if g[1] - g[0] >= min_gap_ns]
    by_cause: Dict[str, List[Interval]] = {}
    for a, b, cause in starved:
        by_cause.setdefault(cause, []).extend(clip([(a, b)], lo, hi))
    by_cause = {cause: union(v) for cause, v in sorted(by_cause.items())}
    charged = union(iv for v in by_cause.values() for iv in v)
    starts, ends = [a for a, _ in ran], [b for _, b in ran]
    lags, leads = [], []
    for a, b in charged:
        before = bisect.bisect_right(ends, a) - 1   # the last one over by `a`
        if before >= 0 and before + 1 < len(ran):
            lags.append(a - ends[before])
            leads.append(b - starts[before + 1])
    s = 1e-9
    return {
        "window_s": (hi - lo) * s,
        "idle_s": (hi - lo - length(worked)) * s,
        "idle_starved_s": {cause: overlap(long_gaps, v) * s
                           for cause, v in by_cause.items()},
        "idle_unaccounted_s": (length(long_gaps)
                               - overlap(long_gaps, charged)) * s,
        "idle_launch_gaps_s": (length(between) - length(long_gaps)) * s,
        "idle_inside_programs_s": (length(ran) - overlap(ran, worked)) * s,
        "long_gaps": len(long_gaps),
        "starved_s": {cause: length(v) * s for cause, v in by_cause.items()},
        "starved_busy_s": overlap(charged, ran) * s,
        "read_lag_ms_p50": statistics.median(lags) / 1e6 if lags else None,
        "launch_lead_ms_p50": statistics.median(leads) / 1e6 if leads else None,
    }


# -- from a trace and a record ------------------------------------------------
def starved_intervals(record) -> List[Tuple[int, int, str]]:
    """`(from_ns, until_ns, cause)` of every interval the record's spans
    carry, on the record's clock."""
    return [(a, b, s.attrs["starved_cause"]) for s in record
            for a, b in s.attrs.get("starved_at", ())]


def clock_offset(admits_in_trace: Dict[int, float], record) -> float:
    """Trace clock less record clock: the median, over the `admit` spans
    both hold (by rid, a stream's first), of the annotation's start less
    the span's."""
    first = {}
    for s in record:
        if s.name == "admit":
            first.setdefault(s.rid, s.start_ns)
    both = [admits_in_trace[rid] - first[rid]
            for rid in admits_in_trace if rid in first]
    if not both:
        raise ValueError("the trace and the record share no `admit` span")
    return statistics.median(both)


def device_clock_lag(programs: Sequence[Interval],
                     enqueues: Sequence[Interval],
                     min_gap_ns: float = MIN_GAP_NS):
    """How far the device plane's clock runs behind the host planes': a
    program launched onto an idle device (a gap of `min_gap_ns` before it)
    cannot start before the host has enqueued it, so the end of the enqueue
    nearest its start, less that start, is the lag (and the few microseconds
    the device takes to begin).  The median over such programs; None
    without enqueue events or such programs."""
    programs = sorted(programs)
    ends = sorted(b for _, b in enqueues)
    lags = []
    for (_, before), (start, _) in zip(programs, programs[1:]):
        if start - before < min_gap_ns or not ends:
            continue
        i = bisect.bisect_left(ends, start)
        near = min(ends[max(i - 1, 0):i + 1], key=lambda t: abs(t - start))
        lags.append(near - start)
    return statistics.median(lags) if lags else None


def read_trace(path: str) -> dict:
    """What the overlay needs of an `.xplane.pb`: the first device plane's
    program runs and operations, the `bench:window` annotation (else the
    extent of the programs), the start of every `dl4j:admit` annotation by
    rid (a stream's first), and the runtime's enqueues."""
    from jax.profiler import ProfileData

    programs, ops, admits, window, enqueues = [], [], {}, None, []
    device = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            device = plane.name
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    into = programs if line.name == "XLA Modules" else ops
                    into.extend((e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name == ENQUEUE:
                        enqueues.append((e.start_ns,
                                         e.start_ns + e.duration_ns))
                    elif e.name == "dl4j:admit":
                        rid = dict(e.stats).get("rid")
                        if rid is not None:
                            admits[rid] = min(e.start_ns,
                                              admits.get(rid, e.start_ns))
    if device is None:
        raise ValueError(f"{path} holds no device plane: it was not traced "
                         f"on a TPU")
    if window is None:
        window = (min(a for a, _ in programs), max(b for _, b in programs))
    return {"programs": programs, "ops": ops or programs, "admits": admits,
            "window": window, "device": device, "enqueues": enqueues}


def audit_trace(path: str, record) -> dict:
    """`audit` of the trace at `path` against the span record of the same
    run (`profiling.spans()`, or `load_record`)."""
    trace = read_trace(path)
    offset = clock_offset(trace["admits"], record)
    lag = device_clock_lag(trace["programs"], trace["enqueues"])
    on_host = [[(a + (lag or 0), b + (lag or 0)) for a, b in trace[key]]
               for key in ("programs", "ops")]
    out = audit(*on_host, [(a + offset, b + offset, cause)
                           for a, b, cause in starved_intervals(record)],
                trace["window"])
    out["admits_matched"] = len(trace["admits"])
    out["device_clock_lag_ms"] = None if lag is None else lag / 1e6
    return out


def dump_record(path: str, record) -> None:
    with open(path, "w") as f:
        json.dump([list(s) for s in record], f)


def load_record(path: str) -> List[profiling.Span]:
    with open(path) as f:
        return [profiling.Span(*row) for row in json.load(f)]


def run_kept(keep: str, module: str, argv: List[str]) -> List[str]:
    """Run `module` as `python3 -m module argv` would, in this process, with
    every profiler session's `.xplane.pb` copied under `keep` as the session
    stops, and the process's span record written beside them when the module
    ends (`spans.json`).  Returns the traces' paths."""
    import jax

    os.makedirs(keep, exist_ok=True)
    kept: List[str] = []
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    dirs: List[str] = []

    def start_kept(log_dir, *args, **kwargs):
        dirs.append(str(log_dir))
        return start(log_dir, *args, **kwargs)

    def stop_kept():
        stop()
        found = sorted(glob.glob(os.path.join(dirs[-1], "**", "*.xplane.pb"),
                                 recursive=True))
        if found:
            kept.append(os.path.join(keep, f"{len(kept)}.xplane.pb"))
            shutil.copyfile(found[-1], kept[-1])

    jax.profiler.start_trace, jax.profiler.stop_trace = start_kept, stop_kept
    argv0 = sys.argv
    sys.argv = [module] + list(argv)
    try:
        runpy.run_module(module, run_name="__main__", alter_sys=True)
    except SystemExit as e:
        if e.code not in (None, 0):
            raise
    finally:
        sys.argv = argv0
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
    dump_record(os.path.join(keep, "spans.json"), profiling.spans())
    return kept


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["--keep"] and argv[2:3] == ["-m"] and len(argv) >= 4:
        paths = run_kept(argv[1], argv[3], argv[4:])
        record = profiling.spans()
        results = [audit_trace(path, record) for path in paths]
    elif len(argv) == 2:
        results = [audit_trace(argv[0], load_record(argv[1]))]
    else:
        print(__doc__.split("\n\n")[-2], file=sys.stderr)
        return 2
    for result in results:
        print("starved_audit " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
