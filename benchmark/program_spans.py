"""What the per-layer readers of PR 25 share: the program's own span record
and its programs' names in the trace.

How a reader gets at the program's spans.  The program keeps one bounded
record of closed spans in `deeplearning4j_tpu/utils/profiling.py`; every
span is `(name, start_ns, end_ns, parent, rid, thread, attrs, sid)` on the
host's monotonic clock, `parent` the `sid` of the span that was open on the
same thread, `rid` the request it belongs to (a generation stream's number,
a `fit` call's number; a child takes its parent's).  The benchmark runs in
the program's process, so a reader imports the record after the job has
run: `record(seen)`.  A test may put its own list under `seen["spans"]`.
A program that keeps no record (a commit before PR 25) gives None, and so
does every reader built on it: the metric is then left out of the line.
The record holds the whole process, so every reader takes its spans from
the measured window alone (`serve_window`, `last_fit`), and none reads a
record that has dropped spans.

The same spans are in the `.xplane.pb` of a traced run as `dl4j:<name>`
annotations on the host's lines; `trace_reduce` does not read them yet.

Programs are found in `seen["trace"]["modules"]` by name: both caches of
the program call a compiled function `dl4j_<entry>`, which the trace shows
as `jit_dl4j_<entry>(<fingerprint>)`.
"""

from __future__ import annotations

import statistics

DECODE = "jit_dl4j_decode"
PREFILL = "jit_dl4j_prefill"
_NAMED = "jit_dl4j_"


def record(seen: dict):
    """The span record: `seen["spans"]` where a test gives one, else the
    program's own.  None where the program has none, and where its bounded
    record has let spans go: a share over a cut record would be wrong."""
    if "spans" in seen:
        return seen["spans"]
    try:
        from deeplearning4j_tpu.utils import profiling

        return None if profiling.dropped() else profiling.spans()
    except (ImportError, AttributeError):
        return None


def seconds(span) -> float:
    return (span.end_ns - span.start_ns) / 1e9


def median_ms(spans):
    """Median duration of `spans` in ms; None of none."""
    if not spans:
        return None
    return statistics.median(seconds(s) for s in spans) * 1e3


def typical_seconds(spans) -> float:
    """For each name among `spans`, the count of its spans times their
    median duration, summed: what the spans took had each been typical."""
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(seconds(s))
    return sum(len(v) * statistics.median(v) for v in by_name.values())


def share(part, whole):
    """100 x the typical seconds of `part` over those of `whole`.  Not the
    sums: per-layer metrics are read in traced runs alone, where the
    harness's profiler start and stop hold a thread of the program for
    seconds at a time (one `admit.init_row` of 4.9 s, one `prefetch.wait`
    of 8.2 s; PERF.md 6, PR 25), and a sum takes that for the program's.
    The price: a stall of the program's own that is rare is not seen."""
    if not whole:
        return None
    total = typical_seconds(whole)
    return 100.0 * typical_seconds(part) / total if total > 0 else None


def submits(admits) -> dict:
    """{rid: when `submit` took the stream}, from its first `admit` span:
    the span's start less its `queue_wait_ns`."""
    out = {}
    for s in admits:
        out.setdefault(s.rid, s.start_ns - s.attrs["queue_wait_ns"])
    return out


def serve_window(seen: dict):
    """The record's spans that lie inside a serving job's measured window,
    without the warm-up, the ramp and the drain (a process's first
    admission is three times a later one).  The harness hands a
    reader no clock, so the window is found in the record: callers send
    nothing once it has ended, so its `requests` are the last the batcher
    took, and it runs from the first of them submitted to the last."""
    rec = record(seen)
    n = seen["counters"].get("requests")
    if not rec or not n:
        return None
    took = sorted(submits(s for s in rec if s.name == "admit").values())[-n:]
    if not took:
        return None
    return [s for s in rec if took[0] <= s.start_ns and s.end_ns <= took[-1]]


def named(seen: dict, name: str):
    """The serving window's spans called `name`; None without a record."""
    window = serve_window(seen)
    if window is None:
        return None
    return [s for s in window if s.name == name]


def loop_thread(seen: dict):
    """Top-level spans of the batcher loop's thread in the serving window,
    `idle` left out: the thread is the one that ran the `admit` spans."""
    admits = named(seen, "admit")
    if not admits:
        return None
    thread = admits[0].thread
    return [s for s in serve_window(seen) if s.thread == thread
            and s.parent is None and s.name != "idle"]


def last_fit(seen: dict):
    """Spans of the consumer's thread in the newest `fit` call (the
    window's: the calls before it are the warm-up's and hold the compiles;
    their rid is the call's number), up to the end of its last step: what
    follows is the drain, the read of a loss some 15 queued steps away."""
    rec = record(seen)
    steps = [s for s in rec or () if s.name == "fit.step"]
    if not steps:
        return None
    last = max(steps, key=lambda s: (s.rid, s.end_ns))
    return [s for s in rec if s.rid == last.rid and s.thread == last.thread
            and s.end_ns <= last.end_ns]


def top_level(spans):
    return [s for s in spans if s.parent is None]


def module_runs(seen: dict, prefix: str):
    """Device seconds of every run of the programs whose name in the trace
    starts with `prefix`; None without a trace or without such a program."""
    trace = seen.get("trace")
    if not trace:
        return None
    runs = [t for name, ts in trace["modules"].items()
            if name.startswith(prefix) for t in ts]
    return runs or None


def median_module_ms(seen: dict, prefix: str):
    runs = module_runs(seen, prefix)
    return None if runs is None else statistics.median(runs) * 1e3


def other_module_seconds(seen: dict, prefixes):
    """Device seconds of every module that starts with none of `prefixes`,
    summed; None unless the trace names the program's modules at all."""
    trace = seen.get("trace")
    if not trace or not any(n.startswith(_NAMED) for n in trace["modules"]):
        return None
    return sum(sum(ts) for name, ts in trace["modules"].items()
               if not name.startswith(tuple(prefixes)))
