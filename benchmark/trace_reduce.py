"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

What a TPU trace holds (looked at by hand, PR 24): a plane `/device:TPU:<n>`
for each chip, with a line `XLA Modules` (one event for each run of a
compiled program, named `jit_<function>(<fingerprint>)`) and a line
`XLA Ops` (one event for each operation inside it); and a plane `/host:CPU`
with a line for each host thread, where `jax.profiler.TraceAnnotation`
spans appear under their own names.  Times are nanoseconds on one clock
(the host's and the device's agree to about a millisecond).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

WINDOW = "bench:window"     # the annotation that brackets the traced window
_PREFIX = "bench:"


def quiet_profile():
    """Device and host spans, no Python call tracing: it slows the host."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def _union(spans: np.ndarray) -> np.ndarray:
    """Merge [start, end) rows that touch or overlap; rows sorted by start."""
    if len(spans) == 0:
        return spans.reshape(0, 2)
    spans = spans[np.argsort(spans[:, 0], kind="stable")]
    ends = np.maximum.accumulate(spans[:, 1])
    first = np.ones(len(spans), bool)
    first[1:] = spans[1:, 0] > ends[:-1]
    starts = spans[first, 0]
    last = np.append(np.nonzero(first)[0][1:] - 1, len(spans) - 1)
    return np.stack([starts, ends[last]], axis=1)


def _op_name(text: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def reduce_trace(path: str, idle_label: str = "host", window=None) -> dict:
    """Reduce one `.xplane.pb`.

    Returns window_s, busy_s (mean over device planes of the union of the
    op intervals inside the window), `modules` {program with its fingerprint:
    [seconds of each run]} over all chips, `device_ops` and `idle_gaps` (the ten largest,
    [name, seconds]), and `chips`.  A gap is named by the innermost
    `bench:` annotation some host thread had open at its middle, else
    `idle_label`.  `window` (start, end in ns) stands in for the annotation.
    Without a device plane (a CPU rehearsal) busy_s is None.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device.append(plane)
        elif plane.name.startswith("/host:"):
            host.append(plane)

    notes = []                      # (name, start, end) of bench: spans
    for plane in host:
        for line in plane.lines:
            for name, start, dur in _events(line):
                if name.startswith(_PREFIX):
                    notes.append((name, start, start + dur))
    if window is None:
        windows = [n for n in notes if n[0] == WINDOW]
        if not windows:
            raise ValueError(f"the trace holds no {WINDOW!r} annotation")
        window = windows[0][1:]
    w0, w1 = window
    labels = sorted((n for n in notes if n[0] != WINDOW),
                    key=lambda n: n[2] - n[1])        # innermost first

    def label_at(t: float) -> str:
        for name, a, b in labels:
            if a <= t <= b:
                return name[len(_PREFIX):]
        return idle_label

    out = {"window_s": (w1 - w0) / 1e9, "busy_s": None, "chips": len(device),
           "modules": {}, "device_ops": [], "idle_gaps": []}
    if not device:
        return out
    modules = defaultdict(list)
    ops = defaultdict(float)
    gaps = defaultdict(float)
    busy = []
    for plane in device:
        lines = {line.name: line for line in plane.lines}
        for name, start, dur in _events(lines["XLA Modules"]):
            if w0 <= start + dur / 2 <= w1:
                modules[name].append(dur / 1e9)
        evs = _events(lines.get("XLA Ops") or lines["XLA Modules"])
        spans = np.array([(s, s + d) for _, s, d in evs], float).reshape(-1, 2)
        for (name, _, dur), (a, b) in zip(evs, spans):
            if w0 <= (a + b) / 2 <= w1:
                ops[_op_name(name)] += dur / 1e9
        merged = np.clip(_union(spans), w0, w1)
        merged = merged[merged[:, 1] > merged[:, 0]]
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) / 1e9)
        edges = np.concatenate([[w0], merged.reshape(-1), [w1]]).reshape(-1, 2)
        for a, b in edges:
            if b > a:
                gaps[label_at((a + b) / 2)] += (b - a) / 1e9
    out["busy_s"] = float(np.mean(busy))
    out["modules"] = dict(modules)
    n = len(device)
    out["device_ops"] = [[k, float(v) / n] for k, v in
                         sorted(ops.items(), key=lambda kv: -kv[1])[:10]]
    out["idle_gaps"] = [[k, float(v) / n] for k, v in
                        sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]
    return out


def program_runs(reduced: dict, most_like: int) -> list:
    """Seconds of each run of one program, known by how often it ran: the
    program's caches name every compiled function alike (`jit_program`), so
    the name does not tell them apart.  Of the programs whose number of runs
    in the window is within a tenth (or 2) of `most_like`, it is the one
    that took the most device time."""
    slack = max(2, most_like // 10)
    near = [runs for runs in reduced["modules"].values()
            if abs(len(runs) - most_like) <= slack]
    return max(near, key=sum) if near else []
