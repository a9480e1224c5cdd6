"""The program's one tracing module: spans and names.

The reference's observability is wall-clock job timing
(`WorkerActor.java:199-203` "Job took X ms"), iteration listeners
(`ScoreIterationListener.java:43-46`), named counters in the state tracker
(`StateTracker.increment/count`, `StateTracker.java:54-56`), and the YARN
`metricsReport(map<string,long>)` RPC (`IterativeReduceService.java:28`).

Here that surface is two things, and every module of the package that
times or names anything does it through them (counters live with what
they count: `ContinuousBatcher.stats()` and `MicroBatcher.stats()`, which
`serving/metrics.py` exports, and the trainer's own trackers):

  spans     `span(name, rid=None, **attrs)` brackets host work.  It opens a
            `jax.profiler.TraceAnnotation` named `dl4j:<name>`, so that
            with a profiler session open the span lands in the same
            `.xplane.pb`, on the same clock, as the device's lines (with
            none open that costs a flag test), and it appends the closed
            span to a bounded in-memory record that `spans()` snapshots.
            The record is always on; there is no switch.
  names     `program_name(entry)` and `named(fn, entry)` give every compiled
            program a stable name (`jit_dl4j_decode(...)` on the trace's
            `XLA Modules` line); `scope(name)` and `layer_scope(i, conf)`
            are `jax.named_scope`, which puts the layer into the metadata
            of every device operation.  Names and scopes are metadata:
            they join no cache key and change no equation.

`Tracer.start/stop/trace` is the operator's way to open a profiler session.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import re
import threading
import time
from typing import Dict, List, NamedTuple, Optional

#: how many closed spans the record keeps; older ones are dropped, counted
MAX_SPANS = 1 << 16


class Span(NamedTuple):
    """One closed span.  `parent` is the `sid` of the span that was open on
    the same thread when this one opened (None at top level); times are
    `time.monotonic_ns()`."""

    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    rid: Optional[int]
    thread: int
    attrs: dict
    sid: int


_record: "collections.deque[Span]" = collections.deque(maxlen=MAX_SPANS)
_record_lock = threading.Lock()
_dropped = 0
_sids = itertools.count(1)
_open = threading.local()       # .stack: the spans open on this thread
_annotation = None              # jax.profiler.TraceAnnotation, once imported


class span:
    """Context manager round one piece of host work.

    `rid` keys the span to a request (a generation stream, a `fit` call);
    a span opened without one takes its parent's.  `attrs` go to the trace
    annotation as its arguments and to the record; `set(**attrs)` adds
    what is known only once the work has run (the record alone gets
    those).  An exception inside the block closes the span and passes."""

    __slots__ = ("name", "rid", "attrs", "sid", "parent", "start_ns",
                 "end_ns", "_note")

    def __init__(self, name: str, rid: Optional[int] = None, **attrs):
        self.name = name
        self.rid = rid
        self.attrs = attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        """Duration of the closed span."""
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "span":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        parent = stack[-1] if stack else None
        self.parent = None if parent is None else parent.sid
        if self.rid is None and parent is not None:
            self.rid = parent.rid
        self.sid = next(_sids)
        stack.append(self)
        if self.rid is None:
            self._note = _annotation("dl4j:" + self.name, **self.attrs)
        else:
            self._note = _annotation("dl4j:" + self.name, rid=self.rid,
                                     **self.attrs)
        self._note.__enter__()
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        global _dropped
        self.end_ns = time.monotonic_ns()
        self._note.__exit__(*exc)
        _open.stack.pop()
        closed = Span(self.name, self.start_ns, self.end_ns, self.parent,
                      self.rid, threading.get_ident(), self.attrs, self.sid)
        with _record_lock:
            if len(_record) == _record.maxlen:
                _dropped += 1
            _record.append(closed)
        return False


def spans() -> List[Span]:
    """Snapshot of the record, oldest first (ordered by when each closed)."""
    with _record_lock:
        return list(_record)


def dropped() -> int:
    """Spans the bounded record has let go of since the last `clear()`."""
    return _dropped


def clear() -> None:
    """Empty the record (tests; a long-lived process never needs to)."""
    global _dropped
    with _record_lock:
        _record.clear()
        _dropped = 0


def self_time(record: List[Span]) -> Dict[int, int]:
    """{sid: nanoseconds} of each span's duration less what its children
    in `record` cover: the time no finer span accounts for."""
    out = {s.sid: s.end_ns - s.start_ns for s in record}
    for s in record:
        if s.parent in out:
            out[s.parent] -= s.end_ns - s.start_ns
    return out


# -- names ------------------------------------------------------------------
def program_name(entry) -> str:
    """`dl4j_<entry>` with everything but letters, digits and `_` turned
    to `_`: "decode-multi[4]" -> "dl4j_decode_multi_4"."""
    return "dl4j_" + re.sub(r"[^0-9A-Za-z_]+", "_", str(entry)).strip("_")


def named(fn, entry):
    """`fn` under the name `dl4j_<entry>`: `jax.jit` calls the module it
    compiles after the function (`jit_dl4j_<entry>`).  The same trace, the
    same jaxpr; the name joins no cache key."""

    def call(*args):
        return fn(*args)

    call.__name__ = call.__qualname__ = program_name(entry)
    return call


def scope(name: str):
    """`jax.named_scope`: every operation traced inside carries `name` in
    its metadata.  No equation, output bit or cache key changes."""
    import jax

    return jax.named_scope(name)


def layer_scope(index: int, conf):
    """The scope of layer `index` of a stack: `L<i>.<layer_type>`, or where
    the layer's typed settings name their own kind (`scope_kind`: a window
    layer beside a full one of one type), `L<i>.<that>`."""
    kind = (getattr(getattr(conf, "layer_spec", None), "scope_kind", None)
            or str(conf.layer_type))
    return scope(f"L{index}.{kind}")


# -- profiler sessions --------------------------------------------------------
class Tracer:
    """XLA trace capture (TensorBoard/Perfetto): the operator's way to open
    a profiler session round a piece of a run.  Every `span` of the
    program that closes inside it is in the capture."""

    def __init__(self, trace_dir: str = "/tmp/dl4j_tpu_trace"):
        self.trace_dir = trace_dir
        self._active = False

    def start(self) -> None:
        import jax

        if not self._active:
            jax.profiler.start_trace(self.trace_dir)
            self._active = True

    def stop(self) -> None:
        import jax

        if self._active:
            jax.profiler.stop_trace()
            self._active = False

    @contextlib.contextmanager
    def trace(self):
        self.start()
        try:
            yield self
        finally:
            self.stop()
