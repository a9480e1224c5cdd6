"""The decode loop's account of what it has put on the device (ISSUE 36).

The loop's thread is the only one that launches programs and the only one
that reads them back, so it knows how many it has on the device.  Every
interval in which that is none is written onto the top-level span that ends
it (`starved_ns`, `starved_cause`, `starved_at`) and added to a counter of
its cause (`stats()["device_starved_seconds_total"]`).  These cases count
and order: which span is charged, with which cause, between which two spans
of the record the interval lies; none holds a duration to a wall clock.
Round it: the admission's two new children, the exporter's four lines, the
benchmark's three readers on hand-made records, and the audit that lays the
account over a device trace.

Tier-1: CPU-only, tiny models."""

import importlib.util
import os
import sys
import textwrap
import threading
import time

import jax
import pytest

from generate_helpers import (_clean_faults, _compiled_tokens, _draft_net,  # noqa: F401
                              lstm_net, transformer_net)
from deeplearning4j_tpu.analysis import starved_audit
from deeplearning4j_tpu.reliability import faults
from deeplearning4j_tpu.serving.batcher import STARVED_CAUSES, ContinuousBatcher
from deeplearning4j_tpu.serving.metrics import (FAMILIES, parse_prometheus_text,
                                                replica_metrics)
from deeplearning4j_tpu.utils import profiling
from deeplearning4j_tpu.utils.profiling import Span


@pytest.fixture
def ledger(monkeypatch):
    """The step hook: every launch and every read of the loop, in order,
    with the count of programs in flight after it."""
    events = []
    launched, landed = ContinuousBatcher._launched, ContinuousBatcher._landed

    def on_launch(self, sp, cause):
        launched(self, sp, cause)
        events.append(("launch", sp.name, cause, self._in_flight))

    def on_read(self, now_ns):
        landed(self, now_ns)
        events.append(("read", None, None, self._in_flight))

    monkeypatch.setattr(ContinuousBatcher, "_launched", on_launch)
    monkeypatch.setattr(ContinuousBatcher, "_landed", on_read)
    return events


def loop_spans(record):
    """The top-level spans of the loop's thread, in the loop's order."""
    thread = next(s.thread for s in record if s.name in ("admit", "decode"))
    return sorted((s for s in record if s.thread == thread
                   and s.parent is None), key=lambda s: s.start_ns)


def children(record, span, name):
    return sorted((s for s in record if s.parent == span.sid
                   and s.name == name), key=lambda s: s.start_ns)


def charged(spans):
    return [s for s in spans if "starved_ns" in s.attrs]


def check_account(record, cb, ledger):
    """What holds of every run: the intervals lie on top-level spans of the
    loop's thread under one of the four causes, do not overlap, add up to
    the span's `starved_ns` and, by cause, to the counters of `stats()`; the
    count of programs in flight never falls under 0 and ends at 0."""
    loop = loop_spans(record)
    marked = charged(record)
    assert marked and all(s in loop for s in marked)
    sums = dict.fromkeys(STARVED_CAUSES, 0)
    last = 0
    for s in charged(loop):
        assert s.name in ("admit", "decode", "idle")
        assert s.attrs["starved_cause"] in STARVED_CAUSES
        assert (s.name == "idle") == (s.attrs["starved_cause"] == "empty")
        assert s.attrs["starved_ns"] == sum(b - a for a, b
                                            in s.attrs["starved_at"])
        for a, b in s.attrs["starved_at"]:
            assert last <= a <= b <= s.end_ns
            last = b
        sums[s.attrs["starved_cause"]] += s.attrs["starved_ns"]
    totals = cb.stats()["device_starved_seconds_total"]
    assert sorted(totals) == sorted(STARVED_CAUSES)
    for cause in STARVED_CAUSES:    # a microsecond a span: `stats()` rounds
        assert totals[cause] == pytest.approx(sums[cause] / 1e9, abs=1e-6)
    counts = [e[3] for e in ledger]
    assert counts and min(counts) >= 0 and counts[-1] == 0
    assert cb._in_flight == 0


def fresh(net, **kwargs):
    """A batcher that has not started, over an empty record, so that the
    record holds all of its loop."""
    kwargs.setdefault("n_slots", 2)
    kwargs.setdefault("max_seq", 64)
    cb = ContinuousBatcher(net, prompt_buckets=(8,), auto_start=False,
                           **kwargs)
    profiling.clear()
    return cb


def test_a_steady_run_ahead_is_never_charged(lstm_net, ledger):
    """One stream: its admission is charged (the account opens dry), the
    step after it restarts the loop, and no step dispatched while its
    predecessor was in flight carries a `starved_ns`.  In flight: one after
    a steady read, two after a steady launch."""
    cb = fresh(lstm_net)
    try:
        s = cb.submit([1, 2], max_new_tokens=24)
        cb.start()
        assert len(list(s.tokens(timeout=60.0))) == 24
    finally:
        cb.stop()
    record = profiling.spans()
    steps = [s for s in loop_spans(record) if "ahead" in s.attrs]
    assert [s.attrs["ahead"] for s in steps] == [0] + [1] * 22
    assert [s.attrs.get("starved_cause") for s in steps] == \
        ["restart"] + [None] * 22
    assert not any("starved_at" in s.attrs for s in steps[1:])
    (admit,) = [s for s in loop_spans(record) if s.name == "admit"]
    assert admit.attrs["starved_cause"] == "admit"
    steady = [e for e in ledger if e[1] == "decode"][1:]
    assert steady and all(e[3] == 2 for e in steady)
    check_account(record, cb, ledger)


def test_an_admission_into_a_running_loop_is_one_admit_and_one_restart(
        lstm_net, ledger):
    """A second stream joins a running one: its `admit` span is charged
    `admit` from the read of the last step in flight to the launch of its
    own program, the `decode` span after it `restart` from the read of the
    admission's token to the launch of the step; nothing else is charged
    between them."""
    cb = fresh(lstm_net)
    try:
        a = cb.submit([1, 2], max_new_tokens=56)
        cb.start()
        tokens = a.tokens(timeout=60.0)
        for _ in range(6):
            next(tokens)
        b = cb.submit([3], max_new_tokens=4)
        assert len(list(b.tokens(timeout=60.0))) == 4
        assert len(list(tokens)) == 50
    finally:
        cb.stop()
    record = profiling.spans()
    loop = loop_spans(record)
    (joined,) = [s for s in loop if s.name == "admit" and s.rid == b.rid]
    at = loop.index(joined)
    before, after = loop[at - 1], loop[at + 1]
    assert before.name == after.name == "decode"
    assert (joined.attrs["starved_cause"], after.attrs["starved_cause"],
            after.attrs["ahead"]) == ("admit", "restart", 0)
    assert "starved_ns" not in before.attrs     # it only completed a step
    # the admission's interval: [the last step's read, its own launch]
    ((dry, launched),) = joined.attrs["starved_at"]
    (read,) = children(record, before, "decode.readback")
    (deliver,) = children(record, before, "decode.deliver")
    (prefill,) = children(record, joined, "admit.prefill")
    (launch,) = children(record, prefill, "admit.launch")
    assert read.end_ns <= dry <= deliver.start_ns
    assert launch.start_ns <= launched <= launch.end_ns
    # the restart's: [the admission's read, the step's launch]
    ((dry, launched),) = after.attrs["starved_at"]
    (read,) = children(record, prefill, "admit.readback")
    (dispatch,) = children(record, after, "decode.dispatch")
    assert read.start_ns <= dry <= read.end_ns
    assert dispatch.start_ns <= launched <= dispatch.end_ns
    # every other charge of the run is the first admission's and its restart
    assert [s.attrs["starved_cause"] for s in charged(loop)
            if s.name != "idle"] == ["admit", "restart", "admit", "restart"]
    check_account(record, cb, ledger)


def test_admissions_back_to_back_are_both_charged_to_admit(lstm_net, ledger):
    """Two streams pending when the loop starts are admitted one after the
    other: the second's dry time, from the read of the first's token to its
    own launch, is an admission's, and the one step that restarts the loop
    follows both."""
    cb = fresh(lstm_net)
    try:
        streams = [cb.submit([1, 2], max_new_tokens=6, rng_seed=0),
                   cb.submit([3], max_new_tokens=6, rng_seed=1)]
        cb.start()
        assert [len(list(s.tokens(timeout=60.0))) for s in streams] == [6, 6]
    finally:
        cb.stop()
    record = profiling.spans()
    loop = [s for s in loop_spans(record) if s.name != "idle"]
    assert [s.name for s in loop[:3]] == ["admit", "admit", "decode"]
    assert [s.attrs["starved_cause"] for s in loop[:3]] == \
        ["admit", "admit", "restart"]
    (prefill,) = children(record, loop[0], "admit.prefill")
    (read,) = children(record, prefill, "admit.readback")
    ((dry, _),) = loop[1].attrs["starved_at"]
    assert read.start_ns <= dry <= read.end_ns
    assert [s.attrs["starved_cause"] for s in charged(loop)] == \
        ["admit", "admit", "restart"]
    check_account(record, cb, ledger)


@pytest.mark.parametrize("mode", ["paged", "speculative", "feed"])
def test_a_loop_that_reads_every_step_back_is_charged_to_sync(lstm_net,
                                                              ledger, mode):
    """Over the paged pool, in speculative rounds and while a slot feeds a
    prompt's rest the device runs dry between a read and
    the next launch, and that is the loop's doing: cause `sync` on every
    such step, `restart` on none but the first step that may run ahead."""
    kwargs = {"paged": dict(page_size=4),
              "speculative": dict(draft_net=_draft_net(), spec_k=3),
              "feed": dict(prefix_cache=True, prefix_match="longest")}[mode]
    cb = fresh(lstm_net, max_seq=16, **kwargs)
    prompt = [1, 2, 3, 4, 5, 6]
    try:
        cb.start()
        if mode == "feed":
            cb.generate(prompt[:2], max_new_tokens=2)   # seeds the cache
            profiling.clear()
            del ledger[:]
        assert len(cb.generate(prompt, max_new_tokens=8, rng_seed=1)) == 8
    finally:
        cb.stop()
    record = profiling.spans()
    steps = charged(s for s in loop_spans(record) if s.name == "decode")
    causes = [s.attrs["starved_cause"] for s in steps]
    if mode == "feed":      # three prompt tokens fed, then the loop is free
        assert causes == ["sync"] * 3 + ["restart"]
    else:
        assert causes and set(causes) == {"sync"}
    if mode == "paged":     # every step of it: dispatched, read, dry again
        assert len(steps) == len([s for s in loop_spans(record)
                                  if "ahead" in s.attrs])
    if mode == "speculative":   # a round is the draft's steps and the verify
        assert max(len(s.attrs["starved_at"]) for s in steps) == 3 + 1
    if mode != "feed":
        totals = cb.stats()["device_starved_seconds_total"]
        assert totals["restart"] == 0 and totals["sync"] > 0
        check_account(record, cb, ledger)


def test_an_idle_wait_is_empty_and_the_admission_pays_from_its_end(
        lstm_net, ledger):
    """With nothing live and nothing pending the dry device is the
    traffic's: the `idle` spans carry it as `empty`, one after the other
    without a hole, and the admission that ends the wait is charged from the
    instant the last `idle` interval ends, not from the last read."""
    cb = fresh(lstm_net)
    try:
        cb.start()
        assert len(cb.generate([1, 2], max_new_tokens=3)) == 3
        seen = len([s for s in profiling.spans() if s.name == "idle"])
        late = time.monotonic() + 30.0
        while (len([s for s in profiling.spans() if s.name == "idle"]) <= seen
               and time.monotonic() < late):
            time.sleep(0.01)        # the loop has gone to wait
        s = cb.submit([3], max_new_tokens=3)
        assert len(list(s.tokens(timeout=60.0))) == 3
    finally:
        cb.stop()
    record = profiling.spans()
    loop = loop_spans(record)
    (joined,) = [x for x in loop if x.name == "admit" and x.rid == s.rid]
    waits = loop[:loop.index(joined)]
    waits = waits[max(i for i, x in enumerate(waits) if x.name != "idle") + 1:]
    assert waits and all(w.attrs["starved_cause"] == "empty" for w in waits)
    # the first wait starts at the last read; each next where the last ended
    ends = [w.attrs["starved_at"][-1][1] for w in waits]
    starts = [w.attrs["starved_at"][0][0] for w in waits]
    assert starts[1:] == ends[:-1]
    assert starts[0] < waits[0].start_ns
    ((dry, launched),) = joined.attrs["starved_at"]
    assert dry == ends[-1] and waits[-1].start_ns <= dry <= waits[-1].end_ns
    assert joined.attrs["starved_ns"] == launched - dry
    assert cb.stats()["device_starved_seconds_total"]["empty"] > 0
    check_account(record, cb, ledger)


@pytest.mark.parametrize("fault", [None, "decode.step", "generate.admit"])
def test_the_counters_are_the_spans_sums_and_the_count_ends_at_zero(
        lstm_net, ledger, fault):
    """Four streams through two slots, with and without a fault that ends
    one of them: the per-cause counters of `stats()` are the record's sums,
    the count of programs in flight is never negative and is 0 once the loop
    has stopped."""
    if fault:
        faults.arm(fault, "raise", nth=3)
    cb = fresh(lstm_net, max_seq=32)
    try:
        streams = [cb.submit([1 + i, 2], max_new_tokens=5 + 3 * i, rng_seed=i)
                   for i in range(4)]
        cb.start()
        done = 0
        for s in streams:
            try:
                done += bool(list(s.tokens(timeout=60.0)))
            except faults.FaultInjected:
                pass
    finally:
        cb.stop()
    assert done == (4 if fault is None else 3)
    st = cb.stats()["streams"]
    assert st["failed"] == (0 if fault is None else 1)
    record = profiling.spans()
    check_account(record, cb, ledger)
    launches = [e for e in ledger if e[0] == "launch"]
    assert len(launches) == len([e for e in ledger if e[0] == "read"])
    assert {e[2] for e in launches} == {"admit", "restart"}


def test_launch_and_readback_tile_the_admissions_prefill(lstm_net,
                                                         monkeypatch):
    """`admit.prefill` is its two children and nothing else: `admit.launch`
    up to the return of the program's call, `admit.readback` blocked until
    the first token is on the host.  With the loop's reads slowed to 20 ms
    the parent's own time is what two spans cost to open and close."""
    real = jax.device_get

    def slow(tree):
        if threading.current_thread().name == "dl4j-decode":
            time.sleep(0.02)
        return real(tree)

    cb = fresh(lstm_net)
    try:
        cb.start()
        cb.generate([9], max_new_tokens=2)      # warm
        monkeypatch.setattr(jax, "device_get", slow)
        profiling.clear()
        for i in range(3):
            assert len(cb.generate([1 + i, 2], max_new_tokens=2)) == 2
    finally:
        monkeypatch.undo()
        cb.stop()
    record = profiling.spans()
    own = profiling.self_time(record)
    prefills = [s for s in record if s.name == "admit.prefill"]
    assert len(prefills) == 3
    for p in prefills:
        kids = sorted((s for s in record if s.parent == p.sid),
                      key=lambda s: s.start_ns)
        assert [k.name for k in kids] == ["admit.launch", "admit.readback"]
        assert p.start_ns <= kids[0].start_ns <= kids[0].end_ns \
            <= kids[1].start_ns <= kids[1].end_ns <= p.end_ns
        assert kids[1].end_ns - kids[1].start_ns >= 20_000_000
    assert sum(own[p.sid] for p in prefills) < 0.03 * sum(
        p.end_ns - p.start_ns for p in prefills)


def test_the_four_causes_reach_the_prometheus_page(lstm_net):
    family = "dl4j_serving_device_starved_seconds_total"
    assert FAMILIES[family] == ("counter", ("cause",))
    cb = fresh(lstm_net)
    try:
        cb.start()
        cb.generate([1, 2], max_new_tokens=4)
    finally:
        cb.stop()
    totals = cb.stats()["device_starved_seconds_total"]
    page = replica_metrics({"generation": cb.stats()})
    got = {dict(labels)["cause"]: value
           for labels, value in parse_prometheus_text(page)[family].items()}
    assert sorted(got) == sorted(STARVED_CAUSES)
    assert got == pytest.approx(totals)
    assert got["admit"] > 0 and got["restart"] > 0 and got["sync"] == 0
    assert f"# TYPE {family} counter" in page
    # a replica of an older build sends no such key: no line, no fault
    assert family not in replica_metrics({"generation": {"tokens": 1}})


# -- the benchmark's three readers ------------------------------------------
def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(
            os.path.dirname(__file__), os.pardir, "benchmark",
            "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


MS = 1_000_000


def hand_made(marked=True):
    """A window of 100 ms on the loop's thread 7, from the first of four
    submits to the last: an admission at 0 (charged 50 ms: a stall that is
    not typical), steps, two admissions back to back at 40 and 50 ms and the
    step that takes the loop up again, more steps, an idle wait, and the
    last admission at 100, which ends outside.  `marked` puts the account's
    attributes and the admission's two children on it; without, it is the
    parent's record."""
    def sp(name, start, end, sid, parent=None, rid=None, **attrs):
        return Span(name, start * MS, end * MS, parent, rid, 7, attrs, sid)

    def starve(ms, cause):
        return {"starved_ns": int(ms * MS), "starved_cause": cause} \
            if marked else {}

    rec = [sp("admit", 0, 9, 1, rid=1, queue_wait_ns=0, bucket=512,
              **starve(50, "admit"))]       # no `decode` before it: no run
    rec += [sp("decode", 10 + 10 * i, 19 + 10 * i, 10 + i, k=1, live=1,
               **(starve(4, "restart") if i == 0 else {})) for i in range(3)]
    rec += [sp("admit", 40, 49, 2, rid=2, queue_wait_ns=0, bucket=512,
               **starve(3, "admit")),
            sp("admit", 50, 59, 3, rid=3, queue_wait_ns=0, bucket=1024,
               **starve(1, "admit")),
            sp("decode", 60, 69, 20, k=1, live=3, **starve(5, "restart")),
            sp("decode", 70, 79, 21, k=1, live=3),
            sp("idle", 80, 89, 22, **starve(9, "empty")),
            sp("decode", 90, 99, 23, k=1, live=3, **starve(2, "sync")),
            sp("admit", 100, 109, 4, rid=4, queue_wait_ns=0, bucket=512,
               **starve(7, "admit"))]
    if marked:
        rec += [sp("admit.prefill", 41, 48, 30, parent=2),
                sp("admit.launch", 41, 42, 31, parent=30),
                sp("admit.readback", 42, 48, 32, parent=30),
                sp("admit.prefill", 51, 58, 33, parent=3),
                sp("admit.launch", 51, 53, 34, parent=33),
                sp("admit.readback", 53, 58, 35, parent=33)]
    return {"counters": {"requests": 4}, "spans": rec}


def test_the_reader_of_the_starved_share():
    """Typical starved milliseconds of the host's three causes (count times
    median: `admit` 3 x 3, the stalled one counted as its kind; `restart`
    2 x 4.5; `sync` 1 x 2; `empty` is the traffic's; the last admission
    ends outside) over the window's 100 ms, first submit to last."""
    assert reader("decode.starved_share")(hand_made()) == pytest.approx(
        100.0 * (3 * 3 + 2 * 4.5 + 2) / 100)


def test_the_reader_of_the_turnround():
    """One run of admissions lies between two `decode` spans of the window:
    3 + 1 ms of its two admissions and 5 of the step after them."""
    assert reader("admit.turnround_ms_p50")(hand_made()) == pytest.approx(9.0)


def test_the_reader_of_the_blocked_rate():
    """`admit.readback` of 6 ms in bucket 512 and of 5 ms in bucket 1,024:
    12 and 5 ms a thousand tokens, the median of the two."""
    assert reader("admit.blocked_ms_per_ktoken")(hand_made()) == \
        pytest.approx(8.5)


@pytest.mark.parametrize("name", ["decode.starved_share",
                                  "admit.turnround_ms_p50",
                                  "admit.blocked_ms_per_ktoken"])
def test_a_record_without_the_account_leaves_the_metric_out(name):
    assert reader(name)(hand_made(marked=False)) is None
    assert reader(name)({"counters": {"requests": 0}, "spans": []}) is None


# -- the audit ----------------------------------------------------------------
def test_the_audit_lays_the_account_over_the_devices_gaps():
    """A device that runs two steps back to back, waits 5 ms for an
    admission (4.6 of them inside the account's interval), runs it, waits 3
    ms for the restart (the account says 3.1: the program started 0.2 ms
    before its call returned, and the read came 0.1 ms late), and once waits
    half a millisecond that no interval covers."""
    ms = 1e6
    programs = [(0, 10 * ms), (10.05 * ms, 20 * ms), (25 * ms, 40 * ms),
                (43 * ms, 53 * ms), (53.5 * ms, 60 * ms)]
    ops = [(a, b - 0.1 * ms) for a, b in programs]
    starved = [(20.2 * ms, 24.8 * ms, "admit"), (40.1 * ms, 43.2 * ms, "restart"),
               (70 * ms, 80 * ms, "empty")]      # outside the window
    got = starved_audit.audit(programs, ops, starved, (0, 60 * ms))
    want = {"window_s": 0.060, "idle_s": 0.00905,
            "idle_unaccounted_s": 0.0010, "idle_launch_gaps_s": 0.00005,
            "idle_inside_programs_s": 0.0005, "starved_busy_s": 0.0002,
            "read_lag_ms_p50": 0.15, "launch_lead_ms_p50": 0.0}
    for key, value in want.items():
        assert got[key] == pytest.approx(value, abs=1e-9), key
    assert got["long_gaps"] == 3
    assert got["idle_starved_s"] == pytest.approx(
        {"admit": 0.0046, "restart": 0.0029, "empty": 0.0})
    assert got["starved_s"] == pytest.approx(
        {"admit": 0.0046, "restart": 0.0031, "empty": 0.0})
    # the parts are the whole
    assert (sum(got["idle_starved_s"].values()) + got["idle_unaccounted_s"]
            + got["idle_launch_gaps_s"] + got["idle_inside_programs_s"]
            ) == pytest.approx(got["idle_s"])


def test_the_audit_finds_how_far_the_device_plane_runs_behind():
    """A trace's device plane shows a program starting before the host has
    enqueued it: programs launched onto an idle device start, on the host's
    clock, when their enqueue ends.  Three such launches (1.4, 1.5 and 1.3 ms
    early) give the lag; the two steps queued behind a running one, whose
    enqueues lie a whole step before their starts, are not asked."""
    ms = 1e6
    programs = [(0, 10 * ms), (10.01 * ms, 20 * ms), (25 * ms, 40 * ms),
                (43 * ms, 53 * ms), (53.01 * ms, 60 * ms), (70 * ms, 80 * ms)]
    enqueues = [(a - 0.1 * ms, a) for a in (
        2 * ms, 26.4 * ms, 44.5 * ms, 45 * ms, 71.3 * ms)]
    assert starved_audit.device_clock_lag(programs, enqueues) == \
        pytest.approx(1.4 * ms)
    assert starved_audit.device_clock_lag(programs, []) is None
    assert starved_audit.device_clock_lag(programs[:2], enqueues) is None


def test_the_audit_finds_the_clocks_offset_in_the_admissions():
    """The trace's clock starts where the profiler did; an `admit` span and
    its annotation are one object stamped twice, so each gives the offset,
    and the median forgives a stray one."""
    record = hand_made()["spans"]
    offset = -7_000_000_123
    in_trace = {1: 0 * MS + offset, 2: 40 * MS + offset + 900,
                3: 50 * MS + offset - 400, 9: 5.0}      # rid 9: not recorded
    assert starved_audit.clock_offset(in_trace, record) == offset
    with pytest.raises(ValueError, match="share no"):
        starved_audit.clock_offset({9: 5.0}, record)
    # the intervals the record carries, with their causes
    live = [s._replace(attrs={**s.attrs, "starved_at": [[1, 2], [5, 9]]})
            if s.sid == 20 else s for s in record]
    assert starved_audit.starved_intervals(live) == [(1, 2, "restart"),
                                                     (5, 9, "restart")]


def test_the_audit_keeps_a_modules_trace_and_the_record(tmp_path, monkeypatch):
    """`--keep DIR -m module`: the module runs as `python3 -m` would, every
    profiler session it opens leaves its `.xplane.pb` under DIR though the
    module deletes its own, and the span record lands beside it and reads
    back equal.  A CPU trace has no device plane, and the audit says so."""
    (tmp_path / "traced_toy.py").write_text(textwrap.dedent("""
        import shutil, sys, tempfile
        import jax
        from deeplearning4j_tpu.utils.profiling import span
        where = tempfile.mkdtemp()
        jax.profiler.start_trace(where)
        with span("admit", rid=int(sys.argv[1]), queue_wait_ns=0) as sp:
            sp.set(starved_ns=5, starved_cause="admit", starved_at=[(1, 6)])
        jax.profiler.stop_trace()
        shutil.rmtree(where)
        sys.exit(0)
        """))
    monkeypatch.syspath_prepend(str(tmp_path))
    profiling.clear()
    keep = tmp_path / "kept"
    (path,) = starved_audit.run_kept(str(keep), "traced_toy", ["41"])
    assert path == str(keep / "0.xplane.pb") and os.path.getsize(path) > 0
    assert sys.argv[0] != "traced_toy"
    assert jax.profiler.stop_trace.__name__ == "stop_trace"
    back = starved_audit.load_record(str(keep / "spans.json"))
    (mine,) = [s for s in back if s.rid == 41]
    assert mine.attrs["starved_cause"] == "admit"
    assert starved_audit.starved_intervals(back) == [(1, 6, "admit")]
    with pytest.raises(ValueError, match="no device plane"):
        starved_audit.audit_trace(path, back)
