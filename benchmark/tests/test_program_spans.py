"""The readers PR 25 adds, each on a hand-made `seen` (modules by name, a
span list) against the number worked out by hand, and None where its source
is missing; then both cells' rehearsals print every `program_span` metric."""

import collections
import importlib.util
import json
import os

import pytest

from benchmark import program_spans, run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
Span = collections.namedtuple(
    "Span", "name start_ns end_ns parent rid thread attrs sid")
MS = 1_000_000


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(HERE, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


#: this PR's per-layer metrics, by name: the cell each reads in
ADDED = {
    "decode.device_ms_p50": "serve-1b3-decode",
    "admit.device_ms": "serve-1b3-decode",
    "admit.host_ms_p50": "serve-1b3-decode",
    "admit.scatter_share": "serve-1b3-decode",
    "queue.wait_ms_p50": "serve-1b3-decode",
    "batcher.admit_wall_share": "serve-1b3-decode",
    "decode.dispatch_ms_p50": "serve-1b3-decode",
    "decode.deliver_ms_p50": "serve-1b3-decode",
    "input.queue_wait_share": "train-590m-2k",
    "train.dispatch_block_share": "train-590m-2k",
}
FROM_SPANS = sorted(set(ADDED) - {"decode.device_ms_p50", "admit.device_ms"})


def serve_spans():
    """The loop's thread (7).  The ramp: an admission of 4900 ms (rid 0: an
    eager program compiled) and a decode step of 50.  The window, whose
    four requests are rids 1 to 4: idle 10 ms; an admission of 100 ms
    (rid 1, waited 2 ms) with children of 10, 20, 60 and 5 ms; a decode
    step of 20 (dispatch 3, readback 15, deliver 1); an admission of 200
    (rid 2, waited 50) that scatters for 140; a decode step of 22 (dispatch
    5, deliver 3); an admission of 3000 (rid 3, waited 10) that the
    profiler's start held in `admit.init_row` for 2900 and that scatters
    for 100; a decode step of 20 (dispatch 4, deliver 2).  Rid 4 is
    submitted as that step ends, which is the last the callers send: its
    admission of 300 and a decode step of 90 are the drain.  A caller's
    thread (9) holds a span of its own."""
    out, sid = [], iter(range(1, 100))

    def add(name, start, ms, parent=None, rid=None, thread=7, **attrs):
        s = Span(name, start * MS, (start + ms) * MS, parent, rid, thread,
                 attrs, next(sid))
        out.append(s)
        return s.sid

    a = add("admit", -6000, 4900, rid=0, queue_wait_ns=1 * MS)
    add("admit.scatter", -1200, 90, a, 0)
    d = add("decode", -1100, 50)
    add("decode.dispatch", -1100, 40, d)
    add("decode.deliver", -1060, 10, d)
    add("idle", 0, 10)
    a = add("admit", 10, 100, rid=1, queue_wait_ns=2 * MS)
    add("admit.init_row", 10, 10, a, 1)
    add("admit.prefill", 20, 20, a, 1)
    add("admit.scatter", 40, 60, a, 1)
    add("admit.deliver", 100, 5, a, 1)
    d = add("decode", 110, 20)
    add("decode.dispatch", 110, 3, d)
    add("decode.readback", 113, 15, d)
    add("decode.deliver", 128, 1, d)
    a = add("admit", 130, 200, rid=2, queue_wait_ns=50 * MS)
    add("admit.scatter", 150, 140, a, 2)
    d = add("decode", 330, 22)
    add("decode.dispatch", 330, 5, d)
    add("decode.deliver", 349, 3, d)
    a = add("admit", 352, 3000, rid=3, queue_wait_ns=10 * MS)
    add("admit.init_row", 352, 2900, a, 3)
    add("admit.scatter", 3252, 100, a, 3)
    d = add("decode", 3352, 20)
    add("decode.dispatch", 3352, 4, d)
    add("decode.deliver", 3370, 2, d)
    a = add("admit", 3372, 300, rid=4, queue_wait_ns=0)
    add("admit.scatter", 3372, 10, a, 4)
    d = add("decode", 3672, 90)
    add("decode.dispatch", 3672, 50, d)
    add("decode.deliver", 3722, 40, d)
    add("caller", -6000, 7000, thread=9)
    return out


def fit_spans():
    """Two `fit` calls on thread 3.  The first (rid 1) holds a compile: a
    step of 900 ms.  The window's (rid 2): next 10 (of which the queue's
    wait 8), step 30, next 2 (wait 1), step 50, a listener's sync 8.  Then
    the harness stops the profiler inside the worker's (thread 4)
    `prefetch.next`, for 5000 ms, and `fit` waits 4990 of them and steps
    for 60.  The drain: the iterator ends and the loss is read for 400."""
    out, sid = [], iter(range(1, 100))

    def add(name, start, ms, parent=None, rid=None, thread=3, **attrs):
        s = Span(name, start * MS, (start + ms) * MS, parent, rid, thread,
                 attrs, next(sid))
        out.append(s)
        return s.sid

    n = add("fit.next", 0, 100, rid=1)
    add("prefetch.wait", 0, 99, n, 1)
    add("fit.step", 100, 900, rid=1, step=1)
    n = add("fit.next", 2000, 10, rid=2)
    add("prefetch.wait", 2001, 8, n, 2)
    add("fit.step", 2010, 30, rid=2, step=1)
    n = add("fit.next", 2040, 2, rid=2)
    add("prefetch.wait", 2040, 1, n, 2)
    add("fit.step", 2042, 50, rid=2, step=2)
    add("fit.sync", 2092, 8, rid=2)
    n = add("fit.next", 2100, 4995, rid=2)
    add("prefetch.wait", 2101, 4990, n, 2)
    add("fit.step", 7095, 60, rid=2, step=3)
    add("fit.next", 7155, 1, rid=2)
    add("fit.sync", 7156, 400, rid=2)
    add("prefetch.next", 2000, 40, thread=4)
    add("prefetch.transfer", 2040, 4, thread=4)
    add("prefetch.next", 2100, 5000, thread=4)
    return out


MODULES = {
    "jit_dl4j_decode(111)": [0.015, 0.016, 0.017],
    "jit_dl4j_decode_multi_4(112)": [0.060],
    "jit_dl4j_prefill(113)": [0.007, 0.009],
    "jit_dl4j_train_step(114)": [0.165, 0.166, 0.164],
    "jit_dl4j_train_step_masked(115)": [0.170],
    "jit_scatter(116)": [0.001] * 6,
    "jit_broadcast_in_dim(117)": [0.002, 0.002],
}


def seen_of(spans=None, modules=None, requests=4, **counters):
    counters.update(requests=requests)
    seen = {"counters": counters,
            "trace": None if modules is None else {"modules": modules}}
    if spans is not None:
        seen["spans"] = spans
    return seen


@pytest.mark.parametrize("name, expected", [
    ("admit.host_ms_p50", 200.0),               # median of 100, 200, 3000
    # three scatters at their median of 100 over three admissions at 200:
    # by sums it were 300 of 3300, the stalled row's zeros being most of it
    ("admit.scatter_share", 50.0),
    ("queue.wait_ms_p50", 10.0),                # median of 2, 50, 10
    # three admissions at 200 and three decode steps at 20; idle left out
    ("batcher.admit_wall_share", 100.0 * 600 / 660),
    ("decode.dispatch_ms_p50", 4.0),            # median of 3, 5, 4
    ("decode.deliver_ms_p50", 2.0),             # median of 1, 3, 2
])
def test_serving_span_readers_by_hand_over_the_window_alone(name, expected):
    assert reader(name)(seen_of(serve_spans())) == pytest.approx(expected)
    assert reader(name)(seen_of([])) is None
    assert reader(name)(seen_of(serve_spans(), requests=0)) is None


def test_the_serving_window_is_its_requests_first_submit_to_their_last():
    window = program_spans.serve_window(seen_of(serve_spans()))
    # rid 1 was submitted at 8 ms, rid 4 at 3372: ramp, drain, caller out
    assert {s.rid for s in window if s.name == "admit"} == {1, 2, 3}
    assert min(s.start_ns for s in window) == 10 * MS
    assert max(s.end_ns for s in window) == 3372 * MS
    # with the ramp's request counted in, its 4.9 s admission is back
    wide = seen_of(serve_spans(), requests=5)
    assert reader("admit.host_ms_p50")(wide) == pytest.approx(1600.0)


@pytest.mark.parametrize("name, expected", [
    # the window's call, typical turns: three nexts at their median of 10,
    # three steps at 50 and the one sync of 8 are 188; three waits at 8
    ("input.queue_wait_share", 100.0 * 24 / 188),
    ("train.dispatch_block_share", 100.0 * 150 / 188),
])
def test_training_span_readers_take_the_windows_fit_by_typical_turns(
        name, expected):
    assert reader(name)(seen_of(fit_spans())) == pytest.approx(expected)
    assert reader(name)(seen_of([])) is None


def test_the_windows_fit_ends_with_its_last_step():
    fit = program_spans.last_fit(seen_of(fit_spans()))
    assert {s.rid for s in fit} == {2} and {s.thread for s in fit} == {3}
    assert max(s.end_ns for s in fit) == 7155 * MS      # no drain
    assert sorted(s.name for s in program_spans.top_level(fit)) == \
        ["fit.next"] * 3 + ["fit.step"] * 3 + ["fit.sync"]


def test_a_fit_that_never_waited_reads_zero_not_nothing():
    spans = [s for s in fit_spans() if s.name != "prefetch.wait"]
    assert reader("input.queue_wait_share")(seen_of(spans)) == 0.0


def test_the_decode_program_is_found_by_name():
    read = reader("decode.device_ms_p50")
    # median of 15, 16, 17, 60
    assert read(seen_of(modules=MODULES)) == pytest.approx(16.5)
    assert read(seen_of()) is None
    # a program of before the names: nothing to read, and no guess by count
    old = {"jit_program(1)": [0.015] * 40, "jit_fn(2)": [0.165] * 3}
    assert read(seen_of(modules=old)) is None


def test_admissions_device_time_is_what_is_neither_decode_nor_prefill():
    read = reader("admit.device_ms")
    serve = {k: v for k, v in MODULES.items() if "train_step" not in k}
    # (6 x 1 ms + 2 x 2 ms) over 2 admissions
    assert read(seen_of(modules=serve, traced_admitted=2)) == \
        pytest.approx(5.0)
    assert read(seen_of(modules=serve, traced_admitted=0)) is None
    assert read(seen_of(traced_admitted=2)) is None
    old = {"jit_program(1)": [0.015] * 40, "jit_scatter(3)": [0.001]}
    assert read(seen_of(modules=old, traced_admitted=2)) is None


def test_a_program_without_a_record_gives_nothing(monkeypatch):
    from deeplearning4j_tpu.utils import profiling

    monkeypatch.delattr(profiling, "spans")
    assert program_spans.record(seen_of()) is None
    for name in FROM_SPANS:
        assert reader(name)(seen_of()) is None


def test_a_record_that_dropped_spans_is_not_read(monkeypatch):
    from deeplearning4j_tpu.utils import profiling

    monkeypatch.setattr(profiling, "spans", serve_spans)
    assert reader("admit.host_ms_p50")(seen_of()) == pytest.approx(200.0)
    monkeypatch.setattr(profiling, "dropped", lambda: 1)
    assert program_spans.record(seen_of()) is None
    assert reader("admit.host_ms_p50")(seen_of()) is None


@pytest.mark.parametrize("name", sorted(ADDED))
def test_every_added_metric_has_its_reader_and_lists_its_cell(name):
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in bench["end_to_end"]}
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert callable(reader(name))
    assert ADDED[name] in m["workloads"]
    assert set(m["workloads"]) <= reports[m["moves"]]


@pytest.mark.parametrize("workload", ["train-590m-2k", "serve-1b3-decode"])
def test_the_rehearsal_prints_every_program_span_metric(capsys, workload):
    assert run.main(["--workload", workload, "--seed", "1", "--seconds", "2",
                     "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    wanted = {name for name in FROM_SPANS if ADDED[name] == workload}
    assert wanted <= set(line["metrics"])
    assert all(line["metrics"][name]["value"] >= 0 for name in wanted)
    assert line["correct"] is True
