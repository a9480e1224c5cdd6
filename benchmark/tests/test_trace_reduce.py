"""The reduction, on a small trace recorded on a TPU v5e (PR 24): five runs
of `jit_step_fn` and three of `jit_other_program`, with `bench:step` and
`bench:other` annotations on the host."""

import os

from benchmark import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "testdata", "small.xplane.pb")


def reduced(**kw):
    return trace_reduce.reduce_trace(TRACE, window=(0.0, 1e12), **kw)


def test_programs_and_their_runs():
    r = reduced()
    runs = {name.split("(")[0]: v for name, v in r["modules"].items()}
    assert len(runs["jit_step_fn"]) == 5 and len(runs["jit_other_program"]) == 3
    assert all(20e-6 < t < 30e-6 for t in runs["jit_step_fn"])
    assert r["chips"] == 1


def test_busy_is_the_union_of_the_ops_and_under_the_window():
    r = reduced()
    total = sum(sum(v) for v in r["modules"].values())
    assert 0 < r["busy_s"] <= total * 1.001
    assert r["busy_s"] < r["window_s"]
    assert abs(sum(s for _, s in r["idle_gaps"]) + r["busy_s"] - r["window_s"]) < 1e-9


def test_a_window_cuts_what_lies_outside():
    whole = reduced()
    first = trace_reduce.reduce_trace(TRACE, window=(0.0, 47.0e6))
    assert sum(len(v) for v in first["modules"].values()) == 2
    assert first["busy_s"] < whole["busy_s"]


def test_gaps_are_named_by_the_annotation_open_at_their_middle():
    names = {name for name, _ in reduced(idle_label="nobody")["idle_gaps"]}
    assert "nobody" in names and names <= {"nobody", "step", "other"}


def test_ops_are_named_without_their_shapes():
    ops = dict(reduced()["device_ops"])
    assert "convert_reduce_fusion" in ops and all(" " not in k for k in ops)


def test_program_by_its_number_of_runs():
    r = reduced()
    assert len(trace_reduce.program_runs(r, 5)) == 5
    assert len(trace_reduce.program_runs(r, 1)) == 3
    assert trace_reduce.program_runs(r, 40) == []


def test_no_window_annotation_is_an_error():
    import pytest
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(TRACE)
