"""The tracing module: spans and their record, names."""

import collections
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.utils import profiling
from deeplearning4j_tpu.utils.profiling import Tracer, self_time, span


@pytest.fixture(autouse=True)
def fresh_record():
    profiling.clear()
    yield
    profiling.clear()


def test_span_is_recorded_with_its_name_rid_and_attrs():
    with span("test-region", rid=7, slot=3) as sp:
        _ = jnp.sum(jnp.arange(10))
        sp.set(live=2)
    (s,) = profiling.spans()
    assert (s.name, s.rid, s.parent) == ("test-region", 7, None)
    assert s.attrs == {"slot": 3, "live": 2}
    assert s.thread == threading.get_ident()
    assert 0 < s.end_ns - s.start_ns == round(sp.seconds * 1e9)


def test_nesting_gives_the_parent_and_hands_down_the_rid():
    with span("outer", rid=11) as outer:
        with span("inner") as inner:
            with span("innermost", rid=12):
                pass
        with span("second"):
            pass
    by_name = {s.name: s for s in profiling.spans()}
    assert by_name["outer"].parent is None
    assert by_name["inner"].parent == outer.sid
    assert by_name["second"].parent == outer.sid
    assert by_name["innermost"].parent == inner.sid
    assert [by_name[n].rid for n in ("inner", "second", "innermost")] == \
        [11, 11, 12]
    # the record is ordered by when each span closed
    assert [s.name for s in profiling.spans()] == \
        ["innermost", "inner", "second", "outer"]


def test_self_time_is_duration_less_the_children():
    with span("outer"):
        time.sleep(0.02)
        with span("child"):
            time.sleep(0.03)
            with span("grandchild"):
                time.sleep(0.01)
    record = profiling.spans()
    own = self_time(record)
    by_name = {s.name: s for s in record}
    dur = {n: s.end_ns - s.start_ns for n, s in by_name.items()}
    assert own[by_name["grandchild"].sid] == dur["grandchild"]
    assert own[by_name["child"].sid] == dur["child"] - dur["grandchild"]
    assert own[by_name["outer"].sid] == dur["outer"] - dur["child"]
    assert sum(own.values()) == dur["outer"]
    assert own[by_name["outer"].sid] >= 0.02e9


def test_two_threads_keep_two_stacks():
    inside = threading.Barrier(2)

    def work(tag):
        with span("outer." + tag):
            inside.wait(timeout=10)     # both outers are open at once
            with span("inner." + tag):
                inside.wait(timeout=10)

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_name = {s.name: s for s in profiling.spans()}
    for tag in "ab":
        assert by_name["inner." + tag].parent == by_name["outer." + tag].sid
        assert by_name["inner." + tag].thread == by_name["outer." + tag].thread
    assert by_name["outer.a"].thread != by_name["outer.b"].thread


def test_the_record_drops_the_oldest_and_counts_it(monkeypatch):
    monkeypatch.setattr(profiling, "_record", collections.deque(maxlen=4))
    for i in range(7):
        with span("s", rid=i):
            pass
    assert [s.rid for s in profiling.spans()] == [3, 4, 5, 6]
    assert profiling.dropped() == 3
    profiling.clear()
    assert profiling.spans() == [] and profiling.dropped() == 0


def test_an_exception_inside_a_span_closes_it_and_passes():
    with pytest.raises(KeyError):
        with span("outer"):
            with span("fails"):
                raise KeyError("x")
    assert [s.name for s in profiling.spans()] == ["fails", "outer"]
    with span("after"):
        pass
    assert profiling.spans()[-1].parent is None     # the stack was unwound


def test_program_name_from_the_entry_kind():
    assert profiling.program_name("decode") == "dl4j_decode"
    assert profiling.program_name("decode-paged") == "dl4j_decode_paged"
    assert profiling.program_name("prefill-logp-slot") == "dl4j_prefill_logp_slot"
    assert profiling.program_name("entry[16]") == "dl4j_entry_16"
    assert profiling.program_name("prefill-logp") == "dl4j_prefill_logp"


def test_named_changes_the_module_name_and_not_the_jaxpr():
    def program(a, b):
        return a @ b + 1.0

    x = jnp.ones((4, 4))
    renamed = profiling.named(program, "unit[1]")
    assert str(jax.make_jaxpr(renamed)(x, x)) == \
        str(jax.make_jaxpr(program)(x, x))
    assert "module @jit_dl4j_unit_1" in jax.jit(renamed).lower(x, x).as_text()
    assert (jax.jit(renamed, donate_argnums=(0,))(x + 0.0, x)
            == program(x, x)).all()


def test_scopes_reach_the_lowered_text_and_not_the_jaxpr():
    def program(x):
        with profiling.scope("outer"):
            with profiling.scope("inner"):
                return jnp.tanh(x) * 2.0

    def bare(x):
        return jnp.tanh(x) * 2.0

    x = jnp.ones((3,))
    assert str(jax.make_jaxpr(program)(x)) == str(jax.make_jaxpr(bare)(x))
    text = jax.jit(program).lower(x).as_text(debug_info=True)
    assert "outer/inner/tanh" in text


def test_a_span_lands_in_an_open_profiler_session(tmp_path):
    from jax.profiler import ProfileData

    tracer = Tracer(str(tmp_path))
    with tracer.trace():
        with span("unit", rid=5, slot=1):
            jnp.sum(jnp.arange(8)).block_until_ready()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert "dl4j:unit" in names
