"""The K=1 decode loop one step ahead (ISSUE 35).

`ContinuousBatcher._decode_rounds` dispatches table step n+1 before it reads
step n back wherever the next step's inputs do not need the host to have
seen the last step's tokens.  The anchor: every stream's tokens are the
synchronous loop's (the compiled prefill-and-decode sequence a slot runs),
whatever ends a row and whenever an admission cuts in; only when a token
reaches its caller changes.  Round it: when the loop engages (`ahead` on the
`decode` spans, the two counters of `stats()`), that a `submit` runs nothing
on the device, and that a fault still ends one stream at its K=1 count.

Tier-1: CPU-only, tiny models."""

import importlib.util
import os
import threading
import time

import jax
import numpy as np
import pytest

from generate_helpers import (_clean_faults, _compiled_tokens, _drain,  # noqa: F401
                              _draft_net, lstm_net, transformer_net)
from deeplearning4j_tpu.reliability import faults
from deeplearning4j_tpu.serving import batcher as batcher_mod
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from deeplearning4j_tpu.serving.metrics import FAMILIES, replica_metrics
from deeplearning4j_tpu.utils import profiling
from deeplearning4j_tpu.utils.profiling import Span

#: (temperature, page size, (prompt, max_new, rng_seed) a stream); two
#: slots, so the third and fourth streams are admitted into a slot released
#: the step before
TWO = [([1, 2, 3], 10, 0), ([4, 5], 10, 1)]
CASES = {
    "greedy": (0.0, 0, [([1, 2, 3], 12, 0), ([4, 5], 12, 1), ([6], 12, 2)]),
    "sampled": (0.8, 0, [([1, 2, 3], 12, 0), ([4, 5], 12, 1), ([6], 12, 2)]),
    "staggered": (0.8, 0, [([1, 2], 3, 0), ([3], 7, 1), ([4, 5, 6], 12, 2),
                           ([7], 5, 3), ([8, 9], 1, 4)]),
    # max_new is cut to the table's edge: max_seq 16 less the prompt
    "table_edge": (0.0, 0, [([1, 2, 3], 40, 0), ([4, 5], 6, 1), ([6], 40, 2)]),
    "two_greedy": (0.0, 0, TWO),
    "two_sampled": (0.8, 0, TWO),
    # over the paged pool the loop stays synchronous
    "paged_greedy": (0.0, 4, TWO),
    "paged_sampled": (0.8, 4, TWO),
}


def _dispatched(record):
    """The `decode` spans that dispatched a step, in the loop's order."""
    return sorted((s for s in record if s.name == "decode"
                   and "ahead" in s.attrs), key=lambda s: s.start_ns)


@pytest.mark.parametrize("model", ["lstm", "transformer"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_streams_are_the_synchronous_loops_token_for_token(request, model,
                                                           case):
    net = request.getfixturevalue(f"{model}_net")
    temperature, page_size, asked = CASES[case]
    refs = [_compiled_tokens(net, p, min(n, 16 - len(p)),
                             temperature=temperature, rng_seed=seed)
            for p, n, seed in asked]
    cb = ContinuousBatcher(net, n_slots=2, max_seq=16, prompt_buckets=(8,),
                           page_size=page_size)
    try:
        streams = [cb.submit(p, max_new_tokens=n, temperature=temperature,
                             rng_seed=seed) for p, n, seed in asked]
        assert _drain(streams) == refs
        st = cb.stats()
        assert st["streams"]["completed"] == len(asked)
        ahead = st["decode_steps_ahead_total"]
        assert (ahead == 0 if page_size else 0 < ahead) \
            and ahead < st["decode_steps_total"]
    finally:
        cb.stop()


def test_two_budgets_the_short_row_ends_while_a_step_is_in_flight(lstm_net):
    """Two streams, 3 tokens and 12: the step that the short row's budget
    ends in is already in flight behind the one before, and is dispatched for
    the long row alone; both streams are their synchronous loops'."""
    refs = [_compiled_tokens(lstm_net, [1, 2], 3, rng_seed=0),
            _compiled_tokens(lstm_net, [3, 4], 12, rng_seed=1)]
    profiling.clear()
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,))
    try:
        a = cb.submit([1, 2], max_new_tokens=3, rng_seed=0)
        b = cb.submit([3, 4], max_new_tokens=12, rng_seed=1)
        assert _drain([a, b]) == refs
    finally:
        cb.stop()
    lives = [d.attrs["live"] for d in _dispatched(profiling.spans())]
    assert 2 in lives and lives[-1] == 1


def test_the_steps_report_the_hosts_share(lstm_net):
    """The host's busy share of the steps, and its seconds, reach
    `stats()`."""
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=32,
                           prompt_buckets=(8,))
    try:
        streams = [cb.submit([1, 2], max_new_tokens=28, rng_seed=i)
                   for i in range(2)]
        assert [len(t) for t in _drain(streams)] == [28, 28]
        st = cb.stats()
        assert 0.0 < st["host_overhead_fraction"] <= 1.0
        assert st["decode_host_seconds_total"] > 0.0
    finally:
        cb.stop()


def test_ahead_reads_one_on_steady_steps_and_zero_after_an_admission(
        lstm_net):
    """One stream in two slots: the step after its admission finds nothing
    in flight, every later one is dispatched while its predecessor runs.
    A second stream's admission puts one more 0 in the row.  The counters
    of `stats()` are the spans'."""
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=32,
                           prompt_buckets=(8,)).start()
    profiling.clear()
    try:
        assert len(cb.generate([1, 2], max_new_tokens=20)) == 20
        first = [s.attrs["ahead"] for s in _dispatched(profiling.spans())]
        assert first == [0] + [1] * 18      # 19 steps after the admission's token
        assert len(cb.generate([3], max_new_tokens=6)) == 6
    finally:
        cb.stop()
    record = profiling.spans()
    steps = _dispatched(record)
    assert [s.attrs["ahead"] for s in steps] == [0] + [1] * 18 + [0] + [1] * 4
    admits = sorted(s.start_ns for s in record if s.name == "admit")
    for at in admits:       # the step after an admission starts from the host
        assert next(s for s in steps if s.start_ns > at).attrs["ahead"] == 0
    st = cb.stats()
    assert st["decode_steps_total"] == len(steps) == 24
    assert st["decode_steps_ahead_total"] == 22
    # a span completes at most one step and dispatches at most one, in that
    # order; a steady one does both, with the wait between them, and what
    # a span holds is named as it was
    kids = {}
    for s in record:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append(s.name)
    decodes = [s for s in record if s.name == "decode"]
    names = [n for d in decodes for n in kids[d.sid]]
    assert names.count("decode.readback") == names.count("decode.deliver") == 24
    assert names.count("decode.dispatch") == 24
    order = ["decode.readback", "decode.deliver", "decode.wait",
             "decode.dispatch"]
    for d in decodes:
        assert kids[d.sid] == [n for n in order if n in kids[d.sid]]
    steady = [d for d in steps if kids[d.sid][0] == "decode.readback"]
    assert len(steady) == 24 - 4 and all(d.attrs["ahead"] for d in steady)
    # two spans start a run with a dispatch alone, two end it with a
    # completion alone
    assert len(decodes) == 24 + 4
    assert all(d.attrs["k"] == 1 for d in decodes)
    assert 0.0 <= st["host_overhead_fraction"] <= 1.0


def test_a_submission_in_the_wait_is_admitted_behind_one_step(lstm_net,
                                                              monkeypatch):
    """A device step of 30 ms (the loop's readback is slowed): with a slot
    standing free the loop holds its next dispatch back in `decode.wait`
    until the device is about to need it, and a stream submitted meanwhile
    is admitted after the one step in flight: no step is dispatched between
    its `submit` and its admission, and it costs the first stream none of
    its tokens."""
    ref = _compiled_tokens(lstm_net, [1, 2], 30, rng_seed=0, max_seq=32)
    real = jax.device_get

    def slow(tree):
        if threading.current_thread().name == "dl4j-decode":
            time.sleep(0.03)
        return real(tree)

    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=32,
                           prompt_buckets=(8,)).start()
    try:
        assert len(cb.generate([3], max_new_tokens=2)) == 2        # warm
        monkeypatch.setattr(jax, "device_get", slow)
        profiling.clear()
        a = cb.submit([1, 2], max_new_tokens=30, rng_seed=0)
        tokens = a.tokens(timeout=60.0)
        got = [next(tokens) for _ in range(16)]     # the clock has its samples
        at = time.monotonic_ns()
        b = cb.submit([4], max_new_tokens=3, rng_seed=1)
        assert len(list(b.tokens(timeout=60.0))) == 3
        assert got + list(tokens) == ref
    finally:
        monkeypatch.undo()
        cb.stop()
    record = profiling.spans()
    waits = [s for s in record if s.name == "decode.wait"]
    assert len(waits) >= 8
    admit = next(s for s in record if s.name == "admit" and s.rid == b.rid)
    between = [s for s in record if s.name == "decode.dispatch"
               and at <= s.start_ns <= admit.start_ns]
    assert between == []
    # the wait that the submission cut short
    assert any(w.start_ns <= at <= w.end_ns + 5_000_000 for w in waits)
    # the loop was blocked, not busy, while it waited
    assert cb.stats()["host_overhead_fraction"] < 0.5


@pytest.mark.parametrize("mode", ["feed", "speculative", "draft_lockstep",
                                  "paged"])
def test_the_loop_stays_synchronous_where_the_host_must_see_the_token(
        lstm_net, mode):
    """A prompt's rest fed through the table, a speculative round, a draft
    network in lockstep (here: rows too near the table's edge for a round)
    and the paged pool: each step is read back before the next is
    dispatched, in today's order, and the tokens are the sequential ones."""
    prompt, n_new, kwargs = [1, 2, 3, 4, 5, 6], 8, {}
    if mode == "feed":
        kwargs = dict(prefix_cache=True, prefix_match="longest")
    elif mode == "speculative":
        kwargs = dict(draft_net=_draft_net(), spec_k=3)
    elif mode == "draft_lockstep":
        # a stream that ends at the table's edge, 16: from position 14 on
        # no chunk of 3 fits, so its last steps are plain ones in lockstep
        kwargs = dict(draft_net=_draft_net(agrees_with=lstm_net), spec_k=3)
        prompt, n_new = prompt[:5], 11
    else:
        kwargs = dict(page_size=4)
    ref = _compiled_tokens(lstm_net, prompt, n_new, rng_seed=1)
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), **kwargs)
    try:
        if mode == "feed":
            cb.generate(prompt[:2], max_new_tokens=2)   # seeds the cache
        profiling.clear()
        s = cb.submit(prompt, max_new_tokens=n_new, rng_seed=1)
        assert list(s.tokens(timeout=60.0)) == ref
    finally:
        cb.stop()
    record = profiling.spans()
    ahead = [s.attrs["ahead"] for s in _dispatched(record)]
    if mode == "feed":
        # four prompt tokens go through the table: three steps that feed,
        # the step after them from the host's arrays, then ahead
        assert ahead[:4] == [0] * 4 and set(ahead[4:]) == {1}
    else:
        assert not any(ahead)
        assert cb.stats()["decode_steps_ahead_total"] == 0
    if mode == "draft_lockstep":
        assert ahead and cb.stats()["speculative"]["rounds"] >= 1
    for d in _dispatched(record):
        kids = [s.name for s in record if s.parent == d.sid]
        if not d.attrs["ahead"]:
            assert kids[0] == "decode.dispatch"


def test_a_fault_ends_its_own_stream_at_the_k1_count(lstm_net):
    """`decode.step` raises for slot 0 at its third step, while the second
    is in flight: what is in flight still reaches the stream (admission's
    token and two steps'), then the injected error; the neighbour's tokens
    are untouched."""
    ref_a = _compiled_tokens(lstm_net, [1, 2], 20, rng_seed=0, max_seq=32)
    ref_b = _compiled_tokens(lstm_net, [3, 4], 20, rng_seed=1, max_seq=32)
    # traversal order with two slots: steps 1 and 2 fire slot 0, slot 1
    faults.arm("decode.step", "raise", nth=5)
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=32,
                           prompt_buckets=(8,), auto_start=False)
    try:
        a = cb.submit([1, 2], max_new_tokens=20, rng_seed=0)
        b = cb.submit([3, 4], max_new_tokens=20, rng_seed=1)
        cb.start()
        assert list(b.tokens(timeout=60.0)) == ref_b
        got = []
        with pytest.raises(faults.FaultInjected):
            for t in a.tokens(timeout=60.0):
                got.append(t)
        assert got == ref_a[:3]
        st = cb.stats()
        assert st["streams"]["failed"] == 1 and st["streams"]["completed"] == 1
        assert st["decode_steps_ahead_total"] > 0
        faults.disarm()
        assert len(cb.generate([5], max_new_tokens=3)) == 3
    finally:
        cb.stop()


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 - 1, 2 ** 31, 2 ** 32 + 5,
                                  -7])
def test_the_host_made_key_is_prngkeys_bit_for_bit(seed):
    want = np.asarray(jax.random.PRNGKey(seed))
    got = batcher_mod._prng_key(seed)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_another_key_implementation_keeps_the_device_call():
    with jax.default_prng_impl("rbg"):
        want = np.asarray(jax.random.PRNGKey(11))
        got = batcher_mod._prng_key(11)
    assert want.shape == (4,)
    np.testing.assert_array_equal(got, want)
    with pytest.raises(OverflowError):
        batcher_mod._prng_key(2 ** 64 + 3)      # as PRNGKey itself


def test_submit_returns_while_a_slow_step_is_in_flight(lstm_net,
                                                       monkeypatch):
    """A step held half a second in its readback (the loop's thread alone
    is slowed): `submit` comes back at once, and it makes its key without
    a call to the device, which would queue behind the step."""
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=32,
                           prompt_buckets=(8,)).start()
    real = jax.device_get
    reading = threading.Event()

    def slow(tree):
        if threading.current_thread().name == "dl4j-decode":
            reading.set()
            time.sleep(0.5)
        return real(tree)

    def no_device_key(seed):
        raise AssertionError("submit made its key on the device")

    try:
        assert len(cb.generate([1, 2], max_new_tokens=2)) == 2     # warm
        monkeypatch.setattr(jax, "device_get", slow)
        a = cb.submit([1, 2], max_new_tokens=6)
        assert reading.wait(timeout=30.0)
        monkeypatch.setattr(jax.random, "PRNGKey", no_device_key)
        t0 = time.monotonic()
        b = cb.submit([3], max_new_tokens=2, rng_seed=2 ** 31)
        took = time.monotonic() - t0
        assert took < 0.25
        monkeypatch.undo()
        assert len(list(a.tokens(timeout=60.0))) == 6
        assert len(list(b.tokens(timeout=60.0))) == 2
    finally:
        cb.stop()


# -- what reads the counter ------------------------------------------------------

def test_the_two_counters_reach_the_prometheus_page():
    for family in ("dl4j_serving_decode_steps_total",
                   "dl4j_serving_decode_steps_ahead_total"):
        assert FAMILIES[family] == ("counter", ())
    page = replica_metrics({"generation": {"decode_steps_total": 40,
                                           "decode_steps_ahead_total": 37}})
    assert "dl4j_serving_decode_steps_total 40" in page
    assert "dl4j_serving_decode_steps_ahead_total 37" in page


def test_the_benchmarks_reader_takes_the_share_from_the_spans():
    """`benchmark/layer_metrics/decode.ahead_share.py`: of the window's
    `decode` spans that dispatched a step, the share with `ahead=1`; None
    from a program whose spans carry no such attribute (the parent's)."""
    spec = importlib.util.spec_from_file_location(
        "reader_decode_ahead_share", os.path.join(
            os.path.dirname(__file__), os.pardir, "benchmark",
            "layer_metrics", "decode.ahead_share.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    admits = [Span("admit", 0, 10, None, 1, 7, {"queue_wait_ns": 0}, 1),
              Span("admit", 90, 100, None, 2, 7, {"queue_wait_ns": 0}, 9)]
    steps = [Span("decode", 10 * i, 10 * i + 9, None, None, 7,
                  {"k": 1, "live": 1}, 1 + i) for i in range(1, 6)]
    seen = {"counters": {"requests": 2}, "spans": admits[:1] + steps + admits[1:]}
    assert module.read(seen) is None
    marked = [s._replace(attrs={**s.attrs, "ahead": int(i > 0)})
              for i, s in enumerate(steps[:4])] + steps[4:]  # one drains only
    seen["spans"] = admits[:1] + marked + admits[1:]
    assert module.read(seen) == pytest.approx(75.0)
