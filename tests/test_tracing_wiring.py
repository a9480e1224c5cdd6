"""The tracing module wired in: a name for every compiled program, a scope
for every layer, and spans through the batcher, the trainer and the
prefetcher.  Names and scopes are metadata: keys and equations stay."""

import contextlib
import hashlib
import os
import re
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.iterator import PrefetchIterator
from deeplearning4j_tpu.models.zoo import char_transformer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.persist import platform_fingerprint
from deeplearning4j_tpu.optimize.step_cache import arg_signature
from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import make_mesh, shard_batch
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from deeplearning4j_tpu.serving.metrics import FAMILIES, replica_metrics
from deeplearning4j_tpu.utils import profiling

VOCAB = 32


def _net():
    conf = char_transformer(VOCAB, d_model=16, n_blocks=2, n_heads=2,
                            max_seq_len=32)
    return MultiLayerNetwork(conf, seed=0).init()


def _batch(rows=4, seq=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, VOCAB, (rows, seq)).astype(np.int32)
    y = np.eye(VOCAB, dtype=np.float32)[rng.integers(0, VOCAB, rows * seq)]
    return x, y


def _module_name(compiled) -> str:
    return compiled.as_text().split(",", 1)[0].split()[1]


@pytest.fixture(autouse=True)
def fresh_record():
    profiling.clear()
    yield
    profiling.clear()


# -- names ---------------------------------------------------------------------
def test_every_infer_program_has_its_own_dl4j_name_and_its_old_key():
    net = _net()
    net.warmup_generate(slots=2, max_seq=16, prompt_buckets=(4,),
                        steps_per_dispatch=4)
    ic = net.infer_cache
    ic.output(net.conf, net.params, np.ones((8, 16), np.int32),
              compile_only=True)
    names = {key: _module_name(fn) for key, fn in ic._programs.items()}
    assert len(set(names.values())) == len(names) >= 6
    for key, name in names.items():
        assert name == "jit_" + profiling.program_name(key[0])
        assert "dl4j" not in repr(key)
    assert {"jit_dl4j_decode", "jit_dl4j_prefill_slot", "jit_dl4j_output",
            "jit_dl4j_decode_multi_4"} <= set(names.values())
    # the key of the pre-name schema, built by hand
    xp = jnp.zeros((ic._serve_bucket(8), 16), jnp.int32)
    assert ("output", ic._fingerprint(net.conf), arg_signature(xp),
            "single") in ic._programs


def test_disk_keys_are_the_old_bytes_and_a_disk_hit_keeps_the_name(tmp_path):
    cache_dir = str(tmp_path / "cc")
    warm = _net()
    store = warm.set_compile_cache(cache_dir)
    ic = warm.infer_cache
    ic.output(warm.conf, warm.params, np.ones((8, 16), np.int32),
              compile_only=True)
    warm.warmup_generate(slots=2, max_seq=16, prompt_buckets=(4,))
    on_disk = {f for f in os.listdir(cache_dir) if f.endswith(".jxp")}
    assert on_disk == {os.path.basename(store.path_for(k))
                       for k in ic._programs}
    xp = jnp.zeros((ic._serve_bucket(8), 16), jnp.int32)
    key = ("output", ic._fingerprint(warm.conf), arg_signature(xp), "single")
    by_hand = hashlib.sha256((platform_fingerprint() + "|" + repr(key))
                             .encode("utf-8")).hexdigest()[:40] + ".jxp"
    assert by_hand in on_disk

    cold = _net()
    cold.set_compile_cache(cache_dir)
    cold.infer_cache.output(cold.conf, cold.params, np.ones((8, 16), np.int32),
                            compile_only=True)
    cold.warmup_generate(slots=2, max_seq=16, prompt_buckets=(4,))
    assert cold.infer_cache.stats.misses == 0
    assert cold.infer_cache.stats.disk_hits == len(ic._programs)
    assert ({k: _module_name(f) for k, f in cold.infer_cache._programs.items()}
            == {k: _module_name(f) for k, f in ic._programs.items()})


def test_every_trainer_program_has_a_dl4j_name_and_its_old_key():
    net = _net()
    trainer = DataParallelTrainer(net, make_mesh({"dp": 2}), mode="sync")
    x, y = _batch()
    trainer.fit([(x, y), (x[:3], y[:24])])      # a full batch, then a tail
    programs = trainer.compile_cache._programs
    names = {key: _module_name(fn) for key, fn in programs.items()}
    assert set(names.values()) == {"jit_dl4j_train_step",
                                   "jit_dl4j_train_step_masked"}
    # one name for each jitted step, whatever the layouts it compiled for
    assert len({(k[:4], n) for k, n in names.items()}) == 2
    assert {k[:4] for k in programs} == {("dp_step", "dp", False, 1),
                                         ("dp_step", "dp", True, 1)}
    assert all("dl4j" not in repr(k) for k in programs)

    zero1 = DataParallelTrainer(_net(), make_mesh({"dp": 2}), zero1=True)
    zero1.fit([(x, y)])
    assert {_module_name(f) for f in zero1.compile_cache._programs.values()} \
        == {"jit_dl4j_zero1_step"}


# -- scopes --------------------------------------------------------------------
def _without_scopes(monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())


def _layer_scopes(conf):
    return [f"L{i}.{conf.conf(i).layer_type}" for i in range(conf.n_layers)]


def test_train_step_carries_every_layer_scope_and_the_same_equations(
        monkeypatch):
    net = _net()
    trainer = DataParallelTrainer(net, make_mesh({"dp": 2}), mode="sync")
    x, y = shard_batch(trainer.mesh, tuple(map(jnp.asarray, _batch())), "dp")
    args = (trainer.state, x, y, trainer._next_key())
    step = trainer._step.__wrapped__
    text = step.lower(*args).as_text(debug_info=True)
    assert "module @jit_dl4j_train_step" in text
    for scope in _layer_scopes(net.conf) + ["qkv", "scores", "attend", "proj",
                                            "ffn", "ln", "head", "loss",
                                            "updater", "allreduce"]:
        assert re.search(rf'[/("]{re.escape(scope)}[/)]', text), scope
    # backward operations say whose they are too
    assert re.search(r"transpose\(jvp\(L1\.attention\)\)[^\"]*/scores/", text)
    scoped = str(jax.make_jaxpr(step)(*args))
    with monkeypatch.context() as m:
        _without_scopes(m)
        fresh = DataParallelTrainer(_net(), make_mesh({"dp": 2}), mode="sync")
        bare = fresh._step.__wrapped__
        assert "L1.attention" not in bare.lower(*args).as_text(debug_info=True)
        assert str(jax.make_jaxpr(bare)(*args)) == scoped


@pytest.mark.parametrize("entry", ["decode", "prefill-slot",
                                   "decode-multi[2]"])
def test_decode_programs_carry_every_layer_scope_and_the_same_equations(
        entry, monkeypatch):
    net = _net()
    net.warmup_generate(slots=2, max_seq=16, prompt_buckets=(4,),
                        steps_per_dispatch=2)
    rec = next(r for r in net.infer_cache.audit_records()
               if r["key"][0] == entry)
    text = jax.jit(rec["build"]()).lower(*rec["abstract"]).as_text(
        debug_info=True)
    wanted = _layer_scopes(net.conf) + ["embed", "qkv", "kv_write", "scores",
                                        "attend", "proj", "ffn", "ln", "head",
                                        "sample"]
    for scope in wanted:
        assert re.search(rf'[/("]{re.escape(scope)}[/)]', text), scope
    scoped = str(jax.make_jaxpr(rec["build"]())(*rec["abstract"]))
    with monkeypatch.context() as m:
        _without_scopes(m)
        assert str(jax.make_jaxpr(rec["build"]())(*rec["abstract"])) == scoped


def test_scopes_and_names_change_no_output_bit(monkeypatch):
    x = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    with_scopes = np.asarray(_net().output(x))
    with monkeypatch.context() as m:
        _without_scopes(m)
        m.setattr(profiling, "named", lambda fn, entry: fn)
        bare = np.asarray(_net().output(x))
    np.testing.assert_array_equal(with_scopes, bare)


# -- the serving loop ----------------------------------------------------------
def test_batcher_leaves_one_admit_span_a_stream_with_its_children():
    net = _net()
    net.warmup_generate(slots=2, max_seq=16, prompt_buckets=(4,))
    batcher = ContinuousBatcher(net, n_slots=2, max_seq=16,
                                prompt_buckets=(4,)).start()
    profiling.clear()
    streams = [batcher.submit(np.arange(1, 4), max_new_tokens=5)
               for _ in range(3)]
    assert [s.rid for s in streams] == [1, 2, 3]
    answers = [list(s.tokens(timeout=60)) for s in streams]
    assert all(len(a) == 5 for a in answers)
    batcher.stop()
    stats = batcher.stats()
    record = profiling.spans()
    admits = [s for s in record if s.name == "admit"]
    assert sorted(s.rid for s in admits) == [1, 2, 3]
    loop_thread = admits[0].thread
    for a in admits:
        kids = [s for s in record if s.parent == a.sid]
        assert [k.name for k in kids] == ["admit.prefill", "admit.deliver"]
        assert a.attrs["path"] == "prefill_slot"
        assert all(k.rid == a.rid and k.thread == loop_thread for k in kids)
        assert all(a.start_ns <= k.start_ns <= k.end_ns <= a.end_ns
                   for k in kids)
        assert a.attrs["slot"] in (0, 1) and a.attrs["bucket"] == 4
        assert a.attrs["prompt_tokens"] == 3 and a.attrs["queue_wait_ns"] > 0
    # the third stream waited for a slot: a whole generation of its neighbours
    assert max(a.attrs["queue_wait_ns"] for a in admits) > \
        10 * min(a.attrs["queue_wait_ns"] for a in admits)
    seconds = sum(a.end_ns - a.start_ns for a in admits) / 1e9
    assert stats["admit_seconds_total"] == pytest.approx(seconds, abs=1e-5)
    assert stats["queue_wait_seconds_total"] == pytest.approx(
        sum(a.attrs["queue_wait_ns"] for a in admits) / 1e9, abs=1e-5)

    # the loop's thread is tiled by admit, decode and idle
    top = sorted((s for s in record
                  if s.thread == loop_thread and s.parent is None),
                 key=lambda s: s.start_ns)
    # (`idle` only where the loop got to wait before the first submit or
    # after the last token: a submit runs nothing on the device any more)
    assert {"admit", "decode"} <= {s.name for s in top} <= {"admit", "decode",
                                                            "idle"}
    decodes = [s for s in top if s.name == "decode"]
    order = ["decode.readback", "decode.deliver", "decode.wait",
             "decode.dispatch"]
    for d in decodes:
        kids = [s.name for s in record if s.parent == d.sid]
        assert set(kids) <= set(order) and kids.count("decode.dispatch") <= 1
        if "decode.dispatch" in kids:
            # a step ahead completes the step before the one in flight,
            # waits while a slot stands free, then dispatches; a span that
            # found nothing in flight dispatches and, where it may not run
            # ahead, completes its own step
            if kids[0] == "decode.readback":
                assert d.attrs["ahead"] == 1 and kids[-1] == "decode.dispatch"
            if kids[-1] == "decode.deliver":
                assert d.attrs["ahead"] == 0 and kids[0] == "decode.dispatch"
            assert 1 <= d.attrs["live"] <= 2
        assert kids.count("decode.readback") == kids.count("decode.deliver")
        assert d.attrs["k"] == 1
    dispatched = [d for d in decodes if "ahead" in d.attrs]
    assert stats["decode_steps_total"] == len(dispatched)
    assert stats["decode_steps_ahead_total"] == sum(
        d.attrs["ahead"] for d in dispatched) > 0
    # decode steps only: the admissions are in no part of this counter
    assert stats["decode_host_seconds_total"] < \
        sum(d.end_ns - d.start_ns for d in decodes) / 1e9


def test_fused_blocks_run_inside_one_decode_span_with_the_same_children():
    net = _net()
    net.warmup_generate(slots=2, max_seq=32, prompt_buckets=(4,),
                        steps_per_dispatch=4)
    batcher = ContinuousBatcher(net, n_slots=2, max_seq=32,
                                prompt_buckets=(4,),
                                steps_per_dispatch=4).start()
    profiling.clear()
    assert len(batcher.generate(np.arange(1, 4), max_new_tokens=20)) == 20
    batcher.stop()
    record = profiling.spans()
    fused = [s for s in record if s.name == "decode" and s.attrs["k"] == 4]
    assert fused and all(s.parent is None for s in fused)
    inside = [s.name for s in record if s.parent in {d.sid for d in fused}]
    assert set(inside) == {"decode.dispatch", "decode.readback",
                           "decode.deliver"}
    # more than one block a span: the rounds are pipelined inside it
    assert inside.count("decode.dispatch") > len(fused)


def test_admission_counters_reach_the_prometheus_page():
    for family in ("dl4j_serving_admit_seconds_total",
                   "dl4j_serving_queue_wait_seconds_total"):
        assert FAMILIES[family] == ("counter", ())
    page = replica_metrics({"generation": {"admit_seconds_total": 1.5,
                                           "queue_wait_seconds_total": 0.25}})
    assert "dl4j_serving_admit_seconds_total 1.5" in page
    assert "dl4j_serving_queue_wait_seconds_total 0.25" in page


# -- training and input ----------------------------------------------------------
def test_fit_spans_tile_its_thread_and_carry_the_calls_number():
    net = _net()
    trainer = DataParallelTrainer(net, make_mesh({"dp": 2}), mode="sync")
    batches = [_batch(seed=i) for i in range(3)]
    trainer.fit(batches)
    profiling.clear()
    trainer.fit(PrefetchIterator(batches))
    record = profiling.spans()
    mine = [s for s in record if s.name.startswith("fit.")]
    assert {s.rid for s in mine} == {2}
    thread = mine[0].thread
    top = sorted((s for s in record if s.thread == thread
                  and s.parent is None), key=lambda s: s.start_ns)
    assert [s.name for s in top] == (["fit.next", "fit.step"] * 3
                                     + ["fit.next", "fit.sync"])
    assert [s.attrs["step"] for s in top if s.name == "fit.step"] == [1, 2, 3]
    waits = [s for s in record if s.name == "prefetch.wait"]
    nexts = {s.sid for s in top if s.name == "fit.next"}
    assert len(waits) == 4 and all(w.parent in nexts and w.rid == 2
                                   for w in waits)
    # the worker's spans are on another thread and are nobody's children
    worker = [s for s in record if s.name in ("prefetch.next",
                                              "prefetch.transfer")]
    assert len([s for s in worker if s.name == "prefetch.transfer"]) == 3
    assert all(s.thread != thread and s.parent is None for s in worker)


def test_prefetch_wait_is_the_slow_base_iterators_time():
    nap = 0.05

    def slow():
        for i in range(6):
            time.sleep(nap)
            yield np.full((2,), i, np.float32)

    got = [int(b[0]) for b in PrefetchIterator(slow(), buffer_batches=1,
                                               to_device=False)]
    assert got == list(range(6))
    record = profiling.spans()
    waits = [(s.end_ns - s.start_ns) / 1e9 for s in record
             if s.name == "prefetch.wait"]
    base = [(s.end_ns - s.start_ns) / 1e9 for s in record
            if s.name == "prefetch.next"]
    assert len(waits) == 7 and len(base) == 7     # the last ones end the run
    assert statistics.median(base) == pytest.approx(nap, abs=0.02)
    assert statistics.median(waits) == pytest.approx(nap, abs=0.02)
    assert sum(waits) == pytest.approx(6 * nap, abs=0.1)
    assert all(s.attrs["depth"] in (0, 1) for s in record
               if s.name == "prefetch.wait")
