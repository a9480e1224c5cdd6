"""Tunables registry, the TunedTable override layer, and the tune search.

Pins the ISSUE-18 contract: with no table installed every call site
behaves byte-identically to the pre-registry constants; a `cli tune` run
persists a table a fresh process inherits with ``fresh_tunes == 0``; a
table tuned for another device kind is never consulted; corrupt
artifacts checksum-evict and the caller re-tunes; the search is
deterministic under a fixed seed and an injected clock; and the
``tune.measure``/``tune.load`` fault points degrade, never block.
"""
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import char_transformer, mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize import tunables
from deeplearning4j_tpu.optimize import tune
from deeplearning4j_tpu.optimize.persist import PersistentProgramStore
from deeplearning4j_tpu.optimize.step_cache import conf_fingerprint
from deeplearning4j_tpu.reliability import faults


@pytest.fixture(autouse=True)
def _clean_tunables():
    tunables.clear()
    faults.reset()
    yield
    tunables.clear()
    faults.reset()


def _mlp_conf():
    return mlp(n_in=4, hidden=[6], n_out=3, lr=0.05)


def _transformer_conf(seq=16):
    return char_transformer(24, d_model=16, n_blocks=1, n_heads=2,
                            max_seq_len=seq)


# -- registry defaults == the legacy constants -------------------------------

def test_registry_defaults_match_legacy_constants():
    """The migrated constants resolve to exactly the values the call
    sites used to hard-code (the no-table byte-identity contract)."""
    from deeplearning4j_tpu.serving import batcher

    assert tunables.default("batcher.target_rows") == 256
    assert batcher.DEFAULT_TARGET_ROWS == 256
    assert tunables.default("batcher.max_delay_ms") == 3.0
    assert tunables.default("decode.slots") == 4
    assert tunables.default("decode.page_size") == 0
    assert tunables.default("data.prefetch_depth") == 2
    assert tunables.default("infer.bucket_ladder") == ()
    # flash-attention fwd/bwd defaults are None: the kernel layer falls
    # back to the measured table, which moved here verbatim
    assert tunables.default("attention.block_fwd") is None
    assert tunables.default("attention.block_bwd") is None


def test_block_table_rows_reach_pick_attention_blocks():
    from deeplearning4j_tpu.nd.pallas_kernels import pick_attention_blocks

    for (seq, hd), row in tunables.ATTENTION_BLOCK_TABLE.items():
        assert pick_attention_blocks(seq, hd) == row[:2]
        assert pick_attention_blocks(seq, hd, bwd=True) == row[2:]


def test_every_registry_entry_is_well_formed():
    for name, tun in tunables.REGISTRY.items():
        assert tun.name == name and "." in name
        assert tun.subsystem and tun.doc
        assert isinstance(tun.space, tuple) and tun.space


# -- resolve / install / clear -----------------------------------------------

def test_resolve_prefers_qualified_then_bare_then_default():
    assert tunables.resolve("batcher.target_rows") == 256
    tunables.install(tunables.TunedTable({
        "batcher.target_rows": 512,
        "attention.block_fwd": (128, 128),
        "attention.block_fwd@256x64": (256, 256),
    }, device_kind="cpu", fingerprint="f"))
    assert tunables.resolve("batcher.target_rows") == 512
    # qualified entry wins over the bare one ...
    assert tunables.resolve("attention.block_fwd", "256x64") == (256, 256)
    # ... and other qualifiers fall through to the bare entry
    assert tunables.resolve("attention.block_fwd", "512x64") == (128, 128)
    # untouched tunables keep their defaults
    assert tunables.resolve("decode.slots") == 4
    tunables.clear()
    assert tunables.resolve("batcher.target_rows") == 256
    assert tunables.active() is None


def test_tuned_blocks_flow_through_pick_attention_blocks():
    from deeplearning4j_tpu.nd.pallas_kernels import pick_attention_blocks

    tunables.install(tunables.TunedTable(
        {"attention.block_fwd@256x64": (256, 256)},
        device_kind="cpu", fingerprint="f"))
    assert pick_attention_blocks(256, 64) == (256, 256)
    # bwd has no tuned entry: the measured-table default stands
    assert pick_attention_blocks(256, 64, bwd=True) == \
        tunables.ATTENTION_BLOCK_TABLE[(256, 64)][2:]


def test_status_reports_table_and_fresh_counter():
    s = tunables.status()
    assert s == {"tuned_tables": 0, "fresh_tunes": 0, "entries": 0,
                 "device_kind": "", "source": ""}
    tunables.install(tunables.TunedTable({"decode.slots": 8},
                                         device_kind="cpu",
                                         fingerprint="f"), source="disk")
    tunables.note_fresh(3)
    s = tunables.status()
    assert s["tuned_tables"] == 1 and s["entries"] == 1
    assert s["fresh_tunes"] == 3 and s["source"] == "disk"
    assert s["device_kind"] == "cpu"


def test_table_serialization_round_trips_tuples():
    t = tunables.TunedTable(
        {"attention.block_fwd@1024x64": (256, 256),
         "infer.bucket_ladder": (8, 64, 256),
         "batcher.target_rows": 512},
        device_kind="cpu", fingerprint="abcd", meta={"rounds": 3})
    back = tunables.TunedTable.from_bytes(t.to_bytes())
    # JSON turns tuples into lists; from_bytes re-tuples recursively
    assert back.entries == t.entries
    assert back.device_kind == "cpu" and back.fingerprint == "abcd"
    assert back.meta == {"rounds": 3}


def test_schema_mismatch_rejected():
    payload = json.loads(tunables.TunedTable({}).to_bytes())
    payload["schema"] = tunables.SCHEMA_VERSION + 1
    with pytest.raises(ValueError):
        tunables.TunedTable.from_bytes(json.dumps(payload).encode())


# -- no-table byte-identity (the regression pin) -----------------------------

def test_no_table_disk_artifacts_byte_identical(tmp_path):
    """A warmup with no table and one with an EMPTY table produce the
    identical artifact set — resolve() with no entries is exactly the
    registry default, so cache keys and programs don't move."""
    conf = _mlp_conf()

    def warm(subdir, table):
        tunables.clear()
        if table is not None:
            tunables.install(table)
        net = MultiLayerNetwork(conf, seed=0).init()
        net.set_compile_cache(str(tmp_path / subdir))
        net.warmup([8], entries=("output",), train=True)
        return sorted(os.listdir(tmp_path / subdir))

    files_none = warm("none", None)
    files_empty = warm("empty", tunables.TunedTable(
        {}, device_kind="cpu", fingerprint=conf_fingerprint(conf)))
    assert files_none and files_none == files_empty


def test_empty_bucket_ladder_keeps_grow_on_demand():
    """The registry default () leaves bucket_rows byte-identical to the
    legacy grow-on-demand loop; a tuned ladder pre-seeds buckets."""
    from deeplearning4j_tpu.optimize.step_cache import CompiledProgramCache

    c = CompiledProgramCache()
    assert c.bucket_rows(5) == 5 and c.buckets == (5,)

    tunables.install(tunables.TunedTable(
        {"infer.bucket_ladder": (8, 32)}, device_kind="cpu",
        fingerprint="f"))
    c2 = CompiledProgramCache()
    assert c2.bucket_rows(5) == 8
    assert c2.bucket_rows(20) == 32
    assert set(c2.buckets) >= {8, 32}
    # fixed bucket sets never merge the ladder (declared policy wins)
    c3 = CompiledProgramCache(buckets=(16,))
    assert c3.bucket_rows(5) == 16 and c3.buckets == (16,)


def test_batcher_defaults_resolve_through_registry():
    from deeplearning4j_tpu.serving.batcher import MicroBatcher

    net = MultiLayerNetwork(_mlp_conf(), seed=0).init()
    mb = MicroBatcher(net)
    try:
        assert mb.max_delay_s == pytest.approx(3.0 / 1e3)
    finally:
        mb.stop()
    tunables.install(tunables.TunedTable(
        {"batcher.max_delay_ms": 1.0}, device_kind="cpu", fingerprint="f"))
    mb2 = MicroBatcher(net)
    try:
        assert mb2.max_delay_s == pytest.approx(1.0 / 1e3)
        # an explicit argument still beats the table
        mb3 = MicroBatcher(net, max_delay_ms=5.0)
        try:
            assert mb3.max_delay_s == pytest.approx(5.0 / 1e3)
        finally:
            mb3.stop()
    finally:
        mb2.stop()


# -- persistence: device-kind isolation + corrupt artifacts ------------------

def test_save_load_round_trip_and_wrong_kind_isolated(tmp_path):
    store = PersistentProgramStore(str(tmp_path))
    kind = store.platform.get("device_kind", "none")
    fp = "feedc0de"
    table = tunables.TunedTable({"decode.slots": 8}, device_kind=kind,
                                fingerprint=fp)
    tunables.save_table(store, table)
    back = tunables.load_table(store, fp, kind)
    assert back is not None and back.entries == {"decode.slots": 8}
    # a table keyed for another kind is simply never found ...
    assert tunables.load_table(store, fp, "tpu-v9") is None
    # ... and a forged payload claiming another kind under this kind's
    # key is rejected (degrades to defaults, one warning)
    forged = tunables.TunedTable({"decode.slots": 16},
                                 device_kind="tpu-v9", fingerprint=fp)
    store.store_bytes(tunables.table_key(fp, kind), forged.to_bytes())
    assert tunables.load_table(store, fp, kind) is None


def test_corrupt_artifact_evicts_then_retune_persists(tmp_path):
    store = PersistentProgramStore(str(tmp_path))
    kind = store.platform.get("device_kind", "none")
    net = MultiLayerNetwork(_mlp_conf(), seed=0).init()
    fp = conf_fingerprint(net.conf)
    tunables.save_table(store, tunables.TunedTable(
        {"decode.slots": 8}, device_kind=kind, fingerprint=fp))
    path = store.path_for(tunables.table_key(fp, kind))
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 0xFF  # flip a payload byte: checksum must catch it
    open(path, "wb").write(bytes(blob))

    assert tunables.load_table(store, fp, kind) is None
    assert not os.path.exists(path)  # checksum-evicted, not left to rot

    # the caller re-tunes and the fresh table persists again
    report = tune.tune_and_store(net, store, groups=("serve",), rounds=1)
    assert report["tuning"]["tuned_tables"] == 1
    assert report["tuning"]["source"] == "fresh"
    assert tunables.load_table(store, fp, kind) is not None


def test_existing_table_inherited_without_search(tmp_path):
    """tune_and_store without --force inherits a stored table: zero
    candidates measured, fresh_tunes == 0, source == disk."""
    store = PersistentProgramStore(str(tmp_path))
    kind = store.platform.get("device_kind", "none")
    net = MultiLayerNetwork(_mlp_conf(), seed=0).init()
    fp = conf_fingerprint(net.conf)
    tunables.save_table(store, tunables.TunedTable(
        {"batcher.target_rows": 512}, device_kind=kind, fingerprint=fp))

    report = tune.tune_and_store(net, store)
    assert report["candidates_measured"] == 0
    assert report["entries"] == {"batcher.target_rows": 512}
    assert report["tuning"]["fresh_tunes"] == 0
    assert report["tuning"]["source"] == "disk"
    assert tunables.resolve("batcher.target_rows") == 512


# -- fault points ------------------------------------------------------------

def test_measure_fault_skips_candidate_search_completes():
    """An armed tune.measure failure skips that candidate (counted) and
    the search still completes with the surviving timings."""
    net = MultiLayerNetwork(_mlp_conf(), seed=0).init()
    faults.arm("tune.measure", "raise", nth=2)
    report = tune.tune_model(net, groups=("serve",), rounds=1)
    n_cands = len(sorted(set(
        tunables.REGISTRY["batcher.target_rows"].space) | {256}))
    assert report["measure_failures"] == 1
    assert report["candidates_measured"] == n_cands - 1
    # the faulted candidate is absent from the measured report
    measured = report["groups"]["serve"]["batcher.target_rows"]["candidates"]
    assert len(measured) == n_cands - 1


def test_load_fault_degrades_to_defaults_one_warning(tmp_path, caplog):
    """A failing table read degrades to registry defaults with ONE
    warning — serving never blocks on tuning."""
    store = PersistentProgramStore(str(tmp_path))
    kind = store.platform.get("device_kind", "none")
    tunables.save_table(store, tunables.TunedTable(
        {"decode.slots": 8}, device_kind=kind, fingerprint="fp"))
    faults.arm("tune.load", "ioerror", times=2)
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        assert tunables.load_and_install(store, "fp") is None
        assert tunables.load_and_install(store, "fp") is None
    warnings = [r for r in caplog.records
                if "tuned-table load failed" in r.getMessage()]
    assert len(warnings) == 1
    assert tunables.active() is None
    assert tunables.resolve("decode.slots") == 4  # registry default
    # once the fault clears, the same store serves the table again
    assert tunables.load_and_install(store, "fp") is not None


def test_tune_fault_points_are_documented():
    assert "tune.measure" in faults.DOCUMENTED_POINTS
    assert "tune.load" in faults.DOCUMENTED_POINTS


# -- the search itself -------------------------------------------------------

def test_prune_drops_analytically_bad_candidates():
    search = tune._Search(rounds=1, clock=lambda: 0.0)
    tun = tunables.Tunable("t", "s", 1, (1, 2, 3, 10),
                           lambda v, **_: float(v), "")
    kept = tune._prune(search, tun, [1, 2, 3, 10], 1)
    # cost >= 2x the incumbent's never compiles (10, 3, and 2 all are)
    assert kept == [1]
    assert search.candidates_pruned == 3
    # no cost hint: everything survives
    tun2 = tunables.Tunable("t2", "s", 1, (1, 2), None, "")
    assert tune._prune(search, tun2, [1, 2, 3], 1) == [1, 2, 3]


def test_attention_pruning_uses_profiling_cost_model():
    from deeplearning4j_tpu.optimize.tunables import attention_block_bytes

    # fewer q tiles restream K/V fewer times: block_q=256 moves less
    assert attention_block_bytes(1024, 64, 128, 128) > \
        attention_block_bytes(1024, 64, 256, 128)
    # the registry's cost hint is wired to this model
    hint = tunables.REGISTRY["attention.block_fwd"].cost_hint
    assert hint((128, 128), seq=1024, head_dim=64) == \
        attention_block_bytes(1024, 64, 128, 128)


def test_search_is_deterministic_under_seed_and_fake_clock():
    """Two runs with the same seed and an injected clock produce the
    byte-identical report — candidate order, timings, and winners."""
    net = MultiLayerNetwork(_mlp_conf(), seed=0).init()

    def mk_clock():
        state = [0.0]

        def clock():
            state[0] += 1.0
            return state[0]

        return clock

    r1 = tune.tune_model(net, groups=("serve",), rounds=1,
                         seed=7, clock=mk_clock())
    r2 = tune.tune_model(net, groups=("serve",), rounds=1,
                         seed=7, clock=mk_clock())
    assert r1["entries"] == r2["entries"]
    assert r1["groups"] == r2["groups"]
    assert r1["tune_seconds"] == r2["tune_seconds"]
    # under a constant-dt clock rows/s scales with rows: the serve
    # group deterministically picks the largest candidate
    g = r1["groups"]["serve"]["batcher.target_rows"]
    assert g["winner"] == max(
        tunables.REGISTRY["batcher.target_rows"].space)


def test_decode_group_skips_non_generative_confs():
    net = MultiLayerNetwork(_mlp_conf(), seed=0).init()
    report = tune.tune_model(net, groups=("decode",), rounds=1)
    assert report["entries"] == {}
    assert report["candidates_measured"] == 0


def test_winner_recorded_only_past_min_gain():
    """pick() keeps the default unless a challenger beats it by
    MIN_GAIN; a clear winner is recorded in entries."""
    search = tune._Search(rounds=1, clock=__import__("time").perf_counter)
    times = {1: 0.010, 2: 0.002}

    def run(c):
        __import__("time").sleep(times[c])

    winner = search.pick("g", "k", [1, 2], 1, run,
                         throughput=lambda c: 1.0)
    assert winner == 2 and search.entries["k"] == 2
    # a same-speed challenger never displaces the default
    search2 = tune._Search(rounds=1, clock=lambda: 0.0)
    fake = [0.0]

    def clock():
        fake[0] += 1.0
        return fake[0]

    search2.clock = clock
    assert search2.pick("g", "k", [1, 2], 1, lambda c: None) == 1
    assert "k" not in search2.entries


# -- end to end: cli tune -> fresh process inherits --------------------------

def test_cli_tune_then_fresh_warmup_inherits(tmp_path):
    """The acceptance loop across REAL processes: `cli tune` persists a
    table; a fresh `cli warmup` pointed at the same --compile-cache
    reports tuned_tables == 1 and fresh_tunes == 0."""
    conf_path = tmp_path / "conf.json"
    conf_path.write_text(_mlp_conf().to_json())
    cache = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    r1 = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.cli", "tune",
         "--model", str(conf_path), "--compile-cache", cache,
         "--groups", "serve", "--rounds", "1"],
        env=env, capture_output=True, text=True, timeout=240)
    assert r1.returncode == 0, r1.stderr[-2000:]
    rep = json.loads(r1.stdout.strip().splitlines()[-1])
    assert rep["tuning"]["tuned_tables"] == 1
    assert rep["tuning"]["source"] == "fresh"
    assert rep["tuning"]["fresh_tunes"] >= 1
    assert rep["candidates_measured"] > 0

    r2 = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.cli", "warmup",
         "--model", str(conf_path), "--compile-cache", cache,
         "--shapes", "8"],
        env=env, capture_output=True, text=True, timeout=240)
    assert r2.returncode == 0, r2.stderr[-2000:]
    summary = json.loads(r2.stdout.strip().splitlines()[-1])
    assert summary["tuning"]["tuned_tables"] == 1
    assert summary["tuning"]["fresh_tunes"] == 0
    assert summary["tuning"]["source"] == "disk"


def test_tune_and_store_transformer_all_groups(tmp_path):
    """Full three-group search on a tiny generative transformer: the
    report carries every group, the table persists, and re-running
    inherits it (fresh_tunes == 0)."""
    store = PersistentProgramStore(str(tmp_path))
    net = MultiLayerNetwork(_transformer_conf(), seed=0).init()
    report = tune.tune_and_store(net, store, rounds=1, max_seq=16)
    assert set(report["groups"]) == {"attention", "serve", "decode"}
    assert report["measure_failures"] == 0
    assert report["candidates_measured"] > 0
    assert report["tuning"]["source"] == "fresh"

    tunables.clear()
    again = tune.tune_and_store(net, store, rounds=1, max_seq=16)
    assert again["candidates_measured"] == 0
    assert again["tuning"]["fresh_tunes"] == 0
    assert again["tuning"]["source"] == "disk"
    assert again["entries"] == report["entries"]


# -- observability -----------------------------------------------------------

def test_metrics_families_strict_parse_and_monotonic():
    from deeplearning4j_tpu.serving.metrics import (FAMILIES,
                                                    parse_prometheus_text,
                                                    replica_metrics)

    assert FAMILIES["dl4j_tuning_table_info"] == ("gauge", ("device_kind",))
    assert FAMILIES["dl4j_tuning_fresh_tunes_total"] == ("counter", ())

    def render(fresh):
        stats = {"tuning": {"tuned_tables": 1, "fresh_tunes": fresh,
                            "entries": 3, "device_kind": "cpu",
                            "source": "disk"}}
        return replica_metrics(stats)

    parsed1 = parse_prometheus_text(render(2))  # raises on any bad line
    info = parsed1["dl4j_tuning_table_info"]
    assert info[(("device_kind", "cpu"),)] == 1
    fresh1 = parsed1["dl4j_tuning_fresh_tunes_total"][()]
    assert fresh1 == 2

    parsed2 = parse_prometheus_text(render(5))
    # the counter never moves backwards across scrapes
    assert parsed2["dl4j_tuning_fresh_tunes_total"][()] >= fresh1


def test_server_stats_carry_tuning_block():
    from deeplearning4j_tpu.serving.batcher import MicroBatcher

    tunables.install(tunables.TunedTable({"decode.slots": 8},
                                         device_kind="cpu",
                                         fingerprint="f"), source="disk")
    net = MultiLayerNetwork(_mlp_conf(), seed=0).init()
    mb = MicroBatcher(net)
    try:
        t = mb.stats()["tuning"]
        assert t["tuned_tables"] == 1 and t["source"] == "disk"
    finally:
        mb.stop()
