"""bench.py's harness: one in-process loop that measures on the chip only.

A measurement path that finds no chip fails instead of carrying on on the
CPU; the first failing bench ends the run non-zero; a device with no peak
on record is an error, not a different metric.  `DL4J_BENCH_SMALL=1` is
the one way to run the suite on the CPU, at tiny shapes, for these tests.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _env(**extra):
    env = dict(os.environ)
    env.update({"DL4J_BENCH_SMALL": "1", "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    env.update(extra)
    return env


@pytest.mark.slow
def test_small_suite_emits_all_metrics_rc0():
    proc = subprocess.run([sys.executable, BENCH], env=_env(),
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    metrics = {l["metric"] for l in lines}
    assert len(lines) == len(metrics), "duplicate metric lines"
    # every line is driver-parseable and names the platform it ran on
    for l in lines:
        assert {"metric", "value", "unit", "vs_baseline"} <= set(l)
        assert l["platform"] == "cpu"
    # BASELINE five + heavyweights (north-star CLI emits two lines)
    expected_frags = ["LeNet5-MNIST", "charLSTM-PTB", "VGG-CIFAR10",
                      "Word2Vec", "all-reduce", "charLSTM-4layer",
                      "north-star CLI LeNet-MNIST",
                      "north-star CLI charLSTM-4layer", "charTransformer"]
    for frag in expected_frags:
        assert any(frag in m for m in metrics), f"missing metric: {frag}"


def _load_bench(monkeypatch, small: bool):
    import importlib.util

    if small:
        monkeypatch.setenv("DL4J_BENCH_SMALL", "1")
    else:
        monkeypatch.delenv("DL4J_BENCH_SMALL", raising=False)
    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_mod)
    return bench_mod


def test_refuses_to_measure_without_a_tpu():
    """No chip, no DL4J_BENCH_SMALL: non-zero exit, not one metric line."""
    env = _env()
    del env["DL4J_BENCH_SMALL"]
    proc = subprocess.run([sys.executable, BENCH], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refusing to measure" in proc.stderr


def test_first_failing_bench_stops_the_run_nonzero(monkeypatch, capsys):
    bench_mod = _load_bench(monkeypatch, small=True)
    ran = []

    def bench_ok(devs):
        ran.append("ok")
        bench_mod._emit("ok metric", 1.0, "x", None)

    def bench_broken(devs):
        ran.append("broken")
        raise RuntimeError("kernel refused")

    def bench_never(devs):
        ran.append("never")

    rc = bench_mod.main([bench_ok, bench_broken, bench_never])
    out = capsys.readouterr()
    assert rc == 1
    assert ran == ["ok", "broken"]
    assert "bench_broken failed" in out.err and "kernel refused" in out.err
    (line,) = [json.loads(l) for l in out.out.splitlines() if l.strip()]
    # every line names the platform it was taken on
    assert line["metric"] == "ok metric" and line["platform"] == "cpu"
    assert line["device_kind"]


def test_all_benches_passing_exits_zero(monkeypatch, capsys):
    bench_mod = _load_bench(monkeypatch, small=True)
    assert bench_mod.main([lambda devs: None]) == 0
    assert bench_mod.main([]) == 0
    capsys.readouterr()


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v5e", 197e12),
                                       ("TPU v4", 275e12)])
def test_peak_flops_known_devices(monkeypatch, kind, peak):
    assert _load_bench(monkeypatch, small=True)._peak_flops(kind) == peak


def test_peak_flops_unknown_device_is_an_error(monkeypatch):
    bench_mod = _load_bench(monkeypatch, small=True)
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        bench_mod._peak_flops("cpu")


def test_host_only_benches_stamp_cpu(monkeypatch, capsys):
    """A line whose children were forced to the CPU says so itself and is
    not overwritten with this process's platform."""
    bench_mod = _load_bench(monkeypatch, small=True)
    bench_mod._emit("m", 1.0, "x", None, platform="cpu", mesh="batch=8")
    line = json.loads(capsys.readouterr().out)
    assert line["platform"] == "cpu" and "device_kind" not in line
