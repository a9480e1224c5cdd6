"""One command runs one cell once:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It finds the cell in `BENCHMARK.json`,
its configuration under `configs/`, its traffic under `traffic/`, the
traffic's job under `jobs/`, its limits under `limits/` and each per-layer
metric's reader under `layer_metrics/`, all by name; the job and the readers
find the model's family under `families/` by the configuration's
`model_type`.  Nothing that belongs to one cell or one model lives here.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()    # set-up counts from here

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import types

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)


def load_cell(workload: str, rehearse: bool) -> types.SimpleNamespace:
    from benchmark import traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    if rehearse:
        cfg = {**cfg, **cfg["rehearse"]}
    return types.SimpleNamespace(
        bench=bench, cell=cell, cfg=cfg,
        mix=traffic.load(cell["traffic"], rehearse))


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The metrics of `group` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def place_compile_cache() -> str:
    """JAX's persistent cache: where `JAX_COMPILATION_CACHE_DIR` says, else a
    fixed directory in the checkout.  Every program goes in, however small."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def find_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it.  No TPU, or fewer chips than the cell
    asks for, ends the run (a rehearsal takes the CPU and says so)."""
    import jax

    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"the cell needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} x {devs[0].platform}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips if devs[0].platform == "tpu" else len(devs)}


@contextlib.contextmanager
def watch_compiles():
    """Counts what JAX compiles, or fetches from its cache, inside the block."""
    import jax.monitoring as mon

    seen = {"count": 0}

    def on_duration(name, seconds, **_):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
            seen["count"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        mon.unregister_event_duration_listener(on_duration)


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend has none)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices()[:chips])


def read_layer_metrics(cell, result: dict, reduced, device: dict) -> dict:
    """Each per-layer metric of the cell, by its own reader.  A reader that
    finds nothing to read returns None and the metric is left out."""
    from benchmark import flops

    seen = {"counters": result["counters"], "trace": reduced, "cfg": cell.cfg,
            "mix": cell.mix, "chips": cell.cell["chips"],
            "peaks": (flops.peaks(device["kind"])
                      if device["platform"] == "tpu" else None)}
    out = {}
    for m in metrics_of(cell.bench, "per_layer", cell.cell["name"]):
        path = os.path.join(_HERE, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark_layer_metric_" + m["name"].replace(".", "_").replace("-", "_"),
            path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        value = reader.read(seen)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; times nothing "
                         "worth reporting and names the CPU under `device`")
    ap.add_argument("--dump", help="also write the job's counters and the "
                    "numbers compared to this file, as JSON")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, args.rehearse)
    chips = int(cell.cell["chips"])
    device = find_device(chips, args.rehearse)
    place_compile_cache()
    from benchmark import correct, trace_reduce

    job = importlib.import_module("benchmark.jobs." + cell.mix["job"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    ctx = types.SimpleNamespace(
        cfg=cell.cfg, mix=cell.mix, seed=args.seed, seconds=args.seconds,
        chips=chips, trace_dir=trace_dir,
        watch_compiles=watch_compiles,
        memory_peak=lambda: memory_peak(chips))
    try:
        result = job.run(ctx)
        reduced = None
        if trace_dir:
            reduced = trace_reduce.reduce_trace(
                trace_reduce.find_xplane(trace_dir),
                idle_label=cell.mix.get("idle_label", "host"))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    if args.dump:
        with open(args.dump, "w") as f:
            json.dump({"counters": result["counters"], "reduced": reduced,
                       "numbers": result["numbers"]}, f)
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    checks = correct.judge(result["numbers"],
                           correct.load_limits(cell.cell["name"]))
    ok = all(c[3] for c in checks)
    if args.trace:
        metrics = read_layer_metrics(cell, result, reduced, device)
        if reduced["busy_s"] is not None:
            device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        values = dict(result["end_to_end"],
                      setup_s=result["t_first"] - _T_PROCESS)
        metrics = {}
        for m in metrics_of(cell.bench, "end_to_end", cell.cell["name"]):
            v = values[m["name"]]
            if not math.isfinite(v):
                ok, v = False, 1e12
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    line = {"correct": bool(ok), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if args.trace and reduced["busy_s"] is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["compiles_in_window"] = result["counters"].get("xla_compiles")
    line["reference_s"] = result["counters"].get("reference_s")
    compared = {name: {"value": value, "limit": limit}
                for name, value, limit, _ in checks}
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    for name, value, limit, good in checks:
        print(f"compared {name} = {value:.6g} limit {limit} "
              f"{'ok' if good else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
