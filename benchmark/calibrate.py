"""Read the two ends a limit of `correct` is set between, on the chip:

    python3 -m benchmark.calibrate --workload <name> --seeds 1 2 3 ... --controls 3

For every seed the program's own numbers against the reference (the lower
reading is their largest), and for the first `--controls` seeds the control
and the faults a cell of this job can have, each put in the program's place
(the upper reading is their smallest).  One process, so that a dozen seeds
pay one set-up.  Prints one JSON object a seed and a summary; the limits go
into `limits/<workload>.json` by hand, with the readings into PERF.md.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import types


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, args.rehearse)
    chips = int(cell.cell["chips"])
    run.find_device(chips, args.rehearse)
    run.place_compile_cache()
    job = importlib.import_module("benchmark.jobs." + cell.mix["job"])
    ctx = types.SimpleNamespace(cfg=cell.cfg, mix=cell.mix, seed=None,
                                seconds=args.seconds, chips=chips)
    records = job.readings(ctx, args.seeds, set(args.seeds[: args.controls]))
    summary = {}
    for rec in records:
        print(json.dumps(rec), flush=True)
        for who in ("program", "control_int8", "fault_half_batch"):
            for name, value in rec.get(who, {}).items():
                summary.setdefault(who, {}).setdefault(name, []).append(value)
    print(json.dumps({"summary": {
        who: {name: {"min": min(v), "max": max(v), "n": len(v)}
              for name, v in numbers.items()}
        for who, numbers in summary.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
