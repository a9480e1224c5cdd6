"""Model FLOPs of one step (the family's, from shapes, no recomputation) over
the median device time of the step program in the trace, over the chips' peak."""
import statistics

from benchmark import families, trace_reduce


def read(seen):
    c, trace = seen["counters"], seen["trace"]
    if not trace or not seen["peaks"] or not c.get("traced_steps"):
        return None
    runs = trace_reduce.program_runs(trace, c["traced_steps"] * seen["chips"])
    if not runs:
        return None
    need = families.of(seen["cfg"]).flops.train_step_flops(seen["cfg"], c)
    peak = seen["peaks"]["bf16_flops_per_s"] * seen["chips"]
    return 100.0 * need / statistics.median(runs) / peak
