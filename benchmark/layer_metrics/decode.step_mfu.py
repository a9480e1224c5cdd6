"""Model FLOPs of one decode step, as the configuration's family counts them
from the live rows and positions, over the decode program's median device
time, over the chip's peak."""
from benchmark import families
from benchmark.jobs.generate import traced_program_seconds


def read(seen):
    c = seen["counters"]
    t = traced_program_seconds(seen, "decode")
    if t is None or not seen["peaks"] or c.get("traced_live_rows") is None:
        return None
    need = families.of(seen["cfg"]).flops.decode_step_flops(seen["cfg"], c)
    return 100.0 * need / t / seen["peaks"]["bf16_flops_per_s"]
