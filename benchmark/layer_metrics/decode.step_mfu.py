"""Model FLOPs of one decode step (2 x matmul parameters x live rows, and
attention over the live positions) over the decode program's median device
time, over the chip's peak."""
from benchmark import flops
from benchmark.jobs.generate import traced_program_seconds


def read(seen):
    c = seen["counters"]
    t = traced_program_seconds(seen, "decode")
    if t is None or not seen["peaks"] or c.get("traced_live_rows") is None:
        return None
    need = flops.decode_step_flops(seen["cfg"], c["traced_live_rows"],
                                   c["traced_live_positions"])
    return 100.0 * need / t / seen["peaks"]["bf16_flops_per_s"]
