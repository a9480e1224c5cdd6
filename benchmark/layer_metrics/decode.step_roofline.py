"""The decode program taken as one kernel, against its roofline.  It is
bound by bytes: what the configuration's family says a step has to read
(weights, and K and V of the live positions), over the chip's memory
bandwidth, over the program's median device time."""
from benchmark import families
from benchmark.jobs.generate import traced_program_seconds


def read(seen):
    c = seen["counters"]
    t = traced_program_seconds(seen, "decode")
    if t is None or not seen["peaks"] or c.get("traced_live_positions") is None:
        return None
    need = families.of(seen["cfg"]).flops.decode_step_bytes(seen["cfg"], c)
    return 100.0 * need / seen["peaks"]["hbm_bytes_per_s"] / t
