"""Median device time of the prefill program (one row a call) in the trace."""
from benchmark.jobs.generate import traced_program_seconds


def read(seen):
    t = traced_program_seconds(seen, "prefill")
    return None if t is None else t * 1e3
