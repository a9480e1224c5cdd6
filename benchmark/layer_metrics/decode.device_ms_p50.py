"""Median device time of the decode programs (`jit_dl4j_decode*`), found in
the trace by name."""
from benchmark import program_spans


def read(seen):
    return program_spans.median_module_ms(seen, program_spans.DECODE)
