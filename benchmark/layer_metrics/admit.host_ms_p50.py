"""Median `admit` span: what one admission holds the batcher's loop for."""
from benchmark import program_spans


def read(seen):
    return program_spans.median_ms(program_spans.named(seen, "admit"))
