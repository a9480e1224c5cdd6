"""Median `decode.deliver`: the per-slot loop that hands tokens to callers."""
from benchmark import program_spans


def read(seen):
    return program_spans.median_ms(program_spans.named(seen, "decode.deliver"))
