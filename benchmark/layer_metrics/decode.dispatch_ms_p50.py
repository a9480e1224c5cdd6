"""Median `decode.dispatch`: the argument copies and the program's call."""
from benchmark import program_spans


def read(seen):
    return program_spans.median_ms(
        program_spans.named(seen, "decode.dispatch"))
