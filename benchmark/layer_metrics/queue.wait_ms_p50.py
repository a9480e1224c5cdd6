"""Median wait of a stream between `submit` and the start of its admission:
the `queue_wait_ns` of its first `admit` span, by rid."""
import statistics

from benchmark import program_spans


def read(seen):
    admits = program_spans.named(seen, "admit")
    if not admits:
        return None
    first = {}
    for s in admits:
        first.setdefault(s.rid, s.attrs["queue_wait_ns"])
    return statistics.median(first.values()) / 1e6
