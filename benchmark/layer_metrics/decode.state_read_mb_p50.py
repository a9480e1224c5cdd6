"""Megabytes of decode state that one step reads, in the median over the
window's `decode` spans: their `state_bytes_read` (every slot's row of every
layer's state that the step reads, as it lies in memory: the cells a table's
read covers, a carried tail whole) over the span's steps.  It moves with the
read, not with the traffic: a read that stops at a row's live cells, or a
layout with fewer bytes a cell, lowers it.  A program whose spans carry no
such count leaves the metric out."""
import statistics

from benchmark import program_spans


def read(seen):
    per_step = [s.attrs["state_bytes_read"] / s.attrs.get("steps", s.attrs.get("k", 1))
                for s in program_spans.named(seen, "decode") or ()
                if "state_bytes_read" in s.attrs]
    if not per_step:
        return None
    return statistics.median(per_step) / 1e6
