"""Megabytes of carried state that one step reads, in the median over the
window's `decode` spans: their `carry_bytes_read` (every slot's row of every
state that advances with each token, read whole: a delta rule's matrices, a
convolution's carried inputs) over the span's steps.  It is the part of
`decode.state_read_mb_p50` that no walk of a row's live cells can shorten,
and it moves with the state's layout and with how often a step reads it.  A
program whose spans carry no such count leaves the metric out."""
import statistics

from benchmark import program_spans


def read(seen):
    per_step = [s.attrs["carry_bytes_read"] / s.attrs.get("steps", s.attrs.get("k", 1))
                for s in program_spans.named(seen, "decode") or ()
                if "carry_bytes_read" in s.attrs]
    if not per_step:
        return None
    return statistics.median(per_step) / 1e6
