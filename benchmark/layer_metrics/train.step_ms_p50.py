"""Median time of one step, from the stream's stamps of its hand-overs to
`fit`.  `fit` runs ahead of the device and is let go in bursts, so single
gaps swing; the time is taken over every run of 8 hand-overs."""
import statistics

_RUN = 8


def read(seen):
    t = seen["counters"].get("stamps_s") or []
    spans = [(b - a) / _RUN for a, b in zip(t, t[_RUN:])]
    return statistics.median(spans) * 1e3 if spans else None
