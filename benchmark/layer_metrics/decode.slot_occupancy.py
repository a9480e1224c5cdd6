"""Mean share of the slot table in use, sampled by the harness from
`ContinuousBatcher.stats()["slots"]["active"]`."""


def read(seen):
    c = seen["counters"]
    s = c.get("occupancy_samples")
    return 100.0 * sum(s) / len(s) / c["slots"] if s else None
