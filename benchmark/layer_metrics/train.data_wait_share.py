"""Share of the window that `fit` spent waiting in `next()` of its feed."""


def read(seen):
    c = seen["counters"]
    if c.get("data_wait_s") is None:
        return None
    return 100.0 * c["data_wait_s"] / c["window_s"]
