"""Share of the decode loop's wall time not spent blocked on the device.
Source: `ContinuousBatcher.stats()["host_overhead_fraction"]`."""


def read(seen):
    v = seen["counters"].get("host_overhead_fraction")
    return None if v is None else 100.0 * v
