"""Fresh compiles of the infer cache after `warmup_generate`; 0 is right.
Source: `net.infer_cache.stats.misses`."""


def read(seen):
    return seen["counters"].get("program_compiles")
