"""Fresh compiles of the trainer's step cache after warm-up; 0 is right.
Source: `trainer.compile_cache.stats.misses`."""


def read(seen):
    return seen["counters"].get("program_compiles")
