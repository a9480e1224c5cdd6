"""Which device this process runs on, and how a process gets one.

`jax.devices()[0].platform` acquires the backend client lock on every
call; kernel dispatch sites (`nd/pallas_kernels._interpret`, the
`attention_impl="auto"` crossover) ask on every trace, so the answer is
memoized once per process.  The platform cannot change after the first
backend initialization, so a process-lifetime cache is safe.

Importing this module initialises no backend: `chip_env` and
`place_compile_cache` are for parents that start the processes which do.
"""

from __future__ import annotations

import functools
import os
from typing import Dict

#: JAX's persistent compilation cache when `JAX_COMPILATION_CACHE_DIR` is
#: unset: one fixed, git-ignored directory at the root of the checkout (the
#: path is part of the cache key, so it must not move between runs)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


@functools.lru_cache(maxsize=None)
def default_platform() -> str:
    """Platform string of the default jax backend ("cpu"/"gpu"/"tpu")."""
    import jax

    return jax.devices()[0].platform


def is_tpu() -> bool:
    return default_platform() == "tpu"


@functools.lru_cache(maxsize=None)
def devices() -> tuple:
    """The visible devices of the default backend, as a tuple (the
    repo-wide replacement for direct `jax.devices()` calls — the
    repo-convention linter bans those outside this module)."""
    import jax

    return tuple(jax.devices())


def device_count() -> int:
    return len(devices())


@functools.lru_cache(maxsize=None)
def default_backend() -> str:
    """`jax.default_backend()`, memoized — the backend cannot change
    after first initialization, and the raw call takes the client lock."""
    import jax

    return jax.default_backend()


def describe() -> dict:
    """What JAX found, for the JSON every CLI command prints: a CPU run
    and a chip run must not read the same.  `chip` is the host chip the
    launcher pinned this process to (`chip_env`), None when unpinned."""
    devs = devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "chip": os.environ.get("TPU_VISIBLE_CHIPS")}


#: the shape libtpu is told a process's chips form, by how many they are
#: (each seen to start on a four-chip v5e host; `2,1,1` for two does not)
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def chip_env(first: int, count: int = 1) -> Dict[str, str]:
    """Environment that gives a child process chips `first` to
    `first + count - 1` of this host and no other, as a slice of its own.
    A chip belongs to one process at a time: a parent that starts several
    replicas or workers hands each its own chips this way and stays off
    JAX itself.  Bounds smaller than the host are also what lets libtpu
    load once per child; they go out under both of libtpu's spellings,
    because a host image may have set the older one to the whole host
    (this is the set that ran four replicas on a four-chip v5e host).
    Harmless where there is no TPU."""
    if count not in _CHIP_BOUNDS:
        raise ValueError(f"a process can be given {sorted(_CHIP_BOUNDS)} "
                         f"chips of a host, not {count}")
    bounds = _CHIP_BOUNDS[count]
    return {"TPU_VISIBLE_CHIPS": ",".join(str(first + i)
                                          for i in range(count)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_CHIPS_PER_HOST_BOUNDS": bounds,
            "TPU_HOST_BOUNDS": "1,1,1"}


def place_compile_cache() -> str:
    """Say where JAX's persistent compilation cache lives, before the
    first compile.  Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it
    itself and nothing is set in code; otherwise the fixed
    `COMPILE_CACHE_DIR`.  Children inherit either.  Returns the
    directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
