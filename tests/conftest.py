"""Test configuration: run everything on a virtual 8-device CPU mesh.

This is the analog of the reference's in-JVM distributed test rig
(`BaseTestDistributed.java:34-98`, `IRUnitDriver.java:51`): distributed
logic is exercised against `xla_force_host_platform_device_count=8` virtual
devices so no TPU pod is needed (SURVEY §4 lesson).
"""

import os
import signal
import threading

import pytest

# force CPU even when the ambient env selects a TPU platform: the virtual
# 8-device mesh only exists on the host platform
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# The CPU backend is the rig these tests run on, not what they test, and most
# of the suite's CPU time is XLA compiling small programs for it: LLVM's
# expensive passes and the last level of XLA's own optimisation are left out
# (a fifth of the suite's CPU-seconds, PERF.md 7; every program is still
# compiled and run).  Level 0 is too low: it moves the seed's weights off
# their pins.
for flag in ("--xla_llvm_disable_expensive_passes=true",
             "--xla_backend_optimization_level=1"):
    if flag.split("=")[0] not in flags:
        flags += " " + flag
os.environ["XLA_FLAGS"] = flags.strip()
# XLA's loader writes a 3 KB error line for every program it takes from the
# compile cache below (a target feature its own compiler adds is "not
# supported on the host"): twenty of them fill the stderr pipe nobody reads of
# a child that a case started, and the child hangs.  Python's own messages
# and tracebacks are not touched.
os.environ["TF_CPP_MIN_LOG_LEVEL"] = "3"

import jax  # noqa: E402  (import after env is set)

jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process integration tests (tens of seconds)")


@pytest.fixture(scope="session", autouse=True)
def compile_cache_of_the_run(tmp_path_factory):
    """JAX's persistent compile cache for this run alone: one directory under
    pytest's own temporary one, shared by the workers and, through the
    environment, by every child a case starts, so that a program compiled
    once for the CPU rig is not compiled again by the next file, worker or
    replica.  It is new with every run, so nothing stale is read, and while
    the variable is set neither `cli` nor `benchmark.run` points a worker at
    `<checkout>/.jax_cache` (ROADMAP Design 2)."""
    base = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent      # the run's directory, above this worker's
    with pytest.MonkeyPatch.context() as patch:
        for name, value in (
                ("jax_compilation_cache_dir", str(base / "jax-cache")),
                ("jax_persistent_cache_min_compile_time_secs", 0.0),
                ("jax_persistent_cache_min_entry_size_bytes", -1)):
            patch.setenv(name.upper(), str(value))
            jax.config.update(name, value)
        yield


# A case that hangs fails by its name and costs this many seconds, not the
# run: the driver's clock would otherwise cut the run at the hung case and
# count nothing after it.  Seven times the slowest sound case under six
# workers on eight cores (54 s) and still twice what the slowest takes where
# the six share two cores, as at the driver (173 s; both PERF.md 7), so that
# nothing sound meets it; the children that cases start carry timeouts under
# it.
LIMIT = 400.0


def _limited(item, phase):
    """Arms a wall-clock timer round one phase of one case.  Signals reach
    the main thread alone, so a case run from another thread is left be."""
    if threading.current_thread() is not threading.main_thread():
        return (yield)

    def expired(signum, frame):
        pytest.fail(f"{item.nodeid} hung: its {phase} took more than "
                    f"{LIMIT:g} s", pytrace=False)

    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, LIMIT)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _limited(item, "set-up"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _limited(item, "call"))
