"""Test configuration: run everything on a virtual 8-device CPU mesh.

This is the analog of the reference's in-JVM distributed test rig
(`BaseTestDistributed.java:34-98`, `IRUnitDriver.java:51`): distributed
logic is exercised against `xla_force_host_platform_device_count=8` virtual
devices so no TPU pod is needed (SURVEY §4 lesson).
"""

import os

# force CPU even when the ambient env selects a TPU platform: the virtual
# 8-device mesh only exists on the host platform
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (import after env is set)

jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process integration tests (tens of seconds)")
