"""chip_smoke.py as a child never reports success without a TPU."""

import os
import subprocess
import sys

import pytest

SMOKE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "chip_smoke.py")


@pytest.mark.parametrize("options", [[], ["--chips", "4"],
                                     ["--phase", "kernels", "--work", "/x",
                                      "--size", "{}"]],
                         ids=["default", "four-chips", "child"])
def test_no_tpu_no_success(options):
    """Whatever the options: without a TPU, a non-zero exit and nothing on
    stdout, so no `"ok": true`."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, SMOKE, *options], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "not 'tpu'" in proc.stderr
