"""The `analyze` gate as a command (the ISSUE 12 acceptance command), apart from
`test_analysis.py`: one child that lints the package and compiles the
programs of all four zoo models, which is a worker's work for a minute."""

import json
import os
import subprocess
import sys

from deeplearning4j_tpu.analysis.report import REPORT_VERSION

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cli_analyze_gate_json_schema():
    """`analyze --fail-on error --format json` exits 0 on this repo and
    emits the versioned report over the package + all four zoo models'
    compiled programs (the ISSUE 12 acceptance command)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.cli", "analyze",
         "--fail-on", "error", "--format", "json"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env,
        timeout=390)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["version"] == REPORT_VERSION
    assert set(rep["counts"]) == {"info", "warn", "error"}
    assert rep["counts"]["error"] == 0
    assert rep["checked"]["files"] > 100
    assert rep["checked"]["programs"] >= 10  # 4 models x (serve+step) + attn
    assert isinstance(rep["findings"], list)
    for f in rep["findings"]:
        assert set(f) == {"rule", "severity", "location", "message"}
