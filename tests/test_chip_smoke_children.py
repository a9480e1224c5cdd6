"""chip_smoke.py's `--chips 4` phases, right at a tiny size on virtual devices:
children only, and a worker's work for a minute, so apart from
`test_chip_smoke.py`'s one-chip phases."""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_FOUR_CHIP_REHEARSAL = """
import sys
import chip_smoke as smoke  # PYTHONPATH holds the repo's root
tiny = smoke.Size(vocab=32, d_model=32, blocks=1, heads=2, seq=16, batch=4,
                  serve_rows=4)
for phase in sys.argv[2:]:
    if phase == "replicas":
        smoke.phase_replicas(tiny, sys.argv[1], want_platform="cpu")
    elif phase == "replicas_mesh":
        smoke.phase_replicas(tiny, sys.argv[1], n=2, mesh="batch=2",
                             chips_each=2, want_platform="cpu")
    elif phase == "mesh_serve":
        smoke.phase_mesh_serve(tiny, sys.argv[1])
    else:
        smoke.phase_mesh_train(tiny, sys.argv[1], phase)
"""


def test_four_chip_phases_rehearsed_on_virtual_devices(tmp_path):
    """The `--chips 4` phases at a tiny size: a one-device process, then a
    four-device one that compares itself with it, serves over a 2x2 mesh,
    starts four replicas behind the router, and then two with a mesh
    each."""
    def run(n_devices, *phases):
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
               "XLA_FLAGS": re.sub(     # the rig's flags, this many devices
                   r"(host_platform_device_count=)\d+", rf"\g<1>{n_devices}",
                   os.environ["XLA_FLAGS"])}
        proc = subprocess.run(
            [sys.executable, "-c", _FOUR_CHIP_REHEARSAL, str(tmp_path),
             *phases], env=env, capture_output=True, text=True, timeout=390)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return [json.loads(l) for l in proc.stdout.splitlines()]

    (one,) = run(1, "mesh_one")
    four, served, replicas, meshed = run(4, "mesh_four", "mesh_serve",
                                         "replicas", "replicas_mesh")
    assert (one["devices"], four["devices"]) == (1, 4)
    assert four["one_device_loss"] == one["loss_after_step"]
    assert four["update_rel_l2_diff"] <= four["tolerance"]["update_rel_l2"]
    assert served["devices_spanned"] == [4]
    assert served["arrays_split_not_replicated"] > 0
    assert [d["chip"] for d in replicas["replica_devices"]] == \
        ["0", "1", "2", "3"]
    assert not replicas["router_loaded_libtpu"]
    assert all(n > 0 for n in replicas["requests_per_replica"])
    assert [d["chip"] for d in meshed["replica_devices"]] == ["0,1", "2,3"]
    assert all(n > 0 for n in meshed["requests_per_replica"])
