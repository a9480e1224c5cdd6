"""Low-precision serving path (ISSUE 8 tentpole): the per-conf
serve-precision policy (optimize/quantize.py) — bf16 cast-on-load,
weight-only per-channel int8 with calibrated clip — threads through the
AOT infer cache as a cache-key dimension, persists the quantized-weight
artifact in the disk store, keeps the f32 path bitwise-identical, and
holds the declared accuracy budgets on all four zoo models
(`test_quantize_budgets.py`).

Tier-1: CPU-only, tmpdir-backed; the two-subprocess disk-coexistence
check is the cross-process acceptance test.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize import quantize
from deeplearning4j_tpu.optimize.persist import PersistentProgramStore

N_IN, N_OUT = 6, 3


def _net(seed=0):
    return MultiLayerNetwork(mlp(n_in=N_IN, hidden=[8], n_out=N_OUT,
                                 lr=0.05), seed=seed).init()


def _x(rows, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(rows, N_IN).astype(np.float32))


# -- quantization mechanics --------------------------------------------------

def test_validate_policy_rejects_unknown():
    for p in quantize.POLICIES:
        assert quantize.validate_policy(p) == p
    with pytest.raises(ValueError):
        quantize.validate_policy("fp8")


def test_quantize_leaf_per_channel_axes():
    """2-D dense weights quantize per output column (last axis); 4-D
    conv kernels per output channel (axis 0, OIHW)."""
    rng = np.random.RandomState(0)
    w2 = rng.randn(5, 7).astype(np.float32)
    q2 = quantize._quantize_leaf(w2, clip=1.0)
    assert q2["q"].dtype == np.int8 and q2["q"].shape == (5, 7)
    assert q2["scale"].shape == (1, 7)

    w4 = rng.randn(4, 3, 2, 2).astype(np.float32)
    q4 = quantize._quantize_leaf(w4, clip=1.0)
    assert q4["scale"].shape == (4, 1, 1, 1)
    # full-range clip keeps every column's max at the int8 rail
    deq = q2["q"].astype(np.float32) * q2["scale"]
    assert float(np.max(np.abs(deq - w2))) <= float(
        np.max(q2["scale"])) * 0.51


def test_quantize_params_only_touches_matrix_weights():
    net = _net()
    qparams = quantize.quantize_params_int8(net.params)
    for layer, qlayer in zip(net.params, qparams):
        for name, leaf in layer.items():
            if quantize._quantizable(name, leaf):
                assert set(qlayer[name]) == {"q", "scale"}
            else:
                np.testing.assert_array_equal(np.asarray(leaf),
                                              np.asarray(qlayer[name]))


def test_pack_unpack_roundtrip_exact():
    net = _net()
    qparams = quantize.quantize_params_int8(net.params, clip=0.995)
    report = {"clip": 0.995, "mse": 1.5e-6, "calibration_rows": 32}
    blob = quantize.pack_quantized(qparams, report)
    q2, r2 = quantize.unpack_quantized(blob)
    assert r2 == report
    for la, lb in zip(qparams, q2):
        assert set(la) == set(lb)
        for name in la:
            if isinstance(la[name], dict):
                np.testing.assert_array_equal(la[name]["q"], lb[name]["q"])
                np.testing.assert_array_equal(la[name]["scale"],
                                              lb[name]["scale"])
            else:
                np.testing.assert_array_equal(np.asarray(la[name]),
                                              np.asarray(lb[name]))


def test_calibration_picks_clip_minimizing_mse():
    net = _net()
    x = _x(32, seed=2)
    qparams, rep = quantize.calibrate_int8(net.conf, net.params, x)
    assert rep["clip"] in quantize.CLIP_GRID
    assert rep["calibration_rows"] == 32
    assert rep["rel_mse"] < 1e-2


# -- cache-key coexistence + f32 bitwise identity ----------------------------

def test_f32_key_is_the_pre_policy_4_tuple():
    """The f32 policy adds NO key suffix — pre-PR disk artifacts stay
    addressable and the f32 path is untouched."""
    net = _net()
    net.output(_x(4))
    keys = list(net.infer_cache._programs)
    assert keys and all(len(k) == 4 for k in keys)


def test_policies_coexist_and_flip_back_is_pure_hits():
    net = _net()
    x = _x(4, seed=1)
    ref = np.asarray(net.output(x))

    net.set_serve_precision("bf16")
    net.output(x)
    net.set_serve_precision("int8")
    net.output(x)

    summary = net.infer_cache.programs_summary()
    assert {row["policy"] for row in summary} == {"f32", "bf16", "int8"}
    assert {row["bucket"] for row in summary} == {4}

    misses = net.infer_cache.stats.misses
    net.set_serve_precision("f32")
    again = np.asarray(net.output(x))
    assert net.infer_cache.stats.misses == misses  # pure in-memory hit
    np.testing.assert_array_equal(ref, again)      # bitwise, not approx


def test_bf16_and_int8_outputs_stay_close_to_f32():
    net = _net()
    x = _x(16, seed=3)
    ref = np.asarray(net.output(x))
    for policy in ("bf16", "int8"):
        net.set_serve_precision(policy)
        out = np.asarray(net.output(x))
        assert out.dtype == np.float32  # programs cast back at the edge
        rel = float(np.mean((out - ref) ** 2) / max(
            float(np.mean(ref ** 2)), 1e-12))
        assert rel < 1e-3, (policy, rel)


def test_mesh_and_policy_compose_in_the_key():
    net = _net()
    x = _x(4, seed=4)
    net.set_serve_mesh()
    net.set_serve_precision("bf16")
    net.output(x)
    keys = list(net.infer_cache._programs)
    assert any(k[3][0] == "mesh" and k[4] == ("policy", "bf16")
               for k in keys), keys
    assert any(row["sharding"].startswith("mesh:") and row["policy"] == "bf16"
               for row in net.infer_cache.programs_summary())


# -- precision report --------------------------------------------------------

def test_set_serve_precision_reports_held_out_accuracy_delta():
    net = _net()
    rep = net.set_serve_precision("int8")
    assert rep["policy"] == "int8"
    assert rep["calibration"]["clip"] in quantize.CLIP_GRID
    delta = rep["accuracy_delta"]
    assert delta["policy"] == "int8" and delta["rows"] > 0
    assert 0.0 <= delta["top1_delta"] <= 1.0
    assert net.serve_precision_report is rep


def test_int8_without_artifact_or_calibration_data_defaults():
    """`set_serve_precision("int8")` with no calibration batch derives
    one from the conf — no user data required for the zero-config path."""
    net = _net()
    rep = net.set_serve_precision("int8", measure=False)
    assert "accuracy_delta" not in rep
    assert net.serve_precision == "int8"


# -- quantized-artifact persistence ------------------------------------------

def test_int8_artifact_round_trips_through_disk_store(tmp_path):
    net = _net()
    net.set_compile_cache(str(tmp_path))
    rep1 = net.set_serve_precision("int8", measure=False)
    store = net.infer_cache.persist
    assert store.writes >= 1  # the artifact write

    # a restarted process: same conf + params digest → artifact loads,
    # calibration is NOT recomputed (identical report, zero new writes)
    net2 = _net()
    net2.set_compile_cache(str(tmp_path))
    writes_before = net2.infer_cache.persist.writes
    rep2 = net2.set_serve_precision("int8", measure=False)
    assert rep2["calibration"] == rep1["calibration"]
    assert net2.infer_cache.persist.writes == writes_before


def test_store_bytes_checksum_and_kind_guard(tmp_path):
    store = PersistentProgramStore(str(tmp_path))
    key = ("quantized-weights", "int8", "fp", "digest")
    assert store.store_bytes(key, b"artifact-bytes")
    assert store.load_bytes(key) == b"artifact-bytes"

    # a program load of a bytes entry is a kind mismatch, not a crash
    assert store.load(key) is None
    assert store.corrupt_evicted == 1
    assert not os.path.exists(store.path_for(key))


def test_corrupt_artifact_is_evicted_and_recalibrated(tmp_path):
    net = _net()
    net.set_compile_cache(str(tmp_path))
    net.set_serve_precision("int8", measure=False)
    store = net.infer_cache.persist
    art_key = quantize.quantize_artifact_key(
        net.infer_cache._fingerprint(net.conf),
        quantize.params_digest(net.params))
    with open(store.path_for(art_key), "r+b") as f:
        f.seek(40)
        f.write(b"\xff\xff\xff\xff")

    net2 = _net()
    net2.set_compile_cache(str(tmp_path))
    rep = net2.set_serve_precision("int8", measure=False)
    assert rep["calibration"]["clip"] in quantize.CLIP_GRID
    assert net2.infer_cache.persist.corrupt_evicted == 1
    assert net2.infer_cache.persist.writes >= 1  # rewritten clean


# -- cross-process disk coexistence (acceptance criterion) -------------------

_CHILD = """\
import json, os
import numpy as np
import jax.numpy as jnp
from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

policy = os.environ["CHILD_POLICY"]
conf = mlp(n_in=6, hidden=[8], n_out=3, lr=0.05)
net = MultiLayerNetwork(conf, seed=0).init()
if policy != "f32":
    net.set_serve_precision(policy, measure=False)
rng = np.random.RandomState(1)
x = jnp.asarray(rng.randn(4, 6).astype(np.float32))
out = net.output(x)
st = net.infer_cache.stats.as_dict()
store = net.infer_cache.persist
print(json.dumps({"stats": st, "writes": store.writes,
                  "evictions": store.evictions,
                  "vanished": store.vanished,
                  "out0": float(np.asarray(out)[0, 0])}))
"""


def test_two_subprocess_f32_and_int8_share_one_disk_store(tmp_path):
    """Warm f32 then int8 into ONE `DL4J_COMPILE_CACHE` dir from two
    real OS processes, then reload both policies from two more: pure
    disk hits (`fresh_compiles == 0`), nothing evicted, nothing
    vanished — the policies coexist on disk, they don't thrash."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DL4J_COMPILE_CACHE=str(tmp_path))

    def run(policy):
        r = subprocess.run([sys.executable, "-c", _CHILD],
                           env=dict(env, CHILD_POLICY=policy),
                           capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    warm_f32 = run("f32")
    warm_int8 = run("int8")
    assert warm_f32["stats"]["misses"] == 1   # each warms its own program
    assert warm_int8["stats"]["misses"] == 1

    hit_f32 = run("f32")
    hit_int8 = run("int8")
    for hit in (hit_f32, hit_int8):
        assert hit["stats"]["misses"] == 0        # fresh_compiles == 0
        assert hit["stats"]["disk_hits"] == 1
        assert hit["evictions"] == 0
        assert hit["vanished"] == 0
    # int8 reload also reused the persisted artifact: no new writes
    assert hit_int8["writes"] == 0
    # f32 outputs are process-invariant (bitwise regression anchor)
    assert hit_f32["out0"] == warm_f32["out0"]
