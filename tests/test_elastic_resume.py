"""Elastic, crash-resumable mesh training (ISSUE 10).

The contract under test: `DataParallelTrainer.fit(checkpoint_dir=...)`
checkpoints the COMPLETE cross-batch state (params, updater moments,
step, host RNG key, batch cursor) atomically; a rerun auto-resumes at
the cursor with a bit-identical trajectory on the same topology, and an
allclose trajectory on a DIFFERENT device count (elastic N->M resume —
only the f32 reduction grouping of the dp collectives changes).  Chaos
variant: a subprocess run is killed mid-epoch by the PR 5 fault
registry at N=4 forced devices and resumed at M=2 in a second
subprocess (`--xla_force_host_platform_device_count` pattern from
test_mesh_infer).
"""

import json
import os
import re
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import (LayerType, NeuralNetConfiguration,
                                        OptimizationAlgorithm, list_builder)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import checkpoint as ckpt
from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.reliability import TrainingInterrupted, faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _mlp_conf(n_in=4, n_hidden=8, n_out=3, **kw):
    base = NeuralNetConfiguration(
        n_in=n_in, n_out=n_out, lr=0.1,
        optimization_algo=OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT,
        num_iterations=5, **kw)
    return (list_builder(base, 2)
            .hidden_layer_sizes([n_hidden], n_in, n_out)
            .override(1, layer_type=LayerType.OUTPUT)
            .pretrain(False).backprop(True).build())


def _net(n_hidden=8):
    net = MultiLayerNetwork(_mlp_conf(n_hidden=n_hidden))
    net.init()
    return net


def _batches(n=48, bs=8, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, size=n)]
    return [(x[i:i + bs], y[i:i + bs]) for i in range(0, n, bs)]


def _mesh(n):
    return make_mesh({"dp": n}, devices=jax.devices()[:n])


class _Recorder:
    """Listener that collects the per-batch score trajectory."""

    def __init__(self):
        self.scores = []

    def iteration_done(self, model, iteration, score):
        self.scores.append(float(score))


def _gather(tree):
    return jax.tree_util.tree_map(
        lambda x: np.array(jax.device_get(x)), tree)


def _trees_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    return len(fa) == len(fb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(fa, fb))


# -- tentpole: resume on the same and on a different topology ---------------

def test_same_topology_resume_is_bitwise(tmp_path):
    """Kill-free framing of the crash contract: train 3 batches with
    checkpointing, hand the dir to a FRESH trainer for the full run —
    final params must be bit-identical to an uninterrupted run."""
    batches = _batches()
    ck = str(tmp_path / "ck")

    t_ref = DataParallelTrainer(_net(), _mesh(4))
    ref_score = t_ref.fit(batches, epochs=2)
    ref_params = _gather(t_ref.state.params)

    t1 = DataParallelTrainer(_net(), _mesh(4))
    t1.fit(batches[:3], epochs=1, checkpoint_dir=ck,
           checkpoint_every_n_batches=1)
    assert t1.checkpoints_written >= 3

    t2 = DataParallelTrainer(_net(), _mesh(4))
    s2 = t2.fit(batches, epochs=2, checkpoint_dir=ck)
    assert t2.resumed_from_step == 3
    assert np.float32(s2) == np.float32(ref_score)
    assert _trees_equal(ref_params, _gather(t2.state.params))
    # updater moments resumed too, not just params
    assert _trees_equal(_gather(t_ref.state.updater),
                        _gather(t2.state.updater))


@pytest.mark.parametrize("n,m", [(4, 2), (1, 4)])
def test_elastic_resume_n_to_m(tmp_path, n, m):
    """A checkpoint written on an N-chip mesh resumes on M chips with the
    same loss trajectory (allclose: the dp reduction grouping changes)."""
    batches = _batches()
    ck = str(tmp_path / "ck")

    rec_ref = _Recorder()
    t_ref = DataParallelTrainer(_net(), _mesh(4))
    t_ref.listeners = [rec_ref]
    t_ref.fit(batches, epochs=2)

    t1 = DataParallelTrainer(_net(), _mesh(n))
    t1.fit(batches[:3], epochs=1, checkpoint_dir=ck,
           checkpoint_every_n_batches=1)

    rec = _Recorder()
    t2 = DataParallelTrainer(_net(), _mesh(m))
    t2.listeners = [rec]
    t2.fit(batches, epochs=2, checkpoint_dir=ck)
    assert t2.resumed_from_step == 3
    np.testing.assert_allclose(rec.scores, rec_ref.scores[3:],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(x).ravel() for x in
                        jax.tree_util.tree_leaves(_gather(t2.state.params))]),
        np.concatenate([np.asarray(x).ravel() for x in
                        jax.tree_util.tree_leaves(_gather(t_ref.state.params))]),
        rtol=1e-5, atol=1e-6)


def test_checkpointing_off_is_bitwise_unchanged():
    """fit() without checkpoint_dir must be byte-for-byte the old path."""
    batches = _batches()
    t1 = DataParallelTrainer(_net(), _mesh(4))
    s1 = t1.fit(batches, epochs=2)
    t2 = DataParallelTrainer(_net(), _mesh(4))
    s2 = t2.fit(batches, epochs=2, checkpoint_dir=None)
    assert np.float32(s1) == np.float32(s2)
    assert _trees_equal(_gather(t1.state.params), _gather(t2.state.params))


def test_sigterm_checkpoints_then_raises(tmp_path):
    """SIGTERM mid-fit checkpoints the cursor and raises
    TrainingInterrupted (single-device trainer contract, PR 5)."""
    batches = _batches()
    ck = str(tmp_path / "ck")

    class KillAt:
        def __init__(self, n):
            self.n, self.c = n, 0

        def iteration_done(self, model, iteration, score):
            self.c += 1
            if self.c == self.n:
                os.kill(os.getpid(), signal.SIGTERM)

    t = DataParallelTrainer(_net(), _mesh(4))
    t.listeners = [KillAt(2)]
    with pytest.raises(TrainingInterrupted):
        t.fit(batches, epochs=2, checkpoint_dir=ck,
              checkpoint_every_n_batches=100)
    _, _, meta = ckpt.load(ck)
    assert meta["data_cursor"]["batches_done"] == 2

    t2 = DataParallelTrainer(_net(), _mesh(4))
    t2.fit(batches, epochs=2, checkpoint_dir=ck)
    assert t2.resumed_from_step == 2


# -- checkpoint format: version + mesh metadata -----------------------------

def test_checkpoint_meta_records_format_and_mesh(tmp_path):
    ck = str(tmp_path / "ck")
    t = DataParallelTrainer(_net(), _mesh(4))
    t.fit(_batches(), epochs=1, checkpoint_dir=ck)
    with open(os.path.join(ck, "meta.json")) as f:
        meta = json.load(f)
    assert meta["format_version"] == ckpt.FORMAT_VERSION == 1
    assert meta["mesh"] == {"axis_names": ["dp"], "shape": {"dp": 4},
                            "zero1": False}
    assert meta["data_cursor"]["batches_done"] == 6
    assert meta["metadata"]["rng_key"] is not None


def test_pre_pr_checkpoint_without_version_still_loads(tmp_path):
    """A pre-versioning checkpoint (no format_version, no mesh block)
    must keep loading — both raw load() and single-device auto-resume."""
    ck = str(tmp_path / "ck")
    net = _net()
    x, y = _batches(n=8, bs=8)[0]
    net.fit([(x, y)] * 3, checkpoint_dir=ck, checkpoint_every_n_batches=1)
    meta_path = os.path.join(ck, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    del meta["format_version"]
    del meta["mesh"]
    with open(meta_path, "w") as f:
        json.dump(meta, f)

    params, _, meta2 = ckpt.load(ck, like_params=net.params)
    assert "format_version" not in meta2
    assert _trees_equal(params, net.params)
    # auto-resume path (load_resilient) tolerates it too
    net2 = _net()
    net2.fit([(x, y)] * 3, checkpoint_dir=ck)
    assert net2.resumed_from_batch == 3


def test_future_format_version_fails_with_one_line_error(tmp_path):
    ck = str(tmp_path / "ck")
    net = _net()
    ckpt.save(ck, net.params, step=1)
    meta_path = os.path.join(ck, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["format_version"] = 99
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ckpt.CheckpointFormatError, match="format_version=99"):
        ckpt.load(ck, like_params=net.params)
    # NOT corruption: load_resilient propagates instead of restarting
    # training from scratch
    with pytest.raises(ckpt.CheckpointFormatError):
        ckpt.load_resilient(ck, like_params=net.params)


def test_structurally_incompatible_tree_fails_actionably(tmp_path):
    ck = str(tmp_path / "ck")
    ckpt.save(ck, _net(n_hidden=8).params, step=1)
    # different layer width -> shape diagnosis, not a downstream explosion
    with pytest.raises(ckpt.CheckpointFormatError, match="shape"):
        ckpt.load(ck, like_params=_net(n_hidden=16).params)
    # params-only checkpoint restored with an updater template -> missing
    # leaves diagnosis (a single-device checkpoint fed to the mesh trainer)
    t = DataParallelTrainer(_net(), _mesh(2))
    with pytest.raises(ckpt.CheckpointFormatError, match="missing"):
        ckpt.load(ck, like_params=t.state.params,
                  like_updater=t.state.updater)


# -- zero1: sharded updater state round-trips elastically -------------------

def test_zero1_round_trip_updater_bitwise(tmp_path):
    """Gathered updater moments are bitwise equal across
    save -> reshard (4 chips -> 2) -> load -> save -> load."""
    batches = _batches()
    ck = str(tmp_path / "ck")

    t4 = DataParallelTrainer(_net(), _mesh(4), zero1=True)
    t4.fit(batches[:4], epochs=1, checkpoint_dir=ck)
    g4 = _gather(t4.state.updater)
    # the live updater state really is sharded over dp
    shardings = [x.sharding.spec for x in
                 jax.tree_util.tree_leaves(t4.state.updater)]
    assert any("dp" in str(s) for s in shardings)

    t2 = DataParallelTrainer(_net(), _mesh(2), zero1=True)
    assert t2.restore(ck) == 4
    assert _trees_equal(g4, _gather(t2.state.updater))
    ck2 = str(tmp_path / "ck2")
    t2._save_checkpoint(ck2, batches_done=4)

    t4b = DataParallelTrainer(_net(), _mesh(4), zero1=True)
    t4b.restore(ck2)
    assert _trees_equal(g4, _gather(t4b.state.updater))
    # and the resharded state still trains
    x, y = batches[4]
    t4b.fit([(x, y)], epochs=1)


def test_zero1_elastic_trajectory_matches_plain_dp(tmp_path):
    """zero1 resume across topologies follows the same loss trajectory
    as replicated dp (zero1 is a memory layout, not different math)."""
    batches = _batches()
    rec_ref = _Recorder()
    t_ref = DataParallelTrainer(_net(), _mesh(4))
    t_ref.listeners = [rec_ref]
    t_ref.fit(batches, epochs=2)

    ck = str(tmp_path / "ck")
    t1 = DataParallelTrainer(_net(), _mesh(4), zero1=True)
    t1.fit(batches[:3], epochs=1, checkpoint_dir=ck,
           checkpoint_every_n_batches=1)
    rec = _Recorder()
    t2 = DataParallelTrainer(_net(), _mesh(2), zero1=True)
    t2.listeners = [rec]
    t2.fit(batches, epochs=2, checkpoint_dir=ck)
    np.testing.assert_allclose(rec.scores, rec_ref.scores[3:],
                               rtol=1e-4, atol=1e-5)


def test_zero1_pads_and_masks_remainder_batches():
    """ISSUE 17 closes PR 10's guard: a non-dp-divisible batch in zero1
    mode pads-and-masks instead of raising, and updates on the divisible
    prefix stay bitwise identical to a run that never saw the tail."""
    batches = _batches(n=16, bs=8)
    x, y = _batches(n=8, bs=8, seed=9)[0]
    tail = (x[:6], y[:6])  # 6 rows on dp=4: pad to 8, mask 2

    t_ref = DataParallelTrainer(_net(), _mesh(4), zero1=True)
    t_ref.fit(batches, epochs=1)

    t = DataParallelTrainer(_net(), _mesh(4), zero1=True)
    t.fit(batches, epochs=1)
    prefix = _gather(t.state.params)
    assert _trees_equal(prefix, _gather(t_ref.state.params))

    t.fit([tail], epochs=1)  # must not raise
    assert int(t.state.step) == 3  # the remainder batch really stepped
    assert not _trees_equal(prefix, _gather(t.state.params))


def test_zero1_requires_sync_mode():
    with pytest.raises(ValueError, match="zero1"):
        DataParallelTrainer(_net(), _mesh(4), mode="async", zero1=True)


# -- satellites: donation race, load faults, corruption ---------------------

def test_async_save_then_immediate_step_donation_race(tmp_path):
    """save_async must snapshot to OWNED host copies before returning:
    the next train step donates the TrainState buffers, so a lazy
    device_get in the writer thread would read freed memory."""
    batches = _batches()
    ck = str(tmp_path / "ck")
    t = DataParallelTrainer(_net(), _mesh(4))
    t.fit(batches[:2], epochs=1)
    want_params = _gather(t.state.params)
    want_updater = _gather(t.state.updater)
    # slow the writer down so the donating step definitely races it
    faults.arm("checkpoint.save", "delay", delay_s=0.2)
    ckpt.save_async(ck, t.state.params, t.state.updater,
                    conf=t.net.conf, step=2)
    t.fit(batches[2:], epochs=1)  # donates the snapshotted buffers
    ckpt.join_async()
    params, updater, meta = ckpt.load(ck, like_params=t.state.params,
                                      like_updater=t.state.updater)
    assert meta["step"] == 2
    assert _trees_equal(params, want_params)
    assert _trees_equal(updater, want_updater)


def test_checkpoint_load_fault_point_falls_back(tmp_path):
    """An armed checkpoint.load fault is a torn read: load_resilient
    falls back to .bak on the first failure and returns None (never
    crashes) when both candidates fail."""
    import shutil

    ck = str(tmp_path / "ck")
    net = _net()
    ckpt.save(ck, net.params, step=7)
    shutil.copytree(ck, ck + ".bak")

    faults.arm("checkpoint.load", "raise", nth=1)
    params, _, meta = ckpt.load_resilient(ck, like_params=net.params)
    assert meta["step"] == 7 and _trees_equal(params, net.params)

    faults.arm("checkpoint.load", "raise", nth=1, times=2)
    assert ckpt.load_resilient(ck, like_params=net.params) is None


@pytest.mark.parametrize("damage", ["truncate_npz", "drop_meta"])
def test_corrupt_mesh_checkpoint_falls_back_to_bak(tmp_path, damage):
    """A torn mesh checkpoint (truncated arrays.npz / missing meta.json)
    is skipped in favor of .bak — auto-resume never crashes on it."""
    import shutil

    batches = _batches()
    ck = str(tmp_path / "ck")
    t = DataParallelTrainer(_net(), _mesh(4))
    t.fit(batches[:3], epochs=1, checkpoint_dir=ck,
          checkpoint_every_n_batches=1)
    # save() drops the .bak on success; recreate one from the good dir,
    # then tear the main dir
    shutil.copytree(ck, ck + ".bak")
    if damage == "truncate_npz":
        p = os.path.join(ck, "arrays.npz")
        with open(p, "r+b") as f:
            f.truncate(os.path.getsize(p) // 2)
    else:
        os.remove(os.path.join(ck, "meta.json"))

    t2 = DataParallelTrainer(_net(), _mesh(2))
    t2.fit(batches, epochs=2, checkpoint_dir=ck)
    assert t2.resumed_from_step == 3  # resumed from the intact .bak


def test_checkpoint_listener_records_mesh_meta(tmp_path):
    """CheckpointListener on the mesh trainer stamps the topology into
    meta.json, like the trainer's own checkpoints."""
    from deeplearning4j_tpu.optimize.listeners import CheckpointListener

    ck = str(tmp_path / "ck")
    li = CheckpointListener(ck, save_every_n=1, asynchronous=False)
    t = DataParallelTrainer(_net(), _mesh(4))
    t.listeners = [li]
    t.fit(_batches()[:2], epochs=1)
    with open(os.path.join(ck, "meta.json")) as f:
        meta = json.load(f)
    assert meta["mesh"]["shape"] == {"dp": 4}
    assert meta["format_version"] == 1


# -- chaos: subprocess kill at N=4, resume at M=2 ---------------------------

_CHAOS_SCRIPT = """
import json, sys
import numpy as np
import jax
assert len(jax.devices()) == int(sys.argv[1]), jax.devices()
from deeplearning4j_tpu.nn.conf import (LayerType, NeuralNetConfiguration,
                                        OptimizationAlgorithm, list_builder)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import make_mesh

base = NeuralNetConfiguration(
    n_in=4, n_out=3, lr=0.1,
    optimization_algo=OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT,
    num_iterations=5)
conf = (list_builder(base, 2).hidden_layer_sizes([8], 4, 3)
        .override(1, layer_type=LayerType.OUTPUT)
        .pretrain(False).backprop(True).build())
net = MultiLayerNetwork(conf); net.init()
rng = np.random.RandomState(0)
x = rng.randn(48, 4).astype(np.float32)
y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, size=48)]
batches = [(x[i:i+8], y[i:i+8]) for i in range(0, 48, 8)]

scores = []
class Rec:
    def iteration_done(self, model, it, s):
        scores.append(float(s))

mesh = make_mesh({"dp": len(jax.devices())})
t = DataParallelTrainer(net, mesh)
t.listeners = [Rec()]
try:
    t.fit(batches, epochs=2, checkpoint_dir=sys.argv[2],
          checkpoint_every_n_batches=3)
finally:
    print("RESULT " + json.dumps(
        {"scores": scores, "resumed": t.resumed_from_step}), flush=True)
"""


def test_chaos_kill_n4_resume_m2_subprocess(tmp_path):
    """The acceptance chaos run: DL4J_FAULT_PLAN kills a 4-device mesh
    run mid-epoch (batch 8 of 12); a 2-device process auto-resumes from
    the batch-6 checkpoint and finishes with the reference trajectory."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ck = str(tmp_path / "ck")

    def run(n_dev, fault_plan=None):
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": re.sub(     # the rig's flags, this many devices
                   r"(host_platform_device_count=)\d+", rf"\g<1>{n_dev}",
                   os.environ["XLA_FLAGS"])}
        env.pop("DL4J_FAULT_PLAN", None)
        if fault_plan:
            env["DL4J_FAULT_PLAN"] = fault_plan
        return subprocess.run(
            [sys.executable, "-c", _CHAOS_SCRIPT, str(n_dev), ck],
            capture_output=True, text=True, cwd=repo, env=env, timeout=300)

    # in-process reference trajectory (uninterrupted, dp=4)
    rec = _Recorder()
    t_ref = DataParallelTrainer(_net(), _mesh(4))
    t_ref.listeners = [rec]
    t_ref.fit(_batches(), epochs=2)

    r1 = run(4, fault_plan="trainer.step=raise@8")
    assert r1.returncode != 0, (r1.stdout, r1.stderr)  # it really died
    out1 = json.loads(r1.stdout.split("RESULT ", 1)[1])
    assert out1["resumed"] is None and len(out1["scores"]) == 7
    _, _, meta = ckpt.load(ck)
    assert meta["data_cursor"]["batches_done"] == 6  # periodic save
    assert meta["mesh"]["shape"] == {"dp": 4}

    r2 = run(2)
    assert r2.returncode == 0, (r2.stdout, r2.stderr)
    out2 = json.loads(r2.stdout.split("RESULT ", 1)[1])
    assert out2["resumed"] == 6
    np.testing.assert_allclose(out2["scores"], rec.scores[6:],
                               rtol=1e-5, atol=1e-6)
    # the pre-kill prefix matched the reference bitwise (same topology)
    np.testing.assert_allclose(out1["scores"][:6], rec.scores[:6],
                               rtol=0, atol=0)


# -- CLI: mesh + checkpoint-dir + zero1 -------------------------------------

def test_cli_mesh_checkpoint_resume_and_zero1(tmp_path, capsys):
    from deeplearning4j_tpu.cli.driver import main

    out = str(tmp_path / "out")
    ck = str(tmp_path / "ck")
    argv = ["train", "--input", "iris:144", "--zoo", "mlp:hidden=8",
            "--output", out, "--runtime", "mesh", "--normalize",
            "--checkpoint-dir", ck,
            "--properties", "epochs=1,batch=16,checkpoint_every=3"]
    assert main(argv) == 0
    j = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert j["resumed_from_step"] is None
    assert j["checkpoint_write_seconds"] >= 0
    assert os.path.isdir(ck)

    assert main(argv) == 0  # rerun: resumes at the final cursor
    j2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert j2["resumed_from_step"] == 9  # 144 rows / 16 = 9 batches

    with pytest.raises(SystemExit, match="--runtime mesh"):
        main(["train", "--input", "iris:144", "--zoo", "mlp:hidden=8",
              "--output", out, "--zero1"])

    assert main(["train", "--input", "iris:144", "--zoo", "mlp:hidden=8",
                 "--output", out, "--runtime", "mesh", "--normalize",
                 "--zero1", "--properties", "epochs=1,batch=16"]) == 0
    j3 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert j3["score"] > 0
