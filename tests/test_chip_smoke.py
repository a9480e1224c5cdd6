"""chip_smoke.py: it never reports success without a TPU, and its phases
are right at a tiny size on the CPU.

The script's own exit code and last line belong to the chip.  What can be
checked here is that it fails without one, and that every phase function
of the one-chip run does what it says when rehearsed small: kernels in
interpret mode, `cli train`, `cli generate`, two real `cli serve`
processes answering over HTTP and drained with SIGTERM.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    if REPO not in sys.path:  # the script imports the package from its dir
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses looks its module up
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.Size(vocab=32, d_model=32, blocks=1, heads=2, seq=16,
                      batch=2, steps=2, serve_rows=2, prompt_len=5,
                      new_tokens=4, attn_batch=1, lstm_batch=8,
                      lstm_hidden=16)


@pytest.fixture(scope="module")
def trained(smoke, tiny, tmp_path_factory):
    """A work directory after the train and reference phases."""
    work = str(tmp_path_factory.mktemp("chip_smoke"))
    train = smoke.phase_train(tiny, work)
    reference = smoke.phase_reference(tiny, work)
    return work, train, reference


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


def test_kernel_phase_rehearsed_in_interpret_mode(smoke, tiny, capsys):
    line = smoke.phase_kernels(tiny, interpret=True)
    assert json.loads(capsys.readouterr().out) == line
    assert [k["kernel"] for k in line["kernels"]] == [
        "flash fwd + fused bwd (causal, block-skip)", "fused LSTM cell",
        "fused LSTM cell"]


def test_kernel_that_gave_way_fails_the_phase(smoke, tiny):
    """Asked for a compiled kernel and given interpret mode (all a CPU
    has), the phase fails: no `tpu_custom_call` in the program."""
    with pytest.raises(smoke.PhaseFailed, match="tpu_custom_call"):
        smoke._run_compiled(lambda x: x + 1, (1.0,), want_kernel=True)


def test_kernel_pinned_to_a_refused_width_fails_the_phase(smoke, tiny):
    import dataclasses

    wide = dataclasses.replace(tiny, lstm_batch=256, lstm_hidden=1024)
    with pytest.raises(ValueError, match="over the 16777216-byte limit"):
        smoke.kernel_lstm(wide, interpret=False)


def test_train_phase(trained, tiny):
    _, train, _ = trained
    assert train["steps"] == tiny.steps and train["corpus"] == "synthetic"
    assert train["compiles_after_first_step"] == 0
    assert train["params"]["all_finite"]
    assert train["params"]["layers_changed"] == 2 * tiny.blocks + 2
    assert train["cpu_reference"]["rel_diff"] < 2e-2
    assert train["attention_impl"] == "full"
    # the CLI's own JSON says which device ran
    assert train["cli"]["platform"] == "cpu"
    assert train["cli"]["xla"]["backend_compile_seconds"] > 0.0


def test_reference_phase(trained, tiny):
    _, _, reference = trained
    greedy, sampled = reference["generate"]
    assert len(greedy["tokens"]) == len(sampled["tokens"]) == tiny.new_tokens
    assert greedy["fresh_compiles"] == sampled["fresh_compiles"] == 0


def test_serve_predict_phase(smoke, trained, tiny):
    line = smoke.phase_serve_predict(tiny, trained[0])
    assert line["precision"] == "bf16" and line["max_rel_diff"] <= 1e-3
    assert line["drained"]["errors"] == 0


def test_serve_generate_phase(smoke, trained, tiny):
    line = smoke.phase_serve_generate(tiny, trained[0])
    assert line["equal_to_cli_generate"]
    assert line["streams"] == {"admitted": 2, "completed": 2, "failed": 0}
    assert line["temperatures"] == [0.0, 8.0]


def test_serve_phase_fails_on_a_wrong_answer(smoke, trained, tiny):
    """The comparison decides: a reference that the server does not match
    fails the phase (and the server is still stopped)."""
    import numpy as np

    work = trained[0]
    path = os.path.join(work, "reference.npz")
    ref = dict(np.load(path))
    try:
        np.savez(path, **{**ref, "out": ref["out"][::-1]})
        with pytest.raises(smoke.PhaseFailed, match="predict differs"):
            smoke.phase_serve_predict(tiny, work)
    finally:
        np.savez(path, **ref)


def _build(compile_s, backend_s, hits, writes):
    return {"cli": {"compile_seconds": compile_s,
                    "xla": {"backend_compile_seconds": backend_s,
                            "cache_hits": hits, "cache_writes": writes}}}


def test_cache_phase(smoke, tmp_path, capsys):
    (tmp_path / "jit_step-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_step-abc-atime").write_bytes(b"x")
    cold, warm = _build(9.6, 4.1, 0, 3), _build(8.4, 0.6, 3, 0)
    line = smoke.phase_cache(str(tmp_path), 0, cold, warm)
    assert line["entries_after"] == 1
    assert line["second_build"]["cache_hits"] == 3
    # a second build that read nothing from the cache fails the phase
    with pytest.raises(smoke.PhaseFailed, match="read nothing from"):
        smoke.phase_cache(str(tmp_path), 0, cold, _build(8.4, 4.0, 0, 3))
    with pytest.raises(smoke.PhaseFailed, match="no entry"):
        smoke.phase_cache(str(tmp_path / "none"), 0, cold, warm)
    capsys.readouterr()


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
               "XLA_FLAGS":
                   f"--xla_force_host_platform_device_count={n_devices}"}
        proc = subprocess.run(
            [sys.executable, "-c", _FOUR_CHIP_REHEARSAL, str(tmp_path),
             *phases], env=env, capture_output=True, text=True, timeout=600)
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
