"""chip_smoke.py: its phases are right at a tiny size on the CPU.

The script's own exit code and last line belong to the chip.  What can be
checked here is that every phase function of the one-chip run does what it
says when rehearsed small: kernels in interpret mode, `cli train`, `cli
generate`, two real `cli serve` processes answering over HTTP and drained
with SIGTERM.  That it fails without a TPU is in `test_chip_smoke_no_tpu.py`,
the `--chips 4` phases in `test_chip_smoke_children.py`.
"""

import importlib.util
import json
import os
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


