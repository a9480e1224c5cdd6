"""The control of `correct`, kept at a size a test run can hold: the
reference computed in int8 and put in the program's place has to fail the
limits each cell is held to on the chip, and so has the reference on half
of the batch.  (At the cells' own sizes both were read on the chip; PERF.md
has the readings.)"""

import types

import numpy as np
import pytest

from benchmark import correct, traffic
from benchmark.families.gpt2 import reference
from benchmark.jobs import generate

CFG = {"n_embd": 256, "n_head": 2, "n_inner": 1024, "n_layer": 2,
       "n_positions": 64, "vocab_size": 1024}
ROWS, SEQ = 2, 64


def failed(numbers: dict, workload: str) -> list:
    return [name for name, _, _, ok in
            correct.judge(numbers, correct.load_limits(workload)) if not ok]


@pytest.fixture(scope="module")
def first_steps():
    batches = traffic.token_batches({"rows": ROWS, "seq": SEQ}, CFG["vocab_size"], 5)
    firsts = [(x, y.reshape(ROWS, SEQ)) for x, y in (next(batches) for _ in range(3))]
    return firsts, reference.first_steps(CFG, 5, firsts)


def test_the_reference_agrees_with_itself(first_steps):
    firsts, ref = first_steps
    assert failed(correct.train_numbers(ref, ref), "train-590m-2k") == []


def test_int8_in_the_programs_place_fails_the_training_cell(first_steps):
    firsts, ref = first_steps
    control = reference.first_steps(CFG, 5, firsts, precision="int8")
    assert "grad_error" in failed(correct.train_numbers(control, ref), "train-590m-2k")


def test_half_the_batch_fails_the_training_cell(first_steps):
    firsts, ref = first_steps
    half = reference.first_steps(CFG, 5, firsts, rows=ROWS // 2)
    bad = failed(correct.train_numbers(half, ref), "train-590m-2k")
    assert {"grad_gap", "change_gap"} <= set(bad)


#: the serving control needs the cell's width and half its depth to read as
#: it does on the chip: rounding shows in the first token after many layers
SERVE_CFG = {"model_type": "gpt2", "n_embd": 2048, "n_head": 16, "n_inner": 8192,
             "n_layer": 12, "n_positions": 128, "vocab_size": 16384}


def test_int8_tokens_fail_the_serving_cell():
    rng = np.random.default_rng(5)
    vocab = SERVE_CFG["vocab_size"]
    sample = [types.SimpleNamespace(
        prompt=rng.integers(0, vocab, 16, dtype=np.int32),
        tokens=rng.integers(0, vocab, 80).tolist()) for _ in range(6)]
    got = generate.served_gaps(SERVE_CFG, 5, sample, ("f32", "int8"))
    assert got["positions"] == 480
    limit = correct.load_limits("serve-1b3-decode")["served_gap_mean"]
    assert got["int8"]["served_gap_mean"] > limit
    assert got["f32"]["served_gap_mean"] > limit    # random tokens are far off
