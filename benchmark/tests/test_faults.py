"""A whole run with the timed path broken underneath has to come out as not
correct.  These drive `run.main` at the rehearsal's tiny sizes on whatever
JAX finds (the look for a chip is what `--rehearse` skips), against the
limits the cells are held to on the chip."""

import json

import pytest

from benchmark import run
from benchmark.jobs import train as train_job


def last_line(capsys, argv):
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


TRAIN = ["--workload", "train-590m-2k", "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearse"]
SERVE = ["--workload", "serve-1b3-decode", "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearse"]


def break_step(monkeypatch, wrap):
    build = train_job.build

    def broken(ctx):
        trainer = build(ctx)
        trainer._step = wrap(trainer._step)
        return trainer

    monkeypatch.setattr(train_job, "build", broken)


def test_a_sound_training_run_is_correct(capsys):
    assert last_line(capsys, TRAIN)["correct"] is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    def unchanged(step):
        def broken(state, x, y, key):
            kept = jax.tree_util.tree_map(jnp.copy, state)   # the step donates
            return kept, step(state, x, y, key)[1]
        return broken

    break_step(monkeypatch, unchanged)
    line = last_line(capsys, TRAIN)
    assert line["correct"] is False
    assert line["compared"]["grad_gap"]["value"] > line["compared"]["grad_gap"]["limit"]


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    break_step(monkeypatch, lambda step: lambda state, x, y, key: step(
        state, x[: x.shape[0] // 2], y[: y.shape[0] // 2], key))
    assert last_line(capsys, TRAIN)["correct"] is False


def test_a_sound_serving_run_is_correct(capsys):
    assert last_line(capsys, SERVE)["correct"] is True


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    from deeplearning4j_tpu.serving.batcher import GenerationStream

    emit = GenerationStream._emit

    def altered(self, tok, now):
        emit(self, (tok + 1) % 512 if self.tokens_emitted % 5 == 4 else tok, now)

    monkeypatch.setattr(GenerationStream, "_emit", altered)
    line = last_line(capsys, SERVE)
    assert line["correct"] is False
    assert line["compared"]["served_gap_mean"]["value"] > line["compared"]["served_gap_mean"]["limit"]


def test_a_stream_cut_short_is_not_correct(capsys, monkeypatch):
    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

    submit = ContinuousBatcher.submit

    def short(self, prompt, max_new_tokens=16, **kw):
        return submit(self, prompt, max_new_tokens=max(1, max_new_tokens - 1), **kw)

    monkeypatch.setattr(ContinuousBatcher, "submit", short)
    assert last_line(capsys, SERVE)["correct"] is False
