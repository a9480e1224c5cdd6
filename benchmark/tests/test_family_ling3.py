"""The family `ling3` (Ling-3.0-flash-VL's language model) by its contract:
its counts and every leaf of its weights pinned, its reference's parts tied
to each other, a whole rehearsal of its cell judged by `correct.py` against
its own reference, and the same run with the timed path broken judged not."""

import json
import os

import numpy as np
import pytest

from benchmark import families, flops, reference, run
from benchmark.tests.test_program_spans import reader

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "ling3_pins.json")) as f:
    PINS = json.load(f)
CELL = "serve-ling3-decode-64"


def cell(rehearse: bool):
    return run.load_cell(CELL, rehearse=rehearse)


def test_the_cut_and_the_uncut_model_count_what_the_issue_counted():
    cfg = cell(False).cfg
    ref = families.of(cfg).reference
    assert ref.count_params(cfg) == PINS["count_params"]
    assert ref.count_params(cfg)["all"] == 5_169_285_056
    whole = ref.count_params(ref.uncut(cfg))
    assert whole == PINS["count_params_uncut"]
    assert round(whole["all"] / 1e9, 2) == 124.05
    kinds = ref.layer_kinds(ref.uncut(cfg))
    assert (kinds.count("kda"), kinds.count("mla")) == (35, 7)
    assert (kinds.count("swiglu"), kinds.count("moe")) == (2, 40)
    assert ref.layer_kinds(cfg) == (
        ["embed", "kda", "swiglu"] + ["kda", "moe"] * 4 + ["mla", "moe", "kda", "moe", "head"])


def test_no_width_differs_from_the_catalogs_config():
    """Every number of the published config under its own key, but the four
    cuts of scale that `reduced` lists; `published` gives those back."""
    cfg = cell(False).cfg
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "num_experts", "vocab_size"]
    for key, value in PINS["published_config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["deployment"]["chips_per_layer"] == 4 and cfg["deployment"]["rank"] == 0
    s = families.of(cfg).reference.sizes(cfg)
    assert (s["experts_held"], s["experts_routed"], s["first_expert"]) == (128, 512, 0)


@pytest.mark.parametrize("count", ["decode_step_flops", "decode_step_bytes"])
def test_flops_and_bytes_are_pinned(count):
    cfg = cell(False).cfg
    got = getattr(families.of(cfg).flops, count)(cfg, PINS["counters"])
    assert got == PINS[count]                   # equal exactly


def test_the_counts_say_what_they_count():
    cfg = cell(False).cfg
    fam = families.of(cfg)
    assert round(fam.flops.experts_hit(cfg, 64)) == 81
    one = {"traced_live_rows": 1.0, "traced_live_positions": 0.0}
    n = fam.reference.count_params(cfg)
    # a row alone: the matrices every token passes, 8 picks of which a
    # quarter land here in each of 6 layers, the KDA states
    assert fam.flops.decode_step_flops(cfg, one) == pytest.approx(
        2.0 * n["always"] + 2.0 * n["expert"] * 8 * 0.25 * 6 + 6.0 * 32 * 128 * 128 * 6)
    more = dict(one, traced_live_positions=1000.0)
    assert (fam.flops.decode_step_bytes(cfg, more)
            - fam.flops.decode_step_bytes(cfg, one)) == 1152 * 1000
    with pytest.raises(NotImplementedError, match="serves only"):
        fam.flops.train_step_flops(cfg, {"rows": 1, "seq": 8})
    with pytest.raises(NotImplementedError, match="serves only"):
        fam.reference.first_steps(cfg, 1, [])


def test_every_leaf_of_the_weights_is_pinned():
    import jax

    cfg = cell(True).cfg
    fam = families.of(cfg).reference
    weights = jax.jit(fam.model_weights, static_argnums=0)(
        reference.Frozen(cfg), reference.base_key(PINS["seed"]))
    got = dict(zip(fam.leaf_names(cfg),
                   (float(n) for n in reference.leaf_norms(weights))))
    assert list(got) == list(PINS["leaf_norms"])
    np.testing.assert_allclose(list(got.values()),
                               list(PINS["leaf_norms"].values()), rtol=1e-6)


def rehearsal(capsys, tmp_path, trace: int = 1):
    """A whole rehearsal: its result line, and what it dumped.  The span
    record is the process's, and bounded: earlier tests' spans go first, so
    that the readers are not refused a record that dropped some."""
    from deeplearning4j_tpu.utils import profiling

    profiling.clear()
    dump = str(tmp_path / "dump.json")
    assert run.main(["--workload", CELL, "--seed", str(PINS["seed"]), "--seconds",
                     "2", "--trace", str(trace), "--rehearse", "--dump", dump]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    with open(dump) as f:
        return line, json.load(f)


def test_a_whole_rehearsal_is_correct_and_reads_its_metrics(capsys, tmp_path):
    line, dumped = rehearsal(capsys, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["short_streams"]["value"] == 0
    assert line["compared"]["lost_requests"]["value"] == 0
    assert line["metrics"]["decode.compiles_in_window"]["value"] == 0
    share = line["metrics"]["moe.experts_hit_share"]["value"]
    assert 0 < share <= 100
    # the whole-step readers go through this family's own counts
    c = dumped["counters"]
    cfg = cell(True).cfg
    seen = {"counters": c, "cfg": cfg, "chips": 1, "peaks": flops.peaks("TPU v5 lite"),
            "trace": {"modules": {
                "jit_dl4j_decode": [0.01] * (c["traced_calls"] - c["traced_admitted"]),
                "jit_dl4j_prefill_slot": [0.02] * c["traced_admitted"]}}}
    need = families.of(cfg).flops
    assert reader("decode.step_mfu")(seen) == pytest.approx(
        100.0 * need.decode_step_flops(cfg, c) / 0.01 / seen["peaks"]["bf16_flops_per_s"])
    assert reader("decode.step_roofline")(seen) == pytest.approx(
        100.0 * need.decode_step_bytes(cfg, c) / 0.01 / seen["peaks"]["hbm_bytes_per_s"])


def test_a_program_without_the_counts_leaves_the_metric_out():
    """The parent's spans carry no `experts_hit`: the reader returns None and
    does not raise."""
    from deeplearning4j_tpu.utils.profiling import Span

    spans = [Span("admit", 0, 10, None, 1, 7, {"queue_wait_ns": 0}, 1),
             Span("decode", 10, 20, None, None, 7, {"k": 1, "live": 1}, 2),
             Span("admit", 20, 30, None, 2, 7, {"queue_wait_ns": 0}, 3)]
    seen = {"counters": {"requests": 2}, "cfg": cell(True).cfg, "spans": spans}
    assert reader("moe.experts_hit_share")(seen) is None
    counted = [s._replace(attrs={**s.attrs, "experts_hit": 24, "picks_here": 30,
                                 "steps": 1}) if s.name == "decode" else s
               for s in spans]
    seen["spans"] = [counted[0], counted[1]._replace(start_ns=1, end_ns=9), counted[2]]
    assert reader("moe.experts_hit_share")(seen) == pytest.approx(100.0 * 24 / (8 * 6))


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, tmp_path, monkeypatch):
    from deeplearning4j_tpu.serving.batcher import GenerationStream

    emit = GenerationStream._emit

    def altered(self, tok, now):
        emit(self, (tok + 1) % 512 if self.tokens_emitted % 5 == 4 else tok, now)

    monkeypatch.setattr(GenerationStream, "_emit", altered)
    line, _ = rehearsal(capsys, tmp_path, trace=0)
    assert line["correct"] is False
    held = line["compared"]["served_gap_mean"]
    assert held["value"] > held["limit"]


def test_a_state_that_never_advances_is_not_correct(capsys, tmp_path, monkeypatch):
    """Every KDA layer's decode step computes from its state and hands back
    the state it was given: the cache stays as the prefill left it."""
    from deeplearning4j_tpu.nn.layers.kda import KDALayer

    step = KDALayer.decode_step
    monkeypatch.setattr(KDALayer, "decode_step", staticmethod(
        lambda params, conf, x, state, pos: (step(params, conf, x, state, pos)[0],
                                             state)))
    line, _ = rehearsal(capsys, tmp_path, trace=0)
    assert line["correct"] is False
    held = line["compared"]["served_gap_mean"]
    assert held["value"] > held["limit"]
