"""The family `lfm2_moe` (LFM2-24B-A2B's language model) by its contract: its
counts pinned, the catalog's config key by key, the cut's layers the first
of the uncut model's, every leaf of its weights pinned, a prefill then decode
through the slot table against the reference on logits, and a whole
rehearsal of its cell (`ContinuousBatcher` under the cell's traffic) judged
by `correct.py` against its own reference."""

import json
import os

import numpy as np
import pytest

from benchmark import families, reference, run
from benchmark.tests.test_program_spans import reader

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "lfm2_pins.json")) as f:
    PINS = json.load(f)
CELL = "serve-lfm2-rag-64"
CUT = ("num_hidden_layers", "layer_types")
# float32 at the rehearsal's widths: what is left is the order of float32
# sums (a block of queries against the keys, the taps' order, the head over
# pieces of positions)
TIGHT = 2e-5


def cell(rehearse: bool):
    return run.load_cell(CELL, rehearse=rehearse)


@pytest.fixture(scope="module")
def weights():
    """The seed's weights at the rehearsal's sizes, in the reference's
    layout and bfloat16, drawn once for the cases of this file (a draw of
    the whole model is one compile of some seconds)."""
    import jax

    cfg = cell(True).cfg
    return jax.jit(families.of(cfg).reference.model_weights, static_argnums=0)(
        reference.Frozen(cfg), reference.base_key(PINS["seed"]))


def test_the_cut_and_the_uncut_model_count_what_the_config_file_states():
    cfg = cell(False).cfg
    ref = families.of(cfg).reference
    n = ref.count_params(cfg)
    assert n == PINS["count_params"] and n["all"] == 5_401_307_904
    whole = ref.count_params(ref.uncut(cfg))
    assert whole == PINS["count_params_uncut"] and whole["all"] == 23_977_879_168
    assert (n["conv"], n["attention"], n["expert"]) == (16_783_360, 10_485_888, 9_437_184)
    # a leading dense convolution layer, and expert layers of either mixer
    norms, router, experts = 2 * 2048, 2048 * 64 + 64, 64 * n["expert"]
    assert n["conv"] + 3 * 2048 * 11776 + norms == 89_139_200
    assert n["conv"] + experts + router + norms == 620_898_368
    assert n["attention"] + experts + router + norms == 614_600_896
    assert whole["all"] - n["all"] == 22 * 620_898_368 + 8 * 614_600_896
    active = whole["all"] - 38 * 60 * whole["expert"]
    assert round(active / 1e9, 2) == 2.46                         # "24B-A2B"
    assert round((active - 2 * 65536 * 2048 - 2048) / 1e9, 2) == 2.19
    kinds = ref.layer_kinds(cfg)
    assert kinds == (["embed", "conv", "dense", "conv", "dense"]
                     + ["attn", "moe"] + ["conv", "moe"] * 3
                     + ["attn", "moe"] + ["conv", "moe"] * 3 + ["head"])
    every = ref.layer_kinds(ref.uncut(cfg))
    assert (every.count("conv"), every.count("attn"), every.count("dense")) == (30, 10, 2)


def test_no_width_differs_from_the_catalogs_config():
    """Every key of the published config under its own name, but the depth
    and the list of layer types cut with it; `published` gives those back."""
    cfg = cell(False).cfg
    assert tuple(cfg["reduced"]) == CUT
    for key, value in PINS["published_config"].items():
        if key in CUT:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == PINS["published_config"]["layer_types"][:10]
    assert cfg["deployment"]["chips_per_layer"] == 1
    for key in ("source", "cut", "assumed", "departures", "precision"):
        assert cfg[key], key
    s = families.of(cfg).reference.sizes(cfg)
    assert (s["heads"], s["kv_heads"], s["head_dim"], s["conv_k"]) == (32, 8, 64, 3)
    assert (s["experts_held"], s["top_k"], s["expert_ffn"], s["ffn"]) == (64, 4, 1536, 11776)


def test_the_cuts_layers_are_the_first_of_the_uncut_models(weights):
    """At the rehearsal's sizes: embedding, the first six layers and the
    head of the uncut model are the cut's, leaf for leaf, a layer at a
    time."""
    cfg = cell(True).cfg
    ref = families.of(cfg).reference
    key = reference.base_key(PINS["seed"])
    whole = ref.uncut(cfg)
    kinds, all_kinds = ref.layer_kinds(cfg), ref.layer_kinds(whole)
    assert len(all_kinds) == 2 * 40 + 2 and all_kinds[: len(kinds) - 1] == kinds[:-1]
    for i, kind in enumerate(kinds):
        there = len(all_kinds) - 1 if kind == "head" else i
        mine = weights[i]
        theirs = ref.layer_weights(whole, key, there, kind)
        assert sorted(mine) == sorted(theirs)
        for name in mine:
            assert np.array_equal(np.asarray(mine[name], np.float32),
                                  np.asarray(theirs[name], np.float32)), (i, name)


@pytest.mark.parametrize("count", ["decode_step_flops", "decode_step_bytes"])
def test_flops_and_bytes_are_pinned(count):
    cfg = cell(False).cfg
    got = getattr(families.of(cfg).flops, count)(cfg, PINS["counters"])
    assert got == pytest.approx(PINS[count], rel=1e-12)


def test_the_counts_say_what_they_count():
    from deeplearning4j_tpu.utils.profiling import Span

    cfg = cell(False).cfg
    fam = families.of(cfg)
    n = fam.reference.count_params(cfg)
    one = {"traced_live_rows": 1.0, "traced_live_row_positions": [3000]}
    assert fam.flops.live_cells(cfg, one) == 2 * 3000
    assert fam.flops.cell_bytes(cfg) == 2048
    # under uniform routing 64 rows of 4 picks hit 98.4 % of 64 experts
    rows64 = {"traced_live_rows": 64.0, "traced_live_row_positions": [3000] * 64}
    assert fam.flops.experts_hit(cfg, rows64) == pytest.approx(64 * (1 - (60 / 64) ** 64))
    # the program's own count where the window's spans carry it
    spans = [Span("admit", 0, 10, None, 1, 7, {"queue_wait_ns": 0}, 1),
             Span("decode", 11, 19, None, None, 7, {"experts_hit": 8 * 50, "steps": 1}, 2),
             Span("admit", 20, 30, None, 2, 7, {"queue_wait_ns": 0}, 3)]
    import benchmark.program_spans as ps
    record = ps.record
    try:
        ps.record = lambda seen: spans
        counted = dict(rows64, requests=2)
        assert fam.flops.experts_hit(cfg, counted) == 50.0
        assert (fam.flops.decode_step_bytes(cfg, rows64) - fam.flops.decode_step_bytes(cfg, counted)
                == pytest.approx(2 * n["expert"] * 8 * (64 * (1 - (60 / 64) ** 64) - 50)))
    finally:
        ps.record = record
    assert fam.flops.decode_step_flops(cfg, one) == pytest.approx(
        2.0 * n["always"] + 2.0 * n["expert"] * 4 * 8 + 2.0 * 3 * 2048 * 8
        + 4.0 * 32 * 64 * 6000)
    with pytest.raises(NotImplementedError, match="serves only"):
        fam.flops.train_step_flops(cfg, {"rows": 1, "seq": 8})
    with pytest.raises(NotImplementedError, match="serves only"):
        fam.reference.first_steps(cfg, 1, [])


def test_every_leaf_of_the_weights_is_pinned(weights):
    cfg = cell(True).cfg
    fam = families.of(cfg).reference
    got = dict(zip(fam.leaf_names(cfg),
                   (float(n) for n in reference.leaf_norms(weights))))
    assert list(got) == list(PINS["leaf_norms"])
    np.testing.assert_allclose(list(got.values()),
                               list(PINS["leaf_norms"].values()), rtol=1e-6)


def test_a_prefill_then_decode_is_the_references_logits(weights):
    """The program in float32 at the rehearsal's sizes (two dense
    convolution layers and one period), on the seed's weights as bfloat16
    holds them: the log-probabilities of a prefill of 19 tokens in a bucket
    of 32, then decode through the slot table, are the reference's
    teacher-forced ones to `TIGHT`, which the reference's own int8 control
    misses by a hundred times.  (Through `ContinuousBatcher`, on the served
    tokens' logits: the whole rehearsal below.)"""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import decode

    cfg = cell(True).cfg
    fam = families.of(cfg)
    conf = fam.program.build_conf(
        dict(cfg, flags={"param_dtype": "float32", "compute_dtype": "float32"}))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    fam.program.to_program(weights))
    ids = np.random.default_rng(3).integers(0, fam.reference.sizes(cfg)["vocab"], 28)
    ids = ids.astype(np.int32)
    logits = fam.reference.teacher_forced_logits(cfg, PINS["seed"], ids[None], ("f32", "int8"))
    want = {p: np.asarray(jax.nn.log_softmax(v, axis=-1))[0, 18:-1] for p, v in logits.items()}

    state = decode.init_state(conf, 1, 48)
    bucket = np.zeros((1, 32), np.int32)
    bucket[0, :19] = ids[:19]
    first, state = jax.jit(lambda p, s, t, n: decode.prefill(conf, p, s, t, n))(
        params, state, bucket, jnp.asarray([19], jnp.int32))
    served = [first[0]]
    step = jax.jit(lambda p, s, t, q: decode.decode_step(conf, p, s, t, q))
    for at in range(19, 27):
        out, state = step(params, state, jnp.asarray(ids[at: at + 1]),
                          jnp.asarray([at], jnp.int32))
        served.append(out[0])
    served = np.stack([np.asarray(a) for a in served])
    assert np.max(np.abs(served - want["f32"])) < TIGHT
    assert np.max(np.abs(served - want["int8"])) > 100 * TIGHT
    # what a step reads of a row: a table of 48 cells of 2 x 2 heads of 64
    # in float32, and five tails of 2 x 256
    assert decode.state_bytes_read(conf, 48) == {"table": 48 * 1024, "carry": 5 * 2 * 256 * 4}


# The cell's limit is read at the published widths on the chip.  At the
# rehearsal's widths a sound run reads 0 (no token off the reference's first
# in some 160 positions; CPU, seed 2147483693): the rehearsal is held
# to 5e-5, and the int8 control misses the logits by a hundred times the
# float32 tolerance (`test_a_prefill_then_decode_is_the_references_logits`).
REHEARSAL_LIMITS = {"served_gap_mean": 5e-5, "short_streams": 0, "lost_requests": 0}


@pytest.fixture
def rehearsal_limits(monkeypatch):
    from benchmark import correct

    monkeypatch.setattr(correct, "load_limits", lambda workload: REHEARSAL_LIMITS)


def rehearsal(capsys, tmp_path, trace: int = 1):
    """A whole rehearsal: its result line, and what it dumped."""
    from deeplearning4j_tpu.utils import profiling

    profiling.clear()
    dump = str(tmp_path / "dump.json")
    assert run.main(["--workload", CELL, "--seed", str(PINS["seed"]), "--seconds",
                     "2", "--trace", str(trace), "--rehearse", "--dump", dump]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    with open(dump) as f:
        return line, json.load(f)


def test_a_whole_rehearsal_is_correct_and_reads_its_metrics(capsys, tmp_path,
                                                            rehearsal_limits):
    line, dumped = rehearsal(capsys, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["served_gap_mean"]["limit"] == 5e-5
    assert line["compiles_in_window"] == 0
    c = cell(True)
    layers = {m["name"] for m in run.metrics_of(c.bench, "per_layer", CELL)}
    ends = {m["name"] for m in run.metrics_of(c.bench, "end_to_end", CELL)}
    assert ends == {"gap_p50_ms", "setup_s"}
    assert set(line["metrics"]) <= layers
    assert 0 < line["metrics"]["attn.kv_live_share"]["value"] <= 100
    # in bfloat16, 4 slots x (a table of 96 cells of 2 x 2 heads of 64 and
    # five tails of 2 x 256)
    assert line["metrics"]["decode.state_read_mb_p50"]["value"] == pytest.approx(
        4 * 2 * (96 * 2 * 2 * 64 + 5 * 2 * 256) / 1e6)
    assert 0 < reader("moe.experts_hit_share")({"counters": dumped["counters"],
                                                "cfg": c.cfg}) <= 100


def test_a_program_without_the_count_leaves_the_metric_out():
    """The parent's spans carry no `state_bytes_read`: the reader returns
    None and does not raise."""
    from deeplearning4j_tpu.utils.profiling import Span

    spans = [Span("admit", 0, 10, None, 1, 7, {"queue_wait_ns": 0}, 1),
             Span("decode", 10, 20, None, None, 7, {"k": 1, "live": 1}, 2),
             Span("admit", 20, 30, None, 2, 7, {"queue_wait_ns": 0}, 3)]
    seen = {"counters": {"requests": 2}, "cfg": cell(True).cfg, "spans": spans}
    assert reader("decode.state_read_mb_p50")(seen) is None
    counted = spans[1]._replace(attrs={"k": 1, "steps": 2, "state_bytes_read": 6e6})
    seen["spans"] = [spans[0], counted._replace(start_ns=1, end_ns=9), spans[2]]
    assert reader("decode.state_read_mb_p50")(seen) == pytest.approx(3.0)
