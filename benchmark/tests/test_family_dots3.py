"""The family `dots3_note` (dots3-note-prev's language model) by its
contract: its counts pinned, the catalog's config key by key, the cut's
layers the first of the uncut model's, every leaf of its weights pinned, a
whole rehearsal of its cell judged by `correct.py` against its own
reference, the same run with the timed path broken three ways judged not,
and the parent's clean refusal of the configuration."""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import families, flops, reference, run
from benchmark.tests.test_program_spans import reader

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "dots3_pins.json")) as f:
    PINS = json.load(f)
CELL = "serve-dots3-long-64"
CUT = ("num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size")


def cell(rehearse: bool):
    return run.load_cell(CELL, rehearse=rehearse)


def test_the_cut_and_the_uncut_model_count_what_the_issue_counted():
    cfg = cell(False).cfg
    ref = families.of(cfg).reference
    n = ref.count_params(cfg)
    assert n == PINS["count_params"]
    assert (n["all"], n["full"], n["window"], n["expert"]) == (
        4_087_154_176, 144_048_128, 90_832_896, 23_592_960)
    whole = ref.count_params(ref.uncut(cfg))
    assert whole == PINS["count_params_uncut"]
    assert whole["all"] == 279_551_726_592
    active = whole["all"] - 45 * (256 - 8) * whole["expert"]
    assert round(active / 1e9, 2) == 16.25          # "288B-A17B" with its towers
    assert ref.layer_kinds(cfg) == (
        ["embed", "full", "swiglu", "full", "moe"] + ["window", "moe"] * 3 + ["head"])
    kinds = ref.layer_kinds(ref.uncut(cfg))
    assert (kinds.count("full"), kinds.count("window")) == (13, 33)
    assert (kinds.count("swiglu"), kinds.count("moe")) == (1, 45)


def test_no_width_differs_from_the_catalogs_config():
    """Every key of the published config under its own name, but the four
    cuts of scale that `reduced` lists; `published` gives those back."""
    cfg = cell(False).cfg
    assert tuple(cfg["reduced"]) == CUT
    for key, value in PINS["published_config"].items():
        if key in CUT:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == PINS["published_config"]["layer_types"][:5]
    assert (cfg["deployment"]["chips_per_layer"], cfg["deployment"]["rank"]) == (8, 0)
    s = families.of(cfg).reference.sizes(cfg)
    assert (s["d"], s["ffn"], s["expert_ffn"], s["shared_ffn"]) == (5120, 13824, 1536, 1536)
    assert s["full"] == {"heads": 128, "q_rank": 1024, "kv_rank": 512, "nope": 128,
                         "rope": 64, "v_dim": 128, "theta": 8e7}
    assert s["window"] == {"heads": 64, "q_rank": 1024, "kv_rank": 1024, "nope": 192,
                           "rope": 64, "v_dim": 128, "theta": 5e4, "span": 513}
    assert (s["index_heads"], s["index_dim"], s["index_topk"]) == (64, 128, 2048)
    assert (s["experts_held"], s["experts_routed"], s["first_expert"], s["top_k"]) == (
        32, 256, 0, 8)
    assert (s["vocab"], s["eps"], s["rescale"]) == (19008, 1e-5, True)


def test_the_program_is_built_at_the_published_widths():
    conf = families.of(cell(False).cfg).program.build_conf(cell(False).cfg)
    full, window = conf.conf(1).layer_spec, conf.conf(5).layer_spec
    assert dataclasses.asdict(full) == {
        "n_heads": 128, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 8e7, "eps": 1e-5,
        "q_lora_rank": 1024, "lora_rescale": True, "window": 0, "gate": True,
        "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048}
    assert dataclasses.asdict(window) == {
        "n_heads": 64, "kv_lora_rank": 1024, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 5e4, "eps": 1e-5,
        "q_lora_rank": 1024, "lora_rescale": True, "window": 513, "gate": True,
        "index_n_heads": 0, "index_head_dim": 0, "index_topk": 0}
    moe = conf.conf(4).layer_spec
    assert (moe.n_routed, moe.n_held, moe.first_held, moe.hidden, moe.shared_hidden,
            moe.top_k, moe.n_group, moe.routed_scaling, moe.score, moe.router_bias) == (
        256, 32, 0, 1536, 1536, 8, 1, 1.0, "sigmoid", True)
    assert conf.conf(2).layer_spec.hidden == 13824
    assert (conf.conf(0).n_in, conf.conf(11).n_out) == (19008, 19008)


def test_a_program_from_before_the_options_refuses_at_once(monkeypatch):
    """The parent's `MLASpec` has none of the seven fields: `build_conf` ends
    with a `SystemExit` that names them, and nothing is compiled."""
    from deeplearning4j_tpu.nn import conf as nn_conf

    @dataclasses.dataclass(frozen=True)
    class Before:
        n_heads: int
        kv_lora_rank: int
        qk_nope_head_dim: int
        qk_rope_head_dim: int
        v_head_dim: int
        rope_theta: float = 10000.0
        eps: float = 1e-6

    monkeypatch.setattr(nn_conf, "MLASpec", Before)
    cfg = cell(True).cfg
    with pytest.raises(SystemExit, match=r"MLASpec has no fields \['gate', 'index_head_dim'"):
        families.of(cfg).program.build_conf(cfg)


def test_the_cuts_layers_are_the_first_of_the_uncut_models():
    """At the rehearsal's sizes: embedding, the first five blocks' attention
    and the head of the uncut model are the cut's, leaf for leaf, and so are
    an expert layer's leaves that are not an expert's."""
    cfg = cell(True).cfg
    ref = families.of(cfg).reference
    key = reference.base_key(PINS["seed"])
    whole = {**ref.uncut(cfg), "n_routed_experts": cfg["n_routed_experts"]}
    assert whole["num_hidden_layers"] == 46 and len(whole["layer_types"]) == 46
    kinds, all_kinds = ref.layer_kinds(cfg), ref.layer_kinds(whole)
    assert len(all_kinds) == 2 * 46 + 2 and all_kinds[: len(kinds) - 1] == kinds[:-1]
    for i, kind in enumerate(kinds):
        there = len(all_kinds) - 1 if kind == "head" else i
        if kind in ("embed", "head"):       # the slice's own shape
            whole_here = {**whole, "vocab_size": cfg["vocab_size"]}
        else:
            whole_here = whole
        mine = ref.layer_weights(cfg, key, i, kind)
        theirs = ref.layer_weights(whole_here, key, there, kind)
        assert sorted(mine) == sorted(theirs)
        for name in mine:
            assert np.array_equal(np.asarray(mine[name], np.float32),
                                  np.asarray(theirs[name], np.float32)), (i, name)


@pytest.mark.parametrize("count", ["decode_step_flops", "decode_step_bytes"])
def test_flops_and_bytes_are_pinned(count):
    cfg = cell(False).cfg
    got = getattr(families.of(cfg).flops, count)(cfg, PINS["counters"])
    assert got == PINS[count]                   # equal exactly


def test_the_counts_say_what_they_count():
    cfg = cell(False).cfg
    fam = families.of(cfg)
    assert round(fam.flops.experts_hit(cfg, 64), 2) == 27.81       # 86.9 % of 32
    n = fam.reference.count_params(cfg)
    # a row alone at 5,000 tokens: every matrix outside the routed experts,
    # 8 picks of which an eighth land here in each of 4 layers; 5,000 index
    # keys and 2,048 latents in each of 2 layers, 513 ring cells in each of 3
    one = {"traced_live_rows": 1.0, "traced_live_row_positions": [5000]}
    assert fam.flops.live_cells(cfg, one) == {"index": 5000, "latents": 2048, "ring": 513}
    assert fam.flops.decode_step_flops(cfg, one) == pytest.approx(
        2.0 * n["always"] + 2.0 * 23_592_960 * 8 * 0.125 * 4
        + 2 * (2.0 * 64 * 128 * 5000 + 2.0 * 128 * (2 * 512 + 64) * 2048)
        + 3 * 2.0 * 64 * (2 * 1024 + 64) * 513)
    short = dict(one, traced_live_row_positions=[300])
    assert fam.flops.live_cells(cfg, short) == {"index": 300, "latents": 300, "ring": 300}
    # a full layer's position: 256 B of index key, and 1,152 B once picked;
    # a ring's cell 2,176 B
    assert (fam.flops.decode_step_bytes(cfg, one)
            - fam.flops.decode_step_bytes(cfg, short)) == (
        2 * (256 * 4700 + 1152 * 1748) + 3 * 2176 * 213)
    # two samples of two rows: the mean over the samples of the rows' sum
    two = {"traced_live_rows": 2.0, "traced_live_row_positions": [100, 200, 3000, 4000]}
    assert fam.flops.live_cells(cfg, two) == {
        "index": 7300 / 2, "latents": (300 + 2 * 2048) / 2, "ring": (300 + 2 * 513) / 2}
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


# The cell's limit is read at the published widths on the chip.  At the
# rehearsal's widths a sound run reads 0 (no served token of some 290 off the
# reference's first) and the three faults below 6e-5 and more (3 to 8 % of
# the served tokens off it; CPU, seed 2147483659, PR 34), so the rehearsals
# are held to 2e-5.  The traffic file's `rehearse` block checks 24 requests:
# over the cell's own 6 (some 75 positions) a fault moved 1 to 4 tokens, and
# on a loaded machine, which sends fewer requests and so draws another
# sample, it once moved none.
REHEARSAL_LIMITS = {"served_gap_mean": 2e-5, "short_streams": 0, "lost_requests": 0}


@pytest.fixture
def rehearsal_limits(monkeypatch):
    from benchmark import correct

    monkeypatch.setattr(correct, "load_limits", lambda workload: REHEARSAL_LIMITS)


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


def test_a_whole_rehearsal_is_correct_and_reads_its_metrics(capsys, tmp_path,
                                                            rehearsal_limits):
    line, dumped = rehearsal(capsys, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["served_gap_mean"]["limit"] == 2e-5
    assert line["compared"]["short_streams"]["value"] == 0
    assert line["compared"]["lost_requests"]["value"] == 0
    assert line["compiles_in_window"] == 0
    # prompts of 12 to 48 and answers of 4 to 24 against index_topk 8 and a
    # window of 5: the selection is never idle
    assert 0 < line["metrics"]["dsa.selected_share"]["value"] < 60
    assert 0 < line["metrics"]["attn.kv_live_share"]["value"] <= 100
    assert line["metrics"]["decode.dispatch_ms_p50"]["value"] > 0
    cell_ = cell(True)
    ends = {m["name"] for m in run.metrics_of(cell_.bench, "end_to_end", CELL)}
    assert {"gap_p50_ms", "setup_s"} <= ends
    # The readers of the whole step go through this family's own counts, and
    # the experts' through the process's span record
    c = dumped["counters"]
    cfg = cell_.cfg
    assert 0 < reader("moe.experts_hit_share")({"counters": c, "cfg": cfg}) <= 100
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
    """The parent's spans carry no `dsa_cells_live`: the reader returns None
    and does not raise."""
    from deeplearning4j_tpu.utils.profiling import Span

    spans = [Span("admit", 0, 10, None, 1, 7, {"queue_wait_ns": 0}, 1),
             Span("decode", 10, 20, None, None, 7, {"k": 1, "live": 1}, 2),
             Span("admit", 20, 30, None, 2, 7, {"queue_wait_ns": 0}, 3)]
    seen = {"counters": {"requests": 2}, "cfg": cell(True).cfg, "spans": spans}
    assert reader("dsa.selected_share")(seen) is None
    counted = [s._replace(attrs={**s.attrs, "dsa_cells_live": 120,
                                 "dsa_cells_selected": 48}) if s.name == "decode" else s
               for s in spans]
    seen["spans"] = [counted[0], counted[1]._replace(start_ns=1, end_ns=9), counted[2]]
    assert reader("dsa.selected_share")(seen) == pytest.approx(40.0)


# ------------------------------------------ the timed path broken three ways

def _not_correct(capsys, tmp_path):
    line, _ = rehearsal(capsys, tmp_path, trace=0)
    assert line["correct"] is False
    held = line["compared"]["served_gap_mean"]
    assert held["value"] > held["limit"]


def test_a_ring_written_one_cell_off_is_not_correct(capsys, tmp_path, monkeypatch,
                                                    rehearsal_limits):
    """Every window layer's decode step writes its latent into the cell after
    the one its position names."""
    from deeplearning4j_tpu.nn.layers.mla import MLALayer

    step = MLALayer.decode_step

    def off_by_one(params, conf, x, state, pos):
        if not conf.layer_spec.window:
            return step(params, conf, x, state, pos)
        out, new = step(params, conf, x, state, pos + 1)
        return step(params, conf, x, new, pos)[0], new

    monkeypatch.setattr(MLALayer, "decode_step", staticmethod(off_by_one))
    _not_correct(capsys, tmp_path)


def specs_changed(monkeypatch, has: str, **change_of):
    """The family's `build_conf`, with every layer spec that has the field
    `has` set (non-zero, not None) rebuilt by `change_of[field](old value)`."""
    from benchmark.families.dots3_note import program

    build = program.build_conf

    def changed(cfg):
        conf = build(cfg)
        return dataclasses.replace(conf, confs=tuple(
            c.replace(layer_spec=dataclasses.replace(c.layer_spec, **{
                k: f(getattr(c.layer_spec, k)) for k, f in change_of.items()}))
            if getattr(c.layer_spec, has, None) else c for c in conf.confs))

    monkeypatch.setattr(program, "build_conf", changed)


def test_an_indexer_that_keeps_half_is_not_correct(capsys, tmp_path, monkeypatch,
                                                   rehearsal_limits):
    """The program's full layers keep 4 positions where the model keeps 8."""
    specs_changed(monkeypatch, "index_topk", index_topk=lambda k: k // 2)
    _not_correct(capsys, tmp_path)


def test_a_window_one_short_is_not_correct(capsys, tmp_path, monkeypatch,
                                           rehearsal_limits):
    """The program's window layers see one position fewer than published.
    (The rescale left off, the fourth fault tried, moved no served token of
    some 200 at these widths, where attention's softmax is all but even:
    `tests/test_dots3_layers.py` holds it in float32 against the layer.)"""
    specs_changed(monkeypatch, "window", window=lambda w: w - 1)
    _not_correct(capsys, tmp_path)
