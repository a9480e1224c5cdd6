"""The family `cohere2_moe` (Command A+'s language model) by its contract: its
counts pinned, the catalog's config key by key, the cut's layers the first
of the uncut model's, every leaf of its weights pinned, the eight ranks'
shares of an expert layer against the uncut layer, a prefill then decode
through the rings and the table against the reference on logits, the
reader of the ring's live share on hand-made spans, and a
whole rehearsal of its cell (`ContinuousBatcher` under the cell's traffic)
judged by `correct.py` against its own reference."""

import json
import os

import numpy as np
import pytest

from benchmark import families, reference, run
from benchmark.tests.test_program_spans import Span, reader

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "cohere2_pins.json")) as f:
    PINS = json.load(f)
CELL = "serve-commanda-long-32"
CUT = ("num_hidden_layers", "layer_types", "num_experts", "vocab_size")
# float32 at the rehearsal's widths: what is left is the order of float32
# sums (a block of queries against the keys, a ring read in cell order, the
# four shared experts as one product, the head over pieces of positions)
TIGHT = 2e-5


def cell(rehearse: bool):
    return run.load_cell(CELL, rehearse=rehearse)


@pytest.fixture(scope="module")
def weights():
    """The seed's weights at the rehearsal's sizes, in the reference's
    layout and bfloat16, drawn once for the cases of this file."""
    import jax

    cfg = cell(True).cfg
    return jax.jit(families.of(cfg).reference.model_weights, static_argnums=0)(
        reference.Frozen(cfg), reference.base_key(PINS["seed"]))


def test_the_cut_and_the_uncut_model_count_what_the_config_file_states():
    cfg = cell(False).cfg
    ref = families.of(cfg).reference
    n = ref.count_params(cfg)
    assert n == PINS["count_params"] and n["all"] == 4_733_292_544
    whole = ref.count_params(ref.uncut(cfg))
    assert whole == PINS["count_params_uncut"] and whole["all"] == 218_254_938_112
    assert whole["active"] == 24_981_409_792                       # "218B-A25B"
    assert (n["attention"], n["expert"], n["head"]) == (142_606_336, 50_331_648, 134_217_728)
    # a layer without its routed experts: attention, four shared experts,
    # router, one LayerNorm; with the 16 held; then embedding and final norm
    outside = n["attention"] + 4 * n["expert"] + 128 * 4096 + 4096
    assert outside == 344_461_312 and outside + 16 * n["expert"] == 1_149_767_680
    assert 4 * 1_149_767_680 + n["head"] + 4096 == n["all"]
    assert 32 * (outside + 128 * n["expert"]) + 8 * n["head"] + 4096 == whole["all"]
    assert ref.layer_kinds(cfg) == ["embed", "window", "window", "window", "full", "head"]
    every = ref.layer_kinds(ref.uncut(cfg))
    assert (every.count("window"), every.count("full")) == (24, 8)


def test_no_width_differs_from_the_catalogs_config():
    """Every key of the published config under its own name, but the depth,
    the list of layer types, the experts held and the vocabulary's slice;
    `published` gives those back."""
    cfg = cell(False).cfg
    assert tuple(cfg["reduced"]) == CUT
    for key, value in PINS["published_config"].items():
        if key in CUT:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == PINS["published_config"]["layer_types"][:4]
    assert (cfg["deployment"]["chips_per_layer"], cfg["deployment"]["rank"]) == (8, 0)
    assert cfg["num_experts"] * 8 == 128 and cfg["vocab_size"] * 8 == 262144
    for key in ("source", "cut", "assumed", "departures", "precision"):
        assert cfg[key], key
    for key in ("window_edge", "shared_experts_average", "nope_full_layers", "init"):
        assert cfg["assumed"][key], key
    s = families.of(cfg).reference.sizes(cfg)
    assert (s["heads"], s["kv_heads"], s["head_dim"], s["window"]) == (128, 8, 128, 4096)
    assert (s["experts_held"], s["experts_routed"], s["first_expert"], s["top_k"],
            s["shared"], s["expert_ffn"]) == (16, 128, 0, 8, 4, 4096)


def test_the_program_is_built_at_the_published_widths():
    cfg = cell(False).cfg
    conf = families.of(cfg).program.build_conf(cfg)
    window, full = conf.conf(1).layer_spec, conf.conf(4).layer_spec
    assert (window.attn.window, window.attn.rotary, full.attn.window, full.attn.rotary) == (
        4096, "pairs", 0, "none")
    assert (full.attn.n_heads, full.attn.n_kv_heads, full.attn.head_dim,
            full.attn.rope_theta) == (128, 8, 128, 50000.0)
    f = full.ffn
    assert (f.n_routed, f.n_held, f.first_held, f.hidden, f.shared_hidden, f.shared_scale,
            f.top_k, f.score, f.router_bias) == (128, 16, 0, 4096, 16384, 0.25, 8,
                                                 "sigmoid", False)
    head = conf.conf(5)
    assert (head.layer_spec.norm, head.layer_spec.tied, head.n_out) == ("layer", True, 32768)


def test_a_program_from_before_the_layer_type_refuses_at_once(monkeypatch):
    """The parent's `nn/conf.py` has no `ParallelSpec`: `build_conf` ends
    with a `SystemExit` that says so, and nothing is compiled."""
    from deeplearning4j_tpu.nn import conf as nn_conf

    monkeypatch.delattr(nn_conf, "ParallelSpec")
    cfg = cell(True).cfg
    with pytest.raises(SystemExit, match="no parallel block"):
        families.of(cfg).program.build_conf(cfg)


def test_the_cuts_layers_are_the_first_of_the_uncut_models(weights):
    """At the rehearsal's sizes: embedding, the four blocks and the head of
    the uncut model are the cut's, leaf for leaf, a routed expert's by its
    published id (the cut holds the first 4 of 32 here)."""
    cfg = cell(True).cfg
    ref = families.of(cfg).reference
    key = reference.base_key(PINS["seed"])
    whole = {**ref.uncut(cfg), "num_experts": cfg["num_experts"]}
    kinds, all_kinds = ref.layer_kinds(cfg), ref.layer_kinds(whole)
    assert len(all_kinds) == 32 + 2 and all_kinds[: len(kinds) - 1] == kinds[:-1]
    for i, kind in enumerate(kinds):
        there = len(all_kinds) - 1 if kind == "head" else i
        # the embedding is drawn at the slice's own shape
        whole_here = {**whole, "vocab_size": cfg["vocab_size"]} if kind == "embed" else whole
        theirs = ref.layer_weights(whole_here, key, there, kind)
        assert sorted(weights[i]) == sorted(theirs)
        for name in theirs:
            assert np.array_equal(np.asarray(weights[i][name], np.float32),
                                  np.asarray(theirs[name], np.float32)), (i, name)


@pytest.mark.parametrize("count", ["decode_step_flops", "decode_step_bytes"])
def test_flops_and_bytes_are_pinned(count):
    cfg = cell(False).cfg
    got = getattr(families.of(cfg).flops, count)(cfg, PINS["counters"])
    assert got == pytest.approx(PINS[count], rel=1e-12)


def test_the_counts_say_what_they_count():
    cfg = cell(False).cfg
    fam = families.of(cfg)
    n = fam.reference.count_params(cfg)
    # a row alone at 6,000 positions: 6,000 cells of the full layer's table,
    # the ring's 4,096 in each of three window layers; 4,096 bytes a cell
    one = {"traced_live_rows": 1.0, "traced_live_row_positions": [6000]}
    assert fam.flops.live_cells(cfg, one) == {"full": 6000, "window": 3 * 4096}
    assert fam.flops.cell_bytes(cfg) == 4096
    young = dict(one, traced_live_row_positions=[3000])
    assert fam.flops.live_cells(cfg, young) == {"full": 3000, "window": 3 * 3000}
    # under uniform routing 32 rows of 8 picks miss a held expert one time in 8
    rows32 = {"traced_live_rows": 32.0, "traced_live_row_positions": [3000] * 32}
    assert fam.flops.experts_hit(cfg, rows32) == pytest.approx(16 * (1 - (120 / 128) ** 32))
    # of a row's 8 picks an eighth land here, in each of 4 layers
    assert fam.flops.decode_step_flops(cfg, one) == pytest.approx(
        2.0 * (n["always"] + n["head"]) + 2.0 * n["expert"] * 8 * 0.125 * 4
        + 4.0 * 128 * 128 * (6000 + 3 * 4096))
    assert (fam.flops.decode_step_bytes(cfg, one) - fam.flops.decode_step_bytes(cfg, young)
            == 4096 * (3000 + 3 * 1096))
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


def test_the_eight_ranks_parts_add_up_to_the_uncut_layer():
    """At the rehearsal's sizes (4 routed experts held of 32, as 16 of 128):
    each rank's routed part, from the reference and from the program, the
    four shared experts counted once, against the reference holding every
    expert."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.experts import MoELayer

    cfg = {**cell(True).cfg, "flags": {"param_dtype": "float32", "compute_dtype": "float32"}}
    fam = families.of(cfg)
    ref, s = fam.reference, fam.reference.sizes(cfg)
    key = reference.base_key(PINS["seed"])
    u = jax.random.normal(jax.random.PRNGKey(6), (40, s["d"]), jnp.float32)

    def parts(c):
        with jax.default_matmul_precision("highest"):
            return ref.moe_parts(ref.layer_weights(c, key, 1, "window"), u, ref.sizes(c))

    whole, shared = parts({**cfg, "num_experts": s["experts_routed"]})
    total, program_total = shared, -7.0 * shared
    for rank in range(8):
        c = {**cfg, "deployment": {"rank": rank}}
        assert ref.sizes(c)["first_expert"] == 4 * rank
        routed, also_shared = parts(c)
        np.testing.assert_array_equal(np.asarray(also_shared), np.asarray(shared))
        total = total + routed
        layer = fam.program.build_conf(c).conf(1)
        ffn = layer.replace(layer_spec=layer.layer_spec.ffn)
        assert (ffn.layer_spec.n_held, ffn.layer_spec.first_held) == (4, 4 * rank)
        params = fam.program.to_program([ref.layer_weights(c, key, 1, "window")])[0]
        out, _ = MoELayer.branch(params["ffn"], ffn, u)
        program_total = program_total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole + shared), atol=TIGHT)
    np.testing.assert_allclose(np.asarray(program_total), np.asarray(whole + shared),
                               atol=TIGHT)


def test_a_prefill_then_decode_is_the_references_logits(weights):
    """The program in float32 at the rehearsal's sizes (three window layers
    with rings of 8 and a full layer), on the seed's weights as bfloat16
    holds them: the log-probabilities of a prefill of 19 tokens in a bucket
    of 32, then 12 tokens decoded through the slot table (the rings wrap, a
    row passes its window), are the reference's teacher-forced ones to
    `TIGHT`, which the reference's own int8 control misses by a hundred
    times.  The parameters hold the embedding's table once: the head reads
    it.  (Through `ContinuousBatcher`, on the served tokens' logits: the
    whole rehearsal below.)"""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import decode

    cfg = cell(True).cfg
    fam = families.of(cfg)
    conf = fam.program.build_conf(
        dict(cfg, flags={"param_dtype": "float32", "compute_dtype": "float32"}))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    fam.program.to_program(weights))
    assert "W" not in params[-1] and params[0]["W"].shape == (512, 64)
    tables = [a for a in jax.tree_util.tree_leaves(params) if a.shape == (512, 64)]
    assert len(tables) == 1
    ids = np.random.default_rng(3).integers(0, fam.reference.sizes(cfg)["vocab"], 32)
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
    for at in range(19, 31):
        out, state = step(params, state, jnp.asarray(ids[at: at + 1]),
                          jnp.asarray([at], jnp.int32))
        served.append(out[0])
    served = np.stack([np.asarray(a) for a in served])
    assert np.max(np.abs(served - want["f32"])) < TIGHT
    assert np.max(np.abs(served - want["int8"])) > 100 * TIGHT
    # what a step reads of a row: three rings of 8 and a table of 48 cells of
    # 2 K/V heads of 16, K and V, in float32
    assert decode.state_bytes_read(conf, 48) == {"table": (3 * 8 + 48) * 256, "carry": 0}


def test_a_rings_live_share_reads_the_decode_spans_ring_counts():
    """`attn.ring_live_share` over the window's `decode` spans: the ring
    cells the steps needed over those their whole-ring reads covered, summed
    (30 + 90 of 40 + 120: 75 %); a program whose spans carry no ring counts
    (one from before the count, or a stack without a ring) leaves the metric
    out.  The metric is listed for the three cells that hold rings."""
    read = reader("attn.ring_live_share")
    spans = [Span("admit", 0, 10, None, 1, 7, {"queue_wait_ns": 0}, 1),
             Span("decode", 11, 19, None, None, 7, {"k": 1, "live": 1}, 2),
             Span("admit", 20, 30, None, 2, 7, {"queue_wait_ns": 0}, 3)]
    seen = {"counters": {"requests": 2}, "spans": spans}
    assert read(seen) is None
    counted = [spans[1]._replace(attrs={"ring_cells_live": 30, "ring_cells_spanned": 40}),
               spans[1]._replace(sid=4, start_ns=20, end_ns=25,
                                 attrs={"ring_cells_live": 90, "ring_cells_spanned": 120})]
    seen["spans"] = [spans[0], counted[0], counted[1], spans[2]._replace(start_ns=26,
                                                                          end_ns=30)]
    assert read(seen) == pytest.approx(75.0)
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        (m,) = [m for m in json.load(f)["per_layer"] if m["name"] == "attn.ring_live_share"]
    assert (m["layer"], m["moves"], m["workloads"]) == (
        "Cache", "gap_p50_ms", [CELL, "serve-mellum2-mixed-64", "serve-dots3-long-64"])


# The cell's limit is read at the published widths on the chip.  At the
# rehearsal's widths a sound run reads 0 (no token off the reference's first
# in some 300 positions; CPU, seed 2147483693): the rehearsal is held to
# 5e-5, and the int8 control misses the logits by a hundred times the
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
    for name in ("attn.kv_live_share", "attn.ring_live_share"):
        assert 0 < line["metrics"][name]["value"] <= 100, name
    # in bfloat16, 4 slots x (three rings of 8 and a table of 96 cells of
    # 2 K/V heads of 16, K and V)
    assert line["metrics"]["decode.state_read_mb_p50"]["value"] == pytest.approx(
        4 * (3 * 8 + 96) * 2 * 2 * 16 * 2 / 1e6)
    assert 0 < reader("moe.experts_hit_share")({"counters": dumped["counters"],
                                                "cfg": c.cfg}) <= 100
