"""The family `qwen3_next` (Qwen3-Next's language model) by its contract: its
counts pinned, the catalog's config key by key, the cut's layers the first
of the uncut model's, every leaf of its weights pinned, the eight ranks'
shares of an expert layer against the uncut layer, a prefill then decode
through the delta-rule states and the table against the reference on
logits (and the faults that the comparison catches), the reader of the
carried state's read on hand-made spans, and a whole rehearsal of its cell
(`ContinuousBatcher` under the cell's traffic) judged by `correct.py`
against its own reference."""

import json
import os

import numpy as np
import pytest

from benchmark import families, reference, run
from benchmark.tests.test_program_spans import Span, reader

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "qwen3next_pins.json")) as f:
    PINS = json.load(f)
CELL = "serve-qwen3next-decode-64"
CUT = ("num_hidden_layers", "num_experts", "vocab_size")
# float32 at the rehearsal's widths: what is left is the order of float32
# sums (a chunk's products against the token loop, a block of queries
# against the keys, the experts one at a time against one product)
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
    assert n == PINS["count_params"] and n["all"] == 2_929_374_400
    whole = ref.count_params(ref.uncut(cfg))
    assert whole == PINS["count_params_uncut"] and whole["all"] == 79_674_391_296
    assert (n["gdn"], n["attn"], n["expert"]) == (33_718_464, 27_263_488, 3_145_728)
    # in_proj_qkvz, in_proj_ba, the convolution, A_log and dt_bias, the gated
    # norm, out_proj; q with its gate, k and v, o, the q and k norms
    assert n["gdn"] == 2048 * 12288 + 2048 * 64 + 8192 * 4 + 2 * 32 + 128 + 4096 * 2048
    assert n["attn"] == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    # router, shared expert, its gate, two norms: the rest of a layer
    rest = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2 * 2048
    assert rest == 4_200_448
    held, every = 64 * n["expert"], 512 * n["expert"]
    assert 9 * (n["gdn"] + held + rest) + 3 * (n["attn"] + held + rest) == 2_851_581_120
    assert n["all"] == 2_851_581_120 + 2 * 18992 * 2048 + 2048
    assert whole["all"] == (36 * (n["gdn"] + every + rest) + 12 * (n["attn"] + every + rest)
                            + 2 * 151936 * 2048 + 2048)
    assert whole["active"] == 3_874_929_408                          # "80B-A3B"
    assert whole["active"] - whole["embed"] - whole["head"] == 3_252_599_552
    period = ["gdn", "moe"] * 3 + ["attn", "moe"]
    assert ref.layer_kinds(cfg) == ["embed"] + 3 * period + ["head"]
    every_kind = ref.layer_kinds(ref.uncut(cfg))
    assert (every_kind.count("gdn"), every_kind.count("attn")) == (36, 12)


def test_no_width_differs_from_the_catalogs_config():
    """Every key of the published config under its own name, but the depth,
    the experts held and the vocabulary's slice; `published` gives those
    back."""
    cfg = cell(False).cfg
    assert tuple(cfg["reduced"]) == CUT
    for key, value in PINS["published_config"].items():
        if key in CUT:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["deployment"]["chips_per_layer"], cfg["deployment"]["rank"]) == (8, 0)
    assert cfg["num_experts"] * 8 == 512 and cfg["vocab_size"] * 8 == 151936
    assert cfg["num_hidden_layers"] * 4 == 48
    for key in ("source", "cut", "assumed", "departures", "precision"):
        assert cfg[key], key
    for key in ("norms", "qkvz_layout", "q_gate_layout", "partial_rotary", "router", "init"):
        assert cfg["assumed"][key], key
    s = families.of(cfg).reference.sizes(cfg)
    assert (s["heads"], s["kv_heads"], s["head_dim"], s["rotary_dim"], s["theta"]) == (
        16, 2, 256, 64, 1e7)
    assert (s["k_heads"], s["v_heads"], s["k_dim"], s["v_dim"], s["conv"]) == (
        16, 32, 128, 128, 4)
    assert (s["experts_held"], s["experts_routed"], s["first_expert"], s["top_k"],
            s["expert_ffn"], s["shared_ffn"], s["ranks"]) == (64, 512, 0, 10, 512, 512, 8)


def test_the_program_is_built_at_the_published_widths():
    cfg = cell(False).cfg
    conf = families.of(cfg).program.build_conf(cfg)
    g = conf.conf(1).layer_spec
    assert (str(conf.conf(1).layer_type), g.n_k_heads, g.n_v_heads, g.k_dim, g.v_dim,
            g.conv_kernel) == ("gdn", 16, 32, 128, 128, 4)
    a = conf.conf(7).layer_spec
    assert (str(conf.conf(7).layer_type), a.n_heads, a.n_kv_heads, a.head_dim, a.window,
            a.rope_theta, a.rotary_dim, a.out_gate, a.qk_norm) == (
        "gqa", 16, 2, 256, 0, 1e7, 64, True, True)
    f = conf.conf(2).layer_spec
    assert (f.n_routed, f.n_held, f.first_held, f.hidden, f.shared_hidden, f.top_k,
            f.n_group, f.score, f.router_bias, f.shared_gate, f.ranks) == (
        512, 64, 0, 512, 512, 10, 1, "softmax", False, True, 8)
    head = conf.conf(25)
    assert (head.layer_spec.tied, head.n_out) == (False, 18992)


def test_a_program_from_before_the_layer_type_refuses_at_once(monkeypatch):
    """The parent's `nn/conf.py` has no `GDNSpec`: `build_conf` ends with a
    `SystemExit` that says so, and nothing is compiled."""
    from deeplearning4j_tpu.nn import conf as nn_conf

    monkeypatch.delattr(nn_conf, "GDNSpec")
    cfg = cell(True).cfg
    with pytest.raises(SystemExit, match="no gdn layer type"):
        families.of(cfg).program.build_conf(cfg)


def test_the_cuts_layers_are_the_first_of_the_uncut_models(weights):
    """At the rehearsal's sizes: the embedding, a layer of each kind and the
    head of the uncut model are the cut's, leaf for leaf, a routed expert's
    by its published id (the cut holds the first 4 of 32 here); the rest of
    the cut's layers are drawn by the same function of their index."""
    cfg = cell(True).cfg
    ref = families.of(cfg).reference
    key = reference.base_key(PINS["seed"])
    whole = {**ref.uncut(cfg), "num_experts": cfg["num_experts"]}
    kinds, all_kinds = ref.layer_kinds(cfg), ref.layer_kinds(whole)
    assert len(all_kinds) == 2 * 48 + 2 and all_kinds[: len(kinds) - 1] == kinds[:-1]
    for i, kind in enumerate(kinds):
        if kinds.index(kind) != i:
            continue
        there = len(all_kinds) - 1 if kind == "head" else i
        # the embedding and the head are drawn at the slice's own shape
        here = ({**whole, "vocab_size": cfg["vocab_size"]} if kind in ("embed", "head")
                else whole)
        theirs = ref.layer_weights(here, key, there, kind)
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
    # a row alone at 1,000 positions: 1,000 cells of each of 3 full layers'
    # tables, 2,048 bytes a cell
    one = {"traced_live_rows": 1.0, "traced_live_row_positions": [1000]}
    assert fam.flops.live_cells(cfg, one) == 3 * 1000 and fam.flops.cell_bytes(cfg) == 2048
    young = dict(one, traced_live_row_positions=[400])
    # a delta-rule layer's row: 32 states of 128 x 128 float32 and three
    # carried inputs of 8,192 in bfloat16
    assert fam.flops.state_row_bytes(cfg) == 32 * 128 * 128 * 4 + 3 * 8192 * 2
    # one row's 10 picks are read by 8 ranks' rows: 80 picks over 512 miss
    # a held expert with probability (502 / 512) ** 8
    assert fam.flops.experts_hit(cfg, one) == pytest.approx(64 * (1 - (502 / 512) ** 8))
    rows64 = {"traced_live_rows": 64.0, "traced_live_row_positions": [1000] * 64}
    assert fam.flops.experts_hit(cfg, rows64) == pytest.approx(64, rel=1e-4)
    # of a row's 10 picks an eighth land here, in each of 12 layers; the
    # state's 6 FLOPs an element in 9 layers; per cell, score and value
    assert fam.flops.decode_step_flops(cfg, one) == pytest.approx(
        2.0 * (n["always"] + n["head"]) + 2.0 * n["expert"] * 10 * 0.125 * 12
        + 6.0 * 32 * 128 * 128 * 9 + 4.0 * 16 * 256 * 3000)
    assert (fam.flops.decode_step_bytes(cfg, one) - fam.flops.decode_step_bytes(cfg, young)
            == 2048 * 3 * 600)
    with pytest.raises(NotImplementedError, match="serves only"):
        fam.flops.train_step_flops(cfg, {"rows": 1, "seq": 8})
    with pytest.raises(NotImplementedError, match="serves only"):
        fam.reference.first_steps(cfg, 1, [])


def test_every_leaf_of_the_weights_is_pinned(weights):
    """Every leaf's norm, pinned; and the program's layout of the weights
    read back into the reference's is the same leaves."""
    cfg = cell(True).cfg
    fam = families.of(cfg)
    got = dict(zip(fam.reference.leaf_names(cfg),
                   (float(n) for n in reference.leaf_norms(weights))))
    assert list(got) == list(PINS["leaf_norms"])
    np.testing.assert_allclose(list(got.values()),
                               list(PINS["leaf_norms"].values()), rtol=1e-6)
    back = fam.program.from_program(fam.program.to_program(weights))
    for layer, there in zip(weights, back):
        assert sorted(layer) == sorted(there)
        for name in layer:
            np.testing.assert_array_equal(np.asarray(there[name], np.float32),
                                          np.asarray(layer[name], np.float32))


def test_the_eight_ranks_parts_add_up_to_the_uncut_layer():
    """At the rehearsal's sizes (4 routed experts held of 32, as 64 of 512):
    each rank's routed part, from the reference and from the program, the
    gated shared expert counted once, against the reference holding every
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
        w = {k: v.astype(jnp.float32) for k, v in ref.layer_weights(c, key, 2, "moe").items()}
        with jax.default_matmul_precision("highest"):
            return ref.moe_parts(w, u, ref.sizes(c))

    whole, shared = parts({**cfg, "num_experts": s["experts_routed"]})
    total, program_total = shared, -7.0 * shared
    for rank in range(8):
        c = {**cfg, "deployment": {**cfg["deployment"], "rank": rank}}
        assert ref.sizes(c)["first_expert"] == 4 * rank
        routed, also_shared = parts(c)
        np.testing.assert_array_equal(np.asarray(also_shared), np.asarray(shared))
        total = total + routed
        layer = fam.program.build_conf(c).conf(2)
        assert (layer.layer_spec.n_held, layer.layer_spec.first_held) == (4, 4 * rank)
        params = fam.program.to_program([ref.layer_weights(c, key, 2, "moe")])[0]
        out, _ = MoELayer.branch(params, layer, u)
        program_total = program_total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole + shared), atol=TIGHT)
    np.testing.assert_allclose(np.asarray(program_total), np.asarray(whole + shared),
                               atol=TIGHT)


def _faulted(fault, monkeypatch, conf):
    """The conf and patches under which the program computes with `fault`:
    the delta rule's state rounded to bfloat16 after every token and chunk,
    the output norm without silu(z), attention without its output gate, or
    the shared expert without its gate."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import gdn, gqa
    from deeplearning4j_tpu.nn.layers.rms import F32, mm, rms_norm

    if fault == "bf16_state":
        def rounded(f):
            def g(*a, **k):
                o, s = f(*a, **k)
                return o, s.astype(jnp.bfloat16).astype(jnp.float32)
            return g
        monkeypatch.setattr(gdn, "delta_step", rounded(gdn.delta_step))
        monkeypatch.setattr(gdn, "chunked_delta_rule", rounded(gdn.chunked_delta_rule))
    elif fault == "no_silu_z":
        def ungated(params, conf, x, o, z):
            o = rms_norm(o, params["o_norm"], conf.layer_spec.eps)
            return x.astype(F32) + mm(o.reshape(o.shape[:-2] + (-1,)), params["Wo"], F32)
        monkeypatch.setattr(gdn.GDNLayer, "_output", staticmethod(ungated))
    elif fault == "no_out_gate":
        monkeypatch.setattr(gqa.GQALayer, "_gated", staticmethod(lambda o, gate: o))
    elif fault == "no_shared_gate":
        import dataclasses

        from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

        return MultiLayerConfiguration(confs=tuple(
            c.replace(layer_spec=dataclasses.replace(c.layer_spec, shared_gate=False))
            if str(c.layer_type) == "moe" else c for c in conf.confs))
    return conf


@pytest.fixture(scope="module")
def logits_wanted(weights):
    """The reference's log-probabilities at positions 18 to 30 of a row of
    32 ids, in float32 and under the int8 control."""
    import jax

    cfg = cell(True).cfg
    fam = families.of(cfg)
    ids = np.random.default_rng(3).integers(0, fam.reference.sizes(cfg)["vocab"], 32)
    ids = ids.astype(np.int32)
    logits = fam.reference.teacher_forced_logits(cfg, PINS["seed"], ids[None], ("f32", "int8"))
    return ids, {p: np.asarray(jax.nn.log_softmax(v, axis=-1))[0, 18:-1]
                 for p, v in logits.items()}


@pytest.mark.parametrize("fault", [None, "bf16_state", "no_silu_z", "no_out_gate",
                                   "no_shared_gate"])
def test_a_prefill_then_decode_is_the_references_logits(weights, logits_wanted,
                                                        monkeypatch, fault):
    """The program in float32 at the rehearsal's sizes (three delta-rule
    layers and a gated full layer), on the seed's weights as bfloat16 holds
    them: the log-probabilities of a prefill of 19 tokens in a bucket of 32
    (chunks of 8), then 12 tokens decoded through the slot table, are the
    reference's teacher-forced ones to `TIGHT`, which the reference's own
    int8 control misses by a hundred times.  A delta-rule state kept in
    bfloat16, or a gate left out (silu(z), attention's output gate, the
    shared expert's), misses by ten times at least.  (Through
    `ContinuousBatcher`, on the served tokens' logits: the whole rehearsal
    below.)"""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import decode
    from deeplearning4j_tpu.nn.layers import kda

    monkeypatch.setattr(kda, "CHUNK", 8)
    cfg = cell(True).cfg
    fam = families.of(cfg)
    conf = _faulted(fault, monkeypatch, fam.program.build_conf(
        dict(cfg, flags={"param_dtype": "float32", "compute_dtype": "float32"})))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    fam.program.to_program(weights))
    ids, want = logits_wanted
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
    miss = np.max(np.abs(served - want["f32"]))
    if fault is None:
        assert miss < TIGHT
        assert np.max(np.abs(served - want["int8"])) > 100 * TIGHT
        # what a step reads of a row: three carried states (4 heads of 16 x
        # 16 float32, 3 inputs of 128 channels) and a table of 48 cells of 2
        # K/V heads of 16, K and V, in float32
        assert decode.state_bytes_read(conf, 48) == {
            "table": 48 * 256, "carry": 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)}
    else:
        assert miss > 10 * TIGHT, fault


def test_the_carry_read_reads_the_decode_spans_carry_counts():
    """`decode.carry_read_mb_p50` over the window's `decode` spans: the
    median of their `carry_bytes_read` a step (a span that completed two
    steps carries their sum); a program whose spans carry no such count
    (one from before the count, or a stack with no carried state) leaves
    the metric out.  The metric is listed for the three cells whose stacks
    carry a state."""
    read = reader("decode.carry_read_mb_p50")
    spans = [Span("admit", 0, 10, None, 1, 7, {"queue_wait_ns": 0}, 1),
             Span("decode", 11, 19, None, None, 7, {"k": 1, "live": 1}, 2),
             Span("admit", 26, 30, None, 2, 7, {"queue_wait_ns": 0}, 3)]
    seen = {"counters": {"requests": 2}, "spans": spans}
    assert read(seen) is None
    decode = spans[1]
    seen["spans"] = [spans[0],
                     decode._replace(attrs={"carry_bytes_read": 3_000_000, "steps": 1}),
                     decode._replace(sid=4, start_ns=19, end_ns=21,
                                     attrs={"carry_bytes_read": 8_000_000, "steps": 2}),
                     decode._replace(sid=5, start_ns=21, end_ns=25,
                                     attrs={"carry_bytes_read": 5_000_000, "steps": 1}),
                     spans[2]]
    assert read(seen) == pytest.approx(4.0)
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        (m,) = [m for m in json.load(f)["per_layer"] if m["name"] == "decode.carry_read_mb_p50"]
    assert (m["layer"], m["moves"], m["source"], m["workloads"]) == (
        "Cache", "gap_p50_ms", "program_span",
        [CELL, "serve-ling3-decode-64", "serve-lfm2-rag-64"])


# The cell's limit is read at the published widths on the chip.  At the
# rehearsal's widths a sound run reads 0 (no token off the reference's first
# in some 200 positions; CPU, seeds 1 and 2147483693): the rehearsal is held
# to 5e-5, and the int8 control misses the logits by a hundred times the
# float32 tolerance (`test_a_prefill_then_decode_is_the_references_logits`).
REHEARSAL_LIMITS = {"served_gap_mean": 5e-5, "short_streams": 0, "lost_requests": 0}


@pytest.fixture
def rehearsal_limits(monkeypatch):
    from benchmark import correct

    monkeypatch.setattr(correct, "load_limits", lambda workload: REHEARSAL_LIMITS)


def test_a_whole_rehearsal_is_correct_and_reads_its_metrics(capsys, tmp_path,
                                                            rehearsal_limits):
    from deeplearning4j_tpu.utils import profiling

    profiling.clear()
    dump = str(tmp_path / "dump.json")
    assert run.main(["--workload", CELL, "--seed", str(PINS["seed"]), "--seconds",
                     "2", "--trace", "1", "--rehearse", "--dump", dump]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    with open(dump) as f:
        dumped = json.load(f)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["served_gap_mean"]["limit"] == 5e-5
    assert line["compiles_in_window"] == 0
    c = cell(True)
    layers = {m["name"] for m in run.metrics_of(c.bench, "per_layer", CELL)}
    ends = {m["name"] for m in run.metrics_of(c.bench, "end_to_end", CELL)}
    assert {"gap_p50_ms", "serve_tokens_per_s", "setup_s"} <= ends
    assert set(line["metrics"]) <= layers
    # in bfloat16, 4 slots x three delta-rule layers (4 heads of 16 x 16
    # float32, 3 carried inputs of 128 channels)
    carry = 4 * 3 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    assert line["metrics"]["decode.carry_read_mb_p50"]["value"] == pytest.approx(carry / 1e6)
    # and the full layer's table of 64 cells, 2 K/V heads of 16, K and V
    assert line["metrics"]["decode.state_read_mb_p50"]["value"] == pytest.approx(
        (carry + 4 * 64 * 2 * 2 * 16 * 2) / 1e6)
    assert 0 < reader("moe.experts_hit_share")({"counters": dumped["counters"],
                                                "cfg": c.cfg}) <= 100
