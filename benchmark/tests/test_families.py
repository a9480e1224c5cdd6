"""A family comes in by files alone.  A toy family (GPT-2's arithmetic under
other key names) and a configuration that names it are written into a
temporary directory, which is put on `benchmark.families.__path__`; whole
rehearsals of both jobs then run on it, are judged by `correct.py` against
its own reference, and are read by the three whole-step readers through its
own `flops.py`, with no file of `benchmark/` edited.  Beside it, what the
move to `families/gpt2` may not change, pinned at its parent: the parameter,
FLOP and byte counts of both configurations and every leaf of the weights."""

import json
import os
import re
import sys
import textwrap

import numpy as np
import pytest

from benchmark import families, flops, run
from benchmark.tests.test_program_spans import reader

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "tests", "gpt2_pins.json")) as f:
    PINS = json.load(f)

TOY_CONFIG = {
    "model_type": "toy", "width": 64, "heads": 2, "inner": 256, "depth": 2,
    "context": 128, "vocab": 512, "window": 8,
    "flags": {"compute_dtype": "bfloat16", "sparse_labels": True,
              "fused_updater": True, "attention_block_skip": True,
              "attention_fused_bwd": True, "attention_impl": "auto"},
    "rehearse": {}}

TOY = {
    "__init__.py": "",
    "reference.py": """
        from benchmark.families.gpt2 import reference as gpt2


        def as_gpt2(cfg):
            return {"n_embd": cfg["width"], "n_head": cfg["heads"],
                    "n_inner": cfg["inner"], "n_layer": cfg["depth"],
                    "n_positions": cfg["context"], "vocab_size": cfg["vocab"],
                    "flags": cfg["flags"]}


        def _under_its_own_keys(f):
            return lambda cfg, *args, **kw: f(gpt2.Frozen(as_gpt2(cfg)), *args, **kw)


        for _name in ("sizes", "layer_kinds", "leaf_names", "model_weights",
                      "count_params", "teacher_forced_logits", "first_steps"):
            globals()[_name] = _under_its_own_keys(getattr(gpt2, _name))
        """,
    "program.py": """
        from benchmark.families.gpt2 import program as gpt2
        from benchmark.families.toy.reference import as_gpt2

        HEAD_BIAS = 1.0     # the fault of the test: the head's bias, scaled
        from_program = gpt2.from_program


        def build_conf(cfg):
            return gpt2.build_conf(as_gpt2(cfg))


        def to_program(weights):
            *body, head = gpt2.to_program(weights)
            return (*body, dict(head, b=HEAD_BIAS * head["b"]))
        """,
    "flops.py": """
        from benchmark.families.gpt2 import flops as gpt2
        from benchmark.families.toy.reference import as_gpt2


        def train_step_flops(cfg, counters):
            return gpt2.train_step_flops(as_gpt2(cfg), counters)


        def _windowed(cfg, counters):
            '''Each row attends over its last `window` positions: from the
            job's flat list, the sum over a step's rows in the mean.'''
            flat = counters["traced_live_row_positions"]
            steps = len(flat) / counters["traced_live_rows"]
            return dict(counters, traced_live_positions=sum(
                min(p, cfg["window"]) for p in flat) / steps)


        def decode_step_flops(cfg, counters):
            return gpt2.decode_step_flops(as_gpt2(cfg), _windowed(cfg, counters))


        def decode_step_bytes(cfg, counters):
            return gpt2.decode_step_bytes(as_gpt2(cfg), _windowed(cfg, counters))
        """,
}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """The toy family on the families' path, and a `BENCHMARK.json` beside it
    whose two configurations are the toy's: cells, traffic, limits and
    readers stay the repo's own."""
    for name, source in TOY.items():
        path = tmp_path / "families" / "toy" / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    (tmp_path / "toy.json").write_text(json.dumps(TOY_CONFIG))
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for config in bench["configs"]:
        config["file"] = str(tmp_path / "toy.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(run.ROOT, ".jax_cache"))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    families.__path__.append(str(tmp_path / "families"))
    before = sorted(os.listdir(os.path.join(HERE, "families")))
    try:
        yield tmp_path
    finally:
        families.__path__.remove(str(tmp_path / "families"))
        for name in [m for m in sys.modules if m.startswith("benchmark.families.toy")]:
            del sys.modules[name]
    assert sorted(os.listdir(os.path.join(HERE, "families"))) == before


def rehearsal(capsys, tmp_path, workload):
    """A whole traced rehearsal: its result line, and what it dumped."""
    dump = str(tmp_path / "dump.json")
    assert run.main(["--workload", workload, "--seed", "2147483659", "--seconds",
                     "2", "--trace", "1", "--rehearse", "--dump", dump]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    with open(dump) as f:
        return line, json.load(f)


def seen_on_a_chip(dumped, programs):
    """What a reader is handed, had the run been on a v5e: the job's own
    counters, and a trace in which each named program ran as often as the
    counters say, 10 ms a run."""
    return {"counters": dumped["counters"], "cfg": TOY_CONFIG, "chips": 1,
            "peaks": flops.peaks("TPU v5 lite"),
            "trace": {"modules": {name: [0.01] * runs
                                  for name, runs in programs.items()}}}


def test_the_toy_family_trains_and_is_read_through_its_own_flops(toy, capsys):
    line, dumped = rehearsal(capsys, toy, "train-590m-2k")
    assert line["correct"] is True and line["failed"] == 0
    c = dumped["counters"]
    seen = seen_on_a_chip(dumped, {"jit_dl4j_train_step": c["traced_steps"]})
    need = families.of(TOY_CONFIG).flops.train_step_flops(TOY_CONFIG, c)
    assert need > 0
    assert reader("train.step_mfu")(seen) == pytest.approx(
        100.0 * need / 0.01 / seen["peaks"]["bf16_flops_per_s"])


def test_the_toy_family_serves_and_is_read_through_its_own_flops(toy, capsys):
    line, dumped = rehearsal(capsys, toy, "serve-1b3-decode")
    assert line["correct"] is True and line["failed"] == 0
    c = dumped["counters"]
    flat = c["traced_live_row_positions"]
    assert len(flat) > 0 and max(flat) > TOY_CONFIG["window"]
    # the flat list is the two means' own samples, row by row
    steps = len(flat) / c["traced_live_rows"]
    assert steps == pytest.approx(round(steps))
    assert sum(flat) / steps == pytest.approx(c["traced_live_positions"])
    seen = seen_on_a_chip(dumped, {
        "jit_dl4j_decode": c["traced_calls"] - c["traced_admitted"],
        "jit_dl4j_prefill_slot": c["traced_admitted"]})
    toy_flops = families.of(TOY_CONFIG).flops
    peaks = seen["peaks"]
    assert reader("decode.step_mfu")(seen) == pytest.approx(
        100.0 * toy_flops.decode_step_flops(TOY_CONFIG, c) / 0.01
        / peaks["bf16_flops_per_s"])
    assert reader("decode.step_roofline")(seen) == pytest.approx(
        100.0 * toy_flops.decode_step_bytes(TOY_CONFIG, c) / 0.01
        / peaks["hbm_bytes_per_s"])
    # and its window shows: fewer positions than GPT-2 would count
    everywhere = dict(TOY_CONFIG, window=TOY_CONFIG["context"])
    assert (toy_flops.decode_step_flops(TOY_CONFIG, c)
            < toy_flops.decode_step_flops(everywhere, c))


@pytest.mark.parametrize("workload, number", [
    ("train-590m-2k", "change_gap"), ("serve-1b3-decode", "served_gap_mean")])
def test_an_adapter_that_scales_one_leaf_is_not_correct(toy, capsys, monkeypatch,
                                                        workload, number):
    monkeypatch.setattr(families.of(TOY_CONFIG).program, "HEAD_BIAS", -1.0)
    line, _ = rehearsal(capsys, toy, workload)
    assert line["correct"] is False
    assert line["compared"][number]["value"] > line["compared"][number]["limit"]


def test_a_model_type_with_no_family_ends_the_run():
    with pytest.raises(SystemExit, match=r"'mamba'.*\['gpt2'\]"):
        families.of({"model_type": "mamba"})


def test_no_file_outside_a_family_names_one():
    """ISSUE 27's grep: GPT-2's keys and leaves appear under `families/gpt2`,
    in the configuration files and in tests, and nowhere else."""
    names = re.compile(r"char_transformer|n_embd|n_head|n_inner|wte|Wqkv|gpt2")
    found = []
    for where, _, files in os.walk(HERE):
        rel = os.path.relpath(where, HERE)
        if rel.split(os.sep)[0] in ("tests", "configs", "testdata", "__pycache__") \
                or rel.startswith(os.path.join("families", "gpt2")):
            continue
        for name in files:
            if name.endswith((".py", ".md", ".json")):
                with open(os.path.join(where, name)) as f:
                    found += [f"{rel}/{name}: {line.strip()}" for line in f
                              if names.search(line)]
    assert found == []


# ------------------------------------------------ pinned at the parent

CONFIGS = {"cerebras-gpt-590m": "train-590m-2k",
           "cerebras-gpt-1.3b": "serve-1b3-decode"}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_count_params_is_the_parents(config):
    cfg = run.load_cell(CONFIGS[config], rehearse=False).cfg
    assert (families.of(cfg).reference.count_params(cfg)
            == PINS["configs"][config]["count_params"])


@pytest.mark.parametrize("count", ["train_step_flops", "decode_step_flops",
                                   "decode_step_bytes"])
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_flops_and_bytes_are_the_parents(config, count):
    cfg = run.load_cell(CONFIGS[config], rehearse=False).cfg
    got = getattr(families.of(cfg).flops, count)(cfg, PINS["counters"])
    assert got == PINS["configs"][config][count]      # equal exactly


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_every_leaf_of_the_weights_is_the_parents(config):
    import jax

    from benchmark import reference

    cfg = run.load_cell(CONFIGS[config], rehearse=True).cfg
    fam = families.of(cfg).reference
    weights = jax.jit(fam.model_weights, static_argnums=0)(
        reference.Frozen(cfg), reference.base_key(PINS["seed"]))
    got = dict(zip(fam.leaf_names(cfg),
                   (float(n) for n in reference.leaf_norms(weights))))
    pinned = PINS["configs"][config]["leaf_norms"]
    assert list(got) == list(pinned)
    np.testing.assert_allclose(list(got.values()), list(pinned.values()), rtol=1e-6)
