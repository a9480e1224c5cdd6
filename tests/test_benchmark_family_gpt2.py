"""The benchmark's own tests of its model families, run from tier 1: a
benchmark PR may add no file outside `benchmark/`, so the cases live there
and `tests/` collects them, so that tier 1 counts every family.  This file
collects `benchmark/tests/test_families.py` (the toy family and GPT-2's pins,
PR 27) but for the toy's faulted rehearsals, which
`test_benchmark_family_toy_faults.py` collects so that no worker holds all
four rehearsals; `test_benchmark_family_ling3*.py` collect the second family,
`test_benchmark_family_mellum*.py` the third and
`test_benchmark_family_dots3*.py` the fourth.

Two cases of `test_families.py` were written when GPT-2 was the only family
and cannot hold beside a second one; a `model_config` PR may not edit them,
so this file defines them anew under their names (a `benchmark` PR can
bring the originals up to date)."""

import os
import re

import pytest

import benchmark_toy    # noqa: F401  (the toy at one block)
from benchmark import families
from benchmark.tests.test_families import *          # noqa: F401,F403

del test_an_adapter_that_scales_one_leaf_is_not_correct     # noqa: F821
BENCHMARK = os.path.dirname(os.path.abspath(families.__path__[0]))


def test_a_model_type_with_no_family_ends_the_run():
    """The original expects the message to list GPT-2 alone."""
    with pytest.raises(SystemExit, match=r"'mamba'.*\['dots3_note', 'gpt2', 'ling3', 'mellum'\]"):
        families.of({"model_type": "mamba"})


def test_no_file_outside_a_family_names_one():
    """ISSUE 27's grep, for four families: GPT-2's keys and leaves appear
    under `families/gpt2`, in the configuration files and in tests, and
    nowhere else; nor do Ling's under anything but `families/ling3`, nor
    Mellum's under anything but `families/mellum`, nor dots3-note-prev's
    under anything but `families/dots3_note`.  The original's pattern takes
    `num_attention_heads`, a published key of the second family, for GPT-2's
    `n_head`; a key is matched whole here.  A key that two families publish
    alike (`kv_lora_rank`, `first_k_dense_replace` and the latent's leaves of
    the second and the fourth, `q_norm` of the third and the fourth) belongs
    to both: it is found everywhere but under either of them."""
    own = [({"gpt2"}, r"char_transformer|\b(n_embd|n_head|n_inner|wte)\b|Wqkv|gpt2"),
           ({"ling3"}, r"layer_group_size|\b(conv_q|A_log)\b|ling3"),
           ({"ling3", "dots3_note"},
            r"kv_lora_rank|first_k_dense_replace|\b(Wkva|Wkvb)\b"),
           ({"mellum"}, r"sliding_window(?!_size)|rope_parameters|"
                        r"mlp_layer_types|\bk_norm\b|mellum"),
           ({"mellum", "dots3_note"}, r"\bq_norm\b"),
           ({"dots3_note"},
            r"index_topk|sliding_window_size|\b(Wiq|Wik|Wiw)\b|dots3")]
    found = []
    for where, _, files in os.walk(BENCHMARK):
        rel = os.path.relpath(where, BENCHMARK)
        top = rel.split(os.sep)
        if top[0] in ("tests", "configs", "testdata", "__pycache__"):
            continue
        inside = top[1] if top[0] == "families" and len(top) > 1 else None
        # the program's own KDA leaf is called Wqkv as its attention's is
        names = re.compile("|".join(
            p if inside is None else p.replace("|Wqkv", "")
            for owners, p in own if inside not in owners))
        for name in files:
            if name.endswith((".py", ".md", ".json")):
                with open(os.path.join(where, name)) as f:
                    found += [f"{rel}/{name}: {line.strip()}" for line in f
                              if names.search(line)]
    assert found == []
