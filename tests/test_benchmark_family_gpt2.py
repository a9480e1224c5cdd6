"""The benchmark's own tests of its model families, run from tier 1: a
benchmark PR may add no file outside `benchmark/`, so the cases live there
and `tests/` collects them, so that tier 1 counts every family.  This file
collects `benchmark/tests/test_families.py` (the toy family and GPT-2's pins,
PR 27) but for the toy's faulted rehearsals, which
`test_benchmark_family_toy_faults.py` collects so that no worker holds all
four rehearsals; `test_benchmark_family_ling3*.py` collect the second family,
`test_benchmark_family_mellum*.py` the third,
`test_benchmark_family_dots3*.py` the fourth,
`test_benchmark_family_lfm2*.py` the fifth and
`test_benchmark_family_cohere2*.py` the sixth.

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
    with pytest.raises(SystemExit, match=r"'mamba'.*\['cohere2_moe', 'dots3_note', 'gpt2', "
                                         r"'lfm2_moe', 'ling3', 'mellum', 'qwen3_next'\]"):
        families.of({"model_type": "mamba"})


def test_no_file_outside_a_family_names_one():
    """The family grep, for seven families: GPT-2's keys and leaves appear
    under `families/gpt2`, in the configuration files and in tests, and
    nowhere else; nor do Ling's under anything but `families/ling3`, nor
    Mellum's under anything but `families/mellum`, nor dots3-note-prev's
    under anything but `families/dots3_note`, nor LFM2's under anything but
    `families/lfm2_moe`, nor Command A+'s under anything but
    `families/cohere2_moe`, nor Qwen3-Next's under anything but
    `families/qwen3_next`.  The original's pattern takes
    `num_attention_heads`, a published key of the second family, for GPT-2's
    `n_head`; a key is matched whole here.  A key that several families
    publish alike (`kv_lora_rank` and the latent's leaves of the second and
    the fourth, `first_k_dense_replace` of those and the sixth, `A_log` of
    the second and the seventh, `q_norm` and `k_norm` of the third, the
    fourth or the fifth and the seventh, `rope_parameters` of the third,
    the fifth and the sixth, `sliding_window` of the third, the sixth and
    the seventh) belongs to all of them: it is found everywhere but under
    any of them."""
    own = [({"gpt2"}, r"char_transformer|\b(n_embd|n_head|n_inner|wte)\b|Wqkv|gpt2"),
           ({"ling3"}, r"layer_group_size|\bconv_q\b|ling3"),
           ({"ling3", "qwen3_next"}, r"\bA_log\b"),
           ({"ling3", "dots3_note"}, r"kv_lora_rank|\b(Wkva|Wkvb)\b"),
           ({"ling3", "dots3_note", "cohere2_moe"}, r"first_k_dense_replace"),
           ({"mellum"}, r"mlp_layer_types|mellum"),
           ({"mellum", "cohere2_moe", "qwen3_next"}, r"sliding_window(?!_size)"),
           ({"mellum", "lfm2_moe", "qwen3_next"}, r"\bk_norm\b"),
           ({"mellum", "lfm2_moe", "cohere2_moe"}, r"rope_parameters"),
           ({"mellum", "dots3_note", "lfm2_moe", "qwen3_next"}, r"\bq_norm\b"),
           ({"lfm2_moe"}, r"conv_L_cache|num_dense_layers|use_expert_bias|"
                          r"\b(in_proj|out_proj)\b|lfm2"),
           ({"dots3_note"},
            r"index_topk|sliding_window_size|\b(Wiq|Wik|Wiw)\b|dots3"),
           ({"cohere2_moe"}, r"use_parallel_block|shared_expert_combination_strategy|"
                             r"position_embedding_type|cohere2"),
           ({"qwen3_next"}, r"linear_num_value_heads|full_attention_interval|"
                            r"\bconv1d\b|qwen3")]
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
