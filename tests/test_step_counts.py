"""What one completed decode step read, counted by `nn.decode.StepCounts` from
the rows' positions: every `decode` span attribute and every `stats()` total
of the serving loop, for eight stacks (the tiny GPT-2-style transformer and
LSTM of `test_generate.py`, and the tiny Ling, Mellum, dots3, LFM2,
Command A+ and Qwen3-Next stacks of the family tests) at four sets of row
positions.  The integers are the ones the batcher counted before the counts
moved out of it, but `carry_bytes_read` (the carries' part of
`state_bytes_read`) and the Qwen3-Next stack's, counted by hand.
The descriptors are asked as on the chip (`is_tpu`, blocks of 32 cells), so
that the tables a kernel walks are counted block by block.

Tier-1: CPU-only; nothing is compiled."""

import functools

import numpy as np
import pytest

from generate_helpers import TOTALS
from deeplearning4j_tpu.nn import decode

SLOTS, MAX_SEQ = 6, 128
#: (positions, advances) of the six slots; a free or finished slot advances 0
POSITIONS = {
    "all-at-zero": ([0] * 6, [1] * 6),
    # either side of rings of 4, 5 and 8 cells and of a walk block of 32
    "across-a-window-and-a-block": ([3, 4, 7, 8, 31, 32], [1] * 6),
    "at-the-tables-end": ([126, 127, 128, 1, 2, 3], [1, 1, 0, 1, 1, 1]),
    "free-slots-among-live-rows": ([10, 40, 0, 77, 9, 20], [1, 0, 1, 0, 0, 1]),
}
#: what the expert layers counted in the step: [picks on held experts, hit]
COUNTS = np.asarray([13, 5], np.int32)

E = {"picks_here": 13, "experts_hit": 5, "steps": 1}
#: stack: (the static `stats()` fields, {position set: the span's counts})
PINS = {
    "gpt2": ({"kernel_layers": 0, "state_bytes_per_row": {"table": 32768, "carry": 0}}, {
        "all-at-zero": dict(state_bytes_read=196608, kv_cells_live=12, kv_cells_spanned=1536),
        "across-a-window-and-a-block": dict(
            state_bytes_read=196608, kv_cells_live=182, kv_cells_spanned=1536),
        "at-the-tables-end": dict(state_bytes_read=196608, kv_cells_live=528,
                                  kv_cells_spanned=1536),
        "free-slots-among-live-rows": dict(state_bytes_read=196608, kv_cells_live=66,
                                           kv_cells_spanned=1536),
    }),
    "lstm": ({"kernel_layers": 0, "state_bytes_per_row": {"table": 0, "carry": 256}}, {
        "all-at-zero": dict(state_bytes_read=1536, carry_bytes_read=1536),
        "across-a-window-and-a-block": dict(state_bytes_read=1536, carry_bytes_read=1536),
        "at-the-tables-end": dict(state_bytes_read=1536, carry_bytes_read=1536),
        "free-slots-among-live-rows": dict(state_bytes_read=1536, carry_bytes_read=1536),
    }),
    "ling": ({"kernel_layers": 0, "state_bytes_per_row": {"table": 20480, "carry": 12800},
              "experts_batched_layers": 0}, {
        "all-at-zero": dict(E, state_bytes_read=199680, carry_bytes_read=76800,
                            kv_cells_live=6, kv_cells_spanned=768),
        "across-a-window-and-a-block": dict(E, state_bytes_read=199680, carry_bytes_read=76800,
                                            kv_cells_live=91, kv_cells_spanned=768),
        "at-the-tables-end": dict(E, state_bytes_read=199680, carry_bytes_read=76800,
                                  kv_cells_live=264, kv_cells_spanned=768),
        "free-slots-among-live-rows": dict(E, state_bytes_read=199680, carry_bytes_read=76800,
                                           kv_cells_live=33, kv_cells_spanned=768),
    }),
    "mellum": ({"kernel_layers": 1, "state_bytes_per_row": {"table": 155648, "carry": 0},
                "experts_batched_layers": 0}, {
        "all-at-zero": dict(E, state_bytes_read=344064, kv_cells_live=24, kv_cells_spanned=336,
                            ring_cells_live=18, ring_cells_spanned=144),
        "across-a-window-and-a-block": dict(
            E, state_bytes_read=376832, kv_cells_live=214, kv_cells_spanned=368,
            ring_cells_live=123, ring_cells_spanned=144),
        "at-the-tables-end": dict(E, state_bytes_read=638976, kv_cells_live=339,
                                  kv_cells_spanned=624, ring_cells_live=75,
                                  ring_cells_spanned=144),
        "free-slots-among-live-rows": dict(
            E, state_bytes_read=442368, kv_cells_live=84, kv_cells_spanned=432,
            ring_cells_live=51, ring_cells_spanned=144),
    }),
    "dots3": ({"kernel_layers": 2, "state_bytes_per_row": {"table": 150016, "carry": 0},
               "experts_batched_layers": 0}, {
        "all-at-zero": dict(E, state_bytes_read=310272, kv_cells_live=30, kv_cells_spanned=1950,
                            dsa_cells_live=12, dsa_cells_selected=12, ring_cells_live=6,
                            ring_cells_spanned=30),
        "across-a-window-and-a-block": dict(
            E, state_bytes_read=343040, kv_cells_live=293, kv_cells_spanned=2014,
            dsa_cells_live=182, dsa_cells_selected=82, ring_cells_live=29,
            ring_cells_spanned=30),
        "at-the-tables-end": dict(E, state_bytes_read=605184, kv_cells_live=597,
                                  kv_cells_spanned=2526, dsa_cells_live=528,
                                  dsa_cells_selected=50, ring_cells_live=19,
                                  ring_cells_spanned=30),
        "free-slots-among-live-rows": dict(
            E, state_bytes_read=408576, kv_cells_live=111, kv_cells_spanned=2142,
            dsa_cells_live=66, dsa_cells_selected=34, ring_cells_live=11,
            ring_cells_spanned=30),
    }),
    "lfm2": ({"kernel_layers": 1, "state_bytes_per_row": {"table": 131072, "carry": 10240},
              "experts_batched_layers": 0}, {
        "all-at-zero": dict(E, state_bytes_read=258048, carry_bytes_read=61440,
                            kv_cells_live=6, kv_cells_spanned=192),
        "across-a-window-and-a-block": dict(E, state_bytes_read=290816, carry_bytes_read=61440,
                                            kv_cells_live=91, kv_cells_spanned=224),
        "at-the-tables-end": dict(E, state_bytes_read=552960, carry_bytes_read=61440,
                                  kv_cells_live=264, kv_cells_spanned=480),
        "free-slots-among-live-rows": dict(E, state_bytes_read=356352, carry_bytes_read=61440,
                                           kv_cells_live=33, kv_cells_spanned=288),
    }),
    "commanda": ({"kernel_layers": 0, "state_bytes_per_row": {"table": 38912, "carry": 0},
                  "experts_batched_layers": 4}, {
        "all-at-zero": dict(E, state_bytes_read=233472, kv_cells_live=24, kv_cells_spanned=912,
                            ring_cells_live=18, ring_cells_spanned=144),
        "across-a-window-and-a-block": dict(
            E, state_bytes_read=233472, kv_cells_live=214, kv_cells_spanned=912,
            ring_cells_live=123, ring_cells_spanned=144),
        "at-the-tables-end": dict(E, state_bytes_read=233472, kv_cells_live=339,
                                  kv_cells_spanned=912, ring_cells_live=75,
                                  ring_cells_spanned=144),
        "free-slots-among-live-rows": dict(
            E, state_bytes_read=233472, kv_cells_live=84, kv_cells_spanned=912,
            ring_cells_live=51, ring_cells_spanned=144),
    }),
    # three delta-rule layers (4 value heads of 16 x 16 float32, 3 carried
    # inputs of 128 channels) and a full table of heads of 16, which the
    # kernel does not walk: read whole
    "qwen3next": ({"kernel_layers": 0, "state_bytes_per_row": {"table": 32768, "carry": 16896},
                   "experts_batched_layers": 4}, {
        "all-at-zero": dict(E, state_bytes_read=297984, carry_bytes_read=101376,
                            kv_cells_live=6, kv_cells_spanned=768),
        "across-a-window-and-a-block": dict(E, state_bytes_read=297984, carry_bytes_read=101376,
                                            kv_cells_live=91, kv_cells_spanned=768),
        "at-the-tables-end": dict(E, state_bytes_read=297984, carry_bytes_read=101376,
                                  kv_cells_live=264, kv_cells_spanned=768),
        "free-slots-among-live-rows": dict(E, state_bytes_read=297984, carry_bytes_read=101376,
                                           kv_cells_live=33, kv_cells_spanned=768),
    }),
}


def _family(cell: str):
    from benchmark import families, run

    cfg = run.load_cell(cell, rehearse=True).cfg
    return families.of(cfg).program.build_conf(
        dict(cfg, flags={"param_dtype": "float32", "compute_dtype": "float32"}))


@functools.lru_cache(maxsize=None)
def stack(name: str):
    """The stack's configuration: no weights are made."""
    from deeplearning4j_tpu.models.zoo import char_lstm, char_transformer

    if name == "gpt2":
        return char_transformer(13, d_model=16, n_blocks=2, n_heads=2, max_seq_len=MAX_SEQ)
    if name == "lstm":
        return char_lstm(13, hidden=16, n_layers=2)
    if name == "lfm2":
        return _family("serve-lfm2-rag-64")
    if name == "commanda":
        return _family("serve-commanda-long-32")
    if name == "qwen3next":
        return _family("serve-qwen3next-decode-64")
    import dots3_model
    import ling3_model
    import mellum_model

    return {"ling": lambda: ling3_model.Model("float32"),
            # heads of 64, which the chip's kernel walks
            "mellum": lambda: mellum_model.Model("float32", head_dim=64),
            "dots3": lambda: dots3_model.Model("float32")}[name]().conf


@pytest.fixture
def on_the_chip(monkeypatch):
    from deeplearning4j_tpu.nd import pallas_kernels
    from deeplearning4j_tpu.nn.layers import gqa, mla

    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    monkeypatch.setattr(mla, "is_tpu", lambda: True)
    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 32)


@pytest.mark.parametrize("positions", sorted(POSITIONS))
@pytest.mark.parametrize("name", sorted(PINS))
def test_a_step_counts_what_its_rows_read(on_the_chip, name, positions):
    static, cases = PINS[name]
    counts = decode.StepCounts(stack(name), SLOTS, MAX_SEQ)
    pos, adv = (np.asarray(a, np.int32) for a in POSITIONS[positions])
    got = counts.step(pos, adv, COUNTS if counts.has_experts else None)
    assert got == cases[positions]
    # the totals of `stats()`, under exactly the names the stack has; one
    # step's counts twice are twice its totals
    twice = {n: 2 * v for n, v in got.items()}
    assert counts.totals(twice) == dict(
        {TOTALS[n]: v for n, v in twice.items() if n in TOTALS}, **static)
    # before any step the same names read 0
    assert counts.totals({}) == dict({TOTALS[n]: 0 for n in got if n in TOTALS}, **static)
