"""Model FLOPs and bytes from shapes: what the algorithm needs, not what a
compiled program happens to do.

A matrix product of a token with an n-parameter matrix is 2n FLOPs.  Each
count takes the job's `counters` whole: of the traced decode steps
`traced_live_rows` (the mean number of live rows a step) and
`traced_live_row_positions` (every sample's rows, flat: `len /
traced_live_rows` samples).  A row at p tokens (its prompt and what it has
been served) has its newest token at position p - 1, so the step reads p
cells of an attention layer's table and the whole tail of a convolution
layer.  The experts a step reads are those the program counted hit in the
window (`experts_hit`).
"""

from __future__ import annotations

from benchmark import program_spans
from benchmark.families.lfm2_moe import reference
from benchmark.flops import _ITEM_BYTES


def _layers(cfg: dict) -> dict:
    kinds = reference.layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("conv", "attn", "dense", "moe")}


def live_cells(cfg: dict, counters: dict) -> float:
    """K/V cells a decode step has to read, summed over the live rows and
    the attention layers, in the mean over the traced samples."""
    positions = counters["traced_live_row_positions"]
    if not positions:
        return 0.0
    samples = len(positions) / counters["traced_live_rows"]
    return _layers(cfg)["attn"] * sum(positions) / samples


def cell_bytes(cfg: dict) -> int:
    """K and V of every K/V head of one position, in the cache's type: 2,048
    bytes as published."""
    s = reference.sizes(cfg)
    return 2 * s["kv_heads"] * s["head_dim"] * _ITEM_BYTES[cfg["flags"]["compute_dtype"]]


def experts_hit(cfg: dict, counters: dict) -> float:
    """Distinct experts an expert layer's step reads, in the mean over the
    window's `decode` spans as the program counted them (`experts_hit`,
    summed over the expert layers and the span's `steps`).  Where no span
    counts them (a record without the window, or none at all), under
    uniform routing at the traced live rows: an expert is missed by all rows
    with probability (1 - top_k / experts) ** rows, 1.6 % at 64 rows of 4
    picks over 64."""
    spans = [s for s in program_spans.named({"counters": counters}, "decode") or ()
             if "experts_hit" in s.attrs]
    steps = sum(s.attrs["steps"] for s in spans)
    if steps:
        return sum(s.attrs["experts_hit"] for s in spans) / (_layers(cfg)["moe"] * steps)
    s = reference.sizes(cfg)
    miss = (1.0 - s["top_k"] / s["experts_held"]) ** counters["traced_live_rows"]
    return s["experts_held"] * (1.0 - miss)


def train_step_flops(cfg: dict, counters: dict) -> float:
    raise NotImplementedError("this configuration serves only")


def decode_step_flops(cfg: dict, counters: dict) -> float:
    """One decode step.  Per live row: every matrix outside the experts once
    (the mixers, the dense FFNs, the routers, the head), of each expert
    layer the row's `num_experts_per_tok` picks (every expert is held), and
    each convolution layer's taps, 2 K d.  Per live cell: the score and the
    value's share, 2 * heads * head_dim each."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    per_row = (2.0 * n["always"]
               + 2.0 * n["expert"] * s["top_k"] * n["expert_layers"]
               + 2.0 * s["conv_k"] * s["d"] * _layers(cfg)["conv"])
    per_cell = 4.0 * s["heads"] * s["head_dim"]
    return (per_row * counters["traced_live_rows"]
            + per_cell * live_cells(cfg, counters))


def decode_step_bytes(cfg: dict, counters: dict) -> float:
    """Bytes one decode step has to move, parameters and state in the types
    the configuration keeps them in: every weight outside the experts and
    the embedding once (of the table a step gathers a row a live row); of
    each expert layer the experts that were hit (`experts_hit`); K and V of
    every live cell (`cell_bytes`); and every live row's tail of each
    convolution layer, read and written."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    item = _ITEM_BYTES[cfg["flags"]["param_dtype"]]
    cache = _ITEM_BYTES[cfg["flags"]["compute_dtype"]]
    rows = counters["traced_live_rows"]
    experts = n["expert"] * s["experts_held"] * n["expert_layers"]
    table = s["vocab"] * s["d"]
    tails = 2.0 * (s["conv_k"] - 1) * s["d"] * cache * _layers(cfg)["conv"] * rows
    return (item * (n["all"] - experts - table + rows * s["d"])
            + item * n["expert"] * experts_hit(cfg, counters) * n["expert_layers"]
            + cell_bytes(cfg) * live_cells(cfg, counters) + tails)
