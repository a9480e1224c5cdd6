"""Model FLOPs and bytes from shapes: what the algorithm needs, not what a
compiled program happens to do.

A matrix product of a token with an n-parameter matrix is 2n FLOPs.  Each
count takes the job's `counters` whole: of the traced decode steps
`traced_live_rows` (the mean number of live rows a step) and
`traced_live_row_positions` (every sample's rows, flat: `len /
traced_live_rows` samples).  A row at p tokens (its prompt and what it has
been served) has its newest token at position p - 1, so the step reads p
cells of a full layer's table and min(p, window) of a window layer's ring.
"""

from __future__ import annotations

from benchmark.families.mellum import reference
from benchmark.flops import _ITEM_BYTES


def _layers(cfg: dict) -> dict:
    kinds = reference.layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("window", "full", "moe")}


def live_cells(cfg: dict, counters: dict) -> float:
    """K/V cells a decode step has to read, summed over the live rows and
    the attention layers, in the mean over the traced samples."""
    s, layers = reference.sizes(cfg), _layers(cfg)
    positions = counters["traced_live_row_positions"]
    if not positions:
        return 0.0
    samples = len(positions) / counters["traced_live_rows"]
    return sum(layers["full"] * p + layers["window"] * min(p, s["window"])
               for p in positions) / samples


def train_step_flops(cfg: dict, counters: dict) -> float:
    raise NotImplementedError("this configuration serves only")


def decode_step_flops(cfg: dict, counters: dict) -> float:
    """One decode step.  Per live row: every matrix outside the experts once
    (attention, router, head) and of each expert layer the row's
    `num_experts_per_tok` picks, all of which land here (every expert is
    held).  Per live cell: the score and the value's share, 2 * heads *
    head_dim each."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    per_row = (2.0 * n["always"]
               + 2.0 * n["expert"] * s["top_k"] * n["expert_layers"])
    per_cell = 4.0 * s["heads"] * s["head_dim"]
    return (per_row * counters["traced_live_rows"]
            + per_cell * live_cells(cfg, counters))


def experts_hit(cfg: dict, rows: float) -> float:
    """Distinct experts a layer's step reads at `rows` live rows, under
    uniform routing: an expert is missed by all rows with probability (1 -
    top_k / experts) ** rows, 0.02 % at 64 rows of 8 picks over 64.
    `moe.experts_hit_share` reads what the program counted."""
    s = reference.sizes(cfg)
    return s["experts_held"] * (1.0 - (1.0 - s["top_k"] / s["experts_held"]) ** rows)


def decode_step_bytes(cfg: dict, counters: dict) -> float:
    """Bytes one decode step has to move, parameters and cache in the types
    the configuration keeps them in: every weight outside the experts and
    the embedding once (of the table a step gathers a row a live row); of
    each expert layer the experts that are hit (`experts_hit`, uniform
    routing); K and V of every live cell, over the `num_key_value_heads`."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    item = _ITEM_BYTES[cfg["flags"]["param_dtype"]]
    cache = _ITEM_BYTES[cfg["flags"]["compute_dtype"]]
    rows = counters["traced_live_rows"]
    experts = n["expert"] * s["experts_held"] * n["expert_layers"]
    table = s["vocab"] * s["d"]
    return (item * (n["all"] - experts - table + rows * s["d"])
            + item * n["expert"] * experts_hit(cfg, rows) * n["expert_layers"]
            + 2.0 * cache * s["kv_heads"] * s["head_dim"] * live_cells(cfg, counters))
