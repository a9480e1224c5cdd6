"""Model FLOPs and bytes from shapes: what the algorithm needs, not what a
compiled program happens to do.

A matrix product of a token with an n-parameter matrix is 2n FLOPs.  Each
count takes the job's `counters` whole: of the traced decode steps
`traced_live_rows` (the mean number of live rows a step) and
`traced_live_row_positions` (every sample's rows, flat: `len /
traced_live_rows` samples).  A row at p tokens (its prompt and what it has
been served) has its newest token at position p - 1, so the step needs p
cells of a full layer's table and min(p, window) of a window layer's ring.
The routed experts a step reads are those the program counted hit in the
window (`experts_hit`); a row's picks land here in the held share of the
routed experts.
"""

from __future__ import annotations

from benchmark import program_spans
from benchmark.families.cohere2_moe import reference
from benchmark.flops import _ITEM_BYTES


def _layers(cfg: dict) -> dict:
    kinds = reference.layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("window", "full")}


def live_cells(cfg: dict, counters: dict) -> dict:
    """K/V cells a decode step needs, summed over the live rows, in the mean
    over the traced samples: of the full layers' tables and of the window
    layers' rings, each summed over its layers."""
    positions = counters["traced_live_row_positions"]
    if not positions:
        return {"full": 0.0, "window": 0.0}
    samples = len(positions) / counters["traced_live_rows"]
    w, n = reference.sizes(cfg)["window"], _layers(cfg)
    return {"full": n["full"] * sum(positions) / samples,
            "window": n["window"] * sum(min(p, w) for p in positions) / samples}


def cell_bytes(cfg: dict) -> int:
    """K and V of every K/V head of one position, in the cache's type: 4,096
    bytes as published."""
    s = reference.sizes(cfg)
    return 2 * s["kv_heads"] * s["head_dim"] * _ITEM_BYTES[cfg["flags"]["compute_dtype"]]


def experts_hit(cfg: dict, counters: dict) -> float:
    """Distinct held experts a layer's step reads, in the mean over the
    window's `decode` spans as the program counted them (`experts_hit`,
    summed over the layers and the span's `steps`).  Where no span counts
    them, under uniform routing at the traced live rows: a held expert is
    missed by all rows with probability (1 - top_k / routed) ** rows, 12.7 %
    at 32 rows of 8 picks over 128."""
    spans = [s for s in program_spans.named({"counters": counters}, "decode") or ()
             if "experts_hit" in s.attrs]
    steps = sum(s.attrs["steps"] for s in spans)
    layers = sum(_layers(cfg).values())
    if steps:
        return sum(s.attrs["experts_hit"] for s in spans) / (layers * steps)
    s = reference.sizes(cfg)
    miss = (1.0 - s["top_k"] / s["experts_routed"]) ** counters["traced_live_rows"]
    return s["experts_held"] * (1.0 - miss)


def train_step_flops(cfg: dict, counters: dict) -> float:
    raise NotImplementedError("this configuration serves only")


def decode_step_flops(cfg: dict, counters: dict) -> float:
    """One decode step.  Per live row: every matrix outside the routed
    experts once (attention, the four shared experts, the router) and the
    tied head's table; of each layer's `num_experts_per_tok` picks, the held
    share.  Per live cell: the score and the value's share, 2 * heads *
    head_dim each."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    held = s["experts_held"] / s["experts_routed"]
    per_row = (2.0 * (n["always"] + n["head"])
               + 2.0 * n["expert"] * s["top_k"] * held * n["expert_layers"])
    per_cell = 4.0 * s["heads"] * s["head_dim"]
    return (per_row * counters["traced_live_rows"]
            + per_cell * sum(live_cells(cfg, counters).values()))


def decode_step_bytes(cfg: dict, counters: dict) -> float:
    """Bytes one decode step has to move, parameters and state in the types
    the configuration keeps them in: every weight outside the routed experts
    once (the embedding's table whole, which the tied head reads, and a row
    of it again a live row for the embedding); of each layer the routed
    experts that were hit (`experts_hit`); K and V of every needed cell
    (`cell_bytes`)."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    item = _ITEM_BYTES[cfg["flags"]["param_dtype"]]
    rows = counters["traced_live_rows"]
    experts = n["expert"] * s["experts_held"] * n["expert_layers"]
    return (item * (n["all"] - experts + rows * s["d"])
            + item * n["expert"] * experts_hit(cfg, counters) * n["expert_layers"]
            + cell_bytes(cfg) * sum(live_cells(cfg, counters).values()))
