"""Model FLOPs and bytes from shapes: what the algorithm needs, not what a
compiled program happens to do.

A matrix product of a token with an n-parameter matrix is 2n FLOPs.  Each
count takes the job's `counters` whole: of the traced decode steps
`traced_live_rows` (the mean number of live rows a step) and
`traced_live_row_positions` (every sample's rows, flat: `len /
traced_live_rows` samples).  A row at p tokens (its prompt and what it has
been served) has its newest token at position p - 1.  In a full layer the
step scores the p index keys of the row and attends to min(p, index_topk)
latents; in a window layer it attends to min(p, sliding_window_size).
"""

from __future__ import annotations

from benchmark.families.dots3_note import reference
from benchmark.flops import _ITEM_BYTES


def _layers(cfg: dict) -> dict:
    kinds = reference.layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("full", "window", "moe")}


def live_cells(cfg: dict, counters: dict) -> dict:
    """Cells a decode step has to read, summed over the live rows and in
    the mean over the traced samples, a layer of each kind: `index` keys
    and picked `latents` of a full layer, `ring` cells of a window layer."""
    s = reference.sizes(cfg)
    positions = counters["traced_live_row_positions"]
    if not positions:
        return {"index": 0.0, "latents": 0.0, "ring": 0.0}
    samples = len(positions) / counters["traced_live_rows"]
    return {"index": sum(positions) / samples,
            "latents": sum(min(p, s["index_topk"]) for p in positions) / samples,
            "ring": sum(min(p, s["window"]["span"]) for p in positions) / samples}


def train_step_flops(cfg: dict, counters: dict) -> float:
    raise NotImplementedError("this configuration serves only")


def decode_step_flops(cfg: dict, counters: dict) -> float:
    """One decode step.  Per live row: every matrix outside the routed
    experts once (attention with its indexer and gate, dense and shared
    FFN, router, head); of each expert layer the row's
    `num_experts_per_tok` picks times the share of the published experts
    that is held here (a pick lands here that often under uniform routing;
    what lands elsewhere is not this chip's work).  Per index key: the J
    heads' products, 2 J n.  Per attended cell: the absorbed score and the
    latents' sum, 2 H (rank + rope) and 2 H rank, in the layer's geometry."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    layers, cells = _layers(cfg), live_cells(cfg, counters)
    held_share = s["experts_held"] / s["experts_routed"]
    per_row = (2.0 * n["always"]
               + 2.0 * n["expert"] * s["top_k"] * held_share * layers["moe"])

    def per_cell(g: dict) -> float:
        return 2.0 * g["heads"] * (2 * g["kv_rank"] + g["rope"])

    return (per_row * counters["traced_live_rows"]
            + layers["full"] * (2.0 * s["index_heads"] * s["index_dim"] * cells["index"]
                                + per_cell(s["full"]) * cells["latents"])
            + layers["window"] * per_cell(s["window"]) * cells["ring"])


def experts_hit(cfg: dict, rows: float) -> float:
    """Distinct held experts a layer's step reads at `rows` live rows, under
    uniform routing: a row's picks land on a given held expert with
    probability top_k / routed (1/32 as published), so a held expert is
    missed by all rows with probability (1 - top_k / routed) ** rows: 27.8
    of 32 at 64 rows.  `moe.experts_hit_share` reads what the program
    counted."""
    s = reference.sizes(cfg)
    return s["experts_held"] * (1.0 - (1.0 - s["top_k"] / s["experts_routed"]) ** rows)


def decode_step_bytes(cfg: dict, counters: dict) -> float:
    """Bytes one decode step has to move, parameters and caches in the
    types the configuration keeps them in: every weight outside the routed
    experts and the embedding once (of the table a step gathers a row a
    live row); of each expert layer the experts that are hit (`experts_hit`,
    uniform routing); a full layer's index keys of every live position and
    the latents and rotary keys of the picked ones; a window layer's ring
    cells that hold a position of the row."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    layers, cells = _layers(cfg), live_cells(cfg, counters)
    item = _ITEM_BYTES[cfg["flags"]["param_dtype"]]
    cache = _ITEM_BYTES[cfg["flags"]["compute_dtype"]]
    rows = counters["traced_live_rows"]
    held = n["expert"] * s["experts_held"] * layers["moe"]
    table = s["vocab"] * s["d"]

    def cell(g: dict) -> float:
        return cache * (g["kv_rank"] + g["rope"])

    return (item * (n["all"] - held - table + rows * s["d"])
            + item * n["expert"] * experts_hit(cfg, rows) * layers["moe"]
            + layers["full"] * (cache * s["index_dim"] * cells["index"]
                                + cell(s["full"]) * cells["latents"])
            + layers["window"] * cell(s["window"]) * cells["ring"])
