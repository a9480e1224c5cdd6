"""Model FLOPs and bytes from shapes: what the algorithm needs, not what a
compiled program happens to do.

A matrix product of a token with an n-parameter matrix is 2n FLOPs.  Each
count takes the job's `counters` whole: of the traced decode steps
`traced_live_rows` (the mean number of live rows a step) and
`traced_live_row_positions` (every sample's rows, flat: `len /
traced_live_rows` samples).  A row at p tokens has its newest token at
position p - 1, so a full layer's step needs p cells of its table.  The
held experts serve the rows of every rank of the expert-parallel group
(`deployment.chips_per_layer`): a held expert is read where any of those
rows picks it.
"""

from __future__ import annotations

from benchmark.families.qwen3_next import reference
from benchmark.flops import _ITEM_BYTES


def _layers(cfg: dict) -> dict:
    kinds = reference.layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("gdn", "attn", "moe")}


def live_cells(cfg: dict, counters: dict) -> float:
    """K/V cells a decode step needs, summed over the live rows and the full
    layers, in the mean over the traced samples."""
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
    """Distinct held experts a layer's step reads, under uniform routing of
    the live rows of every rank: a held expert is missed by all of them with
    probability (1 - top_k / routed) ** (rows x ranks), 4.1e-5 at 64 rows a
    rank over 8 ranks of 10 picks over 512 (so every held expert is read)."""
    s = reference.sizes(cfg)
    rows = counters["traced_live_rows"] * s["ranks"]
    return s["experts_held"] * (1.0 - (1.0 - s["top_k"] / s["experts_routed"]) ** rows)


def state_row_bytes(cfg: dict) -> float:
    """One row's delta-rule state of one layer, read or written once: the
    float32 state of every value head and the convolution's carried inputs."""
    s = reference.sizes(cfg)
    conv = 2 * s["k_heads"] * s["k_dim"] + s["v_heads"] * s["v_dim"]
    return (4.0 * s["v_heads"] * s["k_dim"] * s["v_dim"]
            + _ITEM_BYTES[cfg["flags"]["compute_dtype"]] * (s["conv"] - 1) * conv)


def train_step_flops(cfg: dict, counters: dict) -> float:
    raise NotImplementedError("this configuration serves only")


def decode_step_flops(cfg: dict, counters: dict) -> float:
    """One decode step.  Per live row: every matrix outside the routed
    experts and the embedding once (the mixers, the router, the shared
    expert and its gate, the head); of each expert layer the row's
    `num_experts_per_tok` picks times the share of the published experts
    that is held here (a pick lands here that often under uniform routing;
    what lands elsewhere is not this chip's work); the delta rule's update
    and read, 6 FLOPs an element of the state a layer (decay, k^T S, the
    rank-1 correction, S^T q).  Per live cell of a full layer: the score and
    the value's share, 2 * heads * head_dim each."""
    s, n, layers = reference.sizes(cfg), reference.count_params(cfg), _layers(cfg)
    held_share = s["experts_held"] / s["experts_routed"]
    per_row = (2.0 * (n["always"] + n["head"])
               + 2.0 * n["expert"] * s["top_k"] * held_share * layers["moe"]
               + 6.0 * s["v_heads"] * s["k_dim"] * s["v_dim"] * layers["gdn"])
    return (per_row * counters["traced_live_rows"]
            + 4.0 * s["heads"] * s["head_dim"] * live_cells(cfg, counters))


def decode_step_bytes(cfg: dict, counters: dict) -> float:
    """Bytes one decode step has to move, parameters and state in the types
    the configuration keeps them in: every weight outside the routed experts
    and the embedding once, and a row of the embedding a live row; of each
    expert layer the held experts that are hit (`experts_hit`); each live
    row's delta-rule state read and written once a layer (`state_row_bytes`);
    K and V of every needed cell of the full layers (`cell_bytes`)."""
    s, n, layers = reference.sizes(cfg), reference.count_params(cfg), _layers(cfg)
    item = _ITEM_BYTES[cfg["flags"]["param_dtype"]]
    rows = counters["traced_live_rows"]
    experts = n["expert"] * s["experts_held"] * layers["moe"]
    return (item * (n["all"] - experts - n["embed"] + rows * s["d"])
            + item * n["expert"] * experts_hit(cfg, counters) * layers["moe"]
            + 2.0 * state_row_bytes(cfg) * layers["gdn"] * rows
            + cell_bytes(cfg) * live_cells(cfg, counters))
