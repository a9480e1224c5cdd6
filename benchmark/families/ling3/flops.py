"""Model FLOPs and bytes from shapes: what the algorithm needs, not what a
compiled program happens to do.

A matrix product of a token with an n-parameter matrix is 2n FLOPs.  Each
count takes the job's `counters` whole: of the traced decode steps
`traced_live_rows` (the mean number of live rows a step) and
`traced_live_positions` (the mean over steps of the sum of their positions).
"""

from __future__ import annotations

from benchmark.families.ling3 import reference
from benchmark.flops import _ITEM_BYTES


def _layers(cfg: dict) -> dict:
    kinds = reference.layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("kda", "mla", "moe")}


def train_step_flops(cfg: dict, counters: dict) -> float:
    raise NotImplementedError("this configuration serves only")


def decode_step_flops(cfg: dict, counters: dict) -> float:
    """One decode step.  Per live row: every matrix outside the routed
    experts once (attention, dense and shared FFN, router, head); of each
    expert layer the row's `num_experts_per_tok` picks times the share of
    the published experts that is held here (a pick lands here that often
    under uniform routing; what lands elsewhere is not this chip's work);
    the KDA state's update and read, 6 FLOPs an element a layer (decay,
    k^T S, the rank-1 correction, S^T q).  Per live position and MLA layer,
    the absorbed scores and the latents' sum: 2 * heads * (rank + rope) and
    2 * heads * rank."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    layers = _layers(cfg)
    held_share = s["experts_held"] / s["experts_routed"]
    per_row = (2.0 * n["always"]
               + 2.0 * n["expert"] * s["top_k"] * held_share * layers["moe"]
               + 6.0 * s["heads"] * s["kda_dim"] ** 2 * layers["kda"])
    per_position = 2.0 * s["heads"] * (2 * s["kv_rank"] + s["rope"]) * layers["mla"]
    return (per_row * counters["traced_live_rows"]
            + per_position * counters["traced_live_positions"])


def experts_hit(cfg: dict, rows: float) -> float:
    """Distinct held experts a layer's step reads at `rows` live rows, under
    uniform routing: a row's picks land on a given held expert with
    probability top_k / routed (1/64 as published), so a held expert is
    missed by all rows with probability (1 - top_k / routed) ** rows: 81 of
    128 at 64 rows.  `moe.experts_hit_share` reads what the program counted."""
    s = reference.sizes(cfg)
    return s["experts_held"] * (1.0 - (1.0 - s["top_k"] / s["experts_routed"]) ** rows)


def decode_step_bytes(cfg: dict, counters: dict) -> float:
    """Bytes one decode step has to move, parameters and caches in the
    types the configuration keeps them in: every weight outside the routed
    experts once; of each expert layer the experts that are hit
    (`experts_hit`, uniform routing); each live row's KDA states read and
    written (float32, with the convolution's window); and the latent and
    rotary key of every live position of each MLA layer."""
    s, n = reference.sizes(cfg), reference.count_params(cfg)
    layers = _layers(cfg)
    item = _ITEM_BYTES[cfg["flags"]["param_dtype"]]
    cache = _ITEM_BYTES[cfg["flags"]["compute_dtype"]]
    rows = counters["traced_live_rows"]
    held = n["expert"] * s["experts_held"] * layers["moe"]
    kda_row = (4.0 * s["heads"] * s["kda_dim"] ** 2
               + cache * (s["conv"] - 1) * 3 * s["heads"] * s["kda_dim"])
    return (item * (n["all"] - held)
            + item * n["expert"] * experts_hit(cfg, rows) * layers["moe"]
            + 2.0 * kda_row * layers["kda"] * rows
            + cache * (s["kv_rank"] + s["rope"]) * layers["mla"]
            * counters["traced_live_positions"])
