"""Model FLOPs and bytes from shapes: what the algorithm needs, not what a
compiled program happens to do (no recomputation, no padding).

A matrix product of a token with an n-parameter matrix is 2n FLOPs forward
and 4n backward.  Causal attention over a context of c positions is, per
layer, 2 * c * d for the scores and the same for the values.

Each count takes the job's `counters` whole and reads what it needs: the
training job's `rows` and `seq`, and of the traced decode steps the mean of
the live rows and of the sum of their positions (every layer attends over
all of a row's positions, so the means are enough here).
"""

from __future__ import annotations

from benchmark.families.gpt2 import reference
from benchmark.flops import _ITEM_BYTES


def train_step_flops(cfg: dict, counters: dict) -> float:
    """Forward and backward of `rows` full rows of `seq` tokens: 6 FLOPs a
    matmul parameter a token, and full (not causal-halved) S x S attention as
    the published counts have it: 12 * layers * tokens * seq * d."""
    s = reference.sizes(cfg)
    seq = counters["seq"]
    tokens = counters["rows"] * seq
    return (6.0 * reference.count_params(cfg)["matmul"] * tokens
            + 12.0 * s["layers"] * tokens * seq * s["d"])


def decode_step_flops(cfg: dict, counters: dict) -> float:
    """One decode step: each live row passes every matmul parameter once, and
    attends over its own live positions (`traced_live_positions` is their sum
    over the rows)."""
    s = reference.sizes(cfg)
    return (2.0 * reference.count_params(cfg)["matmul"] * counters["traced_live_rows"]
            + 4.0 * s["layers"] * counters["traced_live_positions"] * s["d"])


def decode_step_bytes(cfg: dict, counters: dict) -> float:
    """Bytes one decode step has to read: every weight once and K and V of
    the live positions, both in the type the configuration computes in."""
    s = reference.sizes(cfg)
    item = _ITEM_BYTES[cfg["flags"]["compute_dtype"]]
    return item * (reference.count_params(cfg)["all"]
                   + 2.0 * s["layers"] * counters["traced_live_positions"] * s["d"])
