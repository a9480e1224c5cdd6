"""Model FLOPs and bytes from shapes: what the algorithm needs, not what a
compiled program happens to do (no recomputation, no padding).

A matrix product of a token with an n-parameter matrix is 2n FLOPs forward
and 4n backward.  Causal attention over a context of c positions is, per
layer, 2 * c * d for the scores and the same for the values.
"""

from __future__ import annotations

import json
import os

from benchmark import reference

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    """Forward and backward of `rows` full rows of `seq` tokens: 6 FLOPs a
    matmul parameter a token, and full (not causal-halved) S x S attention as
    the published counts have it: 12 * layers * tokens * seq * d."""
    s = reference.sizes(cfg)
    tokens = rows * seq
    return (6.0 * reference.count_params(cfg)["matmul"] * tokens
            + 12.0 * s["layers"] * tokens * seq * s["d"])


def decode_step_flops(cfg: dict, live_rows: float, live_positions: float) -> float:
    """One decode step: each live row passes every matmul parameter once, and
    attends over its own live positions (`live_positions` is their sum over
    the rows)."""
    s = reference.sizes(cfg)
    return (2.0 * reference.count_params(cfg)["matmul"] * live_rows
            + 4.0 * s["layers"] * live_positions * s["d"])


_ITEM_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def decode_step_bytes(cfg: dict, live_positions: float) -> float:
    """Bytes one decode step has to read: every weight once and K and V of
    the live positions, both in the type the configuration computes in."""
    s = reference.sizes(cfg)
    item = _ITEM_BYTES[cfg["flags"]["compute_dtype"]]
    return item * (reference.count_params(cfg)["all"]
                   + 2.0 * s["layers"] * live_positions * s["d"])
