"""The comparison that decides `correct`: each number beside its limit.

The limits of a cell are data, `limits/<workload>.json`, set from readings
on the chip (PERF.md gives them).  A number with no limit in the file is
reported and not held.
"""

from __future__ import annotations

import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(workload: str) -> dict:
    with open(os.path.join(_HERE, "limits", workload + ".json")) as f:
        return json.load(f)["limits"]


def leaf_gaps(program, reference) -> np.ndarray:
    """For every leaf, the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    program, reference = np.asarray(program, float), np.asarray(reference, float)
    return np.abs(program - reference) / np.maximum(reference,
                                                     np.median(reference))


def moving_leaves(reference_grad_norms) -> np.ndarray:
    """Leaves whose gradient is more than rounding in the reference: at
    least a thousandth of the median leaf's.  The others (a key's bias
    under softmax) move under Adam by round-off alone."""
    g = np.asarray(reference_grad_norms, float)
    return g >= 1e-3 * np.median(g)


def train_numbers(program: dict, reference: dict) -> dict:
    """What a training cell compares, from two records of the first steps
    (`losses`, `grad_norms`, `grad_projections`, `change_norms`).
    `grad_error` is the first gradient's error as a share of its norm, the
    root of the mean over the leaves, each read from a few projections and
    against its own norm or the median leaf's: the error of direction,
    which rounding in a lower precision makes and the norms do not show."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"]), 1):
        out[f"loss{i}_gap"] = abs(a - b) / abs(b)
    out["grad_gap"] = float(np.max(leaf_gaps(program["grad_norms"],
                                             reference["grad_norms"])))
    miss = np.asarray(program["grad_projections"]) - np.asarray(
        reference["grad_projections"])              # [leaves, projections]
    norms = np.asarray(reference["grad_norms"], float)
    share = np.mean(miss ** 2, axis=1) / np.maximum(norms, np.median(norms)) ** 2
    out["grad_error"] = float(np.sqrt(np.mean(share)))
    keep = moving_leaves(reference["grad_norms"])
    gaps = leaf_gaps(np.asarray(program["change_norms"])[keep],
                     np.asarray(reference["change_norms"])[keep])
    out["change_gap"] = float(np.max(gaps))
    return out


def judge(numbers: dict, limits: dict) -> list:
    """[(name, value, limit, ok)]; a value that is not a number fails."""
    out = []
    for name, value in numbers.items():
        limit = limits.get(name)
        ok = limit is None or (np.isfinite(value) and value <= limit)
        out.append((name, float(value), limit, bool(ok)))
    for name in limits:
        if name not in numbers:
            out.append((name, float("nan"), limits[name], False))
    return out
