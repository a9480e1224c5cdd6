"""The one generator of traffic.  A mix is a data file of parameters under
`traffic/`; everything drawn comes from the seed.

Every seed gets the same set of sizes and arrivals in another order: a
length or a gap is a quantile of its distribution on a fixed grid of
`cycle` points, and the seed only permutes the grid (and draws the token
ids).  So two seeds give the system the same work.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, rehearse: bool = False) -> dict:
    with open(os.path.join(_HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if rehearse:
        mix = _merge(mix, mix.get("rehearse", {}))
    return mix


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def quantiles(spec: dict, n: int) -> np.ndarray:
    """`n` quantiles of `spec`'s distribution at (i + 1/2) / n."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, float(spec["value"]))
    if dist == "uniform":
        return spec["low"] + u * (spec["high"] - spec["low"])
    if dist == "log_uniform":
        lo, hi = math.log(spec["low"]), math.log(spec["high"])
        return np.exp(lo + u * (hi - lo))
    if dist == "exponential":
        return -np.log1p(-u) * spec["mean"]
    if dist == "log_normal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(x, spec.get("low", 0), spec.get("high", np.inf))
    raise ValueError(f"no distribution {dist!r}")


def token_batches(mix: dict, vocab: int, seed: int):
    """Forever: (x [rows, seq] int32, y [rows * seq] int32 next-token
    labels), every row full and every row different."""
    rng = np.random.default_rng([int(seed), 1])
    rows, seq = int(mix["rows"]), int(mix["seq"])
    while True:
        ids = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
        yield ids[:, :-1].copy(), ids[:, 1:].reshape(rows * seq).copy()


def requests(mix: dict, vocab: int, seed: int):
    """Forever: dicts with `prompt` (int32 ids), `max_new`, and `gap_s`, the
    time after the previous arrival at which an open loop sends this one."""
    rng = np.random.default_rng([int(seed), 2])
    n = int(mix["cycle"])
    prompts = np.rint(quantiles(mix["prompt_tokens"], n)).astype(int)
    outputs = np.rint(quantiles(mix["output_tokens"], n)).astype(int)
    arrival = mix["arrival"]
    gaps = (quantiles({"dist": "exponential",
                       "mean": 1.0 / arrival["rate_per_s"]}, n)
            if arrival["loop"] == "open" else np.zeros(n))
    while True:
        order = [rng.permutation(n) for _ in range(3)]
        for a, b, c in zip(*order):
            yield {"prompt": rng.integers(0, vocab, int(prompts[a]), dtype=np.int32),
                   "max_new": int(outputs[b]), "gap_s": float(gaps[c])}
