"""The chip's published peaks, and the bytes of an item of each type.  What
a model's step needs in FLOPs and bytes is its family's, `families/
<model_type>/flops.py`, from shapes and from what the job observed."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


_ITEM_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
