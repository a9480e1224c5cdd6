"""A model family is a directory here, `families/<model_type>/`, found by the
configuration's own `model_type` key and by nothing else: `reference.py` (the
plain float32 model; it imports neither its `program.py` nor the program),
`program.py` (the family's adapter to the system under test) and `flops.py`
(what a step needs, from shapes and from the job's counters).  README.md has
each module's contract.  A new family is new files; nothing here lists them."""

from __future__ import annotations

import importlib
import pkgutil
import types


def of(cfg: dict) -> types.SimpleNamespace:
    """The three modules of `cfg`'s family; a `model_type` with no directory
    here ends the run with the families that exist."""
    name = cfg.get("model_type")
    there = sorted(m.name for m in pkgutil.iter_modules(__path__) if m.ispkg)
    if name not in there:
        raise SystemExit(f"no family for model_type {name!r} under "
                         f"benchmark/families; there are {there}")
    return types.SimpleNamespace(**{
        part: importlib.import_module(f"{__name__}.{name}.{part}")
        for part in ("reference", "program", "flops")})
