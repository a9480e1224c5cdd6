"""`fit.step` over all top-level spans of the consumer's thread, in the
window's `fit` call, each name's count times its median: placing the batch
and dispatching the step, which blocks once the device's queue is full.
High means the device, not the host, sets the pace."""
from benchmark import program_spans


def read(seen):
    fit = program_spans.last_fit(seen)
    if not fit:
        return None
    return program_spans.share([s for s in fit if s.name == "fit.step"],
                               program_spans.top_level(fit))
