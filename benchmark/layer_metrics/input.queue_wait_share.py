"""`prefetch.wait` on the consumer's thread over all its top-level spans, in
the window's `fit` call, each name's count times its median: what `fit`
waits for data in a typical turn of its loop."""
from benchmark import program_spans


def read(seen):
    fit = program_spans.last_fit(seen)
    if not fit:
        return None
    return program_spans.share([s for s in fit if s.name == "prefetch.wait"],
                               program_spans.top_level(fit))
