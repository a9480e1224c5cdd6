"""Share of the window's decode steps that were dispatched while the step
before was still in flight, so that the device found its next step queued:
the `decode` spans whose `ahead` reads 1 over those that carry the
attribute (a span that dispatched a step).  A program whose spans carry no
such attribute (a loop that reads every step back before the next) leaves
the metric out."""
from benchmark import program_spans


def read(seen):
    ahead = [s.attrs["ahead"] for s in program_spans.named(seen, "decode") or ()
             if "ahead" in s.attrs]
    if not ahead:
        return None
    return 100.0 * sum(ahead) / len(ahead)
