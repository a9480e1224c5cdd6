"""Share of the held experts that a decode step picked, over the window's
`decode` spans: their `experts_hit` (distinct held experts hit, summed over
the expert layers and over the span's `steps`) over held experts x expert
layers x steps.  It is the share of the expert weights a step has to read.
A program whose spans carry no such count gives None."""
from benchmark import families, program_spans


def read(seen):
    spans = [s for s in program_spans.named(seen, "decode") or ()
             if "experts_hit" in s.attrs]
    if not spans:
        return None
    ref = families.of(seen["cfg"]).reference
    held = ref.sizes(seen["cfg"])["experts_held"]
    layers = ref.count_params(seen["cfg"])["expert_layers"]
    return (100.0 * sum(s.attrs["experts_hit"] for s in spans)
            / (held * layers * sum(s.attrs["steps"] for s in spans)))
