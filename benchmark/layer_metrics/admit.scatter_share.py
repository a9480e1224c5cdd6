"""`admit.scatter` over `admit`, counts times medians: the share of a typical
admission that writes the prefilled row into the slot table."""
from benchmark import program_spans


def read(seen):
    return program_spans.share(program_spans.named(seen, "admit.scatter"),
                               program_spans.named(seen, "admit"))
