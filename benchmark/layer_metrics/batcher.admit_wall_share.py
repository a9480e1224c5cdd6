"""`admit` over every top-level span of the batcher loop's thread but `idle`,
each name's count times its median: the share of the loop's working time in
which every live stream stalls."""
from benchmark import program_spans


def read(seen):
    loop = program_spans.loop_thread(seen)
    if not loop:
        return None
    return program_spans.share([s for s in loop if s.name == "admit"], loop)
