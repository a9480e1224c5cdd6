"""95th percentile of the gap between consecutive tokens of a stream, at the
caller.  About one gap in twenty is a neighbour's admission, so this
percentile sits on the edge between a decode step and an admission and
swings between the two; it is recorded here and bounds nothing."""
import numpy as np


def read(seen):
    v = seen["counters"].get("gaps_ms")
    return float(np.percentile(v, 95)) if v else None
