"""99th percentile of the gap between consecutive tokens of a stream, at the
caller: what a neighbour's admission costs a stream that is decoding.  One
run in six reads it far off, so it is recorded here and bounds nothing."""
import numpy as np


def read(seen):
    v = seen["counters"].get("gaps_ms")
    return float(np.percentile(v, 99)) if v else None
