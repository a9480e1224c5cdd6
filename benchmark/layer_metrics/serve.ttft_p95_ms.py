"""95th percentile of time to first token over the window's requests, at the
caller.  In a full closed loop it is the tail of admissions that coincide;
it swings from run to run, so it is recorded here and bounds nothing."""
import numpy as np


def read(seen):
    v = seen["counters"].get("ttfts_ms")
    return float(np.percentile(v, 95)) if v else None
