"""itl_ms_p95: 95th percentile of the gap between consecutive tokens of one
session, over every gap in the window (host clock, at the host's readback
of each step's tokens).  A session's first token has no gap before it."""

import numpy as np


def read(r):
    rec = r.record
    gaps = np.diff(rec["ends"])[rec["positions"][1:] > 0]
    if gaps.size == 0:
        return None
    return float(np.percentile(gaps * 1e3, 95))
