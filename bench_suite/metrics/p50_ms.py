"""Median latency of every request of the window, in ms (open loop:
from when it was due; closed loop: from submit; a failed request counts
as +inf, missing every limit)."""

import numpy as np


def read(run):
    lat = [r.latency for r in run.window.requests]
    if not lat:
        return None
    method = "linear" if np.all(np.isfinite(lat)) else "higher"
    return float(np.percentile(lat, 50, method=method)) * 1e3
