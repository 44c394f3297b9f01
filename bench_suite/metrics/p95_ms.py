"""95th-percentile latency of every request of the window, in ms, timed
as ``p50_ms`` is. A failed request's +inf latency stays +inf."""

import numpy as np


def read(run):
    lat = [r.latency for r in run.window.requests]
    if not lat:
        return None
    method = "linear" if np.all(np.isfinite(lat)) else "higher"
    return float(np.percentile(lat, 95, method=method)) * 1e3
