"""Serving layer: the 95th percentile of the open-loop window's latencies
(each request from when it was due to its result in hand; a failed request
counts as +inf), in ms.  Too noisy at this cell's rate to carry a bound
(PERF.md §2), so it is read here, beside the bounded median."""
import numpy as np


def read(ctx):
    lat = ctx["window"].latency_s
    if lat is None or not len(lat):
        return None
    return float(np.percentile(np.where(np.isfinite(lat), lat, np.inf), 95) * 1e3)
