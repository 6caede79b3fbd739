"""95th percentile of request latency over every request of the
open-loop window, from scheduled arrival to completion (host clock)."""
import numpy as np


def read(ctx):
    lat = ctx["window"].latency_s
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 95)) * 1e3
