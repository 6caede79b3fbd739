"""Median request latency of the open-loop window, from each request's
scheduled arrival to the router's completion stamp (host clock)."""
import numpy as np


def read(ctx):
    lat = ctx["window"].latency_s
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 50)) * 1e3
