"""Median latency of the window's requests, due time to answer."""

import numpy as np


def read(ctx):
    lat = ctx.window.latencies()
    return float(np.percentile(lat, 50)) * 1e3 if lat.size else None
