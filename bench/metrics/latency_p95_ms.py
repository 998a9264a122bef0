"""95th percentile latency of the window's requests, due time to answer:
the highest percentile a window of a few hundred requests supports."""

import numpy as np


def read(ctx):
    lat = ctx.window.latencies()
    return float(np.percentile(lat, 95)) * 1e3 if lat.size else None
