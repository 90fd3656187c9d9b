"""95th percentile of every decision's latency in the window, from its due
time on the open-loop schedule to its result on the host; a decision never
served counts as infinitely late."""

import numpy as np


def read(ctx):
    lat = ctx["stats"].latencies_ms
    if lat is None or lat.size == 0:
        return None
    return float(np.quantile(lat, 0.95, method="inverted_cdf"))
