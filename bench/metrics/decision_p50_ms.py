"""Median decision latency, the same latencies as ``decision_p95_ms``."""

import numpy as np


def read(ctx):
    lat = ctx["stats"].latencies_ms
    if lat is None or lat.size == 0:
        return None
    return float(np.quantile(lat, 0.5, method="inverted_cdf"))
