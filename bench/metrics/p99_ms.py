"""End to end: the 99th percentile over all requests of an open-loop window,
each timed from its due time to the completion of its future, so a stalled
generator counts (host clock)."""
import numpy as np


def read(rec):
    lat = rec["window"].latencies_s
    if lat is None or lat.size == 0:
        return None
    return float(np.percentile(lat, 99)) * 1e3
