"""Front-door layer: 99th percentile, over the window's requests, of the
time from a request's due time to the ``engine.begin`` call of the
dispatch that carried it (stamped by the benchmark's engine proxy)."""
import numpy as np


def read(rec):
    w = rec["window"].queue_waits_s
    if rec["mode"] != "open" or w is None or w.size == 0:
        return None
    return float(np.percentile(w, 99)) * 1e3
