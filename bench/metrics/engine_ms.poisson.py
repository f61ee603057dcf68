"""Engine layer: 99th percentile, over the window's dispatches, of the time
from ``engine.begin`` to the return of ``engine.finish_from`` (stamped by
the benchmark's engine proxy)."""
import numpy as np


def read(rec):
    d = [(b, e) for b, e, _ in rec["window"].dispatches if e is not None]
    if rec["mode"] != "open" or not d:
        return None
    return float(np.percentile([e - b for b, e in d], 99)) * 1e3
