"""Walk layer: the mean budget the law granted per (query, shard) over
the window's batches (``BatchResult.astats.budget``, one column per
shard, as ``bench.backends.sharded`` records it): the budget law's work, which the hops follow."""
import numpy as np

from bench import spec


def read(rec):
    if rec["backend"] != "sharded":
        return None
    budgets = [r["budget"] for r in
               spec.backend_module("sharded").window_results(rec["window"])]
    if not budgets:
        return None
    return float(np.concatenate([np.asarray(b, np.float64).ravel()
                                 for b in budgets]).mean())
