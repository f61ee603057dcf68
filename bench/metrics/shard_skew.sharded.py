"""Mesh layer: how much more the busiest shard walked than the mean
shard, 100 x (max / mean - 1) of the hops per shard summed over the
window's batches (``BatchResult.extras["shard_hops"]``, per query and
shard, from the continue program).  A program without that counter reads
nothing."""
import numpy as np

from bench import spec


def read(rec):
    if rec["backend"] != "sharded":
        return None
    hops = [r["shard_hops"] for r in
            spec.backend_module("sharded").window_results(rec["window"])]
    if not hops or any(h is None for h in hops):
        return None
    per_shard = np.sum([np.asarray(h).sum(axis=0) for h in hops], axis=0)
    mean = float(per_shard.mean())
    return None if mean <= 0 else 100.0 * (float(per_shard.max()) / mean - 1)
