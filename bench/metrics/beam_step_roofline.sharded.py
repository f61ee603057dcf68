"""Kernel layer: the fused PQ hop's share of its roofline on the mesh.

The work is that of every live shard's walk: ``bench.roofline.hop_work``
over the window's distance evaluations summed over the shards
(``BatchResult.stats.dist_evals``), with one lookup table per query and
shard.  ``bench.trace`` sums the ``beam_step`` device time over the traced
chips and divides it by their number, so the time is one chip's mean; the
work is divided by the same number, and the share is one chip's work over
one chip's time against one chip's peak."""
from bench import roofline


def read(rec):
    win, tr = rec["window"], rec["trace"]
    kernel_s = tr["kernel_s"].get("beam_step", 0.0)
    if rec["backend"] != "sharded" or win.evals is None or kernel_s <= 0:
        return None
    cfg = rec["config"]
    shards = 1
    for v in cfg["mesh"].values():
        shards *= int(v)
    chips = max(1, int(tr["devices"]))
    flops, nbytes = roofline.hop_work("pq", float(win.evals.sum()),
                                      int(win.evals.size) * shards,
                                      int(cfg["d"]), int(cfg["pq"]["m"]))
    return roofline.share_pct(flops / chips, nbytes / chips, kernel_s,
                              rec["peaks"])[0]
