"""Slow-tier layer: share of the window's record fetches served by the
tier's cache and pinned set, Δhits / Δ(hits + misses), in percent."""


def read(rec):
    st = rec["slow_tier"]
    if st is None or st["hits"] + st["misses"] == 0:
        return None
    return 100.0 * st["hits"] / (st["hits"] + st["misses"])
