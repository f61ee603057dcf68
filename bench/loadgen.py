"""The one general arrival generator: reads a mix's ``arrivals`` and gives
due times from the seed.

A mix is a data file, ``bench/traffic/<name>.json``; its ``mode`` names
the driver that runs it (``bench/drivers/<mode>.py``).  An open-loop mix
gives ``"arrivals": {"process": "poisson", "rate": r}``.

Poisson gaps are a fixed set — the exponential distribution's quantiles at
``(i + 0.5) / n`` — in an order drawn from the seed, so every seed offers the
same load in the same window and only the order differs.
"""
from __future__ import annotations

import numpy as np


def poisson_offsets(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate * seconds)``
    requests with exponential gaps of mean ``1 / rate``."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps = gaps[rng.permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def arrival_offsets(arrivals: dict, seconds: float, rng) -> np.ndarray:
    proc = arrivals["process"]
    if proc == "poisson":
        return poisson_offsets(float(arrivals["rate"]), seconds, rng)
    raise ValueError(f"unknown arrival process {proc!r}")
