"""Work a kernel must do, and its share of the chip's roofline.

The count is of the work the algorithm needs, not of what one kernel
happens to move, so the same walk reads the same whatever implements it:

* exact hop: each distance evaluation reads one vector and its id,
  ``d * 4 + 4`` bytes, and takes ``3 * d`` operations (subtract, multiply,
  add);
* PQ hop: each evaluation reads ``M`` code bytes and the id, ``M + 4``
  bytes, and takes ``M`` table additions; each query also reads its
  ``M * 256`` float32 lookup table once.

Visited-set copies and layout rebuilds are not counted.  The least time is
the larger of bytes over peak bandwidth and operations over peak rate
(:data:`peaks.json`, keyed by ``device_kind``); the share is that over the
kernel's measured device time.
"""
from __future__ import annotations

import json

from bench.spec import BENCH_DIR

PEAKS_JSON = BENCH_DIR / "peaks.json"


class UnknownDevice(KeyError):
    """A device kind with no entry in ``peaks.json``."""


def peaks(device_kind: str, path=PEAKS_JSON) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{path.name}; add them with their source")
    return table[device_kind]


def hop_work(kind: str, dist_evals: float, queries: int, d: int,
             m_pq: int | None = None) -> tuple[float, float]:
    """(operations, bytes) the walk's hops need for ``dist_evals``
    distance evaluations over ``queries`` queries."""
    if kind == "exact":
        return 3.0 * d * dist_evals, (4.0 * d + 4.0) * dist_evals
    if kind == "pq":
        m = int(m_pq)
        return (float(m) * dist_evals,
                (m + 4.0) * dist_evals + m * 256 * 4.0 * queries)
    raise ValueError(f"unknown hop kind {kind!r}")


def least_time(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """(seconds, which bound) of the roofline for this work."""
    t_mem = nbytes / float(pk["hbm_bytes_per_s"])
    t_ops = flops / float(pk["flops_per_s"])
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")


def share_pct(flops: float, nbytes: float, seconds: float,
              pk: dict) -> tuple[float, str]:
    """Percent of the roofline reached when the work took ``seconds``."""
    t, bound = least_time(flops, nbytes, pk)
    return 100.0 * t / seconds, bound
