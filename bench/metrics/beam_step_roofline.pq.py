"""Kernel layer: the fused PQ hop's share of the chip's roofline — the
least time the window's ADC evaluations and lookup tables need
(``bench.roofline``) over the summed device time of the ``beam_step``
events in the trace."""
from bench import roofline


def read(rec):
    win, tr = rec["window"], rec["trace"]
    kernel_s = tr["kernel_s"].get("beam_step", 0.0)
    if (rec["backend"] != "tiered-disk" or win.evals is None
            or kernel_s <= 0):
        return None
    flops, nbytes = roofline.hop_work("pq", float(win.evals.sum()),
                                      int(win.evals.size),
                                      int(rec["config"]["d"]),
                                      int(rec["config"]["pq"]["m"]))
    return roofline.share_pct(flops, nbytes, kernel_s, rec["peaks"])[0]
