"""Device layer: percent of the traced open-loop window in which no op ran on
the device (1 - busy union / window, from the profiler trace)."""


def read(rec):
    tr = rec["trace"]
    if rec["mode"] != "open" or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
