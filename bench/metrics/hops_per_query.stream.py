"""Walk layer: mean hops per answered query (``BatchResult.stats.hops``:
nodes expanded, probe and continue), over the stream window."""


def read(rec):
    win = rec["window"]
    if rec["mode"] != "stream" or win.hops is None or win.hops.size == 0:
        return None
    return float(win.hops.mean())
