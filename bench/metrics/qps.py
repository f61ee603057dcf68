"""End to end: queries answered ``ok`` (malformed rows left out) over the
time from the window's first dispatch to the completion of the last batch
begun in it — all the work over all the time (host clock)."""


def read(rec):
    win = rec["window"]
    if win.seconds <= 0:
        return None
    return (win.ids.shape[0] - rec["numbers"]["malformed_rows"]) / win.seconds
