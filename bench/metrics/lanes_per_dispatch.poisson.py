"""Front-door layer: mean number of real request lanes per engine dispatch
(padding lanes excluded) over the window."""


def read(rec):
    d = rec["window"].dispatches
    if rec["mode"] != "open" or not d:
        return None
    return sum(n for _, _, n in d) / len(d)
