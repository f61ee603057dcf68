"""Engine layer: percent of continue lanes that are padding, 100 ×
(Σ ``padded_lanes`` ÷ Σ ``lanes`` − 1) over the program's
``engine.schedule.launch`` spans in a traced stream window
(``bench.spans``)."""
from bench import spans


def read(rec):
    if rec["mode"] != "stream":
        return None
    r = spans.load()
    return None if r is None else r["padded_pct"]
