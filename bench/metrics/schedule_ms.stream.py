"""Engine layer: mean host ms per batch in the program's
``engine.schedule`` span — the whole bucket stage: budget sync, plan and
launch — over a traced stream window (``bench.spans``)."""
from bench import spans


def read(rec):
    return spans.mean_ms(rec, "engine.schedule")
