"""Engine layer: mean host ms per batch in the program's
``engine.schedule.plan`` span — the bucket-family dynamic program and the
partition — over a traced stream window (``bench.spans``)."""
from bench import spans


def read(rec):
    return spans.mean_ms(rec, "engine.schedule.plan")
