"""Engine layer: mean host ms per batch in the program's
``engine.schedule.launch`` span — the per-bucket lane gathers and continue
dispatches — over a traced stream window (``bench.spans``)."""
from bench import spans


def read(rec):
    return spans.mean_ms(rec, "engine.schedule.launch")
