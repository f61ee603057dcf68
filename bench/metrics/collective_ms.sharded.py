"""Mesh layer: device ms per batch in collective ops (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute and their
async halves), read from the trace's op kinds: self time, one chip's mean
(``bench.trace``), over the batches that finished in the window.  The
trace keeps its ten largest op kinds only: where no collective is among
them the metric reads nothing rather than 0, since collectives that fell
out of the ten are not gone."""
from bench import spec

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(rec):
    if rec["backend"] != "sharded":
        return None
    batches = len(spec.backend_module("sharded").window_results(
        rec["window"]))
    if batches == 0:
        return None
    kept = [t for kind, t in rec["trace"]["device_ops"]
            if kind.startswith(COLLECTIVES)]
    return 1e3 * sum(kept) / batches if kept else None
