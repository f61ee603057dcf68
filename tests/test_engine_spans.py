"""The serving engine's host spans, read back from a CPU profile.

Every stage of a staged batch runs inside a ``jax.profiler.TraceAnnotation``
that carries the batch's sequence number (``batch``); the launch span also
carries the partition's counts.  A profile of ``search_batches``, ``search``
and ``begin`` + ``finish_from`` must hold one span of each stage per batch,
each sub-stage inside its stage, with ``lanes`` / ``padded_lanes`` equal to
the bucket partition — and the results must not depend on whether a
profiler is running.
"""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import serving
from repro.serving import pipeline as pipe
from tests._backend_fixtures import BUDGET, built, split

PARENT = {
    "engine.dispatch": None,
    "engine.schedule": None,
    "engine.schedule.sync": "engine.schedule",
    "engine.schedule.plan": "engine.schedule",
    "engine.schedule.launch": "engine.schedule",
    "engine.gather": None,
    "engine.gather.sync": "engine.gather",
}
BATCH = 12


def _engine(num_buckets) -> serving.SearchEngine:
    x, _, _, idx, _ = built()
    return serving.SearchEngine(serving.ExactBackend(x, idx.adj, idx.entry),
                                BUDGET, k=10, num_buckets=num_buckets)


def _serve(eng: serving.SearchEngine, q: np.ndarray) -> list:
    """Three streamed batches, then one ``search`` and one ``begin`` +
    ``finish_from`` — every entry point through the stage methods."""
    out = list(eng.search_batches(split(q[:3 * BATCH], BATCH)))
    out.append(eng.search(q[3 * BATCH:4 * BATCH]))
    out.append(eng.finish_from(eng.begin(q[:BATCH // 2])))
    return out


def _engine_spans(trace_dir) -> dict:
    """{batch: [(name, start_ns, end_ns, stats)]} of the profile's
    ``engine.*`` host events."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    by_batch: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    stats = dict(ev.stats)
                    s = float(ev.start_ns)
                    by_batch.setdefault(stats["batch"], []).append(
                        (ev.name, s, s + float(ev.duration_ns), stats))
    return by_batch


@pytest.mark.parametrize("num_buckets", ["auto", 4])
def test_engine_spans_per_batch_nested_with_partition_counts(
        tmp_path, num_buckets):
    q = built()[1]
    plain = _serve(_engine(num_buckets), q)      # no profiler running
    eng = _engine(num_buckets)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _serve(eng, q)
    finally:
        jax.profiler.stop_trace()

    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.d2, b.d2)
        assert a.ceilings == b.ceilings

    by_batch = _engine_spans(tmp_path)
    assert sorted(by_batch) == list(range(len(traced)))
    multi = 0
    for bid, res in zip(sorted(by_batch), traced):
        spans = {}
        for name, s, e, stats in by_batch[bid]:
            assert name not in spans, (bid, name)
            spans[name] = (s, e, stats)
        assert set(spans) == set(PARENT), bid
        for name, parent in PARENT.items():
            if parent is not None:
                s, e, _ = spans[name]
                ps, pe, _ = spans[parent]
                assert ps <= s <= e <= pe, (bid, name)

        stats = spans["engine.schedule.launch"][2]
        nq = res.ids.shape[0]
        assert stats["lanes"] == nq
        if len(res.ceilings) <= 1:
            assert stats["buckets"] == 1 and stats["padded_lanes"] == nq
            continue
        parts = pipe.partition_by_bucket(res.astats.budget, res.ceilings,
                                         eng.pad_quantum)
        assert stats["buckets"] == len(parts)
        assert stats["padded_lanes"] == sum(p.size for _, _, p in parts)
        multi += len(parts) > 1
    assert multi > 0            # a padded multi-bucket partition was seen
