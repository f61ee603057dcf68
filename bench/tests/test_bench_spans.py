"""The reduction of the engine's own spans (``bench.spans``) on plain event
lists: the idle split at span boundaries, stat-less wrappers left out,
per-batch means, the padded-lane share, compile events, and the readers."""
from __future__ import annotations

import pytest

from bench import spans, spec

MS = 1e6


def _events():
    """Window [0, 100] ms; device busy [0, 10], [30, 50], [80, 95], so idle
    gaps [10, 30], [50, 80], [95, 100] (55 ms).  Batch 0's gap runs from
    its budget sync through its plan into its launch; batch 1's launch
    holds a compile event."""
    dev = {"/device:TPU:0": [(0, 10 * MS), (30 * MS, 40 * MS),
                             (35 * MS, 50 * MS), (80 * MS, 95 * MS)]}
    launch0 = {"batch": 0, "buckets": 2, "lanes": 10, "padded_lanes": 12}
    launch1 = {"batch": 1, "buckets": 1, "lanes": 10, "padded_lanes": 10}
    host = [
        ("bench.window", 0, 100 * MS, {}),
        ("engine.schedule", -50 * MS, -10 * MS, {"batch": 99}),  # before
        ("engine.schedule", 5 * MS, 40 * MS, {"batch": 0}),
        ("engine.schedule.sync", 5 * MS, 12 * MS, {"batch": 0}),
        ("engine.schedule.plan", 12 * MS, 20 * MS, {"batch": 0}),
        ("engine.schedule.launch", 20 * MS, 35 * MS, launch0),
        ("engine.schedule", 45 * MS, 90 * MS, {"batch": 1}),
        ("engine.schedule.sync", 45 * MS, 47 * MS, {"batch": 1}),
        ("engine.schedule.plan", 47 * MS, 55 * MS, {"batch": 1}),
        ("engine.schedule.launch", 55 * MS, 88 * MS, launch1),
        ("backend_compile_and_load", 60 * MS, 70 * MS, {}),
    ]
    # The benchmark's own stat-less wrappers of the same names.
    wrappers = [
        ("engine.schedule", 4 * MS, 41 * MS, {}),
        ("engine.schedule.plan", 21 * MS, 29 * MS, {}),
        ("engine.schedule", 44 * MS, 91 * MS, {}),
    ]
    return dev, host, wrappers


def test_gap_is_cut_at_span_boundaries_and_compile_takes_its_piece():
    dev, host, _ = _events()
    r = spans.reduce_events(dev, host)
    split = {k: v * 1e3 for k, v in r["idle_split"].items()}
    # [10, 12] sync, [12, 20] plan, [20, 30] launch; [50, 55] plan,
    # [55, 60] + [70, 80] launch, [60, 70] compile; [95, 100] outside.
    assert split == pytest.approx({
        "engine.schedule.sync": 2.0, "engine.schedule.plan": 13.0,
        "engine.schedule.launch": 25.0, "backend_compile_and_load": 10.0,
        spans.OUTSIDE: 5.0})
    assert r["idle_s"] == pytest.approx(0.055)
    assert sum(r["idle_split"].values()) == pytest.approx(r["idle_s"])
    assert r["window_s"] == pytest.approx(0.100)
    assert r["compile_events"] == 1


def test_stat_less_wrappers_of_the_same_names_are_ignored():
    dev, host, wrappers = _events()
    assert spans.reduce_events(dev, host + wrappers) == \
        spans.reduce_events(dev, host)
    assert spans.reduce_events(dev, [host[0]] + wrappers) is None
    with pytest.raises(ValueError):
        spans.reduce_events(dev, host[1:])       # no bench.window


def test_per_batch_means_padded_share_and_buckets():
    dev, host, _ = _events()
    r = spans.reduce_events(dev, host)
    assert r["batches"] == 2                     # batch 99 is outside
    ms = r["per_batch_ms"]
    assert ms["engine.schedule"] == pytest.approx(40.0)     # (35 + 45) / 2
    assert ms["engine.schedule.sync"] == pytest.approx(4.5)
    assert ms["engine.schedule.plan"] == pytest.approx(8.0)
    assert ms["engine.schedule.launch"] == pytest.approx(24.0)
    assert r["padded_pct"] == pytest.approx(10.0)          # 22 / 20 - 1
    assert r["buckets_per_batch"] == pytest.approx(1.5)
    assert "engine.schedule.sync" in spans.table(r, 0.5)


def test_readers_report_the_reduction_in_stream_cells_only(monkeypatch):
    dev, host, _ = _events()
    r = spans.reduce_events(dev, host)
    monkeypatch.setattr(spans, "load", lambda trace_dir=None: r)
    want = {"schedule_ms.stream": 40.0, "plan_ms.stream": 8.0,
            "launch_ms.stream": 24.0, "padded_lanes.stream": 10.0}
    for name, value in want.items():
        read = spec.metric_reader(name)
        assert read({"mode": "stream"}) == pytest.approx(value)
        assert read({"mode": "open"}) is None
    monkeypatch.setattr(spans, "load", lambda trace_dir=None: None)
    for name in want:
        assert spec.metric_reader(name)({"mode": "stream"}) is None


def test_no_trace_reads_as_nothing(tmp_path):
    assert spans.load(tmp_path) is None


def test_host_events_and_stats_from_a_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("engine.schedule"):
                with jax.profiler.TraceAnnotation(
                        "engine.schedule.launch", batch=3, buckets=2,
                        lanes=5, padded_lanes=8):
                    jax.jit(lambda x: x * 3 + 1)(
                        jnp.ones(3)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    busy, host = spans.read_xplane(
        spans.trace_mod.Capture(tmp_path).xplane())
    assert busy == {}                            # no TPU on this host
    got = {(n, tuple(sorted(st.items()))) for n, _, _, st in host}
    assert ("engine.schedule", ()) in got
    assert ("engine.schedule.launch", (("batch", 3), ("buckets", 2),
                                       ("lanes", 5),
                                       ("padded_lanes", 8))) in got
    assert any(n in spans.COMPILE_EVENTS for n, _, _, _ in host)
    r = spans.load(tmp_path)
    assert r["per_batch_ms"].keys() == {"engine.schedule.launch"}
    assert r["padded_pct"] == pytest.approx(60.0)


def test_trace_dir_is_the_one_the_run_writes():
    from bench import run

    assert spans.TRACE_DIR == run.TRACE_DIR
