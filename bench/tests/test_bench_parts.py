"""The harness's arithmetic, each part alone: schedules, end-to-end
metrics on a synthetic window, roofline counting, peaks, the trace
reduction, the index cache key, and the refusal to run without a chip."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from bench import index_cache, loadgen, roofline, spec
from bench import trace as tr


# ----------------------------------------------------------------- traffic


def test_poisson_schedule_is_a_fixed_set_in_seed_order():
    a = loadgen.poisson_offsets(200.0, 10.0, np.random.default_rng(1))
    b = loadgen.poisson_offsets(200.0, 10.0, np.random.default_rng(2))
    assert a.size == b.size == 2000
    assert a[0] == b[0] == 0.0
    assert np.all(np.diff(a) > 0)
    gaps_a, gaps_b = np.diff(a), np.diff(b)
    assert not np.array_equal(gaps_a, gaps_b)
    # Same gaps, other order: the n - 1 gaps used are all but one quantile.
    full = -np.log1p(-(np.arange(2000) + 0.5) / 2000) / 200.0
    nearest = np.abs(gaps_a[:, None] - full[None, :]).min(axis=1)
    assert nearest.max() < 1e-12
    assert abs(full.mean() - 1 / 200.0) < 0.02 / 200.0
    assert 9.0 < a[-1] < 10.0
    again = loadgen.poisson_offsets(200.0, 10.0, np.random.default_rng(1))
    np.testing.assert_array_equal(a, again)


def test_unknown_arrival_process_is_an_error():
    rng = np.random.default_rng(0)
    off = loadgen.arrival_offsets({"process": "poisson", "rate": 50.0}, 2.0,
                                  rng)
    assert off.size == 100
    with pytest.raises(ValueError):
        loadgen.arrival_offsets({"process": "bursty", "rate": 50.0}, 2.0, rng)


# ------------------------------------------------- end-to-end on a window


def _window(**kw):
    from bench import drive

    base = dict(qidx=np.zeros(4, int), ids=np.zeros((4, 10), int),
                d2=np.zeros((4, 10)), attempted=4, not_ok=0, t_first=0.0,
                t_last=1.0)
    base.update(kw)
    return drive.Window(**base)


def _e2e(names, win, numbers, setup_s):
    from bench import run as R

    rec = {"window": win, "numbers": numbers, "setup_s": setup_s}
    return R.read_metrics(tuple({"name": n, "unit": "x"} for n in names),
                          rec, required=True)


def test_qps_counts_all_work_over_all_time_stall_included():
    numbers = {"malformed_rows": 0, "recall_at_10": 1.0}
    steady = _window(ids=np.zeros((1000, 10), int), t_first=0.0, t_last=2.0)
    stalled = _window(ids=np.zeros((1000, 10), int), t_first=0.0,
                      t_last=2.5)          # a 0.5 s stall before the end
    q1 = _e2e(["qps"], steady, numbers, 1.0)["qps"]["value"]
    q2 = _e2e(["qps"], stalled, numbers, 1.0)["qps"]["value"]
    assert q1 == pytest.approx(500.0) and q2 == pytest.approx(400.0)
    bad = dict(numbers, malformed_rows=100)
    assert _e2e(["qps"], steady, bad, 1.0)["qps"]["value"] == \
        pytest.approx(450.0)


def test_p99_is_from_due_time_so_a_stall_shows():
    numbers = {"malformed_rows": 0, "recall_at_10": 1.0}
    lat = np.full(1000, 0.010)
    # A 300 ms generator stall: the 30 requests due in it wait for it.
    lat_stall = lat.copy()
    lat_stall[500:530] = np.linspace(0.31, 0.01, 30)
    p_ok = _e2e(["p99_ms"], _window(latencies_s=lat), numbers, 0)
    p_st = _e2e(["p99_ms"], _window(latencies_s=lat_stall), numbers, 0)
    assert p_ok["p99_ms"]["value"] == pytest.approx(10.0)
    assert p_st["p99_ms"]["value"] > 200.0


def test_end_to_end_metric_with_nothing_to_read_is_an_error():
    numbers = {"malformed_rows": 0, "recall_at_10": 0.99}
    got = _e2e(["recall_at_10", "setup_s"], _window(), numbers, 12.5)
    assert got["recall_at_10"]["value"] == 0.99
    assert got["setup_s"]["value"] == 12.5
    with pytest.raises(spec.SpecError):
        _e2e(["p99_ms"], _window(), numbers, 0)   # a stream has no p99


def test_compile_cache_dir_honours_the_environment(tmp_path):
    from bench import run as R

    assert R.compile_cache_dir({}) == R.JAX_CACHE
    assert R.JAX_CACHE.is_relative_to(spec.BENCH_DIR)
    assert R.compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == \
        R.JAX_CACHE
    given = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")}
    assert R.compile_cache_dir(given) == tmp_path / "jc"


# ---------------------------------------------------------------- roofline


def test_roofline_counts_from_evaluations_and_peaks():
    pk = roofline.peaks("TPU v5 lite")
    flops, nbytes = roofline.hop_work("exact", 1e6, 100, 128)
    assert flops == 3 * 128 * 1e6 and nbytes == 516 * 1e6
    t, bound = roofline.least_time(flops, nbytes, pk)
    assert bound == "memory" and t == pytest.approx(516e6 / 819e9)
    share, _ = roofline.share_pct(flops, nbytes, 2 * t, pk)
    assert share == pytest.approx(50.0)
    flops, nbytes = roofline.hop_work("pq", 1e6, 100, 128, m_pq=16)
    assert flops == 16e6 and nbytes == 20e6 + 16 * 256 * 4 * 100
    compute_bound = roofline.least_time(1e15, 1.0, pk)
    assert compute_bound[1] == "compute"
    assert compute_bound[0] == pytest.approx(1e15 / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("cpu")
    pk = json.loads(roofline.PEAKS_JSON.read_text())
    for entry in pk.values():
        assert entry["source"] and entry["flops_per_s"] > 0


# ------------------------------------------------------------------- trace


def _synthetic_trace():
    ms = 1e6
    dev = {"/device:TPU:0": [
        ("%while.3 = (s32[8]) while(...)", 0 * ms, 3 * ms),
        ("%beam_step.1 = (s32[8]) custom-call(...)", 0 * ms, 2 * ms),
        ("%beam_step.1 = (s32[8]) custom-call(...)", 2 * ms, 2.5 * ms),
        ("%fusion.12 = f32[8] fusion(...)", 2.5 * ms, 3.5 * ms),
        ("%topk.3 = f32[8] custom-call(...)", 6 * ms, 7 * ms),
        ("%beam_step.6 = (s32[8]) custom-call(...)", 9 * ms, 12 * ms),
        ("%fusion.40 = f32[8] fusion(...)", 20 * ms, 21 * ms),
    ]}
    host = [("bench.window", 0 * ms, 10 * ms),
            ("engine.schedule", 3.4 * ms, 5 * ms),
            ("bench.feed", 4 * ms, 6.5 * ms),
            ("engine.gather", 7 * ms, 9.5 * ms),
            ("other.span", 7 * ms, 9 * ms)]
    return dev, host


def test_trace_reduction_busy_union_kernels_and_gaps():
    dev, host = _synthetic_trace()
    r = tr.reduce_events(dev, host)
    assert r["window_s"] == pytest.approx(0.010)
    # busy: [0, 3.5] + [6, 7] + [9, 10] = 5.5 ms
    assert r["busy_s"] == pytest.approx(0.0055)
    # beam_step: 2 + 0.5 inside the while, 1 of the last inside the window
    assert r["kernel_s"]["beam_step"] == pytest.approx(0.0035)
    assert r["kernel_s"]["topk"] == pytest.approx(0.001)
    ops = dict(r["device_ops"])
    assert ops["beam_step"] == pytest.approx(0.0035)
    assert ops["fusion"] == pytest.approx(0.001)
    assert ops["while"] == pytest.approx(0.0005)   # 3 ms less its body
    gaps = dict(r["idle_gaps"])
    # gap [3.5, 6]: engine.schedule covers 1.5 ms, bench.feed 2 ms
    assert gaps["bench.feed"] == pytest.approx(0.0025)
    # gap [7, 9]: engine.gather (other.span is not a benchmark span)
    assert gaps["engine.gather"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(0.0045)


def test_trace_reduction_unattributed_gap_and_missing_window():
    ms = 1e6
    r = tr.reduce_events({"/device:TPU:0": [("x", 1 * ms, 2 * ms)]},
                         [("bench.window", 0, 4 * ms)])
    assert dict(r["idle_gaps"]) == {
        "host.unattributed": pytest.approx(0.003)}
    with pytest.raises(ValueError):
        tr.reduce_events({"/device:TPU:0": []}, [])


def test_op_kind_and_self_time():
    assert tr.op_kind("%beam_step.12 = (s32[2]) custom-call(x)") == \
        "beam_step"
    assert tr.op_kind("%copy-start.1 = (f32[2]) copy-start(y)") == \
        "copy-start"
    assert tr.op_kind("fusion.3.1") == "fusion"
    own = tr.self_times([("a", 0, 10), ("b", 1, 3), ("c", 2, 3),
                         ("d", 5, 6), ("e", 12, 13)])
    assert own == [10 - 2 - 1, 2 - 1, 1, 1, 1]


def test_union_and_merge():
    assert tr.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.merged([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


# ------------------------------------------------------------- index cache


def test_cache_key_follows_config_seed_and_program(tmp_path):
    src = tmp_path / "src" / "repro"
    (src / "core").mkdir(parents=True)
    (src / "core" / "a.py").write_text("x = 1\n")
    (src / "b.py").write_text("y = 2\n")
    cfg = tmp_path / "c.json"
    cfg.write_text('{"n": 1}')
    k0 = index_cache.cache_key(cfg, 7, src=src)
    assert index_cache.cache_key(cfg, 7, src=src) == k0
    assert index_cache.cache_key(cfg, 8, src=src) != k0
    assert index_cache.cache_key(cfg, 2**31 + 7, src=src) != k0
    cfg.write_text('{"n": 2}')
    k1 = index_cache.cache_key(cfg, 7, src=src)
    assert k1 != k0
    (src / "core" / "a.py").write_text("x = 3\n")
    k2 = index_cache.cache_key(cfg, 7, src=src)
    assert k2 != k1
    (src / "core" / "new.py").write_text("")
    assert index_cache.cache_key(cfg, 7, src=src) != k2


def test_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setattr(index_cache, "INDEX_DIR", tmp_path)
    assert index_cache.load("k") is None
    index_cache.save("k", {"adj": np.arange(6).reshape(2, 3)})
    np.testing.assert_array_equal(index_cache.load("k")["adj"],
                                  np.arange(6).reshape(2, 3))
    assert not list(tmp_path.glob("k/*tmp*"))


def test_bench_gitignore_keeps_the_cache_out():
    lines = (spec.BENCH_DIR / ".gitignore").read_text().split()
    assert "cache/" in lines


# -------------------------------------------------------------------- CLI


def test_cli_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_PALLAS_INTERPRET", None)
    cell = spec.load_benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload", cell,
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_cli_refuses_interpreted_kernels():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS_INTERPRET="1")
    p = subprocess.run(
        [sys.executable, str(pathlib.Path(spec.BENCH_DIR, "run.py")),
         "--workload", "sift1m-exact.batch256", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
