"""The sharded backend through the whole run, on four virtual CPU devices
(a process of its own: ``bench/tests/_sharded_worker.py``): a sound run
reads ``correct: true`` with the per-shard readers reading, and a fault
planted in the served answers reads ``correct: false``."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import spec


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_PALLAS_INTERPRET", None)
    proc = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "tests" / "_sharded_worker.py")],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(spec.ROOT))
    assert proc.returncode == 0, proc.stderr[-4000:]
    outs = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    return {o["fault"]: o for o in outs}


def test_sound_sharded_run_is_correct_on_four_devices(runs):
    out = runs[False]
    assert out["devices"] == 4 and out["device"]["count"] == 4
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    for name, c in out["compared"].items():
        assert c["value"] <= c["limit"], name
    assert out["metrics"]["qps"]["value"] > 0


def test_per_shard_readers_read_the_window(runs):
    r = runs[False]["readings"]
    assert r["shard_skew.sharded"] is not None
    assert 0.0 <= r["shard_skew.sharded"] < 300.0
    cfg = json.loads((spec.BENCH_DIR / "tests" / "data"
                      / "tiny-sharded.json").read_text())
    s = cfg["search"]
    assert s["l_min"] <= r["granted_budget.sharded"] <= s["l_search"]


def test_planted_fault_makes_the_sharded_run_incorrect(runs):
    out = runs[True]
    assert out["correct"] is False
    assert out["compared"]["d2_rel_err"]["value"] > \
        out["compared"]["d2_rel_err"]["limit"]


def _window_rec(results, trace=None, backend="sharded"):
    import numpy as np

    from bench import drive
    from bench.backends import sharded

    sharded.RECORD[:] = [(10.0 + i, sharded.counters(r))
                         for i, r in enumerate(results)]
    sharded.RECORD.append((99.0, sharded.counters(results[0])))  # after
    evals = np.concatenate([np.asarray(r.stats.dist_evals) for r in results])
    win = drive.Window(qidx=np.arange(evals.size), ids=None, d2=None,
                       attempted=evals.size, not_ok=0, t_first=9.5,
                       t_last=9.5 + len(results), evals=evals)
    cfg = {"mesh": {"data": 2, "model": 2}, "d": 128, "pq": {"m": 16}}
    return {"backend": backend, "config": cfg, "window": win,
            "trace": trace, "peaks": {"flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9}}


def _result(hops, budgets, evals, per_shard=True):
    import numpy as np

    from repro.core.search import AdaptiveStats, SearchStats
    from repro.serving import BatchResult

    hops = np.asarray(hops)
    extras = {"shard_hops": hops} if per_shard else {}
    return BatchResult(
        ids=None, d2=None,
        stats=SearchStats(hops=hops.sum(axis=1),
                          dist_evals=np.asarray(evals)),
        astats=AdaptiveStats(q_lid=None, budget=np.asarray(budgets)),
        extras=extras)


def test_per_shard_readers_on_a_synthetic_window():
    """The four readers of the sharded cell on a window of two batches:
    the batch finished after the window is left out, a program without
    per-shard counters reads no skew, and a trace whose kept op kinds hold
    no collective reads no collective time (not 0)."""
    from bench.roofline import hop_work, share_pct

    res = [_result([[10, 20, 30, 40]], [[8, 16, 32, 64]], [1000]),
           _result([[10, 20, 30, 40]], [[8, 8, 8, 8]], [3000])]
    trace = {"kernel_s": {"beam_step": 0.5}, "devices": 4,
             "device_ops": [["beam_step", 0.5], ["all-gather", 0.02],
                            ["all-gather-start", 0.01], ["fusion", 0.1]]}
    rec = _window_rec(res, trace)
    read = {n: spec.metric_reader(n) for n in (
        "beam_step_roofline.sharded", "collective_ms.sharded",
        "shard_skew.sharded", "granted_budget.sharded")}
    assert read["collective_ms.sharded"](rec) == pytest.approx(15.0)
    assert read["shard_skew.sharded"](rec) == pytest.approx(60.0)
    assert read["granted_budget.sharded"](rec) == pytest.approx(19.0)
    flops, nbytes = hop_work("pq", 4000.0, 2 * 4, 128, 16)
    assert read["beam_step_roofline.sharded"](rec) == pytest.approx(
        share_pct(flops / 4, nbytes / 4, 0.5, rec["peaks"])[0])
    parent = _window_rec([_result([[1, 2, 3, 4]], [[8] * 4], [10],
                                  per_shard=False)], trace)
    assert read["shard_skew.sharded"](parent) is None
    assert read["granted_budget.sharded"](parent) == 8.0
    no_coll = dict(trace, device_ops=[["beam_step", 0.5], ["fusion", 0.1]])
    assert read["collective_ms.sharded"](_window_rec(res, no_coll)) is None
    other = _window_rec(res, trace, backend="exact")
    assert all(r(other) is None for r in read.values())
