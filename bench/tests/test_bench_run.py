"""Whole runs at a tiny size on the CPU, past the harness's look for a chip:
the result line's keys, the control failing the comparison, and faults
planted in the timed path coming out as ``correct: false``."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import index_cache, reference, spec

TINY = spec.BENCH_DIR / "tests" / "data" / "tiny-exact.json"
STREAM = {"mode": "stream", "batch": 16}
OPEN = {"mode": "open", "arrivals": {"process": "poisson", "rate": 200.0},
        "class": {"name": "interactive", "deadline_s": 30.0,
                  "batch_window_s": 0.005, "max_lanes": 8,
                  "lane_quantum": 8},
        "max_queue": 256, "dispatch_workers": 2}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "compared"]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    d = tmp_path_factory.mktemp("index")
    saved = index_cache.INDEX_DIR
    index_cache.INDEX_DIR = d
    yield d
    index_cache.INDEX_DIR = saved


def tiny_cell(traffic: dict) -> spec.Cell:
    rate = ("qps", "queries/s") if traffic["mode"] == "stream" else \
        ("p99_ms", "ms")
    e2e = tuple({"name": n, "unit": u} for n, u in
                (rate, ("recall_at_10", "ratio"), ("setup_s", "s")))
    return spec.Cell(name="tiny." + traffic["mode"], chips=1,
                     config_name="tiny-exact", traffic_name=traffic["mode"],
                     config=json.loads(TINY.read_text()), traffic=traffic,
                     end_to_end=e2e, per_layer=())


def run_tiny(traffic: dict, seed: int = 2**31 + 5) -> dict:
    import jax

    from bench import run as R

    return R.run(tiny_cell(traffic), seed, 0.5, 0, jax.devices(),
                 config_file=TINY)


@pytest.mark.parametrize("traffic", [STREAM, OPEN], ids=["stream", "open"])
def test_sound_run_is_correct_with_the_result_keys(cache, traffic):
    out = run_tiny(traffic)
    assert list(out) == RESULT_KEYS
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(out["metrics"]) == {m["name"] for m in
                                   tiny_cell(traffic).end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    for name, c in out["compared"].items():
        assert c["value"] <= c["limit"], name
    json.dumps(out)


def test_threaded_lane_family_leaves_the_stream_nothing_to_compile(cache):
    """The lane family, warmed from several threads at a batch size no
    other test uses, holds every program a stream of that size runs."""
    import gc

    from bench import drive
    from bench import run as R

    _, pool, served, engine, counter = R.setup(tiny_cell(STREAM), 2**31 + 5,
                                               TINY)
    gc.unfreeze()
    rng = np.random.default_rng(0)
    batch = 40
    try:
        before = counter.count
        drive.warm_lane_family(
            engine, pool[rng.integers(0, pool.shape[0], batch)],
            engine.pad_quantum)
        assert counter.count > before
        before = counter.count
        for _ in engine.search_batches(
                [pool[rng.integers(0, pool.shape[0], batch)]
                 for _ in range(4)]):
            pass
        assert counter.count == before
    finally:
        engine.close()
        served.close()


def _alter_answer(res):
    res.ids = np.array(res.ids)
    res.ids[:, 0] = (res.ids[:, 0] + 1) % 2000      # d2 left as scored
    return res


def _drop_half(res):
    res.ids, res.d2 = np.array(res.ids), np.array(res.d2)
    half = res.ids.shape[0] // 2
    res.ids[half:] = -1
    res.d2[half:] = np.inf
    return res


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half],
                         ids=["answer-altered", "half-batch-left-out"])
@pytest.mark.parametrize("traffic", [STREAM, OPEN], ids=["stream", "open"])
def test_planted_fault_makes_the_run_incorrect(cache, monkeypatch, fault,
                                               traffic):
    from repro.serving import engine as eng

    finish = eng.ExactBackend.finish

    def broken(self, *a, **kw):
        return fault(finish(self, *a, **kw))

    monkeypatch.setattr(eng.ExactBackend, "finish", broken)
    out = run_tiny(traffic)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["compared"].values())


def test_control_fails_and_the_reference_passes(cache):
    """The reference computed one precision lower (bfloat16 inputs) in the
    program's place fails the comparison; the reference itself passes.
    (The control runs on the chip at the cells' own size through
    ``bench/control.py``.)"""
    import jax

    from bench import data

    cfg = json.loads(TINY.read_text())
    limits = cfg["correct"]["limits"]
    for seed in (1, 2, 3):
        base, pool = data.make_data(cfg, seed)
        qidx = np.arange(pool.shape[0])
        ids, d2 = reference.control_answers(base, pool, 10)
        low = reference.compare(base, pool, qidx, ids, d2, 10)
        assert reference.judge(low, limits)[0] is False
        assert low["d2_rel_err"] > 10 * limits["d2_rel_err"]
        ids, d2 = reference.exact_topk(base, pool, 10)
        ref = reference.compare(base, pool, qidx, ids, d2, 10)
        assert reference.judge(ref, limits)[0] is True
        assert ref["recall_at_10"] == 1.0
    assert jax.devices()[0].platform == "cpu"


def test_malformed_rows_and_recall():
    ids = np.array([[0, 1, 2], [0, 0, 2], [0, 1, -1], [3, 1, 2]])
    d2 = np.array([[0., 1., 2.], [0., 1., 2.], [0., 1., np.inf],
                   [2., 1., 3.]])
    bad = reference.malformed(ids, d2, n=4)
    assert bad.tolist() == [False, True, True, True]
    gt = np.array([[0, 1, 2]] * 4)
    assert reference.recall(ids, gt) == pytest.approx((3 + 2 + 2 + 2) / 12)
