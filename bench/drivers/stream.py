"""Driver of ``"mode": "stream"`` mixes: a closed loop with one client.

Batches of ``batch`` pool rows, drawn with replacement from the seed, go
through ``SearchEngine.search_batches``, the next as soon as the pipeline
takes it, until ``seconds`` have passed since the first dispatch; the
batches begun by then are drained.
"""
from __future__ import annotations

import time

import numpy as np

from bench.data import sub_seed
from bench.drive import Window, span, warm_lane_family

WARM_BATCHES = 4      # batches per warm pass
WARM_PASSES = 6       # at most; a pass that compiles nothing ends the warm-up


def warm(engine, pool: np.ndarray, traffic: dict, seed: int, counter,
         log) -> None:
    """Warm every shape the stream uses: the lane family, then passes of
    the cell's own traffic until one compiles nothing new."""
    batch = int(traffic["batch"])
    rng = np.random.default_rng(sub_seed(seed, "warm"))
    first = pool[rng.integers(0, pool.shape[0], batch)]
    before = counter.count
    warm_lane_family(engine, first, engine.pad_quantum)
    log(f"lane family: {counter.count - before} programs obtained")
    for p in range(WARM_PASSES):
        before = counter.count
        qs = [pool[rng.integers(0, pool.shape[0], batch)]
              for _ in range(WARM_BATCHES)]
        for _ in engine.search_batches(qs):
            pass
        new = counter.count - before
        log(f"warm pass {p + 1}: {new} programs obtained")
        if new == 0:
            return


def run(engine, pool: np.ndarray, traffic: dict, seed: int,
        seconds: float) -> Window:
    rng = np.random.default_rng(sub_seed(seed, "traffic"))
    b = int(traffic["batch"])
    fed: list = []
    clock = {"t0": None}

    def feed():
        while True:
            with span("bench.feed"):
                now = time.perf_counter()
                if clock["t0"] is None:
                    clock["t0"] = now
                elif now - clock["t0"] >= seconds:
                    return
                idx = rng.integers(0, pool.shape[0], b)
                fed.append(idx)
                qb = pool[idx]
            yield qb

    ids, d2, hops, evals = [], [], [], []
    t_last = None
    with span("bench.window"):
        for res in engine.search_batches(feed()):
            t_last = time.perf_counter()
            with span("bench.collect"):
                ids.append(res.ids)
                d2.append(res.d2)
                if res.stats is not None:
                    hops.append(np.asarray(res.stats.hops))
                    evals.append(np.asarray(res.stats.dist_evals))
    qidx = np.concatenate(fed)
    return Window(
        qidx=qidx, ids=np.concatenate(ids), d2=np.concatenate(d2),
        attempted=int(qidx.size), not_ok=0, t_first=clock["t0"],
        t_last=t_last,
        hops=np.concatenate(hops) if hops else None,
        evals=np.concatenate(evals) if evals else None)
