"""Batch-stream throughput of the staged double-buffered serving engine.

The PR 2 bucketed path syncs on every batch's probe before host-side
bucketing, so the accelerator idles exactly while the host partitions — and
again between buckets, whose results it gathered eagerly. The serving engine
(``repro.serving.SearchEngine.search_batches``) removes both stalls: batch
i+1's probe is dispatched before batch i's bucketing/continue are collected
(double buffering), and within a batch every bucket's continue program is
dispatched before any is gathered. Scheduling only — results are
bit-identical to the unpipelined path, so the comparison is equal-recall by
construction (asserted here, and property-tested in
``tests/test_serving_pipeline.py``).

Reported: batch-stream throughput (queries/s over a fixed stream of batches)
for (a) the PR 2 bucketed path (per-batch
``beam_search_exact_adaptive(num_buckets=4)``, blocking each batch), (b) the
engine unpipelined (same staging, no lookahead), (c) the engine
double-buffered, and (d) double-buffered with the auto-picked bucket family
(granted-budget histogram) instead of the fixed 4.

Distributed rows (``--distributed``, 8 devices: virtual host devices when
the process is pinned to ``JAX_PLATFORMS=cpu``): the same
comparison for the sharded scatter-gather backend over a *micro-batch*
stream (a hot admission batcher) — monolithic dispatch (the PR 3
behaviour: one whole-mesh program per arriving batch, step-granularity
overlap at best) vs the staged path (probe checkpointed at the horizon,
host scheduling between mesh programs, continues into the hedged merge),
pipelined and — the headline — with cross-batch admission coalescing
merging micro-batches to the engine's lane threshold before dispatch.
Identity is asserted across all rows here too (the staged split is
property-tested in ``tests/test_engine_parity.py`` and the
``staged_engine`` worker scenario).

``python -m benchmarks.pipeline_throughput --smoke`` runs a ~60s CPU smoke
(tiny graph) that asserts result identity and a sane speedup; CI runs it
next to the bucketed smoke, plus a ``--smoke --distributed`` row in the
multi-device matrix job.
"""
from __future__ import annotations

import sys
import time

from repro import runtime

if "--distributed" in sys.argv:  # must precede the first jax import
    runtime.virtual_cpu_devices(8)

import jax
import numpy as np

from benchmarks import common
from repro import serving
from repro.core import build, distance, search

BUDGET = search.AdaptiveBeamBudget(l_min=16, l_max=96, lam=0.35,
                                   lid_k=16, probe_hops=8, hop_factor=4)
NUM_BUCKETS = 4          # the PR 2 fixed bucket family
BATCH = 24
NUM_BATCHES = 16


def make_stream(q, batch: int = BATCH, num_batches: int = NUM_BATCHES,
                seed: int = 0):
    """Deterministic batch stream: fixed-size batches sampled with
    replacement from the query pool (a steady-traffic proxy). Returns
    (batches, selections) — selections index the ground-truth rows."""
    rng = np.random.default_rng(seed)
    qn = np.asarray(q)
    sels = [rng.integers(0, qn.shape[0], batch) for _ in range(num_batches)]
    return [qn[s] for s in sels], sels


def _baseline_pr2(x, idx, batches, budget, num_buckets):
    """The PR 2 bucketed path: one blocking engine call per batch."""
    out = []
    for qb in batches:
        ids, d2, stats, astats = search.beam_search_exact_adaptive(
            x, idx.adj, qb, idx.entry, budget, k=10, num_buckets=num_buckets)
        jax.block_until_ready(ids)
        out.append((np.asarray(ids), np.asarray(d2),
                    np.asarray(stats.hops)))
    return out


def _engine_results(results):
    return [(r.ids, r.d2, np.asarray(r.stats.hops)) for r in results]


def _timed_rounds(fns: dict, warmup: int = 1, rounds: int = 4):
    """Interleaved timing: each round runs every variant once, in order, and
    each variant keeps its best round.  Interleaving decorrelates the
    comparison from time-local machine noise (CPU throttling, co-tenants) —
    sequential best-of-N was measured to swing the ratio by +/-30% on a
    shared 2-core box.  Returns ({name: last result}, {name: best seconds})."""
    outs = {}
    for _ in range(warmup):
        for name, fn in fns.items():
            outs[name] = fn()
    best = {name: float("inf") for name in fns}
    for _ in range(rounds):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            outs[name] = fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return outs, best


def _assert_identical(a, b, what):
    for (ia, da, ha), (ib, db, hb) in zip(a, b):
        np.testing.assert_array_equal(ia, ib, err_msg=what)
        np.testing.assert_array_equal(da, db, err_msg=what)
        np.testing.assert_array_equal(ha, hb, err_msg=what)


def compare(csv: common.Csv, x, q, gt, idx, budget=BUDGET,
            num_buckets=NUM_BUCKETS, batch=BATCH, num_batches=NUM_BATCHES):
    """Throughput of baseline vs engine (unpipelined / pipelined / auto)."""
    batches, sels = make_stream(q, batch, num_batches)
    n_q = batch * num_batches
    backend = serving.ExactBackend(x, idx.adj, idx.entry)
    eng = serving.SearchEngine(backend, budget, k=10, num_buckets=num_buckets)
    eng_auto = serving.SearchEngine(backend, budget, k=10, num_buckets="auto")

    outs, times = _timed_rounds({
        "pr2": lambda: _baseline_pr2(x, idx, batches, budget, num_buckets),
        "unp": lambda: _engine_results([eng.search(qb) for qb in batches]),
        "pip": lambda: _engine_results(list(eng.search_batches(batches))),
        "auto": lambda: list(eng_auto.search_batches(batches)),
    })
    base_out, dt_base = outs["pr2"], times["pr2"]
    unp_out, dt_unp = outs["unp"], times["unp"]
    pip_out, dt_pip = outs["pip"], times["pip"]
    auto_res, dt_auto = outs["auto"], times["auto"]

    # Equal results by construction: the pipeline only reorders dispatch,
    # and the bucket family (fixed or histogram-picked) is pure scheduling.
    _assert_identical(pip_out, unp_out, "pipelined != unpipelined")
    _assert_identical(pip_out, base_out, "engine != PR2 bucketed path")
    _assert_identical(_engine_results(auto_res), base_out,
                      "auto-bucketed != PR2 bucketed path")

    # Headline: the engine as deployed (double buffering + deferred bucket
    # gathers + auto bucket family) vs the PR 2 per-batch bucketed path.
    speedup = dt_base / max(dt_auto, 1e-12)
    speedup_fixed = dt_base / max(dt_pip, 1e-12)
    recall = float(np.mean([
        distance.recall_at_k(ids, gt[s]) for (ids, _, _), s
        in zip(pip_out, sels)]))
    csv.add("pipeline/pr2_bucketed", dt_base / n_q,
            f"stream_wall={dt_base * 1e3:.1f}ms qps={n_q / dt_base:.1f} "
            f"recall={recall:.4f} (all rows serve identical results)")
    csv.add("pipeline/engine_unpipelined_fixed4", dt_unp / n_q,
            f"stream_wall={dt_unp * 1e3:.1f}ms qps={n_q / dt_unp:.1f}")
    csv.add("pipeline/engine_pipelined_fixed4", dt_pip / n_q,
            f"stream_wall={dt_pip * 1e3:.1f}ms qps={n_q / dt_pip:.1f} "
            f"speedup_vs_pr2={speedup_fixed:.2f}x")
    ceilings = sorted({r.ceilings for r in auto_res})
    csv.add("pipeline/engine_pipelined", dt_auto / n_q,
            f"stream_wall={dt_auto * 1e3:.1f}ms qps={n_q / dt_auto:.1f} "
            f"speedup_vs_pr2={speedup:.2f}x ceilings={ceilings}")
    return {"pr2": dt_base, "unpipelined": dt_unp,
            "pipelined_fixed": dt_pip, "pipelined": dt_auto,
            "speedup": speedup, "speedup_fixed": speedup_fixed}


def _dist_results(results):
    return [(r.ids, r.d2) for r in results]


def _assert_dist_identical(a, b, what):
    for (ia, da), (ib, db) in zip(a, b):
        np.testing.assert_array_equal(ia, ib, err_msg=what)
        np.testing.assert_array_equal(da, db, err_msg=what)


def compare_distributed(csv: common.Csv, x, q, gt, *, budget,
                        budget_buckets=4, batch=8, num_batches=24,
                        coalesce_lanes=32, build_cfg=None, m_pq=8):
    """Distributed batch-stream throughput over a *micro-batch* stream —
    the admission pattern of a hot scatter-gather batcher (many small
    batches per unit time), which is where serving granularity actually
    bites: monolithic dispatch pays one whole-mesh program per arriving
    batch, however thin, while the staged engine pipelines sub-steps across
    batches and (the headline) coalesces admissions up to the lane
    threshold before dispatch.

    ``query_chunk`` is pinned to the micro-batch size and
    ``coalesce_lanes`` to a multiple of it, so the probe sees identical
    chunk boundaries in every row and — with the pinned LID center — all
    rows serve bit-identical per-query results (asserted)."""
    from repro import compat
    from repro.distributed import sharded_search as ss

    assert coalesce_lanes % batch == 0, (coalesce_lanes, batch)
    assert budget.center is not None, "rows need a pinned LID center"
    mesh = compat.make_mesh((2, 4), ("data", "model"),
                            devices=runtime.first_devices(8))
    build_cfg = build_cfg or build.BuildConfig(
        degree=16, beam_width=32, iters=1, batch=512, max_hops=64)
    arrays, per = ss.build_sharded_arrays(x, mesh, build_cfg=build_cfg,
                                          m_pq=m_pq)
    batches, sels = make_stream(q, batch, num_batches)
    n_q = batch * num_batches

    # One backend for every engine: jit caches (and therefore compile time,
    # which the ~90s CI smoke pays) live per backend instance, and none of
    # the engines mutate it.
    shared = serving.DistributedBackend(
        mesh, arrays, beam_width=budget.l_max, max_hops=budget.l_max * 2,
        k=10, query_chunk=batch, beam_budget=budget,
        budget_buckets=budget_buckets)
    mono = serving.SearchEngine(shared, None, k=10)
    staged = serving.SearchEngine(shared, budget, k=10, num_buckets="auto")
    coal = serving.SearchEngine(shared, budget, k=10, num_buckets="auto",
                                coalesce_lanes=coalesce_lanes)

    outs, times = _timed_rounds({
        "mono": lambda: _dist_results([mono.search(qb) for qb in batches]),
        "mono_pip": lambda: _dist_results(list(mono.search_batches(batches))),
        "staged_pip": lambda: _dist_results(
            list(staged.search_batches(batches))),
        "coal_pip": lambda: _dist_results(list(coal.search_batches(batches))),
    })
    _assert_dist_identical(outs["staged_pip"], outs["mono"],
                           "staged != monolithic distributed step")
    _assert_dist_identical(outs["mono_pip"], outs["mono"],
                           "pipelined monolithic != eager monolithic")
    _assert_dist_identical(outs["coal_pip"], outs["mono"],
                           "coalesced staged != monolithic per-batch")

    per_all = per * mesh.devices.size
    recall = float(np.mean([
        distance.recall_at_k(jax.numpy.asarray(ids), gt[s])
        for (ids, _), s in zip(outs["coal_pip"], sels)]))
    speedup = times["mono"] / max(times["coal_pip"], 1e-12)
    speedup_pip = times["mono_pip"] / max(times["coal_pip"], 1e-12)
    csv.add("pipeline/dist_monolithic", times["mono"] / n_q,
            f"stream_wall={times['mono'] * 1e3:.1f}ms "
            f"qps={n_q / times['mono']:.1f} recall={recall:.4f} "
            f"n={per_all} batch={batch} (all rows serve identical results)")
    csv.add("pipeline/dist_monolithic_pipelined", times["mono_pip"] / n_q,
            f"stream_wall={times['mono_pip'] * 1e3:.1f}ms "
            f"qps={n_q / times['mono_pip']:.1f} (step-granularity overlap)")
    csv.add("pipeline/dist_staged_pipelined", times["staged_pip"] / n_q,
            f"stream_wall={times['staged_pip'] * 1e3:.1f}ms "
            f"qps={n_q / times['staged_pip']:.1f} (sub-step pipelining, "
            f"no coalescing)")
    csv.add("pipeline/dist_staged_coalesced", times["coal_pip"] / n_q,
            f"stream_wall={times['coal_pip'] * 1e3:.1f}ms "
            f"qps={n_q / times['coal_pip']:.1f} "
            f"coalesce_lanes={coalesce_lanes} "
            f"speedup_vs_monolithic={speedup:.2f}x "
            f"vs_monolithic_pipelined={speedup_pip:.2f}x")
    return {"mono": times["mono"], "mono_pip": times["mono_pip"],
            "staged_pip": times["staged_pip"], "coal_pip": times["coal_pip"],
            "speedup": speedup, "speedup_pip": speedup_pip}


def run(csv: common.Csv, scale: str = "small"):
    x, q, gt = common.dataset("gist-proxy", scale)
    idx = common.cached_graph(
        f"gist-proxy-{scale}-mcgi",
        lambda: build.build_mcgi(x, common.BUILD_CFG))
    out = compare(csv, x, q, gt, idx)
    csv.add("pipeline/headline", 0.0,
            f"double-buffered engine {out['speedup']:.2f}x vs PR2 bucketed "
            f"path on gist-proxy {scale} (identical results)")
    return out


def run_distributed(csv: common.Csv, scale: str = "small"):
    x, q, gt = common.dataset("gist-proxy", scale)
    budget = search.AdaptiveBeamBudget(l_min=16, l_max=96, lam=0.35,
                                       center=10.0)
    out = compare_distributed(csv, x, q, gt, budget=budget)
    csv.add("pipeline/dist_headline", 0.0,
            f"staged+coalesced distributed engine {out['speedup']:.2f}x vs "
            f"monolithic dispatch ({out['speedup_pip']:.2f}x vs monolithic "
            f"pipelined) on the 8-device mesh micro-batch stream, "
            f"gist-proxy {scale} (identical results)")
    return out


def smoke() -> None:
    """~60s CPU smoke (CI): tiny graph; asserts identity + a sane speedup."""
    from repro.data import make_dataset

    x, q = make_dataset("tiny-mixture", seed=0)
    x, q = x[:2000], q[:64]
    gt_d, gt = distance.brute_force_topk(q, x, k=10)
    idx = build.build_mcgi(
        x, build.BuildConfig(degree=16, beam_width=32, iters=1, batch=512,
                             max_hops=64))
    csv = common.Csv()
    budget = search.AdaptiveBeamBudget(l_min=8, l_max=32, lam=0.35)
    out = compare(csv, x, q, gt, idx, budget=budget, num_buckets=4,
                  batch=16, num_batches=8)
    # Identity is asserted inside compare(); the smoke only sanity-bounds the
    # schedule (CI boxes are noisy — the >=1.2x claim is the full run's).
    assert out["pipelined"] <= out["pr2"] * 1.15, out
    print(f"# smoke ok: pipelined {out['speedup']:.2f}x vs PR2 bucketed, "
          f"identical results")


def smoke_distributed() -> None:
    """~90s CPU smoke (CI multi-device matrix): the staged distributed path
    serves identical results (asserted inside compare_distributed) on a
    micro-batch stream, and the coalesced pipeline beats per-micro-batch
    monolithic dispatch."""
    from repro.data import make_dataset

    x, q = make_dataset("tiny-mixture", seed=0)
    x, q = np.asarray(x), np.asarray(q[:64])
    gt_d, gt = distance.brute_force_topk(
        jax.numpy.asarray(q), jax.numpy.asarray(x[:4000]), k=10)
    gt = np.asarray(gt)
    csv = common.Csv()
    budget = search.AdaptiveBeamBudget(l_min=8, l_max=32, lam=0.35,
                                       center=10.0)
    out = compare_distributed(csv, x[:4000], q, gt, budget=budget,
                              batch=4, num_batches=24, coalesce_lanes=32)
    # Identity is asserted inside compare_distributed(); the smoke bounds
    # the schedule (CI boxes are noisy — the full run carries the claim).
    assert out["coal_pip"] <= out["mono"] * 1.1, out
    print(f"# smoke ok: staged+coalesced distributed {out['speedup']:.2f}x "
          f"vs monolithic dispatch, identical results")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="~60s CI smoke of the pipelined engine")
    ap.add_argument("--distributed", action="store_true",
                    help="distributed rows on 8 devices (virtual host "
                         "devices under JAX_PLATFORMS=cpu; must be the "
                         "process entry)")
    ap.add_argument("--scale", default="small", choices=("small", "paper"))
    args = ap.parse_args()
    if args.smoke and args.distributed:
        smoke_distributed()
    elif args.smoke:
        smoke()
    elif args.distributed:
        out_csv = common.Csv()
        print("name,us_per_call,derived")
        run_distributed(out_csv, scale=args.scale)
    else:
        out_csv = common.Csv()
        print("name,us_per_call,derived")
        run(out_csv, scale=args.scale)
