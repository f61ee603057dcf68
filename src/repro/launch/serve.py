"""MCGI serving launcher — build (or load) a tiered index and serve batched
queries through the unified serving engine (:mod:`repro.serving`), reporting
the paper's operational metrics (QPS, recall if ground truth is available,
I/O per query, modelled SSD latency).

    PYTHONPATH=src python -m repro.launch.serve --dataset tiny-mixture \
        --beam 48 --batch 64 --num-batches 20 [--index PATH] [--online] \
        [--disk PATH] [--distributed N] [--kernel reference|pallas|auto] \
        [--adaptive [--l-min 16] [--l-max 64] [--lam 0.35] [--buckets auto] \
         [--pipeline] [--calibrate [--joint | --per-shard] \
          [--recall-target 0.95]]]

``--adaptive`` serves the per-query adaptive-beam engine (Prop. 4.2
deployed): each query's budget is set from its probe-phase LID, so easy
queries stop paying slow-tier reads for hard ones. ``--buckets`` controls
the continue phase's bucket family — ``auto`` (default) picks it per batch
from the granted-budget histogram, an integer pins the fixed family, 0/1
disables bucketing. ``--pipeline`` streams the batches through the
double-buffered executor (batch i+1's probe dispatched before batch i is
collected) instead of blocking per batch — identical results, higher
throughput. ``--calibrate`` refits ``lam`` (and ``hop_factor`` if binding)
to ``--recall-target`` on a held-out sample before serving; with ``--joint``
the budget floor ``l_min`` is fitted too (smallest feasible floor, then the
largest feasible lam at it). All serving paths — fixed and adaptive — lower
through :class:`repro.serving.SearchEngine`.

``--disk PATH`` serves the slow tier out of core: a block-aligned store
(one checksummed block per node: vector + adjacency) is written to PATH if
absent and the rerank fetches candidate blocks from it — through the
hot-node cache (entry-proximal nodes pinned) and, with ``--pipeline``, the
async-prefetch stage that overlaps batch i's block reads with batch i+1's
continue programs. Results are bit-identical to the in-memory slow tier;
the final report adds measured block-read latency next to the
``DiskTierModel``'s modelled figure plus the cache hit rate and fetch
latency percentiles. ``--cache-nodes`` / ``--pin-nodes`` size the LRU and
the statically pinned entry-proximal set; ``--hot-nodes`` (with
``--hot-chunk`` / ``--freq-decay``) adds the frequency-aware hot tier —
per-stream promotion/demotion counters are reported at the end; and
``--io-workers`` sizes the tier's prefetch pool.

``--serve`` (with ``--adaptive``) runs the closed-loop *front door* instead
of the batch benchmark: live requests are paced at ``--qps`` (Poisson or
bursty ``--arrival``), admitted into two QoS classes (``--interactive-frac``
splits the mix) with their own deadlines (``--deadline-ms`` /
``--batch-deadline-ms``) and their own budget-law engines over the shared
backend — with ``--calibrate``, one (lam, l_min) law per class is fitted to
``--interactive-recall-target`` / ``--recall-target``.  The report is
per-class: outcome counts, latency p50/p99 vs the deadline, recall, and the
per-class I/O counters (mean granted budget, walk hops).  Timing runs on
the production wall-clock seam (:class:`repro.serving.server.WallClock` +
``ThreadDispatcher``); the deterministic virtual-clock twin of this loop is
``benchmarks/serving_load.py``.

``--filter-frac F`` serves a multi-tenant workload: the corpus is split
into ~``1/F`` namespaces and every query carries an *allowed* mask for its
namespace, enforced in-graph (the packed filter pre-seeds the walk's
visited bitset — excluded nodes are never expanded and never returned, no
post-filtering). Recall is reported against the per-namespace ground truth
and the report counts out-of-filter results (must be 0). Single-host only:
the distributed backend has no global-id view for the bitset (see ROADMAP
carry-overs).

``--distributed N`` shards the dataset over the first N devices (the chips
of a TPU host; N virtual host devices when the process is pinned to
``JAX_PLATFORMS=cpu``) with one locally built sub-graph per shard, and serves scatter-gather through a
``DistributedBackend``. With ``--adaptive`` the distributed step runs
*staged* at full engine parity — probe checkpointed at the horizon, host
bucket scheduling between mesh programs, per-bucket continues into the
hedged merge — so ``--pipeline`` overlaps batch i+1's distributed probe
with batch i's bucketing and continues. ``--calibrate --per-shard`` fits
one (lam, l_min) law per shard on shard-local held-out queries and serves
the laws as runtime arrays. On the CPU it sets XLA_FLAGS itself, so run it
as the process entry point (the flag must precede the first jax import).

The first ``[serve]`` line names the platform, device kind and device count.
The run exits non-zero when any front-door request ends in ``"error"`` (or
a deadline hedge raised) and when a filtered run returns an out-of-filter
id (:func:`run_failures`).
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Mapping

from repro import runtime


def _distributed_engine(args, x, queries, budget_cfg, num_buckets):
    """Shard the dataset over the virtual mesh and build the distributed
    serving engine (staged at engine parity when adaptive; per-shard
    calibrated budget laws with --calibrate --per-shard). Returns
    (engine, x truncated to the sharded row count)."""
    import numpy as np

    from repro import compat, serving
    from repro.core import build, calibrate
    from repro.distributed import sharded_search as ss

    mesh = compat.make_mesh((args.distributed,), ("data",),
                            devices=runtime.first_devices(args.distributed))
    n_shards = mesh.devices.size
    t0 = time.time()
    arrays, per = ss.build_sharded_arrays(
        x, mesh, build_cfg=build.BuildConfig(), m_pq=args.m_pq)
    print(f"[serve] sharded build in {time.time()-t0:.1f}s: "
          f"{per * n_shards} points over {n_shards} shards ({per}/shard)")
    shard_laws = None
    if args.calibrate:
        fit = calibrate.calibrate_budget_law_per_shard(
            calibrate.shard_exact_recall_evals(
                np.asarray(arrays["vectors"]), np.asarray(arrays["adj"]),
                np.asarray(arrays["entries"]), np.asarray(queries),
                n_shards, k=args.k, sample=args.calib_sample),
            budget_cfg, recall_target=args.recall_target,
            n_shards=n_shards)
        shard_laws = fit.law_arrays()
        # hop_factor is global in the step: serve the largest fitted
        # escalation so no shard runs under a tighter deadline than it was
        # calibrated to.
        budget_cfg = fit.serving_budget(budget_cfg)
        print(f"[serve] per-shard laws "
              f"({'hit' if fit.achieved else 'partial'}): "
              f"lam={np.round(shard_laws[0], 3).tolist()} "
              f"l_min={shard_laws[1].tolist()} "
              f"hop_factor={budget_cfg.hop_factor}")
    backend = serving.DistributedBackend(
        mesh, arrays, beam_width=args.beam, max_hops=2048, k=args.k,
        query_chunk=args.batch, beam_budget=budget_cfg,
        budget_buckets=(4 if budget_cfg is not None else None),
        shard_laws=shard_laws, step_kernel=args.kernel)
    engine = serving.SearchEngine(backend, budget_cfg, k=args.k,
                                  num_buckets=num_buckets)
    return engine, x[: per * n_shards]


def _report_disk_tier(backend, model) -> None:
    """Measured slow-tier figures next to the DiskTierModel's modelled ones
    (stats stay readable after engine close)."""
    st = backend.slow_tier.stats()
    lat = backend.slow_tier.fetch_latency_us()
    print(f"[serve] disk tier: hit_rate={st['hit_rate']:.3f} "
          f"(hits={st['cache_hits']} misses={st['cache_misses']}) "
          f"blocks_read={st['blocks_read']} "
          f"measured_read={st['measured_read_us']:.1f}us vs "
          f"modelled={model.read_latency_us:.1f}us "
          f"fetch p50={lat['fetch_p50_us']:.0f}us "
          f"p99={lat['fetch_p99_us']:.0f}us")
    if "hot_capacity" in st:
        print(f"[serve] hot tier: resident={st['hot_nodes']}"
              f"/{st['hot_capacity']} hot_hits={st['hot_hits']} "
              f"promotions={st['promotions']} "
              f"demotions={st['demotions']} "
              f"ticks={st['promotion_ticks']} "
              f"promotion_io_blocks={st['promotion_io_blocks']}")


def run_failures(door_stats: Mapping[str, int] | None = None,
                 out_of_filter: int = 0) -> list[str]:
    """Why a serving run must exit non-zero (empty when it may exit 0).

    ``door_stats`` is :meth:`repro.serving.server.FrontDoor.stats`: any
    request that ended in ``"error"`` or any deadline hedge that raised is
    a failure — shed and timeout are load outcomes, not faults.
    ``out_of_filter`` counts filtered-run results outside their filter."""
    reasons = []
    if door_stats is not None:
        if door_stats.get("error", 0):
            reasons.append(f"{door_stats['error']} front-door request(s) "
                           "ended in status 'error'")
        if door_stats.get("partial_errors", 0):
            reasons.append(f"{door_stats['partial_errors']} deadline "
                           "hedge(s) raised")
    if out_of_filter:
        reasons.append(f"{out_of_filter} result(s) outside their filter")
    return reasons


def _serve_front_door(args, backend, index, queries, gt_i,
                      budget_cfg, num_buckets) -> dict:
    """Closed-loop front-door serving on the wall clock: one budget-law
    engine per QoS class over the shared backend, arrival pacing at --qps,
    per-class SLO report.  Returns the front door's stats."""
    import dataclasses

    import numpy as np

    from repro import serving
    from repro.core import calibrate
    from repro.serving import server as sv

    laws = {"interactive": budget_cfg,
            "batch": dataclasses.replace(budget_cfg,
                                         l_min=budget_cfg.l_max)}
    if args.calibrate:
        def make_eval(cfg):
            return calibrate.tiered_recall_eval(
                index, queries, np.asarray(gt_i), k=args.k,
                sample=args.calib_sample, base_cfg=cfg)

        fits = calibrate.calibrate_budget_law_per_class(
            make_eval, budget_cfg,
            {"interactive": args.interactive_recall_target,
             "batch": args.recall_target},
            joint=args.joint)
        laws = calibrate.class_budget_cfgs(fits, budget_cfg)
        for name, r in fits.items():
            print(f"[serve] class {name}: lam={r.lam:.4f} "
                  f"l_min={laws[name].l_min} recall={r.recall:.4f} "
                  f"({'hit' if r.achieved else 'MISSED'} {r.target:.2f})")
    lanes = {"interactive": 8, "batch": 32}
    engines = {name: serving.SearchEngine(backend, law, k=args.k,
                                          num_buckets=num_buckets)
               for name, law in laws.items()}
    classes = [
        sv.QoSClass("interactive", deadline_s=args.deadline_ms / 1e3,
                    batch_window_s=0.002, max_lanes=lanes["interactive"],
                    lane_quantum=lanes["interactive"]),
        sv.QoSClass("batch", deadline_s=args.batch_deadline_ms / 1e3,
                    batch_window_s=0.02, max_lanes=lanes["batch"],
                    lane_quantum=lanes["batch"]),
    ]
    qn = np.asarray(queries)
    for name, eng in engines.items():      # warm the padded dispatch shape
        eng.search(qn[:lanes[name]])
    rng = np.random.default_rng(0)
    n = args.requests
    if args.arrival == "poisson":
        arr = np.cumsum(rng.exponential(1.0 / args.qps, size=n))
    else:                                  # bursty: on/off modulated Poisson
        out, t, on, phase_end = [], 0.0, True, 0.05
        while len(out) < n:
            t += float(rng.exponential(
                1.0 / (args.qps * 8.0 if on else args.qps / 8.0)))
            if t >= phase_end:
                t, on = phase_end, not on
                phase_end += 0.05 if on else 0.2
            else:
                out.append(t)
        arr = np.asarray(out)
    rows = rng.integers(0, qn.shape[0], size=n)
    cls_of = ["interactive" if rng.random() < args.interactive_frac
              else "batch" for _ in range(n)]
    door = sv.FrontDoor(engines, classes)
    t0 = time.perf_counter()
    futs = []
    for t_arr, row, cls in zip(arr, rows, cls_of):
        lag = t_arr - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        futs.append((int(row), cls, door.submit(qn[row], cls=cls)))
    door.close(wait=True, timeout=600)
    wall = time.perf_counter() - t0
    gt = np.asarray(gt_i)
    print(f"[serve] front door: {n} requests in {wall:.2f}s "
          f"({n / wall:.1f} qps, offered {args.qps:.0f}, "
          f"arrival={args.arrival})")
    for c in classes:
        rs = [(row, f.result(timeout=0)) for row, cls, f in futs
              if cls == c.name]
        lat = [r.latency * 1e3 for _, r in rs if r.status != "shed"]
        ok = [(row, r) for row, r in rs if r.status == "ok"]
        counts: dict[str, int] = {}
        for _, r in rs:
            counts[r.status] = counts.get(r.status, 0) + 1
        rec = (float(np.mean([np.isin(r.ids, gt[row][: args.k]).mean()
                              for row, r in ok])) if ok else float("nan"))
        bud = (float(np.mean([r.budget for _, r in ok
                              if r.budget is not None]))
               if ok else float("nan"))
        hops = (float(np.mean([r.hops for _, r in ok
                               if r.hops is not None]))
                if ok else float("nan"))
        p50 = float(np.percentile(lat, 50)) if lat else float("nan")
        p99 = float(np.percentile(lat, 99)) if lat else float("nan")
        print(f"[serve] class {c.name}: {counts} "
              f"lat p50={p50:.1f}ms p99={p99:.1f}ms "
              f"(deadline {c.deadline_s * 1e3:.0f}ms) "
              f"recall@{args.k}={rec:.4f} meanL={bud:.1f} hops={hops:.1f}")
    st = door.stats()
    print(f"[serve] admission: submitted={st['submitted']} "
          f"admitted={st['admitted']} shed={st['shed']} "
          f"dispatches={st['dispatches']} "
          f"max_open={st['max_open_lanes']}/{door.max_queue} "
          f"errors={st['error']} partial_errors={st['partial_errors']}")
    return st


def buckets_arg(value: str):
    """--buckets accepts 'auto' (histogram-picked family) or an integer."""
    if value == "auto":
        return value
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto' or an integer, got {value!r}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="tiny-mixture")
    ap.add_argument("--beam", type=int, default=48)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--num-batches", type=int, default=10)
    ap.add_argument("--m-pq", type=int, default=8)
    ap.add_argument("--index", default=None, help="load/save index path")
    ap.add_argument("--disk", default=None, metavar="PATH",
                    help="serve the slow tier from a block-aligned on-disk "
                         "store at PATH (written there first if absent); "
                         "bit-identical results, real block I/O")
    ap.add_argument("--cache-nodes", type=int, default=4096,
                    help="with --disk: hot-node LRU capacity")
    ap.add_argument("--pin-nodes", type=int, default=256,
                    help="with --disk: statically pinned entry-proximal "
                         "node count (0 disables pinning)")
    ap.add_argument("--hot-nodes", type=int, default=0,
                    help="with --disk: capacity of the frequency-aware hot "
                         "tier (0 disables it); hot nodes are promoted in "
                         "chunks off the serving path and demoted as the "
                         "traffic's hot set drifts — results stay "
                         "bit-identical")
    ap.add_argument("--hot-chunk", type=int, default=256,
                    help="with --hot-nodes: max promotions per tick")
    ap.add_argument("--freq-decay", type=float, default=0.5,
                    help="with --hot-nodes: per-tick EMA decay of the "
                         "per-node access frequencies")
    ap.add_argument("--io-workers", type=int, default=None,
                    help="with --disk: prefetch worker threads (default: "
                         "1 for the rerank-only tier; the out-of-core "
                         "backend adopts its io_groups)")
    ap.add_argument("--online", action="store_true",
                    help="build with Online-MCGI (Algorithm 2)")
    ap.add_argument("--vamana", action="store_true",
                    help="baseline build (static alpha=1.2)")
    ap.add_argument("--adaptive", action="store_true",
                    help="per-query adaptive beam budgets (Prop. 4.2)")
    ap.add_argument("--l-min", type=int, default=16)
    ap.add_argument("--l-max", type=int, default=None,
                    help="adaptive budget ceiling (default: --beam)")
    ap.add_argument("--lam", type=float, default=0.35)
    ap.add_argument("--buckets", default="auto", type=buckets_arg,
                    help="continue-phase bucket family: 'auto' (histogram-"
                         "picked, default), an integer count, or 0/1 for "
                         "the single-program path")
    ap.add_argument("--pipeline", action="store_true",
                    help="double-buffered batch stream (identical results, "
                         "higher throughput)")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit lam to --recall-target on a held-out sample "
                         "before serving")
    ap.add_argument("--joint", action="store_true",
                    help="with --calibrate: fit (lam, l_min) jointly")
    ap.add_argument("--per-shard", action="store_true",
                    help="with --calibrate --distributed: fit one "
                         "(lam, l_min) law per shard on shard-local "
                         "held-out queries")
    ap.add_argument("--recall-target", type=float, default=0.95)
    ap.add_argument("--calib-sample", type=int, default=256)
    ap.add_argument("--filter-frac", type=float, default=None, metavar="F",
                    help="multi-tenant filtered serving: split the corpus "
                         "into ~1/F namespaces and enforce each query's "
                         "namespace in-graph (recall measured against the "
                         "filtered ground truth)")
    ap.add_argument("--serve", action="store_true",
                    help="closed-loop front-door serving (QoS classes, "
                         "deadlines, load shedding) instead of the batch "
                         "benchmark; requires --adaptive")
    ap.add_argument("--qps", type=float, default=200.0,
                    help="with --serve: offered arrival rate")
    ap.add_argument("--requests", type=int, default=256,
                    help="with --serve: total requests to pace in")
    ap.add_argument("--interactive-frac", type=float, default=0.5,
                    help="with --serve: fraction of requests in the "
                         "interactive class (rest are batch)")
    ap.add_argument("--deadline-ms", type=float, default=100.0,
                    help="with --serve: interactive-class deadline")
    ap.add_argument("--batch-deadline-ms", type=float, default=2000.0,
                    help="with --serve: batch-class deadline")
    ap.add_argument("--arrival", default="poisson",
                    choices=("poisson", "bursty"),
                    help="with --serve: arrival process (bursty = on/off "
                         "modulated Poisson)")
    ap.add_argument("--interactive-recall-target", type=float, default=0.85,
                    help="with --serve --calibrate: interactive class's "
                         "recall target (--recall-target is the batch "
                         "class's)")
    ap.add_argument("--distributed", type=int, default=0, metavar="N",
                    help="shard over N virtual host devices and serve "
                         "scatter-gather (staged at engine parity with "
                         "--adaptive)")
    ap.add_argument("--kernel", default="auto",
                    choices=("reference", "pallas", "auto"),
                    help="beam-walk hop implementation: the reference hop "
                         "chain, the fused Pallas beam step (interpret mode "
                         "off-TPU), or auto (fused on TPU / under "
                         "REPRO_PALLAS_INTERPRET=1, reference otherwise; "
                         "default) — bit-identical results either way")
    args = ap.parse_args()
    num_buckets = args.buckets
    if not args.adaptive and (args.calibrate or args.pipeline
                              or (num_buckets != "auto" and num_buckets > 1)):
        ap.error("--calibrate/--buckets/--pipeline configure the adaptive "
                 "engine; pass --adaptive as well")
    if args.joint and not args.calibrate:
        ap.error("--joint refines --calibrate; pass both")
    if args.serve and not args.adaptive:
        ap.error("--serve runs per-class budget-law engines (and deadline "
                 "hedges need the staged probe); pass --adaptive")
    if args.serve and args.distributed:
        ap.error("--serve is the single-host front door (the distributed "
                 "backend has no host probe view for deadline partials)")
    if args.serve and args.pipeline:
        ap.error("--pipeline is the batch-stream benchmark mode; --serve "
                 "paces individual requests through the front door")
    if args.per_shard and not (args.calibrate and args.distributed):
        ap.error("--per-shard refines --calibrate for --distributed serving;"
                 " pass all three")
    if args.distributed and args.calibrate and not args.per_shard:
        ap.error("distributed calibration is per-shard (shard geometry "
                 "differs); pass --per-shard")
    if args.filter_frac is not None:
        if not (0.0 < args.filter_frac <= 1.0):
            ap.error("--filter-frac must be in (0, 1]")
        if args.distributed:
            ap.error("--filter-frac is single-host: the filter bitset is "
                     "indexed by global node id, which the sharded walk "
                     "has no view of")
        if args.serve:
            ap.error("--filter-frac drives the batch benchmark; the front "
                     "door paces unfiltered requests")
    if args.distributed and (args.index or args.online or args.vamana):
        ap.error("--distributed builds per-shard sub-graphs in process; "
                 "--index/--online/--vamana apply to single-host serving")
    if args.distributed and args.disk:
        ap.error("--disk is the single-host out-of-core slow tier; the "
                 "distributed path keeps per-shard slow tiers in memory")
    if args.distributed:
        if "jax" in sys.modules:
            ap.error("--distributed must set XLA_FLAGS before jax is "
                     "imported; run repro.launch.serve as the process "
                     "entry point")
        runtime.virtual_cpu_devices(args.distributed)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import serving
    from repro.core import build, distance, online, search
    from repro.data import make_dataset
    from repro.index import build_tiered_index, load_index, save_index
    from repro.index.disk import DiskTierModel

    runtime.use_compile_cache()
    dev = jax.devices()
    print(f"[serve] platform={dev[0].platform} "
          f"device_kind={dev[0].device_kind} devices={len(dev)} "
          f"kernel={args.kernel}")
    x, queries = make_dataset(args.dataset, seed=0)
    import pathlib

    model = DiskTierModel()
    budget_cfg = None
    if args.adaptive:
        l_max = args.l_max or args.beam
        budget_cfg = search.AdaptiveBeamBudget(
            l_min=min(args.l_min, l_max), l_max=l_max, lam=args.lam)

    if args.distributed:
        engine, x = _distributed_engine(args, x, queries, budget_cfg,
                                        num_buckets)
        gt_d, gt_i = distance.brute_force_topk(queries, x, k=args.k)
        rerank_batch = budget_cfg.l_max if budget_cfg else args.beam
    else:
        if args.index and pathlib.Path(args.index).exists():
            index = load_index(args.index)
            print(f"[serve] loaded index: n={index.n}")
        else:
            cfg = build.BuildConfig()
            t0 = time.time()
            if args.online:
                graph = online.build_online_mcgi(x, cfg, progress=print)
            elif args.vamana:
                graph = build.build_vamana(x, 1.2, cfg, progress=print)
            else:
                graph = build.build_mcgi(x, cfg, progress=print)
            index = build_tiered_index(x, graph, m_pq=args.m_pq)
            print(f"[serve] built index in {time.time()-t0:.1f}s "
                  f"(fast tier {index.fast_tier_bytes()/1e6:.1f}MB, "
                  f"slow tier {index.slow_tier_bytes()/1e6:.1f}MB)")
            if args.index:
                save_index(args.index, index)

        gt_d, gt_i = distance.brute_force_topk(queries, x, k=args.k)
        slow_tier = None
        if args.disk:
            from repro.index import open_or_build_slow_tier

            slow_tier = open_or_build_slow_tier(
                args.disk, index, cache_nodes=args.cache_nodes,
                pin_nodes=args.pin_nodes, io_workers=args.io_workers,
                hot_nodes=args.hot_nodes, hot_chunk=args.hot_chunk,
                freq_decay=args.freq_decay,
                log=lambda m: print(f"[serve] {m}"))
            hot_part = (f" hot={args.hot_nodes} (chunk={args.hot_chunk} "
                        f"decay={args.freq_decay})" if args.hot_nodes else "")
            print(f"[serve] disk slow tier: n={slow_tier.store.n} "
                  f"block={slow_tier.store.block_size}B "
                  f"pinned={slow_tier.stats()['pinned_nodes']}" + hot_part)
        backend = serving.TieredBackend(index, slow_tier=slow_tier,
                                        step_kernel=args.kernel)
        if args.serve:
            st = _serve_front_door(args, backend, index, queries, gt_i,
                                   budget_cfg, num_buckets)
            if args.disk:
                _report_disk_tier(backend, model)
            _exit_on_failures(run_failures(st))
            return
        if args.adaptive:
            engine = serving.SearchEngine(backend, budget_cfg, k=args.k,
                                          num_buckets=num_buckets)
            if args.calibrate:
                result = engine.recalibrate(
                    queries, gt_i, recall_target=args.recall_target,
                    joint=args.joint, sample=args.calib_sample)
                fitted = engine.budget_cfg
                print(f"[serve] calibrated lam={result.lam:.4f} "
                      f"l_min={fitted.l_min} hop_factor={result.hop_factor} "
                      f"recall={result.recall:.4f} "
                      f"(target {result.target:.2f}, "
                      f"{'hit' if result.achieved else 'MISSED'}, "
                      f"{len(result.history)} evals)")
            rerank_batch = engine.budget_cfg.l_max
        else:
            engine = serving.SearchEngine(backend, None, k=args.k,
                                          beam_width=args.beam)
            rerank_batch = args.beam

    # Warmup compile.
    _ = engine.search(queries[: args.batch])
    rng = np.random.default_rng(0)
    sels = [rng.integers(0, queries.shape[0], args.batch)
            for _ in range(args.num_batches)]
    qn = np.asarray(queries)
    batches = [qn[s] for s in sels]
    xn = np.asarray(x)
    masks = None
    gts = [np.asarray(gt_i)[s] for s in sels]
    out_of_filter = 0
    if args.filter_frac is not None:
        # Multi-tenant namespaces: each node lives in one of ~1/F tenants,
        # each query is allowed exactly its tenant's nodes.  Ground truth is
        # recomputed per batch inside the namespace — unfiltered gt would
        # mis-score a correctly filtered answer.
        tenants = max(2, round(1.0 / args.filter_frac))
        ns_rng = np.random.default_rng(1)
        node_ns = ns_rng.integers(0, tenants, size=xn.shape[0])
        masks, gts = [], []
        for s, qb in zip(sels, batches):
            q_ns = ns_rng.integers(0, tenants, size=qb.shape[0])
            allowed = node_ns[None, :] == q_ns[:, None]
            d2 = np.einsum("qnd,qnd->qn", qb[:, None] - xn[None],
                           qb[:, None] - xn[None], dtype=np.float32)
            d2[~allowed] = np.inf
            masks.append(allowed)
            gts.append(np.argsort(d2, axis=1)[:, : args.k])
        print(f"[serve] filtered serving: {tenants} namespaces "
              f"(~{xn.shape[0] // tenants} nodes each), masks enforced "
              f"in-graph")
        _ = engine.search(batches[0], filter=masks[0])  # warm filtered path
    lat_ms, recalls, ios, budgets = [], [], [], []

    def account(res, sel, t0, bi):
        nonlocal out_of_filter
        lat_ms.append((time.perf_counter() - t0) * 1e3)
        recalls.append(float(distance.recall_at_k(
            jnp.asarray(res.ids), jnp.asarray(gts[bi]))))
        if masks is not None:
            ids = np.asarray(res.ids)
            ok = masks[bi][np.arange(ids.shape[0])[:, None],
                           np.maximum(ids, 0)] | (ids < 0)
            out_of_filter += int((~ok).sum())
        if res.stats is not None:
            ios.append(float(np.mean(np.asarray(res.stats.hops))))
        if res.astats is not None:
            budgets.append(float(np.mean(np.asarray(res.astats.budget))))

    t_all = time.perf_counter()
    if args.pipeline:
        # Double-buffered stream: per-batch latency is completion-to-
        # completion (the pipeline hides the probe sync inside it).
        t0 = t_all
        stream = engine.search_batches(batches, filter=masks)
        for bi, (res, sel) in enumerate(zip(stream, sels)):
            account(res, sel, t0, bi)
            t0 = time.perf_counter()
    else:
        for bi, (qb, sel) in enumerate(zip(batches, sels)):
            t0 = time.perf_counter()
            flt = None if masks is None else masks[bi]
            account(engine.search(qb, filter=flt), sel, t0, bi)
    total = time.perf_counter() - t_all
    if args.pipeline and len(lat_ms) > 1:
        # The first completion spans the whole pipeline fill (two batches
        # dispatched + scheduled before anything is gathered); keep it in
        # the throughput figure but not in the steady-state percentiles.
        lat_ms = lat_ms[1:]
    qps = args.batch * args.num_batches / total
    # The monolithic distributed step reports no hop counters (the staged
    # adaptive path does); skip the I/O-derived figures when absent.
    io_part = ssd_part = ""
    if ios:
        ssd_ms = float(model.latency_us(
            jnp.float32(np.mean(ios)), rerank_reads=rerank_batch,
            overlapped=args.pipeline)) / 1e3
        io_part = f"io/query={np.mean(ios):.1f} "
        ssd_part = f" ssd_model={ssd_ms:.2f}ms/query"
    extra = f"meanL={np.mean(budgets):.1f} " if budgets else ""
    mode = "pipelined" if args.pipeline else "per-batch"
    print(f"[serve] recall@{args.k}={np.mean(recalls):.4f} qps={qps:.1f} "
          f"{io_part}{extra}({mode}) "
          f"batch_lat p50={np.percentile(lat_ms,50):.1f}ms "
          f"p99={np.percentile(lat_ms,99):.1f}ms" + ssd_part)
    if masks is not None:
        print(f"[serve] filter enforcement: out_of_filter={out_of_filter} "
              f"(in-graph, must be 0)")
    if not args.distributed and args.disk:
        _report_disk_tier(backend, model)
    _exit_on_failures(run_failures(out_of_filter=out_of_filter))


def _exit_on_failures(reasons: list[str]) -> None:
    if reasons:
        raise SystemExit("[serve] FAILED: " + "; ".join(reasons))


if __name__ == "__main__":
    main()
