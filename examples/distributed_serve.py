"""Distributed MCGI serving on a virtual 8-device mesh, lowered through the
unified serving engine (``repro.serving.SearchEngine`` over a
``DistributedBackend``): shard the index, fan out queries, merge global
top-k, then kill a shard and watch the hedged merge degrade gracefully — the
fault-tolerance story at example scale.

With a budget law on both the backend and the engine, the distributed step
runs *staged* at full engine parity: the probe program checkpoints every
shard's walk at the probe horizon, the host buckets queries by granted
budget (the mean over shards — a lane's expected per-shard work) while the
next batch's probe runs on the mesh, and per-bucket continue programs
resume the warm walks into the hedged merge. Results are bit-identical to the monolithic
single-program step (asserted below). The example finishes with a per-shard
(lam, l_min) calibration pass — each shard's sub-graph has its own geometry,
so one global law under- or over-budgets some shards.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/distributed_serve.py

Pinned to the CPU it asks for 8 virtual host devices (run it as a script,
not inside another jax process); on a host with 8 chips the mesh is built
from the chips.
"""
from repro import runtime

runtime.virtual_cpu_devices(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import BuildConfig, brute_force_topk, recall_at_k  # noqa: E402
from repro.core import calibrate  # noqa: E402
from repro.data import make_dataset  # noqa: E402
from repro.distributed import sharded_search as ss  # noqa: E402


def main():
    mesh = compat.make_mesh((2, 4), ("data", "model"),
                            devices=runtime.first_devices(8))
    n_shards = mesh.devices.size
    x, queries = make_dataset("tiny-mixture", seed=0)
    queries = np.asarray(queries[:64])

    cfg = BuildConfig(degree=16, beam_width=32, iters=1, batch=256, max_hops=64)
    arrays, per = ss.build_sharded_arrays(x, mesh, build_cfg=cfg, m_pq=8)
    x = np.asarray(x)[: per * n_shards]
    print(f"[dist] {per * n_shards} points over {n_shards} shards "
          f"({per}/shard)")
    gt_d, gt_ids = brute_force_topk(jnp.asarray(queries), jnp.asarray(x), k=10)

    from repro import serving  # noqa: E402

    backend = serving.DistributedBackend(
        mesh, arrays, beam_width=32, max_hops=64, k=10, query_chunk=16)
    engine = serving.SearchEngine(backend, k=10)

    # Stream two chunks through the pipelined executor: batch 1 is
    # dispatched before batch 0 is collected (step-granularity overlap for
    # the fixed-beam path).
    res = list(engine.search_batches([queries[:32], queries[32:]]))
    gids = np.concatenate([r.ids for r in res])
    print(f"[dist] all shards up:   recall@10="
          f"{float(recall_at_k(jnp.asarray(gids), gt_ids)):.4f} "
          f"(2-batch double-buffered stream)")

    # Straggler/fault injection: shard 5 misses its deadline — a runtime
    # mask on the live engine, no recompilation.
    flag = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "model")))
    ok = jnp.ones((n_shards,), jnp.bool_).at[5].set(False)
    backend.set_shard_ok(jax.device_put(ok, flag))
    res = engine.search(queries)
    r = float(recall_at_k(jnp.asarray(res.ids), gt_ids))
    print(f"[dist] shard 5 dropped: recall@10={r:.4f} "
          f"(graceful: lost ~1/{n_shards} of the data, no recompilation, "
          f"no stall)")
    assert (res.extras["shard_ids"] != 5).all()
    backend.set_shard_ok(jax.device_put(jnp.ones((n_shards,), jnp.bool_),
                                        flag))

    # Adaptive per-query budgets on every shard (Prop. 4.2 in the engine),
    # served *staged*: the engine holds the same budget law as the backend,
    # so probe / host-bucket / continue are separate mesh programs and
    # search_batches overlaps batch i+1's probe with batch i's bucketing
    # and continues — sub-step pipelining for the distributed backend.
    from repro.core.search import AdaptiveBeamBudget
    # Pinned LID center: batch-mean centering would make budgets depend on
    # which queries share a probe chunk, and the staged stream's chunking
    # differs from the monolithic full-batch step — the bit-identity shown
    # below is a property of the *scheduling*, so the reducer is pinned.
    budget = AdaptiveBeamBudget(l_min=8, l_max=32, lam=0.35, center=8.0)
    staged_backend = serving.DistributedBackend(
        mesh, arrays, beam_width=32, max_hops=64, k=10, query_chunk=16,
        beam_budget=budget, budget_buckets=4)
    adaptive = serving.SearchEngine(staged_backend, budget, k=10,
                                    num_buckets="auto")
    batches = [queries[:16], queries[16:40], queries[40:]]
    res = list(adaptive.search_batches(batches))
    gids = np.concatenate([r.ids for r in res])
    r = float(recall_at_k(jnp.asarray(gids), gt_ids))
    io = float(np.mean(np.concatenate(
        [np.asarray(b.stats.hops) for b in res])))
    print(f"[dist] staged adaptive:  recall@10={r:.4f} "
          f"io/query={io:.0f} (probe checkpointed at the horizon, "
          f"budget-bucketed continues, pipelined stream)")

    # The staged split is result-transparent: the monolithic one-program
    # step returns the same global top-k, bit for bit.
    mono = serving.SearchEngine(serving.DistributedBackend(
        mesh, arrays, beam_width=32, max_hops=64, k=10, query_chunk=16,
        beam_budget=budget, budget_buckets=4), k=10)
    ref = mono.search(queries)
    assert (np.concatenate([b.d2 for b in res]) == ref.d2).all()
    print("[dist] staged == monolithic step (bit-identical d2)")

    # Per-shard budget laws: fit (lam, l_min) on each shard's own held-out
    # sample — shard geometry differs, so the calibrated laws do too — and
    # serve them as runtime arrays (no recompilation on recalibration).
    fit = calibrate.calibrate_budget_law_per_shard(
        calibrate.shard_exact_recall_evals(
            x, np.asarray(arrays["adj"]), np.asarray(arrays["entries"]),
            queries, n_shards, k=10, sample=32),
        budget, recall_target=0.9, n_shards=n_shards, max_iters=3)
    lam_arr, l_min_arr = fit.law_arrays()
    # hop_factor is global in the step: serve the largest fitted escalation
    # (never tighter than any shard's calibrated deadline).
    budget_srv = fit.serving_budget(budget)
    print(f"[dist] per-shard laws:   lam={np.round(lam_arr, 3).tolist()} "
          f"l_min={l_min_arr.tolist()} hop_factor={budget_srv.hop_factor}")
    per_shard = serving.SearchEngine(
        serving.DistributedBackend(
            mesh, arrays, beam_width=32, max_hops=64, k=10, query_chunk=16,
            beam_budget=budget_srv, budget_buckets=4,
            shard_laws=(lam_arr, l_min_arr)),
        budget_srv, k=10, num_buckets="auto")
    res = per_shard.search(queries)
    r = float(recall_at_k(jnp.asarray(res.ids), gt_ids))
    io = float(np.mean(np.asarray(res.stats.hops)))
    print(f"[dist] per-shard serve:  recall@10={r:.4f} io/query={io:.0f} "
          f"(each shard on its own calibrated budget law)")


if __name__ == "__main__":
    main()
