"""Multi-device scenarios, executed in a subprocess with 8 host devices.

Invoked by tests/test_distributed.py as
    python tests/_distributed_worker.py <scenario>
Prints one JSON line with the scenario's measurements.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import json  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import compat  # noqa: E402


def make_mesh(shape=(2, 4), names=("data", "model")):
    return compat.make_mesh(shape, names)


def scenario_sharded_search():
    from repro.core import build, distance
    from repro.distributed import sharded_search as ss
    from repro.pq import pq_encode, train_pq

    mesh = make_mesh()
    n_shards = 8
    n, d = 2048, 32
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d), jnp.float32)
    q = jax.random.normal(jax.random.fold_in(key, 1), (64, d), jnp.float32)
    gt_d, gt_i = distance.brute_force_topk(q, x, k=10)

    # Build one sub-graph per shard (shard-local ids).
    per = n // n_shards
    cfg = build.BuildConfig(degree=12, beam_width=32, iters=1, batch=128,
                            max_hops=64)
    adjs = []
    for s in range(n_shards):
        adjs.append(build.build_with_alpha(
            x[s * per:(s + 1) * per],
            jnp.full((per,), 1.2, jnp.float32), cfg))
    adj = jnp.concatenate(adjs, axis=0)
    book = train_pq(x, m=8, iters=4)
    codes = pq_encode(x, book)

    arrays = {
        "adj": jax.device_put(adj, NamedSharding(mesh, P(("data", "model"), None))),
        "codes": jax.device_put(codes, NamedSharding(mesh, P(("data", "model"), None))),
        "vectors": jax.device_put(x, NamedSharding(mesh, P(("data", "model"), None))),
        "centroids": jax.device_put(book.centroids, NamedSharding(mesh, P())),
    }
    d2, shard_ids, local_ids = ss.distributed_search(
        mesh, arrays, q, beam_width=32, max_hops=64, k=10, query_chunk=16,
        use_pq=True,
    )
    global_ids = np.asarray(shard_ids) * per + np.asarray(local_ids)
    recall = float(distance.recall_at_k(jnp.asarray(global_ids), gt_i))

    # Hedged-read: drop shard 3.
    ok = jnp.ones((n_shards,), jnp.bool_).at[3].set(False)
    ok = jax.device_put(ok, NamedSharding(mesh, P(("data", "model"))))
    d2b, sb, lb = ss.distributed_search(
        mesh, arrays, q, shard_ok=ok, beam_width=32, max_hops=64, k=10,
        query_chunk=16, use_pq=True,
    )
    gids_b = np.asarray(sb) * per + np.asarray(lb)
    recall_drop = float(distance.recall_at_k(jnp.asarray(gids_b), gt_i))
    from_dead = int((np.asarray(sb) == 3).sum())
    print(json.dumps({
        "recall": recall, "recall_dropped_shard": recall_drop,
        "results_from_dead_shard": from_dead,
    }))


def scenario_checkpoint_reshard(tmpdir):
    from repro.training import checkpoint as ckpt

    mesh_a = make_mesh((2, 4))
    mesh_b = make_mesh((4, 2))
    tree = {
        "w": jax.device_put(
            jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8),
            NamedSharding(mesh_a, P("data", "model")),
        ),
        "b": jax.device_put(jnp.ones((16,)), NamedSharding(mesh_a, P("model"))),
    }
    ckpt.save_checkpoint(tmpdir, 5, tree)
    shardings = {
        "w": NamedSharding(mesh_b, P("data", "model")),
        "b": NamedSharding(mesh_b, P("model")),
    }
    restored, step = ckpt.restore_checkpoint(tmpdir, tree, shardings=shardings)
    same = bool(
        (np.asarray(restored["w"]) == np.asarray(tree["w"])).all()
        and (np.asarray(restored["b"]) == np.asarray(tree["b"])).all()
    )
    new_mesh_ok = restored["w"].sharding.mesh.shape == mesh_b.shape
    print(json.dumps({"step": step, "identical": same,
                      "resharded": bool(new_mesh_ok)}))


def scenario_sharded_train_matches_single():
    """One pjit'd train step on the mesh == the same step on one device."""
    from repro.configs import base as cfg_base
    from repro.models import transformer as tfm
    from repro.training import optimizer as opt_mod
    from repro.training import train_step as ts_mod

    mesh = make_mesh()
    spec = cfg_base.get("qwen2-7b")
    cfg = spec.smoke_config
    key = jax.random.PRNGKey(0)
    params = tfm.init_lm(cfg, key)
    tokens = jax.random.randint(key, (8, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    step = ts_mod.make_train_step(
        lambda p, b: tfm.lm_loss(cfg, p, b),
        opt_mod.AdamWConfig(lr=1e-3),
    )
    state = ts_mod.init_train_state(params)
    _, m_single = jax.jit(step)(state, batch)

    from repro.launch import shardings as shard_mod
    state_spec = shard_mod.train_state_specs("lm", jax.eval_shape(lambda: state))
    shardt = jax.tree.map(lambda s: NamedSharding(mesh, s), state_spec,
                          is_leaf=lambda s: isinstance(s, P))
    state_sharded = jax.tree.map(jax.device_put, state, shardt)
    batch_sharded = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("data", None))), batch
    )
    _, m_mesh = jax.jit(step)(state_sharded, batch_sharded)
    print(json.dumps({
        "loss_single": float(m_single["loss"]),
        "loss_mesh": float(m_mesh["loss"]),
    }))


def scenario_moe_expert_parallel():
    """shard_map expert-parallel MoE == reference path (ample capacity)."""
    from repro.models import moe as moe_mod
    from repro.models.layers import ShardCtx

    mesh = make_mesh()
    ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model")
    cfg = moe_mod.MoeConfig(d_model=32, n_experts=8, top_k=2, d_expert=16,
                            n_shared=1, d_shared=16, capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    p = moe_mod.moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 32))
    ref, aux_ref = moe_mod.moe_apply(p, cfg, x, ctx=None, n_groups=1)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
    ep, aux_ep = jax.jit(
        lambda pp, xx: moe_mod.moe_apply_expert_parallel(pp, cfg, xx, ctx)
    )(p, xs)
    print(json.dumps({
        "max_err": float(jnp.abs(ep - ref).max()),
        "aux_err": abs(float(aux_ref) - float(aux_ep)),
    }))


def scenario_merge_modes():
    """flat and hierarchical distributed-search merges agree exactly."""
    from repro.core import build
    from repro.distributed import sharded_search as ss
    from repro.pq import pq_encode, train_pq

    mesh = make_mesh()
    n_shards = 8
    n, d = 1024, 16
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d), jnp.float32)
    q = jax.random.normal(jax.random.fold_in(key, 1), (32, d), jnp.float32)
    per = n // n_shards
    cfg = build.BuildConfig(degree=8, beam_width=16, iters=1, batch=128,
                            max_hops=32)
    adj = jnp.concatenate([
        build.build_with_alpha(x[s * per:(s + 1) * per],
                               jnp.full((per,), 1.2, jnp.float32), cfg)
        for s in range(n_shards)
    ])
    book = train_pq(x, m=4, iters=3)
    codes = pq_encode(x, book)
    row = NamedSharding(mesh, P(("data", "model"), None))
    arrays = {
        "adj": jax.device_put(adj, row),
        "codes": jax.device_put(codes, row),
        "vectors": jax.device_put(x, row),
        "centroids": jax.device_put(book.centroids, NamedSharding(mesh, P())),
    }
    outs = {}
    for mode in ("flat", "hierarchical"):
        d2, sid, lid = ss.distributed_search(
            mesh, arrays, q, beam_width=16, max_hops=32, k=5,
            query_chunk=8, use_pq=True, merge=mode)
        outs[mode] = (np.asarray(sid) * per + np.asarray(lid),
                      np.asarray(d2))
    same_ids = bool((outs["flat"][0] == outs["hierarchical"][0]).all())
    same_d2 = bool(np.allclose(outs["flat"][1], outs["hierarchical"][1]))
    print(json.dumps({"ids_match": same_ids, "d2_match": same_d2}))


def scenario_staged_engine():
    """The staged distributed serving path at engine parity: staged ==
    monolithic (bitwise), pipelined == eager (incl. ragged tails),
    permutation-invariant, coalescing-transparent, identity per-shard laws,
    and graceful mid-stream fault injection with pinned jit caches."""
    from repro import serving
    from repro.core import build, distance
    from repro.core.search import AdaptiveBeamBudget
    from repro.distributed import sharded_search as ss

    mesh = make_mesh()
    n_shards = mesh.devices.size
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2048, 32), jnp.float32)
    q = np.asarray(jax.random.normal(jax.random.fold_in(key, 1), (48, 32),
                                     jnp.float32))
    cfg = build.BuildConfig(degree=12, beam_width=32, iters=1, batch=128,
                            max_hops=64)
    arrays, per = ss.build_sharded_arrays(x, mesh, build_cfg=cfg, m_pq=8)
    gt_d, gt_i = distance.brute_force_topk(jnp.asarray(q), x, k=10)
    budget = AdaptiveBeamBudget(l_min=8, l_max=32, lam=0.35, center=8.0)

    def backend(**kw):
        return serving.DistributedBackend(
            mesh, arrays, beam_width=32, max_hops=64, k=10, query_chunk=16,
            beam_budget=budget, budget_buckets=4, **kw)

    staged = serving.SearchEngine(backend(), budget, k=10,
                                  num_buckets="auto")
    mono = serving.SearchEngine(backend(), None, k=10)
    out = {}

    # Staged == monolithic step, bitwise (chunk-divisible batch).
    rs, rm = staged.search(q), mono.search(q)
    out["staged_eq_mono_ids"] = bool((rs.ids == rm.ids).all())
    out["staged_eq_mono_d2"] = bool((rs.d2 == rm.d2).all())

    # Pipelined == eager, ragged tail included (staged accepts raggedness
    # the monolithic step rejects).
    batches = [q[:16], q[16:35], q[35:]]
    piped = list(staged.search_batches(batches))
    out["pipelined_eq_eager"] = all(
        bool((p.ids == staged.search(b).ids).all()
             and (p.d2 == staged.search(b).d2).all())
        for p, b in zip(piped, batches))

    # Zero-query batch through the staged distributed path: the empty
    # fallback must carry the *distributed* continue signature (5-tuple,
    # shard ids included), not a hardcoded single-host 4-tuple.
    r0 = staged.search(q[:0])
    out["zero_query_ok"] = (
        r0.ids.shape == (0, 10) and r0.d2.shape == (0, 10)
        and np.asarray(r0.extras["shard_ids"]).shape == (0, 10))

    # The compiled bucket gather on the sharded probe state: the continue
    # receives what the eager per-leaf ``a[sel]`` gave it — the same
    # values, shapes, dtypes, weak types, shardings and commitment (so its
    # jit cache gains no entry) — and returns the same results.
    from repro.serving import pipeline as pipe

    gb = staged.backend
    ctxs = gb.admit(q)
    state, budgets, hop_limits, _ = gb.probe(ctxs, budget)
    # Four buckets of 13, 13, 13 and 9 lanes, padded to 16, 16, 16, 12.
    parts = pipe.partition_by_bucket(
        np.minimum(np.arange(q.shape[0]) // 13, 3), (0, 1, 2, 3), 4)
    cont = gb.continue_fn(budget)
    same_inputs, eager_out = True, []
    for _bi, _members, padded in parts:
        sel = jnp.asarray(padded)
        eager = (jax.tree_util.tree_map(lambda a: a[sel], state), ctxs[sel],
                 budgets[sel], hop_limits[sel])
        got = pipe.gather_lanes(state, ctxs, budgets, hop_limits, padded)
        for e, g in zip(jax.tree_util.tree_leaves(eager),
                        jax.tree_util.tree_leaves(got)):
            same_inputs &= (
                e.shape == g.shape and e.dtype == g.dtype
                and e.weak_type == g.weak_type and e.sharding == g.sharding
                and e.committed == g.committed
                and bool((np.asarray(e) == np.asarray(g)).all()))
        eager_out.append([np.asarray(h) for h in cont(*eager)])
    compiled = gb._continue_step._cache_size()
    same_outputs = all(
        all((np.asarray(h) == w).all()
            for h, w in zip(cont(*pipe.gather_lanes(
                state, ctxs, budgets, hop_limits, padded)), want))
        for (_bi, _m, padded), want in zip(parts, eager_out))
    out["gather_eq_eager"] = bool(same_inputs and same_outputs
                                  and len(parts) > 1)
    out["gather_no_continue_recompile"] = (
        gb._continue_step._cache_size() == compiled)

    # Permutation invariance (pinned center).
    perm = np.random.default_rng(7).permutation(q.shape[0])
    inv = np.argsort(perm)
    rp = staged.search(q[perm])
    out["permutation_invariant"] = bool(
        (np.asarray(rp.ids)[inv] == rs.ids).all())

    # Coalescing: micro-batches merged to the lane threshold, split back.
    coal = serving.SearchEngine(backend(), budget, k=10, num_buckets="auto",
                                coalesce_lanes=24)
    micro = [q[i:i + 8] for i in range(0, 48, 8)]
    res_c = list(coal.search_batches(micro))
    out["coalesce_count"] = len(res_c) == len(micro)
    out["coalesce_identical"] = all(
        bool((c.ids == staged.search(b).ids).all())
        for c, b in zip(res_c, micro))

    # Identity per-shard laws == the scalar law, bitwise.
    laws = (np.full(n_shards, budget.lam, np.float32),
            np.full(n_shards, budget.l_min, np.int32))
    with_laws = serving.SearchEngine(backend(shard_laws=laws), budget, k=10,
                                     num_buckets="auto")
    rl = with_laws.search(q)
    out["identity_laws_bitwise"] = bool(
        (rl.ids == rs.ids).all() and (rl.d2 == rs.d2).all())

    # Fault injection mid-stream: flip shard_ok between batches of a
    # pipelined stream — later batches exclude the dead shard, recall loss
    # is bounded by its data fraction, results stay best-so-far finite
    # under the bucket hop deadlines, and nothing recompiles.
    fb = backend()
    eng = serving.SearchEngine(fb, budget, k=10, num_buckets=None)
    stream = [q[:16]] * 6
    list(eng.search_batches(stream))          # warm every program
    caches = (fb._probe_step._cache_size(),
              fb._continue_step._cache_size())
    dead = jnp.ones((n_shards,), jnp.bool_).at[3].set(False)
    results = []
    for i, res in enumerate(eng.search_batches(stream)):
        results.append(res)
        if i == 1:
            fb.set_shard_ok(dead)
    r_before = float(distance.recall_at_k(jnp.asarray(results[0].ids),
                                          gt_i[:16]))
    r_after = float(distance.recall_at_k(jnp.asarray(results[-1].ids),
                                         gt_i[:16]))
    out["fault_no_dead_results"] = bool(
        (results[-1].extras["shard_ids"] != 3).all())
    out["fault_best_so_far_finite"] = bool(
        np.isfinite(results[-1].d2).all())
    out["fault_recall_bounded"] = bool(
        r_after >= r_before - 1.0 / n_shards - 0.08)
    out["fault_no_recompile"] = (
        (fb._probe_step._cache_size(),
         fb._continue_step._cache_size()) == caches)
    out["recall_before"] = r_before
    out["recall_after"] = r_after
    print(json.dumps(out))


def scenario_sharded_serving():
    """The staged mesh walk served through ``SearchEngine`` on a (2, 2) mesh
    of four devices, under the engine's own budget center, against a plain
    float32 brute force over the whole set: the distance of every served
    global id, recall@10, and the per-shard counters against the totals."""
    from repro import serving
    from repro.core import build
    from repro.core.search import AdaptiveBeamBudget
    from repro.data.synthetic import gaussian_subspace_clusters
    from repro.distributed import sharded_search as ss

    mesh = compat.make_mesh((2, 2), ("data", "model"),
                            devices=jax.devices()[:4])
    pts = np.asarray(gaussian_subspace_clusters(
        jax.random.PRNGKey(7), 2048 + 96, 32, d_intrinsic=6, n_clusters=8))
    perm = np.random.default_rng(7).permutation(pts.shape[0])
    x, q = pts[perm[:2048]], pts[perm[2048:]]
    cfg = build.BuildConfig(degree=16, beam_width=32, iters=1, batch=128,
                            max_hops=64)
    arrays, per = ss.build_sharded_arrays(jnp.asarray(x), mesh,
                                          build_cfg=cfg, m_pq=8, seed=7)
    budget = AdaptiveBeamBudget(l_min=8, l_max=32, lam=0.25, probe_hops=4)
    backend = serving.DistributedBackend(
        mesh, arrays, beam_width=32, max_hops=4 + 4 * 32, k=10,
        query_chunk=32, beam_budget=budget)
    engine = serving.SearchEngine(backend, budget, k=10)
    results = list(engine.search_batches([q[:32], q[32:64], q[64:]]))
    ids = np.concatenate([r.ids for r in results])
    d2 = np.concatenate([r.d2 for r in results])
    # Plain float32 brute force, on the host.
    diff = x[None, :, :] - q[:, None, :]
    full = np.einsum("qnd,qnd->qn", diff, diff, dtype=np.float32)
    gt = np.argsort(full, axis=1, kind="stable")[:, :10]
    served = np.take_along_axis(full, ids, axis=1)
    hits = (gt[:, :, None] == ids[:, None, :]).any(axis=2)
    counters = []
    for r in results:
        e = r.extras
        counters.append(bool(
            e["shard_hops"].shape == (r.ids.shape[0], 4)
            and (e["shard_hops"].sum(axis=1) == r.stats.hops).all()
            and (e["shard_budget"] == r.astats.budget).all()
            and e["shard_capped"].dtype == np.bool_
            and (e["shard_hops"][e["shard_capped"]]
                 >= 4 + 4 * 8).all()))
    # Each query starts every shard's walk from the nearest of that shard's
    # entry candidates (the medoid first); the staged walk is still the
    # monolithic step's, bit for bit.
    mono = serving.SearchEngine(serving.DistributedBackend(
        mesh, arrays, beam_width=32, max_hops=4 + 4 * 32, k=10,
        query_chunk=32, beam_budget=budget), None, k=10).search(q[:32])
    entries = np.asarray(arrays["entries"])
    print(json.dumps({
        "entries_shape": list(entries.shape),
        "medoid_first": bool((entries[:, 0] == np.asarray(
            ss.shard_medoids(jnp.asarray(x), 4))).all()),
        "staged_eq_mono": bool((results[0].ids == mono.ids).all()
                               and (results[0].d2 == mono.d2).all()),
        "rows_per_shard": int(per),
        "ids_in_range": bool(((ids >= 0) & (ids < x.shape[0])).all()),
        "d2_max_rel_err": float(np.max(np.abs(d2 - served)
                                       / np.maximum(served, 1e-12))),
        "recall_at_10": float(hits.sum(axis=1).mean() / 10),
        "counters_sum_to_totals": all(counters),
        "budget_in_range": bool(all(
            ((r.astats.budget >= 8) & (r.astats.budget <= 32)).all()
            for r in results)),
    }))


def scenario_front_door():
    """The serving front door over the staged distributed backend: served
    lanes bit-identical to a direct engine dispatch, a mid-stream shard
    loss (``set_shard_ok`` between dispatches) excludes the dead shard from
    later served results, and a wedged mesh dispatch completes as timeout
    (the distributed backend has no host probe view, so no partials) while
    the open-lane bound sheds and every future completes."""
    import math

    from repro import serving
    from repro.core import build
    from repro.core.search import AdaptiveBeamBudget
    from repro.distributed import sharded_search as ss
    from repro.serving import server as sv

    mesh = make_mesh()
    n_shards = mesh.devices.size
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1024, 16), jnp.float32)
    q = np.asarray(jax.random.normal(jax.random.fold_in(key, 1), (32, 16),
                                     jnp.float32))
    cfg = build.BuildConfig(degree=8, beam_width=16, iters=1, batch=128,
                            max_hops=32)
    arrays, per = ss.build_sharded_arrays(x, mesh, build_cfg=cfg, m_pq=4)
    budget = AdaptiveBeamBudget(l_min=8, l_max=16, lam=0.35, center=8.0)
    fb = serving.DistributedBackend(
        mesh, arrays, beam_width=16, max_hops=32, k=5, query_chunk=8,
        beam_budget=budget, budget_buckets=4)
    eng = serving.SearchEngine(fb, budget, k=5, num_buckets=None)
    out = {"supports_partial": bool(eng.supports_partial)}

    clock = sv.VirtualClock()
    door = sv.FrontDoor(
        {"c": eng},
        [sv.QoSClass("c", deadline_s=60.0, batch_window_s=0.01,
                     max_lanes=8)],
        clock=clock, dispatcher=sv.VirtualDispatcher(clock))
    ref = eng.search(q[:8])
    futs = [door.submit(q[i]) for i in range(8)]        # flush at max_lanes
    clock.advance(0.1)
    rows = [f.result(timeout=0) for f in futs]
    out["served_ok"] = all(r.status == "ok" for r in rows)
    out["bit_identical"] = all(
        bool((r.ids == np.asarray(ref.ids)[i]).all()
             and (r.d2 == np.asarray(ref.d2)[i]).all())
        for i, r in enumerate(rows))

    # Shard loss between the front door's dispatches: the next served
    # batch must exclude the dead shard (per-lane extras carry shard ids).
    fb.set_shard_ok(jnp.ones((n_shards,), jnp.bool_).at[3].set(False))
    futs2 = [door.submit(q[8 + i]) for i in range(8)]
    clock.advance(0.1)
    rows2 = [f.result(timeout=0) for f in futs2]
    out["post_flip_ok"] = all(r.status == "ok" for r in rows2)
    out["post_flip_no_dead"] = all(
        bool((np.asarray(r.extras["shard_ids"]) != 3).all()) for r in rows2)

    # Wedged mesh dispatch: deadline hedges find no partial support and
    # complete as timeout; the open-lane bound converts overload to sheds.
    clock2 = sv.VirtualClock()
    door2 = sv.FrontDoor(
        {"c": eng},
        [sv.QoSClass("c", deadline_s=0.5, batch_window_s=0.0, max_lanes=4)],
        max_queue=8, clock=clock2,
        dispatcher=sv.VirtualDispatcher(clock2, service_time=math.inf,
                                        probe_time=0.001))
    futs3 = [door2.submit(q[i % 16]) for i in range(12)]
    clock2.advance(1.0)
    st = door2.stats()
    out["wedge_timeout_no_partials"] = (st["timeout"] == 8
                                        and st["partial"] == 0)
    out["wedge_shed_at_bound"] = (st["shed"] == 4
                                  and st["max_open_lanes"] <= 8)
    out["wedge_all_futures_done"] = all(f.done() for f in futs3)
    print(json.dumps(out))


def scenario_cells_lower():
    from repro.launch import cells as cells_mod

    mesh = make_mesh()
    results = {}
    # decode_32k instead of train_4k: the train cell's full 1M-token shape
    # with the smoke config's tiny attn chunks fully unrolls a 256-step scan
    # (the per-cell dry-run covers it; too slow for this smoke check).
    for arch, shape in [("qwen3-moe-30b-a3b", "decode_32k"),
                        ("bert4rec", "retrieval_cand"),
                        ("mcgi-gist1m", "serve")]:
        cell = cells_mod.build_cell(arch, shape, mesh, smoke=True)
        compiled = cell.lower().compile()
        cost = compiled.cost_analysis()
        results[f"{arch}/{shape}"] = cost.get("flops", 0) > 0
    print(json.dumps(results))


if __name__ == "__main__":
    scen = sys.argv[1]
    if scen == "sharded_search":
        scenario_sharded_search()
    elif scen == "checkpoint_reshard":
        scenario_checkpoint_reshard(sys.argv[2])
    elif scen == "train_match":
        scenario_sharded_train_matches_single()
    elif scen == "cells_lower":
        scenario_cells_lower()
    elif scen == "moe_ep":
        scenario_moe_expert_parallel()
    elif scen == "merge_modes":
        scenario_merge_modes()
    elif scen == "staged_engine":
        scenario_staged_engine()
    elif scen == "front_door":
        scenario_front_door()
    elif scen == "sharded_serving":
        scenario_sharded_serving()
    else:
        raise SystemExit(f"unknown scenario {scen}")
