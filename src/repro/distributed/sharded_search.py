"""Distributed MCGI serving: sharded beam search + global top-k merge.

Layout (DESIGN.md §5): base points are sharded into n_shards = |data|x|model|
(x|pod|) partitions; every shard holds its *own locally built* MCGI sub-graph
(adjacency with shard-local ids), its PQ codes and its full-precision
vectors. A query fans out to all shards, each runs the PQ-routed beam search
+ local exact rerank on its sub-index, and the per-shard top-k are merged
into the global top-k with one all_gather + sort — the standard
scatter-gather ANN serving pattern expressed as jax collectives inside
``shard_map``.

Straggler mitigation: the merge takes a per-shard ``shard_ok`` mask; a shard
that misses its deadline (or is down) contributes +inf distances and the
merge degrades gracefully (recall loss ~ its data fraction) instead of
stalling the query — the hedged-read policy of production ANN serving. The
mask is a runtime input, so dropping shards needs no recompilation.

Memory discipline at N=10^9: per device the shard is ~3.9M points; queries
are processed in ``query_chunk`` groups under ``lax.map`` so the visited
bitmap stays at chunk x N_local bools.

Two execution shapes are built here:

* the **monolithic step** (:func:`make_distributed_search`) — probe, budget,
  continue, local rerank and hedged merge fused into one compiled program.
  This is what the dry-run prices (``launch/cells.py`` via
  ``DistributedBackend.make_step``) and what fixed-beam serving runs.
* the **staged step** (:func:`make_distributed_probe` +
  :func:`make_distributed_continue`) — the same walk split at the probe
  horizon, PR 1's init/run split lifted to the mesh: the probe program
  checkpoints every shard's frontier (beam + visited bitmap + counters,
  laid out ``(Q, n_shards, ...)`` so the host schedules on the query axis)
  and grants per-shard budgets; the continue program resumes any *subset*
  of queries with warm state, reranks locally and runs the hedged merge.
  ``repro.serving.SearchEngine`` drives the two halves from different
  pipeline stages — batch i+1's probe is dispatched before batch i's
  host-side bucket scheduling and per-bucket continues — and the split is
  result-transparent: both programs run the same per-query kernels as the
  monolithic step (property-tested in ``tests/test_engine_parity.py`` /
  the ``staged_engine`` distributed-worker scenario). The staged walk
  checkpoints the full (Q x N_local/32) visited bitmap between the stages,
  so it targets serving micro-batches; bulk scans keep the monolithic step.

Per-shard budget laws: shard sub-graphs have different geometry (a shard of
a heterogeneous collection is *not* a scaled-down copy of it), so a single
global (lam, l_min) budget law under- or over-budgets some shards. Both the
monolithic and staged builders accept ``per_shard_laws=True`` and then take
``(n_shards,)`` lam / l_min arrays as runtime inputs — one calibrated law
per shard (:func:`repro.core.calibrate.calibrate_budget_law_per_shard`),
threaded through :class:`ShardedIndexSpecs` for the dry-run and applied as
traced scalars in-graph (no recompilation when a recalibration updates
them). ``l_max`` stays global: it is the physical beam shape.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import search as search_mod

Array = jax.Array
INVALID = -1


@dataclasses.dataclass(frozen=True)
class ShardedIndexSpecs:
    """ShapeDtypeStructs (with shardings) of a sharded tiered index.

    ``shard_lam`` / ``shard_l_min`` are present when the index carries
    per-shard calibrated budget laws (``per_shard_laws=True``): one
    (lam, l_min) pair per shard, sharded like ``shard_ok``.
    """

    adj: jax.ShapeDtypeStruct
    codes: jax.ShapeDtypeStruct
    vectors: jax.ShapeDtypeStruct
    centroids: jax.ShapeDtypeStruct
    queries: jax.ShapeDtypeStruct
    shard_ok: jax.ShapeDtypeStruct
    entries: jax.ShapeDtypeStruct
    shard_lam: jax.ShapeDtypeStruct | None = None
    shard_l_min: jax.ShapeDtypeStruct | None = None


def _shard_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)  # points shard over every axis


def sharded_index_specs(
    mesh,
    *,
    n: int,
    d: int,
    degree: int,
    m_pq: int | None,
    n_queries: int,
    data_dtype=jnp.float32,
    per_shard_laws: bool = False,
) -> ShardedIndexSpecs:
    axes = _shard_axes(mesh)
    n_shards = mesh.devices.size
    n_pad = ((n + n_shards - 1) // n_shards) * n_shards
    row = NamedSharding(mesh, P(axes))
    repl = NamedSharding(mesh, P())
    m = m_pq or 0
    laws = {}
    if per_shard_laws:
        laws = dict(
            shard_lam=jax.ShapeDtypeStruct((n_shards,), jnp.float32, sharding=row),
            shard_l_min=jax.ShapeDtypeStruct((n_shards,), jnp.int32, sharding=row),
        )
    return ShardedIndexSpecs(
        adj=jax.ShapeDtypeStruct((n_pad, degree), jnp.int32, sharding=NamedSharding(mesh, P(axes, None))),
        codes=jax.ShapeDtypeStruct((n_pad, max(m, 1)), jnp.uint8, sharding=NamedSharding(mesh, P(axes, None))),
        vectors=jax.ShapeDtypeStruct((n_pad, d), data_dtype, sharding=NamedSharding(mesh, P(axes, None))),
        centroids=jax.ShapeDtypeStruct(
            (max(m, 1), 256, max(d // max(m, 1), 1)), jnp.float32, sharding=repl
        ),
        queries=jax.ShapeDtypeStruct((n_queries, d), jnp.float32, sharding=repl),
        shard_ok=jax.ShapeDtypeStruct((n_shards,), jnp.bool_, sharding=row),
        entries=jax.ShapeDtypeStruct((n_shards,), jnp.int32, sharding=row),
        **laws,
    )


def _shard_eval(codes, vectors, use_pq: bool):
    """The shard-local distance evaluator (PQ/ADC or exact).

    Tagged with ``kind``/``table`` like the in-memory evaluators so the fused
    beam-step kernel can route the shard's table itself (see
    :class:`repro.core.search.PallasBeamStep`).
    """
    if use_pq:
        def eval_dists(lut, ids, valid):
            c = codes[ids].astype(jnp.int32)
            m = lut.shape[0]
            gathered = jax.vmap(lambda row: lut[jnp.arange(m), row])(c)
            return gathered.sum(axis=-1)

        eval_dists.kind = "pq"
        eval_dists.table = codes
        return eval_dists

    def eval_dists(q, ids, valid):
        vecs = vectors[ids].astype(jnp.float32)
        diff = vecs - q[None, :]
        return jnp.sum(diff * diff, axis=-1)

    eval_dists.kind = "exact"
    eval_dists.table = vectors
    return eval_dists


def _shard_ctxs(centroids, queries, use_pq: bool):
    """Per-query walk contexts: ADC LUTs (PQ) or the raw queries (exact)."""
    if use_pq:
        from repro.pq.adc import build_lut

        return build_lut(queries.astype(jnp.float32), centroids)
    return queries


def _local_rerank(beam_ids, vectors, queries, k: int):
    """Local exact rerank from the shard's own full-precision rows (the
    "disk read" happens on the shard that owns the node). Returns
    (d2, local_ids), each (Q, k) ascending."""
    safe = jnp.maximum(beam_ids, 0)
    vecs = vectors[safe].astype(jnp.float32)
    diff = vecs - queries[:, None, :].astype(jnp.float32)
    d2 = jnp.sum(diff * diff, axis=-1)
    d2 = jnp.where(beam_ids == INVALID, jnp.inf, d2)
    order = jnp.argsort(d2, axis=-1)[:, :k]
    return (
        jnp.take_along_axis(d2, order, axis=1),
        jnp.take_along_axis(beam_ids, order, axis=1),
    )


def _hedged_merge(d2, ids, ok_l, mesh, axes, merge: str):
    """Global top-k merge of per-shard (Q, k) candidates, hedged by the
    ``shard_ok`` mask (a late/dead shard contributes +inf). Shared verbatim
    by the monolithic step and the staged continue step, so the two paths
    merge identically.

    merge:
      * "flat"          — one all_gather over every axis at once, then one
        sort (the obvious baseline; payload grows with total shard count).
      * "hierarchical"  — axis-by-axis gather+top-k reduction (model, then
        data, then pod): each stage's payload is only n_axis * Q * k rows and
        later stages ship already-reduced candidate sets (§Perf iteration on
        the mcgi serve cells; also the natural topology map — the first merge
        stays inside a chip row).
    """
    # Hedged-read mask: a late/dead shard contributes nothing.
    d2 = jnp.where(ok_l[0], d2, jnp.inf)
    q, k = d2.shape

    if merge == "flat":
        sid = jnp.int32(0)
        stride = 1
        for a in reversed(axes):
            sid = sid + jax.lax.axis_index(a).astype(jnp.int32) * stride
            stride *= mesh.shape[a]
        cat_d2 = jax.lax.all_gather(d2, axes, tiled=False)
        cat_ids = jax.lax.all_gather(ids, axes, tiled=False)
        cat_sid = jax.lax.all_gather(
            jnp.full((1,), sid, jnp.int32), axes, tiled=False
        ).reshape(-1)
        s = cat_d2.shape[0]
        flat_d2 = cat_d2.transpose(1, 0, 2).reshape(q, s * k)
        flat_ids = cat_ids.transpose(1, 0, 2).reshape(q, s * k)
        flat_sid = jnp.broadcast_to(
            cat_sid[None, :, None], (q, s, k)).reshape(q, s * k)
        order = jnp.argsort(flat_d2, axis=1)[:, :k]
        return (
            jnp.take_along_axis(flat_d2, order, axis=1),
            jnp.take_along_axis(flat_sid, order, axis=1),
            jnp.take_along_axis(flat_ids, order, axis=1),
        )

    # Hierarchical: reduce one mesh axis at a time (innermost first —
    # 'model' neighbours share the fastest links).
    planes = {"local": ids}
    for a in reversed(axes):
        n_a = mesh.shape[a]
        g_d2 = jax.lax.all_gather(d2, a, tiled=False)  # (n_a, Q, k)
        g_planes = {
            name: jax.lax.all_gather(pl, a, tiled=False)
            for name, pl in planes.items()
        }
        flat_d2 = g_d2.transpose(1, 0, 2).reshape(q, n_a * k)
        order = jnp.argsort(flat_d2, axis=1)[:, :k]
        d2 = jnp.take_along_axis(flat_d2, order, axis=1)
        new_planes = {}
        for name, pl in g_planes.items():
            flat = pl.transpose(1, 0, 2).reshape(q, n_a * k)
            new_planes[name] = jnp.take_along_axis(flat, order, axis=1)
        # Which member of this axis each winner came from.
        src = jnp.broadcast_to(
            jnp.arange(n_a, dtype=jnp.int32)[None, :, None],
            (q, n_a, k),
        ).reshape(q, n_a * k)
        new_planes[f"pos_{a}"] = jnp.take_along_axis(src, order, axis=1)
        planes = new_planes

    sid = jnp.zeros_like(planes["local"])
    stride = 1
    for a in reversed(axes):
        sid = sid + planes[f"pos_{a}"] * stride
        stride *= mesh.shape[a]
    return d2, sid, planes["local"]


def _local_search(
    adj, codes, vectors, centroids, queries, entry, *,
    beam_width: int, max_hops: int, k: int, query_chunk: int, use_pq: bool,
    beam_budget: search_mod.AdaptiveBeamBudget | None = None,
    bucket_ceilings: tuple[int, ...] | None = None,
    lam=None, l_min=None,
    step_kernel: str | None = None,
):
    """Per-shard search over the local sub-graph. Returns (d2, local_ids)
    each (Q, k).

    ``entry`` is the shard's own entry point (its local medoid, computed at
    index-build time and threaded through :class:`ShardedIndexSpecs`). With
    ``beam_budget`` set, the shard runs the adaptive engine: each query's
    budget is computed *on this shard* from its local probe beam (shard
    geometry differs, so budgets legitimately differ per shard) and the
    per-shard top-k are merged exactly as in the fixed-beam path.
    ``lam``/``l_min``, when given, are this shard's traced budget-law
    overrides (the per-shard calibration path).

    ``bucket_ceilings`` additionally quantizes each granted budget up to its
    bucket ceiling *in-graph* and derives the per-query hop limit from that
    ceiling, giving the shard a small *discrete family of hop deadlines*
    (probe + hop_factor * ceiling, always capped by ``max_hops``): a walk
    that hits its deadline stops mid-graph and still contributes its
    best-so-far beam to the merge. Note the quantization rounds *up*, so a
    query's limit is never tighter than the raw adaptive path's — the hedge
    is against unbounded straggling (deadlines are enforced mid-walk and the
    shard's completion time is governed by its top occupied bucket), not a
    tightening of the budget law. ``shard_ok`` remains the orthogonal
    mechanism for shards that are down entirely.
    """
    n_local = adj.shape[0]
    entry = entry.astype(jnp.int32)
    eval_dists = _shard_eval(codes, vectors, use_pq)
    ctxs = _shard_ctxs(centroids, queries, use_pq)

    def chunk_fn(args):
        ctx_chunk, q_chunk = args
        if beam_budget is not None:
            # max_hops still caps every per-query hop limit: enabling
            # adaptivity must not silently exceed the operator's I/O SLO.
            beam_ids, beam_d, _, _ = search_mod.adaptive_search_batch(
                ctx_chunk, adj, entry, eval_dists, n_local, beam_budget,
                max_hops=max_hops, bucket_ceilings=bucket_ceilings,
                lam=lam, l_min=l_min, step_kernel=step_kernel)
        else:
            beam_ids, beam_d, _ = search_mod.fixed_search_batch(
                ctx_chunk, adj, entry, eval_dists, n_local, beam_width,
                max_hops, step_kernel=step_kernel)
        d2, ids = _local_rerank(beam_ids, vectors, q_chunk, k)
        return d2, ids

    nq = queries.shape[0]
    assert nq % query_chunk == 0, (nq, query_chunk)
    ctx_chunks = ctxs.reshape((nq // query_chunk, query_chunk) + ctxs.shape[1:])
    q_chunks = queries.reshape(nq // query_chunk, query_chunk, -1)
    d2, ids = jax.lax.map(chunk_fn, (ctx_chunks, q_chunks))
    return d2.reshape(nq, k), ids.reshape(nq, k)


def make_distributed_search(
    mesh,
    *,
    beam_width: int,
    max_hops: int,
    k: int,
    query_chunk: int = 128,
    use_pq: bool = True,
    merge: str = "hierarchical",
    beam_budget: search_mod.AdaptiveBeamBudget | None = None,
    budget_buckets: int | None = None,
    per_shard_laws: bool = False,
    step_kernel: str | None = None,
):
    """Builds the jit-able *monolithic* sharded search step for ``mesh``.

    step(adj, codes, vectors, centroids, queries, shard_ok, entries
         [, shard_lam, shard_l_min])
      -> (d2 (Q, k), shard_id (Q, k), local_id (Q, k))

    ``entries`` is the (n_shards,) array of per-shard entry points (local
    medoids), sharded one per device like ``shard_ok``.

    Global ids are returned as (shard, local_id) pairs — billion-scale ids
    exceed int32 when flattened.

    beam_budget:
      None runs every query at the fixed ``beam_width``; an
      :class:`repro.core.search.AdaptiveBeamBudget` switches each shard to
      the per-query adaptive engine (probe -> online LID -> budget ->
      continue). Budgets are computed per shard from the shard's own probe
      beam; the global merge is unchanged.

    budget_buckets:
      with ``beam_budget`` set, quantizes each shard's granted budgets up to
      at most this many power-of-two bucket ceilings
      (:func:`repro.core.search.budget_bucket_ceilings`) and derives every
      query's hop limit from its bucket ceiling — a discrete per-shard
      deadline family (see :func:`_local_search`): straggling walks stop at
      their bucket's deadline, mid-graph, and still contribute best-so-far
      candidates to the merge. Complements (does not replace) ``shard_ok``,
      which stays the drop mechanism for dead shards; quantization rounds
      up, so recall is >= the unquantized adaptive path's at slightly more
      counted I/O.

    per_shard_laws:
      the step takes two extra trailing inputs — (n_shards,) ``shard_lam``
      float32 and ``shard_l_min`` int32 arrays, sharded like ``shard_ok`` —
      and each shard's budget law uses *its* calibrated (lam, l_min)
      instead of ``beam_budget``'s globals. Runtime inputs: recalibration
      never recompiles. The bucket-ceiling family stays derived from the
      global config's (l_min, l_max) range (ceilings are static); rounding
      up is still never tighter than any shard's law.

    For the staged split of this step (probe / continue as separate
    programs, resumable at the probe horizon) see
    :func:`make_distributed_probe` / :func:`make_distributed_continue`.
    """
    axes = _shard_axes(mesh)
    bucket_ceilings = None
    if beam_budget is not None and budget_buckets and budget_buckets > 1:
        bucket_ceilings = search_mod.budget_bucket_ceilings(
            beam_budget.l_min, beam_budget.l_max, budget_buckets)

    def step(adj, codes, vectors, centroids, queries, shard_ok, entries,
             *laws):
        def shard_fn(adj_l, codes_l, vectors_l, centroids_l, queries_l, ok_l,
                     entry_l, *laws_l):
            lam_l = laws_l[0][0] if per_shard_laws else None
            l_min_l = laws_l[1][0] if per_shard_laws else None
            d2, ids = _local_search(
                adj_l, codes_l, vectors_l, centroids_l, queries_l, entry_l[0],
                beam_width=beam_width, max_hops=max_hops, k=k,
                query_chunk=query_chunk, use_pq=use_pq,
                beam_budget=beam_budget, bucket_ceilings=bucket_ceilings,
                lam=lam_l, l_min=l_min_l, step_kernel=step_kernel,
            )
            return _hedged_merge(d2, ids, ok_l, mesh, axes, merge)

        specs_in = [
            P(axes, None),  # adj
            P(axes, None),  # codes
            P(axes, None),  # vectors
            P(),            # centroids
            P(),            # queries
            P(axes),        # shard_ok (1 flag per shard)
            P(axes),        # entries  (1 entry point per shard)
        ]
        if per_shard_laws:
            specs_in += [P(axes), P(axes)]  # shard_lam, shard_l_min
        return compat.shard_map(
            shard_fn, mesh=mesh, in_specs=tuple(specs_in),
            out_specs=(P(), P(), P()),
        )(adj, codes, vectors, centroids, queries, shard_ok, entries, *laws)

    return step


def make_distributed_probe(
    mesh,
    *,
    budget_cfg: search_mod.AdaptiveBeamBudget,
    max_hops: int,
    query_chunk: int = 128,
    use_pq: bool = True,
    budget_buckets: int | None = None,
    per_shard_laws: bool = False,
    step_kernel: str | None = None,
):
    """The probe half of the staged distributed step.

    probe(adj, codes, vectors, centroids, queries, entries
          [, shard_lam, shard_l_min])
      -> (probe_state, budgets, hop_limits, q_lid)

    Every shard walks every query ``probe_hops`` hops at its budget floor,
    estimates per-query LID from its local probe beam and grants per-shard
    budgets/hop deadlines (quantized up to the in-graph bucket ceilings when
    ``budget_buckets`` is set — exactly as the monolithic step does between
    its probe and continue phases). The walk is *checkpointed at the probe
    horizon*: ``probe_state`` is (beam_ids, beam_d, beam_exp, visited, hops,
    evals, ctx) with the per-shard leaves laid out ``(Q, n_shards, ...)``
    (shard axis second, sharded in place — no cross-device traffic), so the
    host scheduler can select any query subset on axis 0;
    ``budgets``/``hop_limits``/``q_lid`` are (Q, n_shards). ``ctx`` is the
    replicated walk context (ADC LUTs or raw queries) — carried in the
    state so the continue program resumes from the *same* buffers the probe
    used.

    Queries are probed in ``query_chunk`` groups under ``lax.map`` exactly
    like the monolithic step (so batch-mean LID centering sees the same
    chunks); a batch not divisible by the chunk runs as one chunk — staged
    serving accepts ragged *micro*-batches the monolithic step would reject
    (bounded at max(4 x query_chunk, 512) lanes, past which the single
    chunk would defeat the visited-bitmap memory discipline and the step
    refuses it at trace time).
    """
    axes = _shard_axes(mesh)
    bucket_ceilings = None
    if budget_buckets and budget_buckets > 1:
        bucket_ceilings = search_mod.budget_bucket_ceilings(
            budget_cfg.l_min, budget_cfg.l_max, budget_buckets)

    def step(adj, codes, vectors, centroids, queries, entries, *laws):
        def shard_fn(adj_l, codes_l, vectors_l, centroids_l, queries_l,
                     entry_l, *laws_l):
            n_local = adj_l.shape[0]
            entry = entry_l[0].astype(jnp.int32)
            eval_dists = _shard_eval(codes_l, vectors_l, use_pq)
            ctxs = _shard_ctxs(centroids_l, queries_l, use_pq)
            lam_l = laws_l[0][0] if per_shard_laws else None
            l_min_l = laws_l[1][0] if per_shard_laws else None
            nq = queries_l.shape[0]
            chunk = query_chunk if nq % query_chunk == 0 else nq
            # Ragged *micro*-batches run as one chunk (their visited
            # bitmaps are small); a bulk batch must land on the chunk grid
            # — refuse the silent (nq x N_local/32) visited blowup the
            # chunking exists to prevent.
            assert chunk <= max(4 * query_chunk, 512), (
                f"batch of {nq} queries is not divisible by "
                f"query_chunk={query_chunk} and too large to probe as one "
                f"chunk; align bulk batches to the chunk grid")

            def chunk_fn(ctx_chunk):
                st, budgets, hop_limits, q_lid = search_mod.adaptive_probe_batch(
                    ctx_chunk, adj_l, entry, eval_dists, n_local, budget_cfg,
                    max_hops=max_hops, lam=lam_l, l_min=l_min_l,
                    step_kernel=step_kernel)
                if bucket_ceilings is not None:
                    _, budgets = search_mod.quantize_budgets(
                        budgets, bucket_ceilings)
                    hop_limits = search_mod._bucket_hop_limits(
                        budget_cfg, budgets, max_hops)
                return st + (budgets, hop_limits, q_lid)

            ctx_chunks = ctxs.reshape((nq // chunk, chunk) + ctxs.shape[1:])
            outs = jax.lax.map(chunk_fn, ctx_chunks)
            outs = jax.tree_util.tree_map(
                lambda a: a.reshape((nq,) + a.shape[2:]), outs)
            b_ids, b_d, b_exp, visited, hops, evals, budgets, hop_limits, \
                q_lid = outs
            shard_axis = lambda a: a[:, None]  # (Q, ...) -> (Q, 1, ...)
            state = (shard_axis(b_ids), shard_axis(b_d), shard_axis(b_exp),
                     shard_axis(visited), shard_axis(hops), shard_axis(evals),
                     ctxs)
            return (state, shard_axis(budgets), shard_axis(hop_limits),
                    shard_axis(q_lid))

        specs_in = [
            P(axes, None),  # adj
            P(axes, None),  # codes
            P(axes, None),  # vectors
            P(),            # centroids
            P(),            # queries
            P(axes),        # entries
        ]
        if per_shard_laws:
            specs_in += [P(axes), P(axes)]
        state_specs = ((P(None, axes, None),) * 4     # beams + visited
                       + (P(None, axes),) * 2         # hops, evals
                       + (P(),))                      # ctx (replicated)
        out_specs = (state_specs, P(None, axes), P(None, axes),
                     P(None, axes))
        return compat.shard_map(
            shard_fn, mesh=mesh, in_specs=tuple(specs_in),
            out_specs=out_specs,
        )(adj, codes, vectors, centroids, queries, entries, *laws)

    return step


def make_distributed_continue(
    mesh,
    *,
    budget_cfg: search_mod.AdaptiveBeamBudget,
    k: int,
    use_pq: bool = True,
    merge: str = "hierarchical",
    step_kernel: str | None = None,
):
    """The continue half of the staged distributed step.

    cont(adj, codes, vectors, centroids, probe_state, queries, budgets,
         hop_limits, shard_ok)
      -> (d2 (q, k), shard_id (q, k), local_id (q, k),
          hops (q,), dist_evals (q,))

    Resumes the checkpointed shard walks (warm beam + visited set, no
    repeated hops) for *any query subset* of a probe's batch — the host
    bucket scheduler selects rows on axis 0 of every probe output — then
    reranks locally and runs the same hedged merge as the monolithic step
    (:func:`_hedged_merge`, shared code). ``shard_ok`` is consumed here, at
    merge time: flipping the mask between batches of a stream affects every
    continue dispatched after the flip, with no recompilation.

    ``hops``/``dist_evals`` are the per-query totals summed over *live*
    shards (the monolithic step reports no counters; the staged path is
    strictly more observable).
    """
    axes = _shard_axes(mesh)

    def step(adj, codes, vectors, centroids, state, queries, budgets,
             hop_limits, shard_ok):
        def shard_fn(adj_l, codes_l, vectors_l, centroids_l, state_l,
                     queries_l, budgets_l, hop_limits_l, ok_l):
            *walk, ctx = state_l
            walk = tuple(jnp.squeeze(a, axis=1) for a in walk)
            eval_dists = _shard_eval(codes_l, vectors_l, use_pq)
            beam_ids, beam_d, hops, evals = search_mod.adaptive_continue_batch(
                walk, ctx, adj_l, eval_dists, budget_cfg,
                budgets_l[:, 0], hop_limits_l[:, 0], step_kernel=step_kernel)
            d2, ids = _local_rerank(beam_ids, vectors_l, queries_l, k)
            d2, sid, lid = _hedged_merge(d2, ids, ok_l, mesh, axes, merge)
            live_hops = jax.lax.psum(jnp.where(ok_l[0], hops, 0), axes)
            live_evals = jax.lax.psum(jnp.where(ok_l[0], evals, 0), axes)
            return d2, sid, lid, live_hops, live_evals

        state_specs = ((P(None, axes, None),) * 4
                       + (P(None, axes),) * 2
                       + (P(),))
        specs_in = (
            P(axes, None),   # adj
            P(axes, None),   # codes
            P(axes, None),   # vectors
            P(),             # centroids
            state_specs,     # checkpointed walks
            P(),             # queries (replicated; local rerank targets)
            P(None, axes),   # budgets
            P(None, axes),   # hop_limits
            P(axes),         # shard_ok
        )
        return compat.shard_map(
            shard_fn, mesh=mesh, in_specs=specs_in,
            out_specs=(P(), P(), P(), P(), P()),
        )(adj, codes, vectors, centroids, state, queries, budgets,
          hop_limits, shard_ok)

    return step


def shard_medoids(vectors: Array, n_shards: int) -> Array:
    """Per-shard entry points: the local medoid of each shard's rows.

    ``vectors`` is shard-major (shard s owns rows [s*per, (s+1)*per)) —
    the layout ``distributed_search`` already requires.
    """
    per = vectors.shape[0] // n_shards
    blocks = vectors[: per * n_shards].reshape(n_shards, per, -1)
    return jax.vmap(search_mod.medoid)(blocks)


def build_sharded_arrays(
    x: Array,
    mesh,
    *,
    build_cfg,
    m_pq: int = 8,
    alpha: float = 1.2,
    pq_iters: int = 4,
    seed: int = 0,
) -> tuple[dict, int]:
    """Build a shard-major distributed index for ``mesh`` and lay it out.

    One locally built sub-graph per shard (shard-local ids, static
    ``alpha``), PQ codebook/codes over the full collection, per-shard entry
    medoids — all ``device_put`` with the shardings
    :func:`make_distributed_search` requires. ``x`` is truncated to a
    multiple of the shard count. Returns (arrays dict, rows_per_shard).

    Each shard's sub-graph is built on the mesh device that will own it,
    all shards concurrently (one host thread per device; the builds are
    independent, so the result does not depend on the interleaving).

    Example/benchmark/test scale: production builds each shard's sub-graph
    on the host that owns it and ships the serializer's per-shard files;
    this helper exists so every in-process harness (examples, workers,
    benchmarks, the serve launcher's ``--distributed`` mode) shards one
    collection the same way.
    """
    import concurrent.futures

    from repro.core import build as build_mod
    from repro.pq import pq_encode, train_pq

    n_shards = mesh.devices.size
    x = jnp.asarray(x)
    n = (x.shape[0] // n_shards) * n_shards
    x = x[:n]
    per = n // n_shards

    def build_shard(s, device):
        xs = jax.device_put(x[s * per:(s + 1) * per], device)
        with jax.default_device(device):
            return np.asarray(build_mod.build_with_alpha(
                xs, jnp.full((per,), alpha, jnp.float32), build_cfg))

    with concurrent.futures.ThreadPoolExecutor(n_shards) as pool:
        adj = np.concatenate(list(pool.map(
            build_shard, range(n_shards), mesh.devices.flat)))
    book = train_pq(x, m=m_pq, iters=pq_iters, seed=seed)
    axes = _shard_axes(mesh)
    row = NamedSharding(mesh, P(axes, None))
    flag = NamedSharding(mesh, P(axes))
    arrays = {
        "adj": jax.device_put(adj, row),
        "codes": jax.device_put(pq_encode(x, book), row),
        "vectors": jax.device_put(x, row),
        "centroids": jax.device_put(book.centroids, NamedSharding(mesh, P())),
        "entries": jax.device_put(shard_medoids(x, n_shards), flag),
    }
    return arrays, per


def distributed_search(mesh, index_arrays, queries, shard_ok=None,
                       shard_laws=None, **kw):
    """Convenience eager entry (tests, examples): index_arrays is a dict with
    adj/codes/vectors/centroids (optionally entries) laid out shard-major.

    When ``entries`` is absent the per-shard medoids are recomputed here on
    *every call* — an O(N·D) scan. Production callers should compute them
    once at index-build time and put them in the dict. ``shard_laws`` is an
    optional (lam (S,), l_min (S,)) pair of per-shard budget-law arrays.
    """
    step = make_distributed_search(
        mesh, per_shard_laws=shard_laws is not None, **kw)
    n_shards = mesh.devices.size
    if shard_ok is None:
        shard_ok = jnp.ones((n_shards,), jnp.bool_)
    entries = index_arrays.get("entries")
    if entries is None:
        entries = shard_medoids(index_arrays["vectors"], n_shards)
    laws = ()
    if shard_laws is not None:
        laws = (jnp.asarray(shard_laws[0], jnp.float32),
                jnp.asarray(shard_laws[1], jnp.int32))
    return step(
        index_arrays["adj"], index_arrays["codes"], index_arrays["vectors"],
        index_arrays["centroids"], queries, shard_ok, entries, *laws,
    )
