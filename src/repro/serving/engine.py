"""The unified serving engine: one API over exact / PQ / tiered /
distributed backends, with a staged double-buffered batch pipeline.

See the package docstring (:mod:`repro.serving`) for the stage graph and the
buffering contract.  The short version:

* :class:`SearchEngine` wraps a *backend* (how distances are evaluated and
  where the slow tier lives) behind ``search`` (one batch) and
  ``search_batches`` (a stream, double-buffered).
* *Staged* backends (:class:`ExactBackend`, :class:`TieredBackend`, and
  :class:`DistributedBackend` when built with a budget law) expose the
  adaptive engine's probe / continue / rerank programs separately, so the
  pipeline can put the host's bucket scheduling *between* device programs of
  different batches.  Results are bit-identical to the unpipelined path —
  the same jitted programs run on the same inputs; only dispatch order moves.
  The distributed backend's stages are whole-mesh programs (shard walks
  checkpoint their frontiers at the probe horizon; see
  :func:`repro.distributed.sharded_search.make_distributed_probe`), its
  granted budgets are *per shard* (host scheduling reduces them to a
  per-query effective budget — the mean over shards, a lane's expected
  per-shard work), and its continue program ends in the hedged merge
  instead of a host rerank.
* *Monolithic* dispatch (fixed-beam serving on any backend, and the
  distributed backend without an engine-level budget law) runs one compiled
  program per batch; the pipeline still overlaps batch i's host-side
  collection with batch i+1's dispatched program.

Admission coalescing: ``coalesce_lanes=`` merges micro-batches below the
threshold into one dispatch batch (per-query result order preserved — each
input batch still yields its own :class:`BatchResult`), so a hot batcher
emitting tiny batches doesn't pay a full pipeline round per handful of
lanes.

Disk slow tier: a :class:`TieredBackend` built with a
:class:`repro.index.disk.BlockSlowTier` serves the rerank fetch from the
block-aligned on-disk store.  The pipeline then grows a third stage —
*prefetch* — between continue-dispatch and gather: batch i's candidate
blocks are read on the tier's host worker thread while batch i+1's continue
programs occupy the device, and the gather stage joins the future.  Cache
hit/miss and measured block-read-latency counters ride in each
``BatchResult.extras["slow_tier"]``.  With a frequency-aware hot tier
(``BlockSlowTier(hot_nodes=...)``) the gather stage additionally kicks one
non-blocking *promotion tick* per batch (``backend.promotion_tick``) — the
hot tier's promoter thread digests the access frequencies the finished
batch recorded while the younger batches' device programs and prefetches
run, so promotion work sits between pipeline stages but never on them;
the promotion counters ride in the same ``extras["slow_tier"]`` payload.

Recalibration is a first-class hook: :meth:`SearchEngine.recalibrate` refits
the budget law (lam — and jointly l_min, see
:func:`repro.core.calibrate.calibrate_budget_law_joint`) against a recall
target on held-out queries and swaps the fitted config into the live engine.
Online-MCGI inserts shift the LID population, so an index refresh calls
:meth:`SearchEngine.update_backend` + ``recalibrate`` instead of rebuilding
the engine; jit caches are keyed on shapes and survive both.

The serving front door (:mod:`repro.serving.server`) sits *above* this
module and owns what the engine deliberately doesn't: arrival, queueing,
deadlines, and overload.  Its request path is admission (bounded queue,
shed when full) -> per-class lane coalescing -> engine dispatch -> deadline
gather.  Three engine hooks carry it:

* :meth:`SearchEngine.begin` — the dispatch stage alone (admission + probe,
  or the whole monolithic program), returning the in-flight handle without
  blocking.  The front door begins a flight the moment a class's lanes
  flush, so device work starts while the completion is still queued behind
  older batches.
* :meth:`SearchEngine.finish_from` — the remaining stages of a begun flight
  (schedule / prefetch / gather).  ``begin`` + ``finish_from`` is exactly
  :meth:`SearchEngine.search` — bit-identical results — just split at a
  seam the front door can put a scheduler between.
* :meth:`SearchEngine.partial_result` — the *deadline-aware gather*: the
  probe state's beam reranked through the normal finish path, a servable
  best-so-far answer for a request whose deadline expired mid-continue.
  Never consumes the flight; a later ``finish_from`` still yields the full
  result.  Available on staged single-host backends (the distributed probe
  state is a mesh checkpoint with no host-side beam view; see
  :attr:`SearchEngine.supports_partial`).

Per-QoS-class budget laws need no engine feature at all: the front door
simply holds one engine per class (sharing one backend — jit caches are
keyed on config + shapes, so classes don't trample each other), each with
its own calibrated (lam, l_min)
(:func:`repro.core.calibrate.calibrate_budget_law_per_class`).

Every pipeline stage runs inside a host span
(``jax.profiler.TraceAnnotation``, free unless a profiler is running) that
carries the batch's sequence number as its ``batch`` stat, so a profile
lines up each device gap with the stage the host was in:

* ``engine.dispatch`` — admission + probe dispatch (or the monolithic
  program); stat ``warmed``, the lane counts whose compiled gather the
  probe warmed (every padded lane count on the first batch of a new shape,
  see :class:`repro.serving.pipeline.LaneGathers`; 0 after);
* ``engine.walk_prefetch`` / ``engine.prefetch`` — the storage backends'
  read-ahead stages;
* ``engine.schedule`` — the bucket stage, split into ``engine.schedule.sync``
  (granted budgets to the host), ``engine.schedule.plan`` (bucket family
  and partition) and ``engine.schedule.launch`` (one compiled lane gather
  + one continue dispatch per bucket; stats ``buckets``, ``lanes`` real,
  ``padded_lanes``);
* ``engine.gather`` — collection, with ``engine.gather.sync`` around the
  wait for the continue outputs.
"""
from __future__ import annotations

import copy
import dataclasses
import itertools
import threading
from typing import Any, Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search as search_mod
from repro.serving import pipeline as pipe

Array = jax.Array
_span = jax.profiler.TraceAnnotation   # host span; see the module docstring


@dataclasses.dataclass
class BatchResult:
    """One batch's results, host-side (numpy), original query order."""

    ids: np.ndarray                       # (Q, k)
    d2: np.ndarray                        # (Q, k)
    stats: search_mod.SearchStats | None = None
    astats: search_mod.AdaptiveStats | None = None
    ceilings: tuple[int, ...] | None = None   # bucket family actually used
    extras: dict[str, Any] = dataclasses.field(default_factory=dict)


def _split_result(res: BatchResult, sizes: list[int]) -> list[BatchResult]:
    """Split a coalesced dispatch's result back into per-input-batch results
    (per-query extras are sliced on axis 0; non-array extras — e.g. the
    slow-tier cache counters — describe the merged dispatch and are shared;
    ``ceilings`` likewise)."""
    outs, off = [], 0
    for s in sizes:
        sl = slice(off, off + s)
        off += s
        stats = None if res.stats is None else search_mod.SearchStats(
            hops=res.stats.hops[sl], dist_evals=res.stats.dist_evals[sl])
        astats = None if res.astats is None else search_mod.AdaptiveStats(
            q_lid=res.astats.q_lid[sl], budget=res.astats.budget[sl])
        outs.append(BatchResult(
            ids=res.ids[sl], d2=res.d2[sl], stats=stats, astats=astats,
            ceilings=res.ceilings,
            extras={k: v[sl] if isinstance(v, np.ndarray) else v
                    for k, v in res.extras.items()}))
    return outs


class _StagedRerankMixin:
    """Shared staged-protocol tail of the single-host backends.

    ``schedule_budgets`` — granted budgets are already per-query scalars, so
    the host scheduler uses them as is.  ``finish`` — the gathered continue
    parts are (beam_ids, beam_d, hops, evals); rerank them into the final
    top-k and assemble the :class:`BatchResult` (``prefetch`` is the joined
    disk-tier fetch future when the pipeline's prefetch stage ran).
    """

    def schedule_budgets(self, budgets_np: np.ndarray) -> np.ndarray:
        return budgets_np

    def partial_parts(self, probe_state) -> tuple:
        """The probe-horizon view of the walk — (beam_ids, beam_d, hops,
        evals) sliced straight out of the warm probe state, the same part
        layout :meth:`finish` reranks.  The serving front door's deadline
        gather serves these as a best-so-far result when a request's
        deadline expires mid-continue (:meth:`SearchEngine.partial_result`).
        Unfilled beam slots are INVALID/inf and the rerank masks them, so a
        partial is always servable once the probe ran."""
        beam_ids, beam_d, _beam_exp, _visited, hops, evals = probe_state
        return beam_ids, beam_d, hops, evals

    def finish_extras(self) -> dict[str, Any]:
        """Per-batch observability payload (backends override)."""
        return {}

    def finish(self, queries, parts, k: int, *, q_lid,
               budgets_np, prefetch=None) -> BatchResult:
        beam_ids, beam_d, hops, evals = parts
        ids, d2 = self.rerank(beam_ids, beam_d, queries, k,
                              prefetch=prefetch)
        return BatchResult(
            ids=np.asarray(ids), d2=np.asarray(d2),
            stats=search_mod.SearchStats(hops=np.asarray(hops),
                                         dist_evals=np.asarray(evals)),
            astats=search_mod.AdaptiveStats(q_lid=np.asarray(q_lid),
                                            budget=budgets_np),
            extras=self.finish_extras())


class ExactBackend(_StagedRerankMixin):
    """Full-precision in-memory backend (benchmark mode): exact distances
    steer the walk; the final "rerank" is just the beam's top-k slice."""

    staged = True

    def __init__(self, x: Array, adj: Array, entry: Array,
                 step_kernel: str | None = "auto"):
        self.step_kernel = step_kernel
        self.lane_gathers = pipe.LaneGathers()
        self.update(x, adj, entry)

    def update(self, x: Array, adj: Array, entry: Array) -> None:
        """Swap the index arrays in place (Online-MCGI refresh path)."""
        self.x, self.adj, self.entry = x, adj, entry

    def set_step_kernel(self, step_kernel: str | None) -> None:
        """Select the walk's hop implementation ("reference" | "pallas" |
        "auto"); a static jit key, so switching recompiles but never rebuilds
        the backend."""
        self.step_kernel = step_kernel

    def num_nodes(self) -> int:
        return int(self.x.shape[0])

    def admit(self, queries: Array) -> Array:
        return jnp.asarray(queries)

    def probe(self, ctxs, budget_cfg, excl=None):
        return self.lane_gathers.warm(search_mod._probe_exact_jit(
            self.x, self.adj, ctxs, self.entry, budget_cfg,
            step_kernel=self.step_kernel, excl=excl), ctxs)

    def continue_fn(self, budget_cfg):
        import functools

        return functools.partial(search_mod._continue_exact_jit, self.x,
                                 self.adj, budget_cfg=budget_cfg,
                                 step_kernel=self.step_kernel)

    def rerank(self, beam_ids, beam_d, queries, k: int, prefetch=None):
        return beam_ids[:, :k], beam_d[:, :k]

    def fixed(self, queries, *, beam_width: int, max_hops: int, k: int,
              excl=None):
        ids, d2, stats = search_mod.beam_search_exact(
            self.x, self.adj, queries, self.entry, beam_width=beam_width,
            max_hops=max_hops, k=k, step_kernel=self.step_kernel, excl=excl)
        return ids, d2, stats, None

    def recall_eval(self, queries, gt_ids, *, k, sample, seed, base_cfg):
        from repro.core import calibrate as calib

        return calib.exact_recall_eval(
            self.x, self.adj, self.entry, queries, gt_ids, k=k,
            sample=sample, seed=seed, base_cfg=base_cfg)


class TieredBackend(_StagedRerankMixin):
    """The deployed two-tier path: PQ codes route the walk (fast tier), the
    final beam is reranked from full-precision vectors (slow tier).
    ``rerank=False`` serves raw ADC results (the pure-PQ variant).

    ``slow_tier`` plugs the rerank's node fetch: ``None`` keeps the
    in-memory rows of ``index.vectors`` (fused in-graph gather); a
    :class:`repro.index.disk.BlockSlowTier` serves it from the block-aligned
    on-disk store instead — the fetch moves to the host (cache + checksummed
    block reads), the rerank arithmetic stays the same jitted program, and
    results are bit-identical.  A disk tier sets :attr:`prefetches`, which
    makes the engine's pipeline insert an async-prefetch stage: batch i's
    block reads run on the tier's worker thread while batch i+1's continue
    programs occupy the device."""

    staged = True

    _UNSET = object()

    def __init__(self, index, rerank: bool = True, slow_tier=None,
                 step_kernel: str | None = "auto"):
        self.do_rerank = rerank
        self.slow_tier = None
        self.step_kernel = step_kernel
        self.lane_gathers = pipe.LaneGathers()
        self.update(index, slow_tier=slow_tier)

    def set_step_kernel(self, step_kernel: str | None) -> None:
        """Select the walk's hop implementation (see
        :meth:`ExactBackend.set_step_kernel`)."""
        self.step_kernel = step_kernel

    def update(self, index, slow_tier=_UNSET) -> None:
        """Swap the tiered index (and the slow tier) in place (Online-MCGI
        refresh path).  A disk-backed backend refuses an index refresh that
        doesn't also name its slow tier: the old block store holds the old
        vectors, so silently keeping it would serve stale reranks and
        silently dropping it would quietly fall back to host memory — pass
        ``slow_tier=`` (a store written from the new vectors, or ``None``
        for in-memory rows) explicitly."""
        if slow_tier is TieredBackend._UNSET:
            if self.slow_tier is not None and self.slow_tier.is_disk:
                raise ValueError(
                    "this backend serves its slow tier from a block store; "
                    "refresh with update(index, slow_tier=...) — a "
                    "BlockSlowTier over a store written from the new "
                    "vectors, or None to return to in-memory rows")
            slow_tier = None
        old = self.slow_tier
        self.index = index
        self.slow_tier = slow_tier
        # A replaced disk tier owns a worker thread — shut it down (the
        # refresh path would otherwise leak one thread per index swap).
        if (old is not None and old is not slow_tier
                and getattr(old, "is_disk", False)):
            old.close()

    def close(self) -> None:
        """Release backend resources: shuts down a disk slow tier's worker
        thread (idempotent; in-memory tiers hold nothing closeable)."""
        if self.slow_tier is not None and getattr(self.slow_tier, "is_disk",
                                                  False):
            self.slow_tier.close()

    @property
    def prefetches(self) -> bool:
        """Whether the rerank fetch is worth hiding behind device work."""
        return (self.do_rerank and self.slow_tier is not None
                and self.slow_tier.is_disk)

    def num_nodes(self) -> int:
        return int(self.index.codes.shape[0])

    def admit(self, queries: Array) -> Array:
        from repro.index.disk import _query_luts

        return _query_luts(self.index, jnp.asarray(queries))

    def probe(self, ctxs, budget_cfg, excl=None):
        return self.lane_gathers.warm(search_mod._probe_pq_jit(
            self.index.codes, self.index.graph.adj, ctxs,
            self.index.graph.entry, budget_cfg,
            step_kernel=self.step_kernel, excl=excl), ctxs)

    def continue_fn(self, budget_cfg):
        import functools

        return functools.partial(
            search_mod._continue_pq_jit, self.index.codes,
            self.index.graph.adj, budget_cfg=budget_cfg,
            step_kernel=self.step_kernel)

    def prefetch_rerank(self, parts):
        """Submit the slow-tier block fetch for gathered continue ``parts``
        (beam_ids first) to the tier's host worker; returns a future the
        engine hands back to :meth:`finish` one pipeline stage later."""
        return self.slow_tier.prefetch(np.asarray(parts[0]))

    def rerank(self, beam_ids, beam_d, queries, k: int, prefetch=None):
        if not self.do_rerank:
            return beam_ids[:, :k], beam_d[:, :k]
        if self.prefetches:
            from repro.index.disk import rerank_with_slow_tier

            return rerank_with_slow_tier(
                self.slow_tier, np.asarray(beam_ids), queries, k,
                prefetched=prefetch.result() if prefetch is not None
                else None)
        x_slow = (jnp.asarray(self.slow_tier.vectors)
                  if self.slow_tier is not None else self.index.vectors)
        return search_mod._rerank_slow_tier_jit(
            jnp.asarray(beam_ids), x_slow, jnp.asarray(queries), k=k)

    def finish_extras(self) -> dict[str, Any]:
        if self.slow_tier is None or not self.slow_tier.is_disk:
            return {}
        return {"slow_tier": self.slow_tier.stats()}

    def promotion_tick(self):
        """Kick one hot-tier promotion round on the disk tier's promoter
        thread (non-blocking; None without a disk tier or hot tier).  The
        engine calls this at every pipeline gather."""
        if self.slow_tier is None or not getattr(self.slow_tier, "is_disk",
                                                 False):
            return None
        tick = getattr(self.slow_tier, "promotion_tick", None)
        return tick() if tick is not None else None

    def fixed(self, queries, *, beam_width: int, max_hops: int, k: int,
              excl=None):
        from repro.index.disk import rerank_with_slow_tier, search_tiered

        if self.prefetches:
            # Disk mode: run the walk un-reranked at full beam width, then
            # rerank through the block store (blocking here — fixed-beam
            # dispatch has no later stage to hide the fetch behind).
            beam_ids, _beam_d, stats = search_tiered(
                self.index, queries, beam_width=beam_width,
                max_hops=max_hops, k=beam_width, rerank=False,
                step_kernel=self.step_kernel, excl=excl)
            ids, d2 = rerank_with_slow_tier(
                self.slow_tier, np.asarray(beam_ids), queries, k)
            return ids, d2, stats, None
        ids, d2, stats = search_tiered(
            self.index, queries, beam_width=beam_width, max_hops=max_hops,
            k=k, rerank=self.do_rerank, step_kernel=self.step_kernel,
            excl=excl)
        return ids, d2, stats, None

    def recall_eval(self, queries, gt_ids, *, k, sample, seed, base_cfg):
        from repro.core import calibrate as calib

        return calib.tiered_recall_eval(
            self.index, queries, gt_ids, k=k, sample=sample, seed=seed,
            base_cfg=base_cfg)


class OutOfCoreBackend(_StagedRerankMixin):
    """Serve an index bigger than device memory: only the PQ codes (and
    codebook + entry) live in HBM to steer the walk — adjacency *and*
    full-precision vectors stay in the block store and are read at walk /
    rerank time through the slow tier's worker thread.

    The walk runs the out-of-core drivers of :mod:`repro.index.disk`
    (:func:`~repro.index.disk.ooc_probe` /
    :func:`~repro.index.disk.ooc_continue`): each hop is split at the
    frontier selection so the host can fetch ``adj[u]`` from the store
    between two small device programs, with ``io_groups`` lane groups
    round-robined to overlap one group's block reads with another's device
    hop.  Results are bit-identical to the in-memory
    :class:`TieredBackend` (the engine-parity matrix pins it).

    ``walk_prefetches`` makes the engine insert a *walk-prefetch* stage:
    the continue phase's first frontier is known as soon as the probe's
    budgets are granted, so up to ``io_depth`` of those adjacency blocks
    are submitted to the tier's worker one pipeline stage before the
    continue runs — a pure cache warm-up, never a result change.

    ``step_kernel`` is accepted for engine-API parity but the out-of-core
    hop always runs the reference op chain: the fused Pallas step fuses
    the full-adjacency HBM gather, which is exactly what this backend
    avoids having in device memory.  (Reference and fused are bit-identical
    anyway, so the parity matrix's kernel axis stays meaningful.)
    """

    staged = True
    prefetches = True        # the rerank fetch is always a disk read here
    walk_prefetches = True

    def __init__(self, codes, codebook, entry, slow_tier, *,
                 io_groups: int = 2, io_depth: int = 32,
                 step_kernel: str | None = "auto"):
        self.io_groups = io_groups
        self.io_depth = io_depth
        self.step_kernel = step_kernel
        self.lane_gathers = pipe.LaneGathers()
        self.slow_tier = None
        self.update(codes, codebook, entry, slow_tier=slow_tier)

    def update(self, codes, codebook, entry, *, slow_tier) -> None:
        """Swap the steering arrays and the block-store tier in place
        (Online-MCGI refresh path).  ``slow_tier`` is a required keyword:
        the store holds the graph itself here, so a refresh that doesn't
        name it would either serve a stale graph or silently lose the
        index.  A replaced tier's worker thread is shut down."""
        if slow_tier is None or not getattr(slow_tier, "is_disk", False):
            raise ValueError(
                "out-of-core serving needs a BlockSlowTier over a store "
                "holding the graph's adjacency + vectors")
        old = self.slow_tier
        self.codes = jnp.asarray(codes)
        self.codebook = codebook
        self.entry = jnp.asarray(entry)
        self.slow_tier = slow_tier
        # Unless the tier was built with an explicit worker count, size its
        # prefetch pool to the round-robin group count — one I/O worker per
        # group is what lets one group's block reads actually overlap
        # another's device hop (a single worker would serialise them).
        adopt = getattr(slow_tier, "default_io_workers", None)
        if adopt is not None:
            adopt(self.io_groups)
        if old is not None and old is not slow_tier:
            old.close()

    def close(self) -> None:
        """Shut down the slow tier's worker thread (idempotent)."""
        if self.slow_tier is not None:
            self.slow_tier.close()

    def set_step_kernel(self, step_kernel: str | None) -> None:
        """Recorded for engine-API parity; the out-of-core walk always runs
        the reference hop ops (see the class docstring)."""
        self.step_kernel = step_kernel

    def admit(self, queries: Array) -> Array:
        # Same LUT ops as the tiered admit (repro.index.disk._query_luts),
        # so admission is bit-identical between the two backends.
        from repro.pq import build_lut

        q = jnp.asarray(queries)
        d_book = self.codebook.m * self.codebook.dsub
        if q.shape[1] < d_book:
            q = jnp.pad(q, ((0, 0), (0, d_book - q.shape[1])))
        return build_lut(q, self.codebook.centroids)

    def num_nodes(self) -> int:
        return int(self.codes.shape[0])

    def probe(self, ctxs, budget_cfg, excl=None):
        from repro.index import disk as disk_mod

        return self.lane_gathers.warm(disk_mod.ooc_probe(
            self.codes, ctxs, self.entry, int(self.codes.shape[0]),
            budget_cfg, self.slow_tier, io_groups=self.io_groups,
            excl=excl), ctxs)

    def continue_fn(self, budget_cfg):
        from repro.index import disk as disk_mod

        def cont(sub_state, sub_ctxs, sub_budgets, sub_hop_limits):
            return disk_mod.ooc_continue(
                self.codes, sub_state, sub_ctxs, sub_budgets,
                sub_hop_limits, budget_cfg.l_max, self.slow_tier,
                io_groups=self.io_groups)

        return cont

    def prefetch_walk(self, probe_state, budgets, hop_limits):
        """Submit the continue phase's first-frontier adjacency reads (up
        to ``io_depth`` nodes) to the tier's worker — the walk-prefetch
        stage's work.  Cache warm-up only; returns the future (or None when
        every lane already converged in the probe)."""
        from repro.index import disk as disk_mod

        u = disk_mod.ooc_first_frontier(
            probe_state, budgets, hop_limits,
            int(probe_state[0].shape[1]))
        u = u[u >= 0][:self.io_depth]
        if u.size == 0:
            return None
        return self.slow_tier.prefetch_adj(u)

    def prefetch_rerank(self, parts):
        """See :meth:`TieredBackend.prefetch_rerank`."""
        return self.slow_tier.prefetch(np.asarray(parts[0]))

    def rerank(self, beam_ids, beam_d, queries, k: int, prefetch=None):
        from repro.index.disk import rerank_with_slow_tier

        return rerank_with_slow_tier(
            self.slow_tier, np.asarray(beam_ids), queries, k,
            prefetched=prefetch.result() if prefetch is not None else None)

    def finish_extras(self) -> dict[str, Any]:
        return {"slow_tier": self.slow_tier.stats()}

    def promotion_tick(self):
        """See :meth:`TieredBackend.promotion_tick` — here the walk itself
        benefits: promoted adjacency rows turn walk-time block reads into
        dense-array hits."""
        tick = getattr(self.slow_tier, "promotion_tick", None)
        return tick() if tick is not None else None

    def fixed(self, queries, *, beam_width: int, max_hops: int, k: int,
              excl=None):
        from repro.index import disk as disk_mod

        ctxs = self.admit(queries)
        nq = int(ctxs.shape[0])
        states = search_mod.ooc_init_pq(
            self.codes, ctxs, self.entry, int(self.codes.shape[0]),
            beam_width, excl=excl)
        state = disk_mod.ooc_walk(
            self.codes, states, ctxs,
            jnp.full((nq,), jnp.int32(beam_width)),
            jnp.full((nq,), jnp.int32(max_hops)),
            beam_width, self.slow_tier, self.io_groups)
        if excl is not None:
            state = search_mod._scrub_state_jit(state, excl)
        ids, d2 = disk_mod.rerank_with_slow_tier(
            self.slow_tier, np.asarray(state[0]), queries, k)
        stats = search_mod.SearchStats(hops=np.asarray(state[4]),
                                       dist_evals=np.asarray(state[5]))
        return ids, d2, stats, None


class DistributedBackend:
    """Sharded scatter-gather serving over a mesh: each shard walks its own
    sub-graph (adaptive budgets and bucket deadlines are *in-graph* —
    see :mod:`repro.distributed.sharded_search`).

    Two execution shapes:

    * built with ``beam_budget`` and driven by an engine holding the *same*
      budget config, the backend is **staged**: the probe program
      checkpoints every shard's walk at the probe horizon and the continue
      program resumes any query subset (warm state) and ends in the hedged
      merge — so ``search_batches`` overlaps batch i+1's mesh-wide probe
      with batch i's host bucketing and per-bucket continues.  Granted
      budgets are per (query, shard); the host schedules on their per-query
      mean (see :meth:`schedule_budgets`).
    * without an engine-level budget config the whole step stays one
      compiled program (:func:`~repro.distributed.sharded_search.make_distributed_search`)
      and the pipeline overlaps at step granularity — the dry-run-priced
      shape, and the only one that runs fixed-beam.

    ``shard_laws=(lam (S,), l_min (S,))`` threads per-shard calibrated
    budget laws through both shapes as runtime arrays (see
    :func:`repro.core.calibrate.calibrate_budget_law_per_shard`) — updating
    them never recompiles.
    """

    def __init__(self, mesh, arrays: dict, *, beam_width: int, max_hops: int,
                 k: int, query_chunk: int = 128, use_pq: bool = True,
                 beam_budget=None, budget_buckets: int | None = None,
                 shard_ok=None, shard_laws=None, merge: str = "hierarchical",
                 step_kernel: str | None = "auto"):
        from repro.distributed import sharded_search as ss

        self.mesh = mesh
        self.arrays = dict(arrays)
        n_shards = mesh.devices.size
        self.rows_per_shard = arrays["vectors"].shape[0] // n_shards
        if "entries" not in self.arrays:
            self.arrays["entries"] = ss.shard_entry_points(
                arrays["vectors"], n_shards)
        self.shard_ok = (shard_ok if shard_ok is not None
                         else jnp.ones((n_shards,), jnp.bool_))
        self.beam_budget = beam_budget
        self.shard_laws = None
        if shard_laws is not None:
            self.shard_laws = (jnp.asarray(shard_laws[0], jnp.float32),
                               jnp.asarray(shard_laws[1], jnp.int32))
        self._build_kw = dict(
            beam_width=beam_width, max_hops=max_hops, k=k,
            query_chunk=query_chunk, use_pq=use_pq,
            budget_buckets=budget_buckets, merge=merge)
        self.step_kernel = step_kernel
        self.lane_gathers = pipe.LaneGathers()
        # One more bucket costs one more *whole-mesh* program (n_shards
        # shard walks + merge collectives + the checkpoint-state gather),
        # not one more single-host kernel launch: scale the scheduler's
        # modelled launch cost accordingly so the bucket DP only splits a
        # batch when the lane-hop savings clear the real dispatch price.
        self.launch_cost_hops = pipe.BUCKET_LAUNCH_COST_HOPS * n_shards
        self._build_programs()

    def _build_programs(self) -> None:
        """(Re)jit the mesh programs against the current ``step_kernel``.

        The step kernel is a builder-time knob of the shard walk, so the
        jitted monolithic/probe/continue programs are rebuilt when it
        changes; the jit wrappers are fresh objects, so stale-kernel
        programs can't be served from a cache."""
        from repro.distributed import sharded_search as ss

        kw = self._build_kw
        # jit the monolithic step: the builder returns a raw traceable (what
        # cells.py lowers); serving it eagerly would retrace per call.
        self.step = jax.jit(ss.make_distributed_search(
            self.mesh, beam_width=kw["beam_width"], max_hops=kw["max_hops"],
            k=kw["k"], query_chunk=kw["query_chunk"], use_pq=kw["use_pq"],
            beam_budget=self.beam_budget,
            budget_buckets=kw["budget_buckets"], merge=kw["merge"],
            per_shard_laws=self.shard_laws is not None,
            step_kernel=self.step_kernel))
        self._probe_step = self._continue_step = None
        if self.beam_budget is not None:
            self._probe_step = jax.jit(ss.make_distributed_probe(
                self.mesh, budget_cfg=self.beam_budget,
                max_hops=kw["max_hops"], query_chunk=kw["query_chunk"],
                use_pq=kw["use_pq"], budget_buckets=kw["budget_buckets"],
                per_shard_laws=self.shard_laws is not None,
                step_kernel=self.step_kernel))
            self._continue_step = jax.jit(ss.make_distributed_continue(
                self.mesh, budget_cfg=self.beam_budget, k=kw["k"],
                use_pq=kw["use_pq"], merge=kw["merge"],
                step_kernel=self.step_kernel))

    def set_step_kernel(self, step_kernel: str | None) -> None:
        """Select the shard walk's hop implementation ("reference" |
        "pallas" | "auto") and rebuild the jitted mesh programs."""
        self.step_kernel = step_kernel
        self._build_programs()

    @property
    def staged(self) -> bool:
        """Stageable iff the walk is adaptive (the probe horizon exists)."""
        return self.beam_budget is not None

    @staticmethod
    def make_step(mesh, *, beam_width: int, max_hops: int, k: int,
                  query_chunk: int = 128, use_pq: bool = True,
                  beam_budget=None, budget_buckets: int | None = None,
                  per_shard_laws: bool = False,
                  step_kernel: str | None = None):
        """The raw jit-able sharded step — what launch/cells.py lowers for
        the dry-run (same builder the live backend runs)."""
        from repro.distributed import sharded_search as ss

        return ss.make_distributed_search(
            mesh, beam_width=beam_width, max_hops=max_hops, k=k,
            query_chunk=query_chunk, use_pq=use_pq, beam_budget=beam_budget,
            budget_buckets=budget_buckets, per_shard_laws=per_shard_laws,
            step_kernel=step_kernel)

    def set_shard_ok(self, shard_ok) -> None:
        """Runtime straggler/fault mask — no recompilation.  Consumed at
        merge time, so in a pipelined stream the new mask applies to every
        continue program dispatched after the call."""
        self.shard_ok = shard_ok

    def _laws(self) -> tuple:
        return self.shard_laws if self.shard_laws is not None else ()

    # ------------------------------------------------- monolithic protocol

    def dispatch(self, queries):
        a = self.arrays
        return self.step(a["adj"], a["codes"], a["vectors"], a["centroids"],
                         jnp.asarray(queries), self.shard_ok, a["entries"],
                         *self._laws())

    def collect(self, handles) -> BatchResult:
        d2, shard_ids, local_ids = handles
        sid = np.asarray(shard_ids).astype(np.int64)
        lid = np.asarray(local_ids).astype(np.int64)
        gids = sid * self.rows_per_shard + lid
        return BatchResult(ids=gids, d2=np.asarray(d2),
                           extras={"shard_ids": sid, "local_ids": lid})

    # ----------------------------------------------------- staged protocol

    def admit(self, queries) -> Array:
        return jnp.asarray(queries)

    def probe(self, ctxs, budget_cfg, excl=None):
        if excl is not None:
            raise NotImplementedError(
                "filtered search is not supported on the distributed "
                "backend: the filter bitset is indexed by global node id "
                "while the mesh programs checkpoint shard-local walks with "
                "no global-id view (see ROADMAP carry-overs)")
        if budget_cfg != self.beam_budget:
            raise ValueError(
                "staged distributed serving needs the engine's budget_cfg "
                f"to equal the backend's beam_budget; got {budget_cfg} vs "
                f"{self.beam_budget}")
        a = self.arrays
        return self.lane_gathers.warm(self._probe_step(
            a["adj"], a["codes"], a["vectors"], a["centroids"], ctxs,
            a["entries"], *self._laws()), ctxs)

    def continue_fn(self, budget_cfg):
        a = self.arrays

        def cont(sub_state, sub_queries, sub_budgets, sub_hop_limits):
            return self._continue_step(
                a["adj"], a["codes"], a["vectors"], a["centroids"],
                sub_state, sub_queries, sub_budgets, sub_hop_limits,
                self.shard_ok)

        return cont

    def schedule_budgets(self, budgets_np: np.ndarray) -> np.ndarray:
        """Per-query effective budget for host scheduling: the *mean* over
        shards — the expected per-shard work a lane adds to a continue
        program.  The max over shards is useless as a key: with many
        independently-centered shard laws, nearly every query draws ~l_max
        on *some* shard (an extreme statistic of S noisy probe estimates),
        so the histogram collapses to one bucket.  Scheduling never changes
        math either way; the continue programs always receive the raw
        per-shard grants."""
        return np.rint(budgets_np.mean(axis=1)).astype(np.int32)

    def finish(self, queries, parts, k: int, *, q_lid,
               budgets_np, prefetch=None) -> BatchResult:
        """``extras`` adds the per (query, shard) counters of the continue
        program: ``shard_hops``, ``shard_budget`` (the granted budget) and
        ``shard_capped`` (the walk stopped at its hop limit)."""
        d2, shard_ids, local_ids, hops, evals, shard_hops, capped = parts
        sid = shard_ids.astype(np.int64)
        lid = local_ids.astype(np.int64)
        return BatchResult(
            ids=sid * self.rows_per_shard + lid, d2=d2,
            stats=search_mod.SearchStats(hops=hops, dist_evals=evals),
            astats=search_mod.AdaptiveStats(q_lid=np.asarray(q_lid),
                                            budget=budgets_np),
            extras={"shard_ids": sid, "local_ids": lid,
                    "shard_hops": shard_hops, "shard_budget": budgets_np,
                    "shard_capped": capped})


@dataclasses.dataclass
class _InFlight:
    """One admitted batch whose device programs are dispatched, not collected.

    ``backend`` is the flight's *snapshot* of the engine's backend, taken at
    dispatch (a shallow ``copy.copy``): every post-dispatch stage runs
    against it, so a concurrent :meth:`SearchEngine.update_backend` (the
    delta-tier merge publishing a new index + block store) never mixes two
    index versions inside one flight.  The shallow copy freezes the
    attribute *bindings* (index, codes, slow tier); a replaced disk tier is
    closed by ``update`` but a closed tier still serves synchronous reads,
    so the snapshot stays fully functional until its last gather.
    """

    queries: Any
    batch: int                 # engine sequence number: the spans' ``batch``
    backend: Any = None
    excl: Any = None           # packed filter words ((Q, nw) uint32) or None
    ctxs: Any = None
    probe_state: Any = None
    budgets: Any = None
    hop_limits: Any = None
    q_lid: Any = None
    handles: Any = None        # monolithic mode: the dispatched program's outputs
    # Filled by the schedule stage (staged mode):
    budgets_np: Any = None
    ceilings: tuple[int, ...] | None = None
    dispatched: Any = None     # [(members, continue handles)] or full-batch handles
    # Filled by the walk-prefetch stage (out-of-core backend only):
    walk_prefetch: Any = None  # future of the first-frontier adjacency reads
    # Filled by the prefetch stage (disk slow tier only):
    parts: Any = None          # continue outputs, synced to host numpy
    prefetch: Any = None       # future of the slow tier's block fetch


class SearchEngine:
    """One serving API over every backend, with a double-buffered pipeline.

    Modes:
      * ``budget_cfg=None`` — fixed-beam serving at ``beam_width``.
      * ``budget_cfg=AdaptiveBeamBudget(...)`` — the adaptive engine
        (probe -> budget -> bucketed continue -> rerank), staged per batch.

    ``num_buckets``: ``"auto"`` (default) picks the bucket-ceiling family per
    batch from the granted-budget histogram
    (:func:`repro.serving.pipeline.auto_bucket_ceilings`); an int >= 2 pins
    the historical fixed family; ``None``/1 disables bucketing (single
    continue program).  Scheduling never changes results.

    ``step_kernel`` ("reference" | "pallas" | "auto") selects the walk's hop
    implementation on the backend (``backend.set_step_kernel``): the
    reference hop chain or the fused Pallas beam step
    (:mod:`repro.kernels.beam_step`) — bit-identical results either way in
    interpret mode (the engine-parity kernel axis asserts it per backend
    and variant).  Backends default to "auto": the fused step on a TPU,
    the reference hop elsewhere.

    ``search`` serves one batch, unpipelined.  ``search_batches`` serves a
    stream with double buffering: batch i+1's admission + probe are
    *dispatched* before batch i's bucketing/continue are *collected*, so the
    accelerator works through the next probe while the host partitions the
    current batch (jax dispatch is asynchronous).  Each batch's results are
    bit-identical between the two entry points — the same compiled programs
    run on the same inputs; only the moment of the blocking host transfer
    moves.

    Batches may be ragged (each shape jit-caches separately; pad upstream to
    a shape quantum if compile count matters).  ``coalesce_lanes`` instead
    merges *small* batches inside the engine: consecutive batches are
    concatenated until the merged lane count reaches the threshold, the
    merged batch flows through the pipeline once, and the results are split
    back so every input batch still yields its own :class:`BatchResult`
    (per-query order preserved) — the cross-batch admission coalescing a hot
    upstream batcher needs.  Coalescing is result-transparent per query
    under a pinned LID center; with batch centering, budgets depend on
    which queries share a dispatch (the reducer's property, as with any
    batching choice).

    The engine is mutable where serving needs it to be: :meth:`recalibrate`
    refits the budget law in place; :meth:`update_backend` swaps refreshed
    index arrays (Online-MCGI inserts) without losing the engine or its jit
    caches.
    """

    def __init__(self, backend, budget_cfg=None, *, k: int = 10,
                 beam_width: int = 48, max_hops: int = 2048,
                 num_buckets: int | str | None = "auto",
                 pad_quantum: int = pipe.LANE_QUANTUM,
                 coalesce_lanes: int | None = None,
                 step_kernel: str | None = None):
        self.backend = backend
        if step_kernel is not None:
            # The knob lives on the backend (it keys the jitted walk
            # programs); the engine-level parameter is pure convenience.
            backend.set_step_kernel(step_kernel)
        self.budget_cfg = budget_cfg
        self.k = k
        self.beam_width = beam_width
        self.max_hops = max_hops
        self.num_buckets = num_buckets
        # Bucket lane counts are padded to this grid (jit-cache shape family
        # vs lane inflation; a per-accelerator tuning knob). The engine's
        # default is finer than the historical 8: with tight DP-chosen
        # ceilings and serving-size micro-batches, quantum-4 padding was
        # measured (CPU) to cut padded-lane inflation enough to beat the
        # extra compile shapes.  The backends' lane gathers are warmed on
        # the ``pipe.LANE_QUANTUM`` grid: a quantum off that grid compiles
        # a lane count's gather when a batch first needs it.
        self.pad_quantum = pad_quantum
        self.coalesce_lanes = coalesce_lanes
        self._batches = itertools.count()
        self._close_lock = threading.Lock()
        self._closed = False
        backend_budget = getattr(backend, "beam_budget", None)
        if (budget_cfg is not None and backend_budget is not None
                and budget_cfg != backend_budget):
            raise ValueError(
                "engine budget_cfg and the distributed backend's beam_budget "
                "must be the same config (the staged programs are compiled "
                f"against the latter): {budget_cfg} vs {backend_budget}")

    # ------------------------------------------------------------- serving

    def search(self, queries, *, filter=None) -> BatchResult:
        """Serve one batch (unpipelined): all stages back to back.

        ``filter`` is a boolean *allowed* mask over the index's nodes —
        ``(n,)`` shared by every query or ``(Q, n)`` per query (a tenant
        namespace, an attribute predicate, the delta tier's live set).  It
        is enforced *in-graph*: the packed mask pre-seeds the walk's visited
        bitset (see :func:`repro.core.search.pack_filter`), so out-of-filter
        nodes never enter the beam and can never be returned — queries with
        fewer than k in-filter reachable nodes pad with INVALID/inf lanes.
        """
        f = self._dispatch(queries, filter)
        if self._walk_prefetching():
            f = self._walk_prefetch(f)
        f = self._schedule(f)
        if self._prefetching():
            f = self._prefetch(f)
        return self._gather(f)

    def search_batches(self, batches: Iterable, *,
                       filter=None) -> Iterator[BatchResult]:
        """Serve a stream of query batches, double-buffered.

        Two batches are in flight (three with a disk slow tier, whose extra
        prefetch stage deepens the window by one): batch i+1's admission +
        probe are dispatched before batch i's budgets are synced and its
        continue programs dispatched, and the oldest batch's continues are
        gathered only after that — the device queue always holds the next
        batch's work while the host buckets and reassembles (and, disk, the
        tier's worker reads blocks). Yields one :class:`BatchResult` per
        input batch, in order. A single-batch stream degrades to exactly
        :meth:`search` (no prefetch partner). The generator is lazy —
        iterate it to drive the pipeline.

        With ``coalesce_lanes`` set, micro-batches below the threshold are
        merged before dispatch and their results split back on gather — one
        result per *input* batch either way.

        ``filter`` (see :meth:`search`) is either one allowed mask shared by
        every batch (``(n,)`` bool), or an iterable yielding one entry per
        input batch — each ``(n,)``, ``(Q_b, n)``, or ``None`` for an
        unfiltered batch.  Coalesced dispatches concatenate the member
        batches' per-query masks (``None`` members expand to all-True), so
        coalescing stays result-transparent per query.
        """
        pairs = self._with_filters(batches, filter)
        if not self.coalesce_lanes or self.coalesce_lanes <= 1:
            yield from self._stream(pairs)
            return
        groups: list[list[int]] = []   # lane counts of each merged dispatch
        for res in self._stream(self._coalesced(pairs, groups)):
            sizes = groups.pop(0)
            if len(sizes) == 1:
                yield res
            else:
                yield from _split_result(res, sizes)

    def _with_filters(self, batches: Iterable, flt) -> Iterator:
        """Pair each query batch with its allowed mask (or None).

        A single array-like ``flt`` is the shared-mask form; any other
        non-None value is treated as an iterable of per-batch masks.
        """
        if flt is None:
            for qb in batches:
                yield np.asarray(qb), None
            return
        if isinstance(flt, (np.ndarray, jax.Array, list, tuple)):
            try:
                shared = np.asarray(flt)
            except ValueError:       # ragged per-batch list
                shared = None
            if (shared is not None and shared.ndim == 1
                    and shared.dtype != object):
                shared = shared.astype(bool)
                for qb in batches:
                    yield np.asarray(qb), shared
                return
        for qb, m in zip(batches, flt):
            yield np.asarray(qb), None if m is None else np.asarray(m)

    def _coalesced(self, pairs: Iterable, groups: list) -> Iterator:
        """Merge consecutive (batch, mask) pairs until ``coalesce_lanes``
        lanes are admitted; append each flushed group's per-batch sizes to
        ``groups`` (recorded at dispatch, so the split plan is always ahead
        of the results)."""
        pend: list[np.ndarray] = []
        pend_m: list = []
        lanes = 0

        def flush():
            groups.append([b.shape[0] for b in pend])
            qb = pend[0] if len(pend) == 1 else np.concatenate(pend)
            if all(m is None for m in pend_m):
                return qb, None
            n = self.backend.num_nodes()
            rows = [np.broadcast_to(
                        np.ones(n, bool) if m is None else m.astype(bool),
                        (b.shape[0], n))
                    for b, m in zip(pend, pend_m)]
            return qb, np.concatenate(rows)

        for qb, m in pairs:
            pend.append(qb)
            pend_m.append(m)
            lanes += qb.shape[0]
            if lanes >= self.coalesce_lanes:
                yield flush()
                pend, pend_m, lanes = [], [], 0
        if pend:
            yield flush()

    def _stream(self, pairs: Iterable) -> Iterator[BatchResult]:
        """The double-buffered pipeline core (one result per input batch).

        ``flight`` holds the batches between dispatch and gather as
        ``[stage index, state]``, oldest first; every new dispatch advances
        each in-flight batch by exactly one stage, newest first — so within
        a tick the order is: dispatch batch i's probe, schedule batch i-1
        (continues enter the device queue), prefetch batch i-2's block reads
        (disk slow tier only), gather the oldest.  With the default stage
        list that is exactly the historical two-in-flight pipeline; a disk
        slow tier adds the prefetch stage, making it three deep so the block
        reads of one batch overlap the continue programs of the next.
        """
        stages: list = [self._schedule]
        if self._walk_prefetching():
            # Runs *before* the bucket/continue stage: the out-of-core
            # backend's first-frontier adjacency reads go to the tier's
            # worker while the newest batch's probe occupies the device.
            stages.insert(0, self._walk_prefetch)
        if self._prefetching():
            stages.append(self._prefetch)
        flight: list[list] = []

        def advance() -> BatchResult | None:
            done = None
            for ent in reversed(flight):
                si, f = ent
                if si < len(stages):
                    ent[1] = stages[si](f)
                    ent[0] = si + 1
                else:
                    done = self._gather(f)
            if done is not None:
                flight.pop(0)
            return done

        for qb, flt in pairs:
            new = self._dispatch(qb, flt)  # batch i hits the device queue first
            res = advance()
            flight.append([0, new])
            if res is not None:
                yield res
        while flight:
            res = advance()
            if res is not None:
                yield res

    # -------------------------------------------- front-door dispatch seam

    def begin(self, queries, *, filter=None) -> _InFlight:
        """Dispatch one batch and return its in-flight handle without
        blocking — the front half of :meth:`search`, split out so the
        serving front door (:mod:`repro.serving.server`) can start device
        work at flush time and finish it on its own scheduler.  Pair with
        :meth:`finish_from` (full result) and :meth:`partial_result`
        (best-so-far at a deadline).  ``filter`` as in :meth:`search`; the
        flight carries its backend snapshot, so a backend refresh between
        ``begin`` and ``finish_from`` never mixes index versions."""
        return self._dispatch(queries, filter)

    def finish_from(self, f: _InFlight) -> BatchResult:
        """Run the remaining stages of a :meth:`begin` flight and gather
        the batch.  ``begin`` + ``finish_from`` executes exactly the stage
        sequence of :meth:`search` — same compiled programs, same inputs,
        bit-identical results."""
        if self._staged() and f.dispatched is None:
            if self._walk_prefetching() and f.walk_prefetch is None:
                f = self._walk_prefetch(f)
            f = self._schedule(f)
            if self._prefetching() and f.prefetch is None:
                f = self._prefetch(f)
        return self._gather(f)

    @property
    def supports_partial(self) -> bool:
        """Whether :meth:`partial_result` can serve a best-so-far answer:
        a staged engine whose backend exposes a host-side probe view
        (``partial_parts``).  The distributed probe state is a whole-mesh
        checkpoint — its beams live shard-local with no host reassembly
        short of the merge program — so the front door falls back to plain
        timeouts there."""
        return (self._staged()
                and hasattr(self.backend, "partial_parts"))

    def partial_result(self, f: _InFlight) -> BatchResult:
        """Best-so-far gather at the probe horizon — the deadline-aware
        gather of the serving front door.  The probe state's beam is
        reranked through the backend's normal finish path (slow-tier fetch
        included, synchronously — a deadline hedge has no later stage to
        hide I/O behind), so a partial is a real servable result: valid
        ids, true distances, just from a shorter walk.  The flight is not
        consumed — :meth:`finish_from` can still run afterwards and sees
        the same probe state.  ``extras["partial"]`` marks the result."""
        if not self.supports_partial:
            raise ValueError(
                "partial results need a staged engine over a backend with "
                "a host-side probe view (partial_parts); the distributed "
                "mesh state has none")
        parts = tuple(np.asarray(a)
                      for a in f.backend.partial_parts(f.probe_state))
        budgets_np = (f.budgets_np if f.budgets_np is not None
                      else np.asarray(f.budgets))
        res = f.backend.finish(f.queries, parts, self.k, q_lid=f.q_lid,
                               budgets_np=budgets_np)
        res.extras["partial"] = True
        return res

    # ------------------------------------------------- pipeline stage thirds

    def _pack_filter(self, flt, nq: int):
        """Normalise an allowed mask to packed exclusion words (or None)."""
        if flt is None:
            return None
        if not hasattr(self.backend, "num_nodes"):
            raise NotImplementedError(
                "filtered search is not supported on this backend (no "
                "global node-id view; see DistributedBackend.probe)")
        n = self.backend.num_nodes()
        allowed = np.asarray(flt, dtype=bool)
        if allowed.ndim == 1:
            allowed = np.broadcast_to(allowed, (nq, n))
        if allowed.shape != (nq, n):
            raise ValueError(
                f"filter mask shape {allowed.shape} != ({nq}, {n}) "
                "(expected an allowed mask of (n,) or (Q, n) bool)")
        return search_mod.pack_filter(allowed, n)

    def _dispatch(self, queries, flt=None) -> _InFlight:
        """Admission + probe (staged) or the whole program (monolithic);
        returns device handles without blocking.  The flight snapshots the
        backend (shallow copy) so every later stage — including ones that
        run after an :meth:`update_backend` — sees one consistent index
        version."""
        batch = next(self._batches)
        with _span("engine.dispatch", batch=batch) as span:
            backend = copy.copy(self.backend)
            excl = self._pack_filter(flt, int(np.asarray(queries).shape[0]))
            if not self._staged():
                if hasattr(backend, "dispatch"):
                    if excl is not None:
                        raise NotImplementedError(
                            "filtered search is not supported on the "
                            "distributed backend (no global node-id view)")
                    handles = backend.dispatch(queries)
                else:
                    q = jnp.asarray(queries)
                    handles = backend.fixed(
                        q, beam_width=self.beam_width, max_hops=self.max_hops,
                        k=self.k, excl=excl)
                return _InFlight(queries=queries, batch=batch,
                                 backend=backend, excl=excl, handles=handles)
            ctxs = backend.admit(queries)
            warmed = backend.lane_gathers.warmed
            probe_state, budgets, hop_limits, q_lid = backend.probe(
                ctxs, self.budget_cfg, excl=excl)
            span.set_metadata(warmed=backend.lane_gathers.warmed - warmed)
            return _InFlight(queries=queries, batch=batch, backend=backend,
                             excl=excl, ctxs=ctxs, probe_state=probe_state,
                             budgets=budgets, hop_limits=hop_limits,
                             q_lid=q_lid)

    def _schedule(self, f: _InFlight) -> _InFlight:
        """Host-bucket stage: sync the granted budgets (the transfer the
        lookahead hides), pick the bucket family, dispatch every continue
        program.  Monolithic batches pass through untouched.

        Bucket membership keys on the backend's *scheduling* view of the
        budgets (``schedule_budgets`` — per-query scalars for the single-host
        backends, the mean over shards for the distributed one); the continue
        programs always receive the raw granted budgets, so scheduling never
        changes math.
        """
        if not self._staged():
            return f
        with _span("engine.schedule", batch=f.batch):
            cfg = self.budget_cfg
            with _span("engine.schedule.sync", batch=f.batch):
                f.budgets_np = np.asarray(f.budgets)
                sched = f.backend.schedule_budgets(f.budgets_np)
            with _span("engine.schedule.plan", batch=f.batch):
                f.ceilings = self._resolve_ceilings(sched, cfg)
                buckets = (None if f.ceilings is None or len(f.ceilings) <= 1
                           else pipe.partition_by_bucket(
                               sched, f.ceilings, self.pad_quantum))
            nq = sched.shape[0]
            if buckets is None:     # one program over the whole batch
                n_buckets, padded = 1, nq
            else:
                n_buckets = len(buckets)
                padded = sum(p.size for _, _, p in buckets)
            with _span("engine.schedule.launch", batch=f.batch,
                       buckets=n_buckets, lanes=nq, padded_lanes=padded):
                cont = f.backend.continue_fn(cfg)
                if buckets is None:
                    f.dispatched = cont(f.probe_state, f.ctxs, f.budgets,
                                        f.hop_limits)
                else:
                    f.dispatched = pipe.dispatch_bucketed_continue(
                        cont, f.probe_state, f.ctxs, f.budgets, f.hop_limits,
                        buckets)
        return f

    def _walk_prefetch(self, f: _InFlight) -> _InFlight:
        """Out-of-core walk-prefetch stage: submit the continue phase's
        first-frontier adjacency block reads (bounded by the backend's
        ``io_depth``) to the tier's worker thread — they land in the
        tier's cache while other batches' device programs run.  Pure cache
        warm-up; results never depend on it."""
        if self._staged():
            with _span("engine.walk_prefetch", batch=f.batch):
                f.walk_prefetch = f.backend.prefetch_walk(
                    f.probe_state, f.budgets, f.hop_limits)
        return f

    def _prefetch(self, f: _InFlight) -> _InFlight:
        """Disk-slow-tier stage: sync the continue outputs to host numpy and
        submit the rerank's block reads to the tier's worker thread.  Runs
        right after the *next* batch's continue programs were dispatched, so
        the block reads overlap that device work; :meth:`_gather` joins the
        future one stage later.  Absent from the stage list unless the
        backend's slow tier is disk-backed."""
        if self._staged():
            with _span("engine.prefetch", batch=f.batch):
                f.parts = self._continue_parts(f)
                f.prefetch = f.backend.prefetch_rerank(f.parts)
        return f

    def _continue_parts(self, f: _InFlight) -> tuple:
        """Continue outputs as host numpy, original query order."""
        if f.parts is not None:
            return f.parts
        if f.ceilings is None or len(f.ceilings) <= 1:
            return tuple(np.asarray(a) for a in f.dispatched)
        return pipe.gather_bucketed_continue(
            f.budgets_np.shape[0], f.dispatched)

    def _gather(self, f: _InFlight) -> BatchResult:
        """Collection stage: pull continue results, finish (rerank or the
        distributed id reassembly), restore original query order.  Then —
        with the batch's results already in hand — kick one hot-tier
        promotion round on the disk tier's promoter thread
        (``backend.promotion_tick``, non-blocking; a no-op for backends
        without a frequency-aware tier): the tick digests the frequency
        the batch just recorded while the next batches' stages run."""
        with _span("engine.gather", batch=f.batch):
            res = self._collect(f)
            tick = getattr(self.backend, "promotion_tick", None)
            if tick is not None:
                tick()
        return res

    def _collect(self, f: _InFlight) -> BatchResult:
        if not self._staged():
            if hasattr(f.backend, "collect"):
                return f.backend.collect(f.handles)
            ids, d2, stats, astats = f.handles
            return BatchResult(
                ids=np.asarray(ids), d2=np.asarray(d2), stats=stats,
                astats=astats,
                extras=getattr(f.backend, "finish_extras", dict)())
        with _span("engine.gather.sync", batch=f.batch):
            parts = self._continue_parts(f)
        res = f.backend.finish(f.queries, parts, self.k, q_lid=f.q_lid,
                               budgets_np=f.budgets_np,
                               prefetch=f.prefetch)
        res.ceilings = f.ceilings
        return res

    def _staged(self) -> bool:
        return self.budget_cfg is not None and self.backend.staged

    def _prefetching(self) -> bool:
        """Whether the pipeline should run the disk-prefetch stage."""
        return self._staged() and getattr(self.backend, "prefetches", False)

    def _walk_prefetching(self) -> bool:
        """Whether the pipeline should run the walk-prefetch stage (the
        out-of-core backend reads adjacency at walk time)."""
        return (self._staged()
                and getattr(self.backend, "walk_prefetches", False))

    def _resolve_ceilings(self, budgets_np, cfg) -> tuple[int, ...] | None:
        if self.num_buckets == "auto":
            return pipe.auto_bucket_ceilings(
                budgets_np, cfg, quantum=self.pad_quantum,
                launch_cost_hops=getattr(self.backend, "launch_cost_hops",
                                         pipe.BUCKET_LAUNCH_COST_HOPS))
        if self.num_buckets is None or self.num_buckets <= 1:
            return None
        return search_mod.budget_bucket_ceilings(
            cfg.l_min, cfg.l_max, self.num_buckets)

    # ------------------------------------------------------- live reconfigure

    def recalibrate(self, queries=None, gt_ids=None, *,
                    recall_target: float = 0.95, joint: bool = False,
                    sample: int = 256, seed: int = 0,
                    eval_recall: Callable | None = None,
                    make_eval: Callable | None = None, **fit_kw):
        """Refit the budget law against ``recall_target`` and deploy it.

        The hook Online-MCGI needs: inserts shift the LID population, so an
        index refresh calls :meth:`update_backend` then this — the engine
        object, its backend wiring, and its shape-keyed jit caches all
        survive; only the (lam, hop_factor[, l_min]) knobs move (one
        recompile of probe/continue, since the config is a static jit key).

        ``joint=True`` runs the joint (lam, l_min) fit
        (:func:`repro.core.calibrate.calibrate_budget_law_joint`); otherwise
        the lam bisection of :func:`~repro.core.calibrate.calibrate_budget_law`.
        Evaluators default to the backend's own recall measurement on a
        held-out sample of ``queries``/``gt_ids``; pass ``eval_recall`` /
        ``make_eval`` to override.  Returns the
        :class:`~repro.core.calibrate.CalibrationResult`; the fitted config is
        already live on return.
        """
        from repro.core import calibrate as calib

        if self.budget_cfg is None:
            raise ValueError("recalibrate() needs an adaptive engine "
                             "(budget_cfg is None)")
        if getattr(self.backend, "beam_budget", None) is not None:
            # Swapping budget_cfg here would desync it from the staged
            # programs compiled against the backend's beam_budget and brick
            # every later search() on the consistency check in probe().
            raise NotImplementedError(
                "distributed engines recalibrate per shard: fit "
                "repro.core.calibrate.calibrate_budget_law_per_shard and "
                "rebuild the DistributedBackend with shard_laws= (runtime "
                "arrays — the rebuild recompiles nothing) and the fit's "
                "serving_budget()")
        base = self.budget_cfg
        if joint:
            if make_eval is None:
                if queries is None or gt_ids is None:
                    raise ValueError("joint recalibration needs queries + "
                                     "gt_ids (or make_eval)")
                make_eval = lambda cfg: self.backend.recall_eval(
                    queries, gt_ids, k=self.k, sample=sample, seed=seed,
                    base_cfg=cfg)
            result = calib.calibrate_budget_law_joint(
                make_eval, base, recall_target, **fit_kw)
        else:
            if eval_recall is None:
                if queries is None or gt_ids is None:
                    raise ValueError("recalibration needs queries + gt_ids "
                                     "(or eval_recall)")
                eval_recall = self.backend.recall_eval(
                    queries, gt_ids, k=self.k, sample=sample, seed=seed,
                    base_cfg=base)
            result = calib.calibrate_budget_law(
                eval_recall, base, recall_target, **fit_kw)
        self.budget_cfg = result.budget_cfg(base)
        return result

    def update_backend(self, *args, **kw) -> None:
        """Swap refreshed index arrays into the live backend (Online-MCGI
        insert path); see the backend's ``update`` signature.  Backends
        owning a disk slow tier close the replaced tier's worker thread
        as part of ``update``."""
        self.backend.update(*args, **kw)

    def close(self) -> None:
        """Release backend-owned resources (disk slow tiers own a worker
        thread).  Idempotent and safe to call concurrently — from any
        thread, including while a ``search_batches`` stream is in flight:
        exactly one caller runs the backend teardown, and a closed disk
        tier keeps serving synchronous reads (its prefetch degrades
        gracefully; see :meth:`repro.index.disk.BlockSlowTier.close`), so
        in-flight batches complete with bit-identical results.  Backends
        without resources are a no-op."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        close = getattr(self.backend, "close", None)
        if close is not None:
            close()
