"""Host-side scheduling for the staged serving pipeline.

This module owns everything the serving engine does *between* jitted device
programs: partitioning a batch into budget buckets, padding bucket lane
counts, choosing the bucket-ceiling family from the granted-budget histogram,
and reassembling per-bucket results into the original query order. The
device-side programs themselves (probe / continue / rerank) stay in
:mod:`repro.core.search` — they are pure kernels; this file is the scheduler
that drives them.

A bucket's lanes are cut out of the batch's probe state and per-lane
inputs by one compiled gather program (:func:`gather_lanes`), so a bucket
costs two dispatches on a single-host backend: that gather and the
continue program.  A staged backend passes every probe state through its
:class:`LaneGathers`, which compiles the gather for every padded lane count
on first sight of a batch shape, so no later batch of that shape compiles
anything.

Two collection disciplines are provided:

* eager (:func:`bucketed_continue`) — each bucket's results are pulled to
  the host before the next bucket is dispatched.  This is the historical
  behaviour that ``repro.core.search.beam_search_{exact,pq}_adaptive``'s
  ``num_buckets=`` convenience keeps, byte for byte, so existing callers
  and property tests see no change.
* deferred (:func:`dispatch_bucketed_continue` +
  :func:`gather_bucketed_continue`) — every bucket's continue program is
  dispatched before any result is gathered, so the device queue runs the
  buckets back to back while the host does its numpy reassembly.  The
  staged engine (:class:`repro.serving.engine.SearchEngine`) runs the two
  halves in different pipeline stages; results are the same arrays either
  way (identical programs, identical inputs — only the moment of the
  blocking transfer moves).

Host work that is *not* scheduling also rides between the stages this
module defines: the engine's gather stage ends by kicking the disk tier's
hot-node promotion tick (``backend.promotion_tick`` — see
:mod:`repro.index.hot_tier`), a non-blocking submit to the tier's promoter
thread.  It lives at the stage boundary for the same reason the bucket
scheduling does: the device queue already holds the younger batches' work,
so the host cycles spent there are free — and the promotion I/O itself runs
on its own thread against a private store handle, so no pipeline stage (or
fetch) ever waits on it.
"""
from __future__ import annotations

import concurrent.futures
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search as search_mod

# Continue-phase dispatch overhead expressed in modelled lane-hops: one more
# bucket costs one more (dispatch + host gather + pad) round trip.  The value
# is a scheduling constant, not a measured quantity — it only has to be large
# enough that splitting a bucket which saves fewer than ~a padded row of hops
# is rejected (measured CPU-only break-even is a few hundred lane-hops).
BUCKET_LAUNCH_COST_HOPS = 512
# Bucket lane counts are padded to multiples of this (the engine's
# ``pad_quantum`` default, and the grid :class:`LaneGathers` compiles).
LANE_QUANTUM = 4
WARM_THREADS = max(1, min(8, os.cpu_count() or 1))


def pad_bucket_size(n: int, quantum: int = 8) -> int:
    """Round a bucket's lane count up to a multiple of ``quantum``.

    A vmapped ``while_loop`` pays full body cost for *every* lane on every
    iteration (padding lanes are not free), so the pad grid must be fine:
    multiples of 8 cap the inflation at <= 12.5% for any bucket of >= 8 real
    lanes, while keeping the jit cache to at most Q/8 shapes per bucket —
    coarser (power-of-two) padding was measured to give back the entire
    bucketing win on the largest bucket (66 -> 128 lanes ~= 2x its work).
    """
    return max(quantum, ((n + quantum - 1) // quantum) * quantum)


def partition_by_bucket(
    budgets: np.ndarray, ceilings: tuple[int, ...], quantum: int = 8
) -> list[tuple[int, np.ndarray, np.ndarray]]:
    """Group queries by bucket: [(bucket_index, members, padded_members)].

    ``members`` are original batch positions; ``padded_members`` repeats
    ``members[0]`` up to the padded lane count (those lanes' results are
    discarded on reassembly).  Empty buckets are skipped.  Membership is a
    per-query property of the granted budget, never of batch order.
    """
    ceil_arr = np.asarray(ceilings, dtype=np.int64)
    bucket_idx = np.minimum(
        np.searchsorted(ceil_arr, np.asarray(budgets), side="left"),
        len(ceilings) - 1)
    out = []
    for bi in range(len(ceilings)):
        members = np.nonzero(bucket_idx == bi)[0]
        if members.size == 0:
            continue
        padded = np.concatenate([
            members,
            np.full(pad_bucket_size(members.size, quantum) - members.size,
                    members[0]),
        ])
        out.append((bi, members, padded))
    return out


def auto_bucket_ceilings(
    budgets: np.ndarray,
    budget_cfg: "search_mod.AdaptiveBeamBudget",
    max_buckets: int = 8,
    quantum: int = 8,
    launch_cost_hops: int = BUCKET_LAUNCH_COST_HOPS,
) -> tuple[int, ...]:
    """Pick the bucket-ceiling family from the granted-budget histogram.

    Replaces the fixed ``num_buckets=4`` default.  The batch's occupied
    budget values v_1 < ... < v_m are partitioned into at most
    ``max_buckets`` contiguous groups; a group's ceiling is its own largest
    occupied value (tight — a halving family's ceilings sit above the
    occupied values and buy nothing), and its modelled cost is

        padded_lanes * hop_factor * ceiling  +  launch_cost_hops

    (each bucket's vmapped while-loop is bounded by its slowest lane, itself
    bounded by the ceiling-derived hop limit, and pays every padded lane on
    every iteration; each extra bucket costs one more dispatch + host
    gather).  The exact minimiser over all contiguous partitions is found by
    a small dynamic program — O(m^2 * max_buckets) with m bounded by the
    distinct granted budgets, at most l_max - l_min + 1.  Ties break toward
    fewer buckets.  The choice is a pure function of the budget *histogram*
    — deterministic, and invariant under batch permutation — and scheduling
    never changes results, so auto-picking is result-transparent.
    """
    budgets = np.asarray(budgets)
    values, counts = np.unique(budgets, return_counts=True)
    m = values.size
    if m == 0:
        return (int(budget_cfg.l_max),)
    k_max = min(max_buckets, m)
    csum = np.concatenate([[0], np.cumsum(counts)])  # O(1) group counts

    def group_cost(i: int, j: int) -> float:
        """Cost of one bucket covering values[i:j] (j exclusive)."""
        lanes = pad_bucket_size(int(csum[j] - csum[i]), quantum)
        return (lanes * budget_cfg.hop_factor * int(values[j - 1])
                + launch_cost_hops)

    # best[j] = (cost, partition) for values[:j] using any number of groups
    # <= k_max; rebuilt k layers deep.
    inf = float("inf")
    prev = [inf] * (m + 1)
    prev[0] = 0.0
    cuts: list[list[tuple[int, ...] | None]] = [[None] * (m + 1)]
    cuts[0][0] = ()
    best_cost, best_cs = inf, None
    for _k in range(k_max):
        cur = [inf] * (m + 1)
        cur_cuts: list[tuple[int, ...] | None] = [None] * (m + 1)
        for j in range(1, m + 1):
            for i in range(j):
                if prev[i] == inf:
                    continue
                c = prev[i] + group_cost(i, j)
                if c < cur[j]:
                    cur[j] = c
                    cur_cuts[j] = cuts[-1][i] + (int(values[j - 1]),)
        cuts.append(cur_cuts)
        prev = cur
        if cur[m] < best_cost:  # strict: ties keep fewer buckets
            best_cost, best_cs = cur[m], cur_cuts[m]
    assert best_cs is not None
    return best_cs


def bucketed_continue(
    continue_fn,
    probe_state,
    ctxs,
    budgets,
    hop_limits,
    ceilings: tuple[int, ...],
):
    """Budget-bucketed continue phase over one batch.

    Queries are grouped by granted budget into the ``ceilings`` buckets and
    each bucket resumes as its own (cached-jit) continue call. A vmapped
    ``while_loop`` iterates until its *slowest* lane converges, so in the
    single-program path a batch with one hard query burns every easy lane's
    compute until the hard one finishes; per-bucket, the slowest lane is
    bounded by the bucket's own ceiling-derived hop limit — converged lanes
    actually free compute instead of idling.

    Per-query budgets/hop limits are passed through *unquantized*, so every
    lane computes exactly what the unbucketed path would: results are
    identical (scheduling changes, math doesn't). Buckets are padded to a
    multiple-of-8 lane count (repeating a member row, results discarded) so
    the jit cache sees a bounded shape family at <= 12.5% lane inflation.

    This is the eager discipline the core ``num_buckets=`` entry points
    keep; the staged engine instead drives the deferred halves
    (:func:`dispatch_bucketed_continue` + :func:`gather_bucketed_continue`)
    from different pipeline stages, so every bucket is dispatched before any
    is gathered and another batch's programs sit in between.

    Returns (beam_ids, beam_d, hops, evals) as numpy, original query order.
    """
    q = ctxs.shape[0]
    out = None
    for _bi, members, padded in partition_by_bucket(
            np.asarray(budgets), ceilings):
        handles = _dispatch_bucket(continue_fn, probe_state, ctxs, budgets,
                                   hop_limits, padded)
        out = _scatter_bucket(out, q, members, handles)
    if out is None:  # zero-query batch: no buckets — dispatch a zero-lane
        # program so the empty outputs carry the *program's* signature
        # (single-host continues return 4 arrays, distributed returns 5)
        members, handles = _zero_lane_bucket(continue_fn, probe_state, ctxs,
                                             budgets, hop_limits)
        out = _scatter_bucket(out, q, members, handles)
    return out


def dispatch_bucketed_continue(
    continue_fn,
    probe_state,
    ctxs,
    budgets,
    hop_limits,
    buckets: list[tuple[int, np.ndarray, np.ndarray]],
) -> list[tuple[np.ndarray, tuple]]:
    """Dispatch half of the deferred discipline: enqueue the continue
    program of every bucket of ``buckets`` (a :func:`partition_by_bucket`
    partition, made by the caller's planning step); nothing blocks.  Returns
    [(members, device handles)] for :func:`gather_bucketed_continue` —
    the staged engine runs the two halves in different pipeline stages, so
    another batch's programs sit between dispatch and gather."""
    dispatched = [
        (members, _dispatch_bucket(continue_fn, probe_state, ctxs, budgets,
                                   hop_limits, padded))
        for _bi, members, padded in buckets
    ]
    if not dispatched:   # zero-query batch — see bucketed_continue
        dispatched = [_zero_lane_bucket(continue_fn, probe_state, ctxs,
                                        budgets, hop_limits)]
    return dispatched


def gather_bucketed_continue(q: int, dispatched):
    """Gather half: pull every dispatched bucket to the host and reassemble
    original query order.

    Generic over the continue program's output signature: any tuple of
    per-lane arrays (axis 0 = query lanes) reassembles — the single-host
    backends return (beam_ids, beam_d, hops, evals), the distributed staged
    backend returns its merged (d2, shard_id, local_id, hops, evals).
    Returns the same-length tuple of (q, ...) numpy arrays.
    """
    out = None
    for members, handles in dispatched:
        out = _scatter_bucket(out, q, members, handles)
    assert out is not None, "no buckets dispatched"
    return out


@jax.jit
def _gather_lanes(leaves: list, sel):
    """Lanes ``sel`` (axis 0) of every array of ``leaves``, as one compiled
    program: the same ``a[sel]`` per leaf that an eager gather runs."""
    return [a[sel] for a in leaves]


def gather_lanes(probe_state, ctxs, budgets, hop_limits, padded: np.ndarray):
    """(sub_state, sub_ctxs, sub_budgets, sub_hop_limits) of the lanes
    ``padded`` (a :func:`partition_by_bucket` member list).

    One dispatch of the compiled gather per placement of the device
    leaves — one in all on a single-host backend; the distributed backend's
    uncommitted queries and its mesh-sharded state are two.  Grouping by
    placement keeps every output what eager ``a[sel]`` gives, bit for bit
    and in shape, dtype, weak type, sharding and commitment, so the
    continue programs' jit caches see the same signatures either way.  A
    host (numpy) leaf, such as the queries the core ``num_buckets=`` entry
    points pass through, is gathered on the host, as eager ``a[sel]`` did."""
    sel = jnp.asarray(padded)
    leaves, tree = jax.tree_util.tree_flatten(
        (probe_state, ctxs, budgets, hop_limits))
    out = [None] * len(leaves)
    groups: dict = {}
    for i, a in enumerate(leaves):
        if isinstance(a, jax.Array):
            groups.setdefault((a.committed, frozenset(a.sharding.device_set)),
                              []).append(i)
        else:
            out[i] = a[padded]
    for idx in groups.values():
        for i, g in zip(idx, _gather_lanes([leaves[i] for i in idx], sel)):
            out[i] = g
    return jax.tree_util.tree_unflatten(tree, out)


class LaneGathers:
    """Warms the compiled lane gather of one backend's probe states.

    A staged backend holds one and passes every probe state it returns
    through :meth:`warm`.  The first probe state of a new signature (every
    gathered leaf's shape, dtype, weak type, sharding and commitment)
    compiles the gather for every padded lane count a bucket of it can
    have, ``LANE_QUANTUM``, ``2 * LANE_QUANTUM``, .., the padded batch, by
    running it on ``arange(p) % Q`` from ``WARM_THREADS`` threads (XLA
    releases the GIL while it compiles).  So whatever probes a new batch
    shape first, a serving stream or a warm-up, compiles the family, and
    no later batch of that shape stalls on a compile.  ``warmed`` counts
    the lane counts warmed so far.
    """

    def __init__(self):
        self.warmed = 0
        self._seen: set = set()
        self._lock = threading.Lock()

    def warm(self, probe_out, ctxs):
        """Return the probe's ``(probe_state, budgets, hop_limits, q_lid)``
        unchanged, after compiling its gather family on first sight."""
        probe_state, budgets, hop_limits = probe_out[:3]
        leaves, tree = jax.tree_util.tree_flatten(
            (probe_state, ctxs, budgets, hop_limits))
        sig = (tree, tuple(
            (a.shape, a.dtype) + tuple(getattr(a, k, None) for k in (
                "weak_type", "sharding", "committed"))
            for a in leaves))
        q = ctxs.shape[0]
        with self._lock:
            if q == 0 or sig in self._seen:
                return probe_out
            self._seen.add(sig)
        counts = range(LANE_QUANTUM, pad_bucket_size(q, LANE_QUANTUM) + 1,
                       LANE_QUANTUM)

        def compile_one(p: int) -> None:   # outputs dropped at once
            gather_lanes(probe_state, ctxs, budgets, hop_limits,
                         np.arange(p) % q)

        with concurrent.futures.ThreadPoolExecutor(WARM_THREADS) as pool:
            list(pool.map(compile_one, counts))
        with self._lock:
            self.warmed += len(counts)
        return probe_out


def _dispatch_bucket(continue_fn, probe_state, ctxs, budgets, hop_limits,
                     padded: np.ndarray):
    return continue_fn(*gather_lanes(probe_state, ctxs, budgets, hop_limits,
                                     padded))


def _zero_lane_bucket(continue_fn, probe_state, ctxs, budgets, hop_limits):
    """A (members, handles) pair for a zero-lane dispatch of the continue
    program: its outputs are empty but correctly typed/shaped, whatever the
    program's signature — the generic way to produce a zero-query batch's
    result tuple without hardcoding any backend's output arity."""
    none = np.empty((0,), np.int64)
    return none, _dispatch_bucket(continue_fn, probe_state, ctxs, budgets,
                                  hop_limits, none)


def _scatter_bucket(out, q: int, members, handles):
    """Pull one bucket's device results and place them at their original
    batch positions, dropping the padding lanes. Output buffers are
    allocated lazily from the first bucket's shapes/dtypes (shape metadata
    only — no device sync)."""
    if out is None:
        out = tuple(
            np.empty((q,) + tuple(h.shape[1:]), dtype=np.dtype(h.dtype))
            for h in handles)
    m = members.size
    for buf, h in zip(out, handles):
        buf[members] = np.asarray(h)[:m]
    return out
