"""Batched greedy beam search over a proximity graph (paper §3.3 / §4.1).

TPU-native adaptation of DiskANN's pointer-chasing loop: the beam is a dense
fixed-shape (L,) state, the visited set a bitmask, and the hop loop a
``lax.while_loop`` with masked convergence — vmapped over the query batch.

Two distance regimes:
  * exact       — full-precision vectors (in-memory benchmark mode);
  * PQ-routed   — LUT/ADC distances steer the walk, the final beam is
    re-ranked with full-precision vectors; each node *expansion* counts as one
    slow-tier I/O (DiskANN's SSD read), which is the quantity Figures 2a/2c
    are about.

I/O accounting is carried in :class:`SearchStats` and surfaced by every
benchmark.

This module holds the *pure search kernels* only — fixed-beam and adaptive
probe/continue programs plus their jit wrappers. Serve-time control flow
(host-side bucket scheduling, batch pipelining, recalibration) lives in
:mod:`repro.serving`; the ``num_buckets=`` convenience on the adaptive entry
points below delegates to that scheduler.

The per-hop body (frontier select -> adjacency gather -> distance eval ->
beam merge -> visited update) is *pluggable*: :class:`BeamStepKernel` is the
reference implementation (the historical inline body, factored verbatim),
and :class:`PallasBeamStep` swaps the whole batched hop for one fused
``repro.kernels.beam_step`` launch per hop (beam state in VMEM, one kernel
instead of a chain of HLOs). Every walk entry point — fixed-beam, probe and
continue — takes ``step_kernel=`` (``None``/"reference" | "pallas" |
"auto"), threaded from the serving engines as a static jit key.  The
serving backends default to "auto", which consults the
:func:`repro.kernels.ops.resolve_impl` policy: the fused kernel on a TPU
(or under ``REPRO_PALLAS_INTERPRET=1``), the reference hop otherwise.
"pallas" forces the fused kernel (compiled on TPU, interpret elsewhere —
bit-identical to the reference there, see :mod:`repro.kernels.beam_step`);
"reference" (and ``None``, the default of the core entry points) is the
reference hop on every platform.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
INVALID = -1

# eval_dists(query_ctx, ids, valid_mask) -> (len(ids),) squared distances.
DistEval = Callable[[Array, Array, Array], Array]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SearchStats:
    """Per-query work counters (the paper's resource-efficiency metrics)."""

    hops: Array        # nodes expanded == slow-tier reads (DiskANN I/O model)
    dist_evals: Array  # distance computations (compute-side cost, RQ4/T2I)

    def mean(self) -> "SearchStats":
        return SearchStats(hops=self.hops.mean(), dist_evals=self.dist_evals.mean())


@dataclasses.dataclass(frozen=True)
class AdaptiveBeamBudget:
    """Serve-time configuration of Prop. 4.2's per-query budget law.

    The engine runs a short *probe* phase at ``l_min`` width, estimates each
    query's LID from the probe beam's own candidate distances
    (:func:`repro.core.lid.online_lid` — no brute-force k-NN pre-pass), maps
    it to a budget ``L(q) = C * exp(lam * (LID(q) - center))`` clipped to
    [l_min, l_max], and *continues* the same search (warm state, no repeated
    hops) with a per-query frontier budget and hop limit.

    Attributes:
      l_min / l_max: operational beam range; the physical beam is ``l_max``
        wide (fixed shape — one compiled program for every budget).
      lam:         budget-law exponent (0 disables adaptivity at l_mid).
      lid_k:       neighbourhood size for the online LID estimate.
      probe_hops:  hops spent in the probe phase before budgets are set.
      hop_factor:  per-query hop limit = probe_hops + hop_factor * budget.
      center:      LID normalisation center; None -> the batch's own
        center, :func:`repro.core.mapping.budget_center` (self normalising —
        robust to the ADC-vs-exact distance scale difference).
    """

    l_min: int
    l_max: int
    lam: float = 0.15
    lid_k: int = 16
    probe_hops: int = 8
    hop_factor: int = 4
    center: float | None = None

    def __post_init__(self):
        assert 0 < self.l_min <= self.l_max, (self.l_min, self.l_max)
        assert self.probe_hops >= 1 and self.hop_factor >= 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AdaptiveStats:
    """Per-query adaptivity diagnostics returned by the adaptive engine."""

    q_lid: Array    # (Q,) online LID estimate from the probe beam
    budget: Array   # (Q,) int32 beam budget actually granted


def _beam_merge(
    beam_ids, beam_d, beam_exp, new_ids, new_d, beam_width
):
    """Merge R freshly evaluated candidates into the beam; keep best L."""
    cat_ids = jnp.concatenate([beam_ids, new_ids])
    cat_d = jnp.concatenate([beam_d, new_d])
    cat_exp = jnp.concatenate([beam_exp, jnp.zeros(new_ids.shape, dtype=bool)])
    order = jnp.argsort(cat_d)[:beam_width]
    return cat_ids[order], cat_d[order], cat_exp[order]


def _init_state(query_ctx: Array, entry: Array, eval_dists: DistEval,
                n: int, beam_width: int, excl_words: Array | None = None):
    """Fresh search state for one query: entry node in the beam, visited set
    seeded. State tuple: (beam_ids, beam_d, beam_exp, visited, hops, evals).

    ``excl_words`` (optional, (ceil(n/32),) uint32) is a per-query attribute
    filter: set bits mark *excluded* nodes.  Seeding the visited set with it
    makes the filter an in-graph lane mask — excluded neighbours fail the
    seen-check in :func:`_expand_frontier` exactly like INVALID lanes, so
    they never enter the beam and the walk only ever ranks in-filter nodes.
    The hop kernels (reference and fused Pallas alike) consume the state
    unchanged.  The entry node is force-seeded to start the walk; when it is
    itself excluded its beam distance is set to inf so it can only be
    traversed *through*, and :func:`scrub_excluded` drops it from the beam
    at walk exit.  Without a filter the code path is byte-identical to the
    historical one.
    """
    nw = (n + 31) // 32
    entry_d = eval_dists(query_ctx, entry[None], jnp.ones((1,), dtype=bool))[0]
    word = entry >> 5
    bit = jnp.uint32(1) << (entry.astype(jnp.uint32) & 31)
    if excl_words is None:
        visited = jnp.zeros((nw,), dtype=jnp.uint32).at[word].set(bit)
    else:
        entry_d = jnp.where((excl_words[word] & bit) != 0, jnp.inf, entry_d)
        visited = excl_words.at[word].set(excl_words[word] | bit)
    beam_ids = jnp.full((beam_width,), INVALID, dtype=jnp.int32).at[0].set(entry)
    beam_d = jnp.full((beam_width,), jnp.inf, dtype=jnp.float32).at[0].set(entry_d)
    beam_exp = jnp.zeros((beam_width,), dtype=bool)
    return beam_ids, beam_d, beam_exp, visited, jnp.int32(0), jnp.int32(0)


def pack_filter(allowed, n: int) -> Array:
    """Pack a boolean *allowed* mask into per-query exclusion bitset words.

    ``allowed`` is (n,) or (Q, n) bool — True for nodes the query may return
    (a tenant namespace, an attribute predicate, live non-tombstoned rows).
    Returns (Q, ceil(n/32)) uint32 words whose set bits mark *excluded*
    nodes, the form :func:`_init_state` seeds the visited bitset with (bit
    ``j`` of word ``w`` is node ``w * 32 + j``, matching the walk's packing).
    Host-side numpy; a (n,) mask packs once and broadcasts over queries.
    """
    allowed = np.atleast_2d(np.asarray(allowed, dtype=bool))
    q, n_mask = allowed.shape
    assert n_mask == n, (n_mask, n)
    nw = (n + 31) // 32
    padded = np.zeros((q, nw * 32), dtype=bool)
    padded[:, :n] = ~allowed
    bits = padded.reshape(q, nw, 32).astype(np.uint32)
    words = (bits << np.arange(32, dtype=np.uint32)).sum(
        axis=2, dtype=np.uint32)
    return jnp.asarray(words)


def scrub_excluded(beam_ids: Array, beam_d: Array, excl_words: Array):
    """Drop excluded ids from final beams: (Q, L) ids/d2 + (Q, nw) words.

    The walk's visited pre-seed keeps excluded nodes out of the beam, with
    one exception — the force-seeded entry node (inf distance, so it sits
    behind every real candidate).  Scrubbing it to INVALID/inf at walk exit
    means every downstream consumer (top-k slice, slow-tier rerank, partial
    results) sees the standard empty-lane convention and can never surface
    an out-of-filter id.  Beams stay distance-sorted (the scrubbed lane was
    already at inf).
    """
    safe = jnp.maximum(beam_ids, 0)
    bit = jnp.uint32(1) << (safe.astype(jnp.uint32) & 31)
    words = jnp.take_along_axis(excl_words, safe >> 5, axis=1)
    blocked = (beam_ids != INVALID) & ((words & bit) != 0)
    return (jnp.where(blocked, INVALID, beam_ids),
            jnp.where(blocked, jnp.inf, beam_d))


_scrub_excluded_jit = jax.jit(scrub_excluded)


def _scrub_state(probe_state, excl_words: Array):
    """Apply :func:`scrub_excluded` to a full search-state tuple."""
    ids, d = scrub_excluded(probe_state[0], probe_state[1], excl_words)
    return (ids, d) + tuple(probe_state[2:])


_scrub_state_jit = jax.jit(_scrub_state)


def _select_frontier(state, in_budget: Array):
    """First half of the hop: pick the closest unexpanded in-budget beam
    entry, mark it expanded, and return its node id.

    This is the point where the walk's next adjacency read becomes known —
    the out-of-core driver runs this half on device, yields ``u`` to the
    host for the block fetch, then resumes with :func:`_expand_frontier`.
    """
    beam_ids, beam_d, beam_exp, visited, hops, evals = state
    cand_d = jnp.where(
        beam_exp | (beam_ids == INVALID) | (~in_budget), jnp.inf, beam_d)
    j = jnp.argmin(cand_d)
    u = beam_ids[j]
    beam_exp = beam_exp.at[j].set(True)
    return (beam_ids, beam_d, beam_exp, visited, hops, evals), u


def _expand_frontier(state, u: Array, nbrs: Array, query_ctx: Array,
                     eval_dists: DistEval, beam_width: int):
    """Second half of the hop: evaluate ``u``'s adjacency row and merge.

    ``nbrs`` is ``adj[u]`` however it was obtained — an in-graph gather
    (:meth:`BeamStepKernel.step`) or a host-side block-store read (the
    out-of-core walk). Identical ops on identical values either way, which
    is what keeps the two walks bit-identical.
    """
    beam_ids, beam_d, beam_exp, visited, hops, evals = state
    valid = (nbrs != INVALID) & (u != INVALID)
    safe = jnp.maximum(nbrs, 0)
    word_idx = safe >> 5
    bit = jnp.uint32(1) << (safe.astype(jnp.uint32) & 31)
    seen = (visited[word_idx] & bit) != 0
    valid = valid & (~seen)
    d = eval_dists(query_ctx, safe, valid)
    d = jnp.where(valid, d, jnp.inf)
    # Distinct ids set distinct bits, so scatter-add implements the OR.
    visited = visited.at[word_idx].add(jnp.where(valid, bit, 0))

    nbr_ids = jnp.where(valid, nbrs, INVALID)
    beam_ids, beam_d, beam_exp = _beam_merge(
        beam_ids, beam_d, beam_exp, nbr_ids, d, beam_width
    )
    return beam_ids, beam_d, beam_exp, visited, hops + 1, evals + valid.sum()


class BeamStepKernel:
    """The pluggable per-hop kernel of the beam walk (reference impl).

    ``step`` advances ONE query's state by one hop — the body factored
    verbatim out of the historical inline ``_run_search`` loop (now split
    into :func:`_select_frontier` + :func:`_expand_frontier` so the
    out-of-core walk can interpose a host-side block read between the two
    halves), so fixed-beam, probe and continue all execute the same code.
    ``run_batch`` drives a batch of lanes to convergence (here: a vmap of
    per-lane while loops, the historical execution shape).  Subclasses
    override ``run_batch`` to change *how* hops execute without touching
    *what* a hop computes; :class:`PallasBeamStep` swaps in the fused
    single-launch hop.
    """

    name = "reference"

    def step(self, state, query_ctx: Array, adj: Array,
             eval_dists: DistEval, beam_width: int, in_budget: Array):
        """One hop of one query's walk (the reference hop body, verbatim)."""
        state, u = _select_frontier(state, in_budget)
        nbrs = adj[jnp.maximum(u, 0)]  # (R,)
        return _expand_frontier(state, u, nbrs, query_ctx, eval_dists,
                                beam_width)

    def run_batch(self, states, ctxs: Array, adj: Array,
                  eval_dists: DistEval, beam_width: int, hop_limits: Array,
                  budgets: Array | None = None):
        """Run a batch of lanes to convergence; leaves of ``states`` are
        (Q, ...) with per-lane ``hop_limits`` and optional ``budgets``."""
        if budgets is None:
            def one(state, c, h):
                return _run_search(state, c, adj, eval_dists, beam_width,
                                   hop_limit=h, step_kernel=self)

            return jax.vmap(one)(states, ctxs, hop_limits)

        def one(state, c, h, b):
            return _run_search(state, c, adj, eval_dists, beam_width,
                               hop_limit=h, budget=b, step_kernel=self)

        return jax.vmap(one)(states, ctxs, hop_limits, budgets)


class PallasBeamStep(BeamStepKernel):
    """Fused-hop execution: one fused ``repro.kernels.beam_step`` launch per
    hop of the whole batch (``repro.kernels.ops.beam_walk``).

    The per-lane ``step`` body is inherited unchanged (it *is* the hop's
    semantics); ``run_batch`` replaces the vmap-of-while shape with one
    batch-level while whose body is the fused kernel.  Both shapes freeze
    converged lanes identically (XLA lowers a vmapped while to exactly this
    any-cond + select-masking form), so results are bit-identical — the
    engine-parity kernel axis asserts it per backend.

    The fused kernel sees through the two standard evaluators via their
    ``kind``/``table`` tags (:func:`_exact_eval`, :func:`_pq_eval`, and the
    distributed shard evaluator); an untagged custom evaluator is an error,
    never a silent switch to the reference hop.
    """

    name = "pallas"

    def run_batch(self, states, ctxs: Array, adj: Array,
                  eval_dists: DistEval, beam_width: int, hop_limits: Array,
                  budgets: Array | None = None):
        kind = getattr(eval_dists, "kind", None)
        table = getattr(eval_dists, "table", None)
        if kind not in ("exact", "pq") or table is None:
            raise ValueError(
                "the fused beam step needs a kind/table-tagged evaluator "
                "(_exact_eval, _pq_eval or the shard evaluator); use "
                "step_kernel='reference' for a custom one")
        from repro.kernels import ops

        q = hop_limits.shape[0]
        b = (jnp.full((q,), beam_width, jnp.int32) if budgets is None
             else jnp.broadcast_to(budgets, (q,)).astype(jnp.int32))
        hl = jnp.broadcast_to(hop_limits, (q,)).astype(jnp.int32)
        return ops.beam_walk(states, ctxs, adj, table, b, hl, kind=kind)


REFERENCE_STEP = BeamStepKernel()
PALLAS_STEP = PallasBeamStep()


def resolve_step_kernel(
    spec: "str | BeamStepKernel | None" = None,
) -> BeamStepKernel:
    """Resolve a ``step_kernel=`` knob to a kernel object.

    ``None``/"reference" -> the reference hop; "pallas" -> the fused kernel
    (compiled on TPU; interpret-mode elsewhere, bit-identical to the
    reference there);
    "auto" -> whatever :func:`repro.kernels.ops.resolve_impl` picks for this
    process (the fused kernel on TPU or under ``REPRO_PALLAS_INTERPRET=1``,
    the reference otherwise).  Kernel instances pass through, so tests can
    inject custom execution shapes.
    """
    if spec is None or spec == "reference":
        return REFERENCE_STEP
    if isinstance(spec, BeamStepKernel):
        return spec
    if spec == "pallas":
        return PALLAS_STEP
    if spec == "auto":
        from repro.kernels import ops

        return PALLAS_STEP if ops.resolve_impl() != "ref" else REFERENCE_STEP
    raise ValueError(
        f"unknown step_kernel {spec!r}; expected 'reference' | 'pallas' | "
        "'auto' (or a BeamStepKernel instance)")


def _run_search(
    state,
    query_ctx: Array,
    adj: Array,
    eval_dists: DistEval,
    beam_width: int,
    hop_limit: Array,
    budget: Array | None = None,
    step_kernel: BeamStepKernel | None = None,
):
    """Advance one query's beam search until its frontier closes.

    The physical beam is fixed-shape ``(beam_width,)``; ``budget`` (a traced
    per-query scalar) restricts the *active frontier* to the best ``budget``
    slots — the per-query knob of the adaptive engine. Because the beam is
    kept sorted by the merge, budget-b convergence is exactly beam-width-b
    search (with a slightly richer candidate pool retained for the final
    top-k). ``hop_limit`` is likewise a traced scalar, so vmapped batches
    retire work lane-by-lane as queries converge: a converged lane's cond is
    False, its state freezes, and its hop counter (== slow-tier I/O) stops —
    easy queries stop paying for hard ones.

    The hop body itself lives on ``step_kernel`` (default: the reference
    :class:`BeamStepKernel`) — this function owns only the convergence loop.
    """
    kernel = step_kernel if step_kernel is not None else REFERENCE_STEP
    slot = jnp.arange(beam_width)
    in_budget = (slot < budget) if budget is not None else jnp.ones(
        (beam_width,), dtype=bool)

    def cond(state):
        beam_ids, _, beam_exp, _, hops, _ = state
        frontier_open = jnp.any((~beam_exp) & (beam_ids != INVALID) & in_budget)
        return (hops < hop_limit) & frontier_open

    def body(state):
        return kernel.step(state, query_ctx, adj, eval_dists, beam_width,
                           in_budget)

    return jax.lax.while_loop(cond, body, state)


def _search_one(
    query_ctx: Array,
    adj: Array,
    entry: Array,
    eval_dists: DistEval,
    n: int,
    beam_width: int,
    max_hops: int,
) -> tuple[Array, Array, SearchStats]:
    """Beam search for a single query context; vmap over the batch.

    The visited set is a *bit-packed* uint32 array (n/32 words): 8x less
    working-set memory and HBM traffic than a bool mask — at billion-scale
    shards (3.9M points/device, 128-query chunks) this is the difference
    between a 500 MB and a 62 MB visited buffer (§Perf, mcgi serve cells).
    Requires duplicate-free adjacency rows (the pruner dedups; random init
    graphs are dedup'd at construction).
    """
    state = _init_state(query_ctx, entry, eval_dists, n, beam_width)
    beam_ids, beam_d, _, _, hops, evals = _run_search(
        state, query_ctx, adj, eval_dists, beam_width,
        hop_limit=jnp.int32(max_hops),
    )
    return beam_ids, beam_d, SearchStats(hops=hops, dist_evals=evals)


def _init_states(ctxs, entry, eval_dists: DistEval, n: int,
                 beam_width: int, excl: Array | None = None):
    """:func:`_init_state` for every lane of a batch; ``entry`` is one node
    for every lane, or one per lane ((Q,) — the per-query entry points of
    the distributed probe)."""
    e_axis = 0 if jnp.ndim(entry) else None
    if excl is None:
        return jax.vmap(
            lambda c, e: _init_state(c, e, eval_dists, n, beam_width),
            in_axes=(0, e_axis))(ctxs, entry)
    return jax.vmap(
        lambda c, e, x: _init_state(c, e, eval_dists, n, beam_width,
                                    excl_words=x),
        in_axes=(0, e_axis, 0))(ctxs, entry, excl)


def fixed_search_batch(
    ctxs: Array,
    adj: Array,
    entry: Array,
    eval_dists: DistEval,
    n: int,
    beam_width: int,
    max_hops: int,
    step_kernel: "str | BeamStepKernel | None" = None,
    excl: Array | None = None,
) -> tuple[Array, Array, SearchStats]:
    """Batched fixed-beam walk through the pluggable step kernel.

    The batch-level counterpart of ``vmap(_search_one)`` (same math, same
    results): init every lane, then hand the batch to the step kernel's
    ``run_batch`` — which is exactly the historical vmapped loop for the
    reference kernel, or one fused launch per hop for the Pallas one.

    ``excl`` ((Q, ceil(n/32)) uint32 from :func:`pack_filter`) runs the walk
    filtered in-graph: excluded nodes never enter the beam (visited
    pre-seed) and the exit beam is scrubbed of the forced entry seed.
    """
    kernel = resolve_step_kernel(step_kernel)
    states = _init_states(ctxs, entry, eval_dists, n, beam_width, excl)
    hop_limits = jnp.full((ctxs.shape[0],), jnp.int32(max_hops))
    beam_ids, beam_d, _, _, hops, evals = kernel.run_batch(
        states, ctxs, adj, eval_dists, beam_width, hop_limits)
    if excl is not None:
        beam_ids, beam_d = scrub_excluded(beam_ids, beam_d, excl)
    return beam_ids, beam_d, SearchStats(hops=hops, dist_evals=evals)


# --------------------------------------------------------------------------
# Out-of-core walk programs.
#
# The reference walk is a vmapped ``lax.while_loop`` whose body gathers
# ``adj[u]`` in-graph — which requires the whole adjacency in device memory.
# The out-of-core walk runs the *same* per-lane ops as a host-driven loop of
# two device programs, yielding each hop's frontier ids to the host so the
# adjacency rows can come from the block store instead:
#
#     select:  (state)            -> (state', u, active)     [device]
#     fetch:   rows = adj[u]      via BlockSlowTier          [host  ]
#     hop:     (state', u, rows)  -> expand, then next select [device]
#
# Bit-identity with the in-graph walk rests on two properties the codebase
# already pins elsewhere: (a) XLA lowers a vmapped while_loop to an any-cond
# loop whose body select-masks converged lanes — ``_lane_active`` +
# ``_freeze_inactive`` below replicate exactly that form, so each lane's
# state sequence is identical; (b) per-lane ops are batch-shape-invariant
# (the bucketed scheduler already slices lanes into differently-shaped
# programs and asserts bitwise equality against the full-batch program).


def _lane_active(state, in_budget: Array, hop_limit: Array) -> Array:
    """One lane's while-loop condition (verbatim from ``_run_search``)."""
    beam_ids, _, beam_exp, _, hops, _ = state
    frontier_open = jnp.any((~beam_exp) & (beam_ids != INVALID) & in_budget)
    return (hops < hop_limit) & frontier_open


def _freeze_inactive(active: Array, new, old):
    """Per-lane select-masking: inactive lanes keep their old state leaves —
    the exact form XLA lowers a vmapped ``while_loop`` body to."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(active, n, o), new, old)


def ooc_select_batch(states, budgets: Array, hop_limits: Array,
                     beam_width: int):
    """First frontier selection of an out-of-core walk segment.

    Returns ``(states, u, active)``: per-lane frontier node ids (INVALID for
    lanes whose loop condition is already False — no I/O is issued for them)
    and the lanes' activity mask. The beam_exp mark of the selection is
    applied only to active lanes.
    """
    def one(state, b, h):
        in_budget = jnp.arange(beam_width) < b
        active = _lane_active(state, in_budget, h)
        sel, u = _select_frontier(state, in_budget)
        return (_freeze_inactive(active, sel, state),
                jnp.where(active, u, jnp.int32(INVALID)), active)

    return jax.vmap(one)(states, budgets, hop_limits)


def ooc_hop_batch(states, u: Array, active: Array, rows: Array, ctxs: Array,
                  eval_dists: DistEval, budgets: Array, hop_limits: Array,
                  beam_width: int):
    """One out-of-core hop: expand the previously selected frontier with its
    host-fetched adjacency rows, then select the next frontier.

    ``rows[i]`` must equal ``adj[u[i]]`` for active lanes (INVALID lanes in
    ``rows`` are ignored — ``_expand_frontier`` masks on ``u``). Returns
    ``(states, u_next, active_next)`` with the same conventions as
    :func:`ooc_select_batch`.
    """
    def one(state, u1, a1, nbrs, c, b, h):
        in_budget = jnp.arange(beam_width) < b
        expanded = _expand_frontier(state, u1, nbrs, c, eval_dists,
                                    beam_width)
        state = _freeze_inactive(a1, expanded, state)
        a2 = _lane_active(state, in_budget, h)
        sel, u2 = _select_frontier(state, in_budget)
        return (_freeze_inactive(a2, sel, state),
                jnp.where(a2, u2, jnp.int32(INVALID)), a2)

    return jax.vmap(one)(states, u, active, rows, ctxs, budgets, hop_limits)


@functools.partial(jax.jit, static_argnames=("n", "beam_width"))
def ooc_init_pq(codes: Array, ctxs: Array, entry: Array, n: int,
                beam_width: int, excl: Array | None = None):
    """Fresh per-lane states for a PQ-steered out-of-core walk (entry node's
    ADC distance comes from the device-resident codes).  ``excl`` seeds the
    per-lane visited bitsets with the filter, exactly as in
    :func:`fixed_search_batch`."""
    if excl is None:
        return jax.vmap(
            lambda c: _init_state(c, entry, _pq_eval(codes), n,
                                  beam_width))(ctxs)
    return jax.vmap(
        lambda c, e: _init_state(c, entry, _pq_eval(codes), n, beam_width,
                                 excl_words=e))(ctxs, excl)


@functools.partial(jax.jit, static_argnames=("beam_width",))
def ooc_select_pq(states, budgets, hop_limits, beam_width: int):
    return ooc_select_batch(states, budgets, hop_limits, beam_width)


@functools.partial(jax.jit, static_argnames=("beam_width",))
def ooc_hop_pq(codes, states, u, active, rows, ctxs, budgets, hop_limits,
               beam_width: int):
    return ooc_hop_batch(states, u, active, rows, ctxs, _pq_eval(codes),
                         budgets, hop_limits, beam_width)


def budget_bucket_ceilings(
    l_min: int, l_max: int, max_buckets: int = 4
) -> tuple[int, ...]:
    """Power-of-two-style budget ceilings covering [l_min, l_max], ascending.

    Halving down from ``l_max`` gives at most ``max_buckets`` ceilings whose
    last element is always ``l_max`` (so every granted budget has a bucket).
    E.g. (16, 96, 4) -> (16, 24, 48, 96). The small, geometric family keeps
    host-side bucket scheduling to a handful of padded batch shapes.
    """
    assert max_buckets >= 1 and 0 < l_min <= l_max
    cs = [int(l_max)]
    while len(cs) < max_buckets and cs[-1] > int(l_min):
        cs.append(max(int(l_min), cs[-1] // 2))
    return tuple(sorted(set(cs)))


def quantize_budgets(
    budgets: Array, ceilings: tuple[int, ...]
) -> tuple[Array, Array]:
    """Round each granted budget *up* to its bucket ceiling (jit-safe).

    Returns (bucket_index, quantized_budget); ``ceilings`` must be ascending
    with ``ceilings[-1] >= budgets.max()``. Used in-graph by the distributed
    path, where the bucket ceiling doubles as the hedged per-query hop
    deadline, and on the host by the bucket scheduler.
    """
    ceil_arr = jnp.asarray(ceilings, dtype=jnp.int32)
    idx = jnp.searchsorted(ceil_arr, budgets.astype(jnp.int32), side="left")
    idx = jnp.minimum(idx, len(ceilings) - 1)
    return idx, ceil_arr[idx]


def _bucket_hop_limits(
    budget_cfg: AdaptiveBeamBudget, budgets: Array, max_hops: int | None
) -> Array:
    """Per-query hop limit = probe + hop_factor * budget, SLO-capped."""
    hop_limits = (jnp.int32(budget_cfg.probe_hops)
                  + jnp.int32(budget_cfg.hop_factor) * budgets)
    if max_hops is not None:
        hop_limits = jnp.minimum(hop_limits, jnp.int32(max_hops))
    return hop_limits


def grant_budgets(
    probe_state,
    budget_cfg: AdaptiveBeamBudget,
    max_hops: int | None = None,
    *,
    lam: Array | None = None,
    l_min: Array | None = None,
):
    """Phase 2 of the adaptive engine: LID estimate + budget grant from a
    finished probe state.

    Factored out of :func:`adaptive_probe_batch` so the out-of-core walk's
    host-driven probe grants budgets through the *same* ops (bit-identical
    LID/budget/hop-limit values for the same probe state). Returns
    ``(budgets, hop_limits, q_lid)``.
    """
    from repro.core import lid as lid_mod
    from repro.core import mapping as mapping_mod

    lam_ = budget_cfg.lam if lam is None else lam
    l_min_ = budget_cfg.l_min if l_min is None else l_min
    p_ids, p_d = probe_state[0], probe_state[1]
    d_pool = jnp.where(p_ids == INVALID, jnp.inf, p_d)
    q_lid = lid_mod.online_lid(d_pool, k=min(budget_cfg.lid_k,
                                             budget_cfg.l_max))
    center = (None if budget_cfg.center is None
              else jnp.float32(budget_cfg.center))
    budgets = mapping_mod.adaptive_beam_budget(
        q_lid, lam_, l_min_, budget_cfg.l_max, mu=center)
    hop_limits = _bucket_hop_limits(budget_cfg, budgets, max_hops)
    return budgets, hop_limits, q_lid


_grant_budgets_jit = jax.jit(
    grant_budgets, static_argnames=("budget_cfg", "max_hops"))


def adaptive_probe_batch(
    ctxs: Array,
    adj: Array,
    entry: Array,
    eval_dists: DistEval,
    n: int,
    budget_cfg: AdaptiveBeamBudget,
    max_hops: int | None = None,
    *,
    lam: Array | None = None,
    l_min: Array | None = None,
    step_kernel: "str | BeamStepKernel | None" = None,
    excl: Array | None = None,
):
    """Phases 1-2 of the adaptive engine: probe walk + budget grant.

    Every query walks ``probe_hops`` hops at ``l_min`` frontier budget into a
    fixed-shape ``l_max``-wide beam; its LID is estimated from the probe
    beam's own candidate distances (``lid.online_lid`` — no brute-force k-NN
    pre-pass) and mapped to ``L(q)`` by ``mapping.adaptive_beam_budget``.

    ``lam``/``l_min`` override the config's values with *traced scalars* —
    the per-shard budget-law path of the distributed engine, where each
    shard's calibrated (lam, l_min) arrives as a runtime array and must not
    recompile the program. Shape knobs (``l_max``, ``probe_hops``,
    ``lid_k``) always come from ``budget_cfg``.

    Returns (probe_state, budgets, hop_limits, q_lid); ``probe_state`` is the
    warm per-query search state the continue phase resumes from.

    ``excl`` ((Q, ceil(n/32)) uint32 from :func:`pack_filter`) makes the
    probe walk filtered in-graph; the returned probe state is already
    scrubbed of the forced entry seed, so the continue phase (which only
    ever admits nodes past the pre-seeded visited set) and every partial
    rerank of the probe beam need no filter awareness of their own.
    """
    l_max = budget_cfg.l_max
    l_min_ = budget_cfg.l_min if l_min is None else l_min

    kernel = resolve_step_kernel(step_kernel)
    states = _init_states(ctxs, entry, eval_dists, n, l_max, excl)
    nq = ctxs.shape[0]
    probe_state = kernel.run_batch(
        states, ctxs, adj, eval_dists, l_max,
        hop_limits=jnp.full((nq,), jnp.int32(budget_cfg.probe_hops)),
        budgets=jnp.broadcast_to(jnp.int32(l_min_), (nq,)))
    if excl is not None:
        probe_state = _scrub_state(probe_state, excl)
    budgets, hop_limits, q_lid = grant_budgets(
        probe_state, budget_cfg, max_hops, lam=lam, l_min=l_min)
    return probe_state, budgets, hop_limits, q_lid


def adaptive_continue_batch(
    probe_state,
    ctxs: Array,
    adj: Array,
    eval_dists: DistEval,
    budget_cfg: AdaptiveBeamBudget,
    budgets: Array,
    hop_limits: Array,
    step_kernel: "str | BeamStepKernel | None" = None,
):
    """Phase 3: resume the probe states (warm beam + visited set, no repeated
    hops) with per-query frontier budgets and hop limits.

    Returns (beam_ids, beam_d, hops, evals); the counters include the probe
    phase (the continue loop resumes them).
    """
    kernel = resolve_step_kernel(step_kernel)
    beam_ids, beam_d, _, _, hops, evals = kernel.run_batch(
        probe_state, ctxs, adj, eval_dists, budget_cfg.l_max,
        hop_limits=hop_limits, budgets=budgets)
    return beam_ids, beam_d, hops, evals


def adaptive_search_batch(
    ctxs: Array,
    adj: Array,
    entry: Array,
    eval_dists: DistEval,
    n: int,
    budget_cfg: AdaptiveBeamBudget,
    max_hops: int | None = None,
    bucket_ceilings: tuple[int, ...] | None = None,
    *,
    lam: Array | None = None,
    l_min: Array | None = None,
    step_kernel: "str | BeamStepKernel | None" = None,
    excl: Array | None = None,
) -> tuple[Array, Array, SearchStats, AdaptiveStats]:
    """The per-query adaptive-beam engine (Prop. 4.2 deployed in-graph).

    Three phases, one compiled program, no host round-trip:
      1. *probe*   — every query walks ``probe_hops`` hops at ``l_min``
         frontier budget, filling the (fixed-shape, ``l_max``-wide) beam;
      2. *budget*  — each query's LID is estimated from the probe beam's own
         candidate distances and mapped to ``L(q)``;
      3. *continue* — the same search states resume (warm state, no repeated
         hops) with per-query frontier budgets and hop limits.

    Returns (beam_ids, beam_d, stats, adaptive_stats); hops in ``stats``
    count probe + continuation. ``max_hops``, when given, caps every
    per-query hop limit — an operator's latency SLO outranks the budget law.

    ``bucket_ceilings`` (an ascending static tuple from
    :func:`budget_bucket_ceilings`) quantizes each granted budget *up* to its
    bucket ceiling in-graph and derives the hop limit from the ceiling — the
    hedged per-shard hop deadline of the distributed path: a straggler
    query's walk is cut off at its bucket's deadline instead of the shard
    dropping its whole contribution. For host-side bucket *scheduling* (which
    keeps results bit-identical to this unbucketed path) see
    :func:`beam_search_exact_adaptive` / :func:`beam_search_pq_adaptive` with
    ``num_buckets``.

    ``lam``/``l_min``, when given, are traced per-shard budget-law overrides
    forwarded to :func:`adaptive_probe_batch` (the distributed path's
    per-shard calibration).
    """
    probe_state, budgets, hop_limits, q_lid = adaptive_probe_batch(
        ctxs, adj, entry, eval_dists, n, budget_cfg, max_hops,
        lam=lam, l_min=l_min, step_kernel=step_kernel, excl=excl)
    if bucket_ceilings is not None:
        _, budgets = quantize_budgets(budgets, bucket_ceilings)
        hop_limits = _bucket_hop_limits(budget_cfg, budgets, max_hops)
    beam_ids, beam_d, hops, evals = adaptive_continue_batch(
        probe_state, ctxs, adj, eval_dists, budget_cfg, budgets, hop_limits,
        step_kernel=step_kernel)
    return (beam_ids, beam_d, SearchStats(hops=hops, dist_evals=evals),
            AdaptiveStats(q_lid=q_lid, budget=budgets))


def _exact_eval(x: Array) -> DistEval:
    """Full-precision squared-L2 distance evaluator (in-memory mode)."""
    def eval_dists(q, ids, valid):
        vecs = x[ids]
        diff = vecs - q[None, :]
        return jnp.sum(diff * diff, axis=-1)

    # Tags let the fused Pallas step route this evaluator's table itself
    # (the kernel gathers rows by DMA instead of calling the closure).
    eval_dists.kind = "exact"
    eval_dists.table = x
    return eval_dists


def _pq_eval(codes: Array) -> DistEval:
    """ADC distance evaluator over PQ codes; the query ctx is its LUT."""
    def eval_dists(lut, ids, valid):
        # lut: (M, K); codes[ids]: (R, M) -> sum_m lut[m, code[r, m]]
        c = codes[ids].astype(jnp.int32)
        m = lut.shape[0]
        gathered = jax.vmap(lambda row: lut[jnp.arange(m), row])(c)
        return gathered.sum(axis=-1)

    eval_dists.kind = "pq"
    eval_dists.table = codes
    return eval_dists


@functools.partial(
    jax.jit, static_argnames=("beam_width", "max_hops", "k", "step_kernel")
)
def beam_search_exact(
    x: Array,
    adj: Array,
    queries: Array,
    entry: Array,
    beam_width: int,
    max_hops: int = 2048,
    k: int = 10,
    step_kernel: str | None = None,
    excl: Array | None = None,
) -> tuple[Array, Array, SearchStats]:
    """Exact-distance beam search, batched over (Q, D) queries.

    Returns (ids, d2, stats): (Q, k) ascending results + per-query counters.
    ``excl`` (from :func:`pack_filter`) runs the walk attribute-filtered
    in-graph; out-of-filter results come back INVALID/inf, never ids.
    """
    n = x.shape[0]
    eval_dists = _exact_eval(x)
    beam_ids, beam_d, stats = fixed_search_batch(
        queries, adj, entry, eval_dists, n, beam_width, max_hops,
        step_kernel=step_kernel, excl=excl)
    return beam_ids[:, :k], beam_d[:, :k], stats


@functools.partial(
    jax.jit,
    static_argnames=("beam_width", "max_hops", "k", "rerank", "step_kernel"),
)
def beam_search_pq(
    codes: Array,
    luts: Array,
    x_slow: Array,
    adj: Array,
    queries: Array,
    entry: Array,
    beam_width: int,
    max_hops: int = 2048,
    k: int = 10,
    rerank: bool = True,
    step_kernel: str | None = None,
    excl: Array | None = None,
) -> tuple[Array, Array, SearchStats]:
    """PQ-routed beam search + optional full-precision re-rank.

    Args:
      codes:  (N, M) uint8 PQ codes — the fast-tier (HBM) representation.
      luts:   (Q, M, K) per-query ADC lookup tables
        (``repro.pq.adc.build_lut``).
      x_slow: (N, D) full-precision vectors — the slow tier; touched only for
        the final beam re-rank (one batched read of ``beam_width`` nodes,
        mirroring DiskANN's read-along-the-path + rerank).
      adj:    (N, R) graph.
      excl:   optional (Q, ceil(n/32)) filter words from :func:`pack_filter`;
        the walk runs filtered in-graph and the rerank sees a pre-scrubbed
        beam (INVALID lanes rank at inf), so it needs no filter awareness.
    """
    n = codes.shape[0]
    eval_dists = _pq_eval(codes)
    beam_ids, beam_d, stats = fixed_search_batch(
        luts, adj, entry, eval_dists, n, beam_width, max_hops,
        step_kernel=step_kernel, excl=excl)

    if rerank:
        ids, d2 = _rerank_slow_tier(beam_ids, x_slow, queries, k)
        return ids, d2, stats
    return beam_ids[:, :k], beam_d[:, :k], stats


def _rerank_slow_tier(beam_ids, x_slow, queries, k):
    """Full-precision re-rank of the final beam (one batched slow-tier read)."""
    safe = jnp.maximum(beam_ids, 0)
    vecs = x_slow[safe]  # (Q, L, D) — the batched slow-tier read
    return _rerank_from_vecs(beam_ids, vecs, queries, k)


def _rerank_from_vecs(beam_ids, vecs, queries, k):
    """Re-rank from pre-gathered beam vectors (Q, L, D).

    The arithmetic tail of :func:`_rerank_slow_tier`, shared with the
    disk-backed slow tier (:class:`repro.index.disk.BlockSlowTier`), whose
    gather happens on the host out of block reads instead of an in-graph
    index — the two paths run identical ops on identical values, so results
    are bit-identical.
    """
    diff = vecs - queries[:, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    d2 = jnp.where(beam_ids == INVALID, jnp.inf, d2)
    order = jnp.argsort(d2, axis=-1)[:, :k]
    return (
        jnp.take_along_axis(beam_ids, order, axis=1),
        jnp.take_along_axis(d2, order, axis=1),
    )


@functools.partial(jax.jit, static_argnames=("budget_cfg", "k", "step_kernel"))
def _beam_search_exact_adaptive_jit(
    x, adj, queries, entry, budget_cfg: AdaptiveBeamBudget, k: int = 10,
    step_kernel: str | None = None, excl: Array | None = None,
):
    """Single-program adaptive path: probe + continue in one compiled call."""
    beam_ids, beam_d, stats, astats = adaptive_search_batch(
        queries, adj, entry, _exact_eval(x), x.shape[0], budget_cfg,
        step_kernel=step_kernel, excl=excl)
    return beam_ids[:, :k], beam_d[:, :k], stats, astats


@functools.partial(jax.jit, static_argnames=("budget_cfg", "step_kernel"))
def _probe_exact_jit(x, adj, queries, entry, budget_cfg: AdaptiveBeamBudget,
                     step_kernel: str | None = None,
                     excl: Array | None = None):
    return adaptive_probe_batch(
        queries, adj, entry, _exact_eval(x), x.shape[0], budget_cfg,
        step_kernel=step_kernel, excl=excl)


@functools.partial(jax.jit, static_argnames=("budget_cfg", "step_kernel"))
def _continue_exact_jit(x, adj, probe_state, ctxs, budgets, hop_limits,
                        budget_cfg: AdaptiveBeamBudget,
                        step_kernel: str | None = None):
    return adaptive_continue_batch(
        probe_state, ctxs, adj, _exact_eval(x), budget_cfg, budgets,
        hop_limits, step_kernel=step_kernel)


@functools.partial(jax.jit, static_argnames=("budget_cfg", "step_kernel"))
def _probe_pq_jit(codes, adj, luts, entry, budget_cfg: AdaptiveBeamBudget,
                  step_kernel: str | None = None,
                  excl: Array | None = None):
    return adaptive_probe_batch(
        luts, adj, entry, _pq_eval(codes), codes.shape[0], budget_cfg,
        step_kernel=step_kernel, excl=excl)


@functools.partial(jax.jit, static_argnames=("budget_cfg", "step_kernel"))
def _continue_pq_jit(codes, adj, probe_state, luts, budgets, hop_limits,
                     budget_cfg: AdaptiveBeamBudget,
                     step_kernel: str | None = None):
    return adaptive_continue_batch(
        probe_state, luts, adj, _pq_eval(codes), budget_cfg, budgets,
        hop_limits, step_kernel=step_kernel)


def _bucketed_continue(
    continue_fn,
    probe_state,
    ctxs: Array,
    budgets: Array,
    hop_limits: Array,
    ceilings: tuple[int, ...],
):
    """Host-side budget-bucketed continue phase, via the serving scheduler.

    The scheduling itself lives in :mod:`repro.serving.pipeline` (this module
    keeps only the device-side search kernels); the eager per-bucket gather
    discipline here is the historical behaviour of the ``num_buckets=`` entry
    points.  The staged engine (:class:`repro.serving.engine.SearchEngine`)
    drives the same scheduler with deferred gathers and double buffering.
    Returns (beam_ids, beam_d, hops, evals) in the original query order.
    """
    from repro.serving import pipeline as pipe

    out_ids, out_d, out_hops, out_evals = pipe.bucketed_continue(
        continue_fn, probe_state, ctxs, budgets, hop_limits, ceilings)
    return (jnp.asarray(out_ids), jnp.asarray(out_d),
            jnp.asarray(out_hops), jnp.asarray(out_evals))


def beam_search_exact_adaptive(
    x: Array,
    adj: Array,
    queries: Array,
    entry: Array,
    budget_cfg: AdaptiveBeamBudget,
    k: int = 10,
    num_buckets: int | None = None,
    step_kernel: str | None = None,
    excl: Array | None = None,
) -> tuple[Array, Array, SearchStats, AdaptiveStats]:
    """Exact-distance adaptive-beam search (probe -> budget -> continue).

    Per-query counterpart of :func:`beam_search_exact`: the frontier budget is
    ``L(q)`` from the probe-phase LID estimate instead of a fixed
    ``beam_width``. Returns (ids, d2, stats, adaptive_stats).

    ``num_buckets`` >= 2 switches the continue phase to budget-bucketed
    execution (:func:`_bucketed_continue`): queries are grouped by granted
    budget and each bucket runs to its own ceiling, so converged lanes free
    real compute. Results are identical to the single-program path.

    ``excl`` filters the walk in-graph (see :func:`pack_filter`); only the
    probe needs it — the continue phase resumes a scrubbed probe state whose
    visited bitset already carries the filter.
    """
    if num_buckets is None or num_buckets <= 1:
        return _beam_search_exact_adaptive_jit(
            x, adj, queries, entry, budget_cfg, k=k, step_kernel=step_kernel,
            excl=excl)
    probe_state, budgets, hop_limits, q_lid = _probe_exact_jit(
        x, adj, queries, entry, budget_cfg, step_kernel=step_kernel,
        excl=excl)
    ceilings = budget_bucket_ceilings(
        budget_cfg.l_min, budget_cfg.l_max, num_buckets)
    cont = functools.partial(_continue_exact_jit, x, adj,
                             budget_cfg=budget_cfg, step_kernel=step_kernel)
    beam_ids, beam_d, hops, evals = _bucketed_continue(
        cont, probe_state, queries, budgets, hop_limits, ceilings)
    return (beam_ids[:, :k], beam_d[:, :k],
            SearchStats(hops=hops, dist_evals=evals),
            AdaptiveStats(q_lid=q_lid, budget=budgets))


@functools.partial(
    jax.jit, static_argnames=("budget_cfg", "k", "rerank", "step_kernel"))
def _beam_search_pq_adaptive_jit(
    codes, luts, x_slow, adj, queries, entry,
    budget_cfg: AdaptiveBeamBudget, k: int = 10, rerank: bool = True,
    step_kernel: str | None = None, excl: Array | None = None,
):
    beam_ids, beam_d, stats, astats = adaptive_search_batch(
        luts, adj, entry, _pq_eval(codes), codes.shape[0], budget_cfg,
        step_kernel=step_kernel, excl=excl)
    if rerank:
        ids, d2 = _rerank_slow_tier(beam_ids, x_slow, queries, k)
        return ids, d2, stats, astats
    return beam_ids[:, :k], beam_d[:, :k], stats, astats


_rerank_slow_tier_jit = jax.jit(_rerank_slow_tier, static_argnames=("k",))
_rerank_from_vecs_jit = jax.jit(_rerank_from_vecs, static_argnames=("k",))


def beam_search_pq_adaptive(
    codes: Array,
    luts: Array,
    x_slow: Array,
    adj: Array,
    queries: Array,
    entry: Array,
    budget_cfg: AdaptiveBeamBudget,
    k: int = 10,
    rerank: bool = True,
    num_buckets: int | None = None,
    step_kernel: str | None = None,
    excl: Array | None = None,
) -> tuple[Array, Array, SearchStats, AdaptiveStats]:
    """PQ-routed adaptive-beam search + optional full-precision re-rank.

    The probe-phase LID is estimated from ADC distances — the same values
    that steer the walk — so the budget decision adds zero extra slow-tier
    reads. Shapes as in :func:`beam_search_pq`. ``num_buckets`` >= 2 enables
    budget-bucketed continue execution (see
    :func:`beam_search_exact_adaptive`); the final rerank stays one batched
    slow-tier read over the whole batch.  ``excl`` filters the walk in-graph
    (probe only — the continue phase inherits the filter via the visited
    bitset, see :func:`beam_search_exact_adaptive`).
    """
    if num_buckets is None or num_buckets <= 1:
        return _beam_search_pq_adaptive_jit(
            codes, luts, x_slow, adj, queries, entry, budget_cfg,
            k=k, rerank=rerank, step_kernel=step_kernel, excl=excl)
    probe_state, budgets, hop_limits, q_lid = _probe_pq_jit(
        codes, adj, luts, entry, budget_cfg, step_kernel=step_kernel,
        excl=excl)
    ceilings = budget_bucket_ceilings(
        budget_cfg.l_min, budget_cfg.l_max, num_buckets)
    cont = functools.partial(_continue_pq_jit, codes, adj,
                             budget_cfg=budget_cfg, step_kernel=step_kernel)
    beam_ids, beam_d, hops, evals = _bucketed_continue(
        cont, probe_state, luts, budgets, hop_limits, ceilings)
    stats = SearchStats(hops=hops, dist_evals=evals)
    astats = AdaptiveStats(q_lid=q_lid, budget=budgets)
    if rerank:
        ids, d2 = _rerank_slow_tier_jit(beam_ids, x_slow, queries, k=k)
        return ids, d2, stats, astats
    return beam_ids[:, :k], beam_d[:, :k], stats, astats


def medoid(x: Array) -> Array:
    """Entry point: the point closest to the dataset centroid (DiskANN's
    choice; O(N·D) instead of the O(N^2) true medoid)."""
    c = jnp.mean(x, axis=0, keepdims=True)
    diff = x - c
    return jnp.argmin(jnp.sum(diff * diff, axis=-1)).astype(jnp.int32)


def nearest_entries(candidates: Array, x: Array, queries: Array) -> Array:
    """(Q,) entry node per query: the row of ``candidates`` ((E,) node ids
    of ``x``; one node is a set of one) nearest each query by exact
    distance, so a walk starts inside the query's own region instead of at
    one medoid many hops from it."""
    candidates = jnp.atleast_1d(candidates).astype(jnp.int32)
    cand = x[candidates].astype(jnp.float32)                   # (E, d)
    diff = queries.astype(jnp.float32)[:, None, :] - cand[None, :, :]
    return candidates[jnp.argmin(jnp.sum(diff * diff, axis=-1), axis=1)]
