"""Fused beam-step Pallas kernel — one launch per hop of the graph walk.

The serving hot loop's per-hop body (frontier select -> adjacency-row fetch ->
neighbor distance evaluation -> beam top-k merge -> visited-bitmap
test/update) otherwise lowers to a chain of separate XLA HLOs per hop; this
kernel fuses the whole hop into one ``pallas_call``.  Grid = one program per
group of ``LANES`` (8) query lanes, so every per-lane block is a whole
(8, ·) sublane tile; the graph adjacency and the distance table (full-precision
rows or PQ codes) stay in ``ANY`` memory (HBM at scale) and are pulled
row-by-row with explicit async copies — the TPU expression of DiskANN's
pointer-chasing gather.

Two static distance variants (the same two evaluators the reference walk
closes over):

* ``kind="exact"`` — ``table`` is (N, D) vectors; squared L2 against the
  query context (D,).
* ``kind="pq"``    — ``table`` is (N, M) uint8 codes; ADC lookup against the
  per-query LUT context (M, K).

Kernel layout.  The TPU compiler (Mosaic) slices HBM refs only along whole
(·, 128) lane tiles, lowers no scalar indexing into vectors and no vector
gather/scatter.  So the walk runs on a *kernel layout* of its state and
tables, converted once per batch around the hop loop (:func:`beam_walk`),
never per hop:

* adjacency and table rows are lane-dense 32-bit rows: (N, 128·C) padded,
  (N, C, 128) when C > 1 (one DMA per row either way; PQ codes widen to
  int32);
* the visited bitset (Q, ceil(N/32)) u32 becomes (Q, ceil(NW/128), 128)
  int32, so one word is a dynamic sublane row plus a lane select;
* ``beam_exp`` is int32, counters are (Q, 1) columns, Q pads to a multiple
  of 8 with lanes whose hop limit is 0 (never active).

Inside the kernel every "pick element j" is an iota compare plus a lane
reduction, the frontier id reaches the scalar unit through a reduction, the
adjacency row lands in SMEM so its ids can address the row DMAs, and the
visited update is a per-neighbour read-modify-write of one 128-word row.

Bit-exactness contract: every arithmetic expression below computes the same
values as the reference hop body (``repro.core.search``), so interpret-mode
results are bit-identical to the reference walk — the engine-parity kernel
axis asserts this end to end.  The substitutions are exact:

* the beam merge's stable ``argsort(cat_d)[:L]`` becomes an L-round
  masked-argmin selection.  The two are bitwise equal under the walk's state
  invariant — a beam/candidate entry has ``d == inf`` iff its id is INVALID
  (payload (INVALID, inf, False)) — because finite keys tie-break
  lowest-index-first in both, and once only inf keys remain the emitted
  payload is forced to the shared (INVALID, inf, False);
* the ADC gather ``lut[m, code]`` becomes a one-hot max-select per
  sub-quantizer (one finite value against -inf: exact), summed over M in the
  reference's order;
* the visited test reads the bitset as already updated by earlier
  neighbours of the same hop.  Adjacency rows are duplicate-free (the
  pruner dedups), so a neighbour's own bit is never set by another one and
  the seen test equals the reference's test against the pre-hop bitset.

On the chip the reductions run in the TPU's order, not XLA-CPU's, so the
compiled kernel and the compiled reference walk can differ in the last bit
of a distance and hence, rarely, in a tie-broken id.

Lane freezing: a converged/hop-capped lane writes its state back unchanged
(the same select-masking XLA applies to a vmapped ``while_loop``), so the
batch-level while of :func:`beam_walk` retires lanes exactly like the
reference's per-lane loops.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
INVALID = -1
LANES = 8         # query lanes per program: one f32 sublane tile
_LANE = 128       # TPU lane width: the unit of every HBM row slice


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _rows_layout(t: Array) -> Array:
    """(N, W) rows -> lane-dense 32-bit rows: (N, 128) when W <= 128, else
    (N, C, 128) with C = ceil(W/128).  Zero padding is never read: the
    kernel slices every row back to its first W entries."""
    n, w = t.shape
    if t.dtype != jnp.float32:
        t = t.astype(jnp.int32)
    c = _cdiv(w, _LANE)
    t = jnp.pad(t, ((0, 0), (0, c * _LANE - w)))
    return t if c == 1 else t.reshape(n, c, _LANE)


def to_kernel_state(state):
    """Walk state of :mod:`repro.core.search` -> the kernel layout (see the
    module docstring); padded lanes are empty beams that never activate."""
    beam_ids, beam_d, beam_exp, visited, hops, evals = state
    q, nw = visited.shape
    pad_q = _cdiv(q, LANES) * LANES - q
    nwr = _cdiv(nw, _LANE)
    lanes = lambda a, v: jnp.pad(a, ((0, pad_q),) + ((0, 0),) * (a.ndim - 1),
                                 constant_values=v)
    vis = jax.lax.bitcast_convert_type(visited, jnp.int32)
    vis = jnp.pad(vis, ((0, pad_q), (0, nwr * _LANE - nw)))
    return (lanes(beam_ids, INVALID), lanes(beam_d, jnp.inf),
            lanes(beam_exp.astype(jnp.int32), 0),
            vis.reshape(q + pad_q, nwr, _LANE),
            lanes(hops, 0)[:, None], lanes(evals, 0)[:, None])


def from_kernel_state(kstate, q: int, nw: int):
    """Inverse of :func:`to_kernel_state` for the first ``q`` lanes."""
    beam_ids, beam_d, beam_exp, vis, hops, evals = kstate
    visited = vis.reshape(vis.shape[0], -1)[:q, :nw]
    return (beam_ids[:q], beam_d[:q], beam_exp[:q] != 0,
            jax.lax.bitcast_convert_type(visited, jnp.uint32),
            hops[:q, 0], evals[:q, 0])


def _lane_column(x: Array, q_pad: int, fill=0) -> Array:
    """(Q,) per-lane values -> (Qp, 1) kernel column."""
    x = x.astype(jnp.int32)
    return jnp.pad(x, (0, q_pad - x.shape[0]), constant_values=fill)[:, None]


def _pick(mask: Array, x: Array, fill) -> Array:
    """Per-row value of ``x`` at the (single) True lane of ``mask``, exactly:
    a max over one value and ``fill`` (-inf for floats, INT_MIN for ints)."""
    return jnp.max(jnp.where(mask, x, fill), axis=1, keepdims=True)


def _select_merge(b_ids, b_d, b_exp, c_ids, c_d):
    """Keep-best-L merge of beam (G, L) and candidates (G, R) as an L-round
    selection loop — the TPU-lowerable stand-in for ``argsort(cat_d)[:L]``
    over ``cat = concat(beam, candidates)``.

    Bitwise equal to the argsort gathers under the invariant that every
    inf-keyed entry carries the identical payload (INVALID, inf, False):
    finite keys pick lowest-index-first in both, and the all-inf tail emits
    that shared payload explicitly.  Candidates are never expanded.
    """
    lanes, beam_width = b_ids.shape
    degree = c_ids.shape[1]
    bi = jax.lax.broadcasted_iota(jnp.int32, (lanes, beam_width), 1)
    ci = jax.lax.broadcasted_iota(jnp.int32, (lanes, degree), 1) + beam_width
    never = beam_width + degree
    int_min = jnp.iinfo(jnp.int32).min

    def select(i, carry):
        out_ids, out_d, out_exp, b_taken, c_taken = carry
        kb = jnp.where(b_taken != 0, jnp.inf, b_d)
        kc = jnp.where(c_taken != 0, jnp.inf, c_d)
        key = jnp.minimum(jnp.min(kb, axis=1, keepdims=True),
                          jnp.min(kc, axis=1, keepdims=True))
        p = jnp.minimum(
            jnp.min(jnp.where(kb == key, bi, never), axis=1, keepdims=True),
            jnp.min(jnp.where(kc == key, ci, never), axis=1, keepdims=True))
        hb, hc = bi == p, ci == p
        exhausted = key == jnp.inf
        pid = jnp.maximum(_pick(hb, b_ids, int_min), _pick(hc, c_ids, int_min))
        pd = jnp.maximum(_pick(hb, b_d, -jnp.inf), _pick(hc, c_d, -jnp.inf))
        pexp = _pick(hb, b_exp, 0)
        here = bi == i
        out_ids = jnp.where(here, jnp.where(exhausted, INVALID, pid), out_ids)
        out_d = jnp.where(here, jnp.where(exhausted, jnp.inf, pd), out_d)
        out_exp = jnp.where(here, jnp.where(exhausted, 0, pexp), out_exp)
        # Loop carries stay 32-bit: Mosaic carries no i1 vectors.
        return (out_ids, out_d, out_exp, jnp.where(hb, 1, b_taken),
                jnp.where(hc, 1, c_taken))

    init = (jnp.zeros_like(b_ids), jnp.zeros_like(b_d), jnp.zeros_like(b_exp),
            jnp.zeros_like(b_ids), jnp.zeros_like(c_ids))
    out_ids, out_d, out_exp, _, _ = jax.lax.fori_loop(
        0, beam_width, select, init)
    return out_ids, out_d, out_exp


def _adc(codes: Array, lut: Array) -> Array:
    """(G, R, M) int32 codes x (G, M, K) LUTs -> (G, R) ADC distances.

    ``lut[m, code]`` by a one-hot max-select per sub-quantizer (exact), then
    the reference's ``gathered.sum(axis=-1)`` over the (G, R, M) table."""
    lanes, degree, m = codes.shape
    k = lut.shape[2]
    kk = jax.lax.broadcasted_iota(jnp.int32, (lanes, degree, k), 2)
    mm = jax.lax.broadcasted_iota(jnp.int32, (lanes, degree, m), 2)
    gathered = jnp.zeros((lanes, degree, m), jnp.float32)
    for j in range(m):
        g = jnp.max(jnp.where(codes[:, :, j:j + 1] == kk,
                              lut[:, j:j + 1, :], -jnp.inf),
                    axis=-1, keepdims=True)
        gathered = jnp.where(mm == j, g, gathered)
    return gathered.sum(axis=-1)


def _beam_step_kernel(
    # per-lane-group blocks (VMEM)
    ids_ref, d_ref, exp_ref, vis_ref, hops_ref, evals_ref, bud_ref, hl_ref,
    ctx_ref,
    # whole arrays (ANY memory; fetched by DMA)
    adj_ref, table_ref,
    # outputs (same per-lane-group layout as the inputs)
    o_ids, o_d, o_exp, o_vis, o_hops, o_evals,
    # scratch
    nbrs_s, rows_s, sem,
    *, kind: str, degree: int, width: int,
):
    beam_ids = ids_ref[...]                    # (G, L)
    beam_d = d_ref[...]
    beam_exp = exp_ref[...]                    # int32 0/1
    hops = hops_ref[...]                       # (G, 1)
    evals = evals_ref[...]
    lanes, beam_width = beam_ids.shape

    slot = jax.lax.broadcasted_iota(jnp.int32, (lanes, beam_width), 1)
    in_budget = slot < bud_ref[...]
    open_ = (beam_exp == 0) & (beam_ids != INVALID) & in_budget
    frontier_open = jnp.max(open_.astype(jnp.int32), axis=1, keepdims=True)
    # Lane-freeze predicate: identical to the reference loop's cond, so an
    # inactive lane writes its state back unchanged.
    active = (hops < hl_ref[...]) & (frontier_open > 0)          # (G, 1)

    # --- frontier select (reference argmin: lowest index of the min) ------
    cand_d = jnp.where(open_, beam_d, jnp.inf)
    best = jnp.min(cand_d, axis=1, keepdims=True)
    j = jnp.min(jnp.where(cand_d == best, slot, beam_width),
                axis=1, keepdims=True)
    u = _pick(slot == j, beam_ids, jnp.iinfo(jnp.int32).min)     # (G, 1)
    new_exp = jnp.where(slot == j, 1, beam_exp)

    # Per-lane scalars for the scalar unit (DMA addresses, update gating).
    sub = jax.lax.broadcasted_iota(jnp.int32, (lanes, 1), 0)
    u_s = [jnp.sum(jnp.where(sub == s, u, 0)) for s in range(lanes)]
    act_s = [jnp.sum(jnp.where(sub == s, active.astype(jnp.int32), 0))
             for s in range(lanes)]

    # --- adjacency rows -> SMEM (inactive/INVALID lanes fetch row 0) ------
    adj_cps = [pltpu.make_async_copy(adj_ref.at[jnp.maximum(u_s[s], 0)],
                                     nbrs_s.at[s], sem)
               for s in range(lanes)]
    for cp in adj_cps:
        cp.start()
    for cp in adj_cps:
        cp.wait()

    # --- neighbour row gather: all R x G row DMAs in flight, then drain ---
    def start(r, carry):
        for s in range(lanes):
            nid = jnp.maximum(nbrs_s[s, r], 0)
            pltpu.make_async_copy(table_ref.at[nid], rows_s.at[s, r],
                                  sem).start()
        return carry

    def drain(r, carry):
        for s in range(lanes):
            pltpu.make_async_copy(table_ref.at[0], rows_s.at[s, r],
                                  sem).wait()
        return carry

    jax.lax.fori_loop(0, degree, start, 0)

    # --- visited test/update, one 128-word row per neighbour (overlaps the
    # row DMAs); also collects the neighbour ids and validity as vectors ---
    o_vis[...] = vis_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    g_sub = jax.lax.broadcasted_iota(jnp.int32, (lanes, degree), 0)
    g_r = jax.lax.broadcasted_iota(jnp.int32, (lanes, degree), 1)

    def visit(r, carry):
        valid, nbrs = carry
        for s in range(lanes):
            nid = nbrs_s[s, r]
            safe = jnp.maximum(nid, 0)
            word = safe >> 5
            row, col = word >> 7, word & (_LANE - 1)
            bit = jnp.left_shift(jnp.int32(1), safe & 31)
            cur = o_vis[s, pl.ds(row, 1), :]                       # (1, 128)
            at = lane == col
            seen = jnp.max(jnp.where(at & ((cur & bit) != 0), 1, 0))
            ok = ((nid != INVALID) & (u_s[s] != INVALID)
                  & (seen == 0)).astype(jnp.int32)
            o_vis[s, pl.ds(row, 1), :] = cur | jnp.where(
                at, bit * (ok * act_s[s]), 0)
            here = (g_sub == s) & (g_r == r)
            valid = jnp.where(here, ok, valid)
            nbrs = jnp.where(here, nid, nbrs)
        return valid, nbrs

    zeros = jnp.zeros((lanes, degree), jnp.int32)
    valid_i, nbrs = jax.lax.fori_loop(0, degree, visit, (zeros, zeros))
    valid = valid_i != 0
    jax.lax.fori_loop(0, degree, drain, 0)

    # --- distance evaluation (the reference evaluators' values) ----------
    rows = rows_s[...].reshape(lanes, degree, -1)[:, :, :width]  # (G, R, W)
    if kind == "pq":
        d = _adc(rows, ctx_ref[...])
    else:
        diff = rows.astype(jnp.float32) - ctx_ref[...][:, None, :]
        d = jnp.sum(diff * diff, axis=-1)                       # (G, R)
    d = jnp.where(valid, d, jnp.inf)
    nbr_ids = jnp.where(valid, nbrs, INVALID)

    # --- beam top-k merge --------------------------------------------------
    m_ids, m_d, m_exp = _select_merge(beam_ids, beam_d, new_exp, nbr_ids, d)

    # --- write-back with lane freezing ------------------------------------
    o_ids[...] = jnp.where(active, m_ids, beam_ids)
    o_d[...] = jnp.where(active, m_d, beam_d)
    o_exp[...] = jnp.where(active, m_exp, beam_exp)
    o_hops[...] = jnp.where(active, hops + 1, hops)
    o_evals[...] = jnp.where(
        active, evals + jnp.sum(valid_i, axis=1, keepdims=True), evals)


def _vmem_limit(kstate, ctxs: Array, table_k: Array, degree: int) -> int:
    """Scoped-VMEM request: double-buffered in+out blocks plus scratch, with
    headroom, never below the compiler's 16 MiB default (v5e has 128 MiB)."""
    group = lambda a: LANES * a.size // a.shape[0] * a.dtype.itemsize
    blocks = sum(group(a) for a in kstate) + group(ctxs)
    rows = LANES * degree * table_k.size // table_k.shape[0] * 4
    need = 4 * blocks + rows + (1 << 20)
    return int(min(max(16 << 20, 1.25 * need), 100 << 20))


def _step(kstate, ctxs, adj_k, table_k, budgets, hop_limits, *, kind: str,
          degree: int, width: int, interpret: bool):
    """One fused hop over kernel-layout state (Qp lanes, Qp % 8 == 0)."""
    qp, beam_width = kstate[0].shape
    nwr = kstate[3].shape[1]
    grid = (qp // LANES,)
    row = lambda i: (i, 0)
    beam = pl.BlockSpec((LANES, beam_width), row)
    col = pl.BlockSpec((LANES, 1), row)
    vis = pl.BlockSpec((LANES, nwr, _LANE), lambda i: (i, 0, 0))
    ctx = pl.BlockSpec((LANES,) + ctxs.shape[1:],
                       lambda i: (i,) + (0,) * (ctxs.ndim - 1))
    out = pl.pallas_call(
        functools.partial(_beam_step_kernel, kind=kind, degree=degree,
                          width=width),
        grid=grid,
        in_specs=[beam, beam, beam, vis, col, col, col, col, ctx,
                  pl.BlockSpec(memory_space=pl.ANY),     # adjacency
                  pl.BlockSpec(memory_space=pl.ANY)],    # table
        out_specs=[beam, beam, beam, vis, col, col],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in kstate],
        scratch_shapes=[
            pltpu.SMEM((LANES, adj_k.shape[1]), jnp.int32),
            pltpu.VMEM((LANES, degree) + table_k.shape[1:], table_k.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={0: 0, 1: 1, 2: 2, 3: 3, 4: 4, 5: 5},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(kstate, ctxs, table_k, degree)),
        name="beam_step",
        interpret=interpret,
    )(*kstate, budgets, hop_limits, ctxs, adj_k, table_k)
    return tuple(out)


def _prepare(states, ctxs, adj, table, budgets, hop_limits):
    q = states[0].shape[0]
    qp = _cdiv(q, LANES) * LANES
    ctx_p = jnp.pad(ctxs, ((0, qp - q),) + ((0, 0),) * (ctxs.ndim - 1))
    return (to_kernel_state(states), ctx_p, _rows_layout(adj),
            _rows_layout(table), _lane_column(budgets, qp),
            _lane_column(hop_limits, qp))


def _check(kind: str, adj: Array) -> dict:
    if kind not in ("exact", "pq"):
        raise ValueError(f"unknown beam-step kind {kind!r}")
    if adj.shape[1] > _LANE:
        raise ValueError(f"degree {adj.shape[1]} > {_LANE}: an adjacency row "
                         "must fit one lane tile")
    return dict(kind=kind, degree=adj.shape[1])


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def beam_step(
    state,
    ctxs: Array,
    adj: Array,
    table: Array,
    budgets: Array,
    hop_limits: Array,
    *,
    kind: str,
    interpret: bool = False,
):
    """Advance every lane of a batched walk state by one fused hop.

    state: (beam_ids (Q, L) i32, beam_d (Q, L) f32, beam_exp (Q, L) bool,
    visited (Q, ceil(N/32)) u32, hops (Q,) i32, evals (Q,) i32) — the walk
    state of :mod:`repro.core.search`.  ``ctxs`` is (Q, D) queries
    (``kind="exact"``) or (Q, M, K) ADC LUTs (``kind="pq"``); ``table`` the
    matching (N, D) vectors / (N, M) uint8 codes; ``budgets``/``hop_limits``
    (Q,) i32.  Returns the post-hop state; lanes whose frontier is closed or
    hop limit reached pass through unchanged.  A walk should use
    :func:`beam_walk`, which converts layouts once instead of every hop.
    """
    static = _check(kind, adj)
    kst, ctx_p, adj_k, table_k, b, h = _prepare(
        state, ctxs, adj, table, budgets, hop_limits)
    kst = _step(kst, ctx_p, adj_k, table_k, b, h, width=table.shape[1],
                interpret=interpret, **static)
    return from_kernel_state(kst, state[0].shape[0], state[3].shape[1])


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def beam_walk(
    states,
    ctxs: Array,
    adj: Array,
    table: Array,
    budgets: Array,
    hop_limits: Array,
    *,
    kind: str,
    interpret: bool = False,
):
    """Run every lane to convergence with one fused launch per hop.

    Same arguments as :func:`beam_step`.  The batch-level ``while`` runs
    while any lane is active (hops below its limit and an open in-budget
    frontier); converged lanes freeze inside the kernel, exactly as XLA
    select-masks a vmapped ``while_loop``.  Layouts convert once on entry
    and once on exit.
    """
    static = _check(kind, adj)
    kst, ctx_p, adj_k, table_k, b, h = _prepare(
        states, ctxs, adj, table, budgets, hop_limits)

    def cond(st):
        beam_ids, _, beam_exp, _, hops, _ = st
        in_b = jax.lax.broadcasted_iota(jnp.int32, beam_ids.shape, 1) < b
        frontier = jnp.any((beam_exp == 0) & (beam_ids != INVALID) & in_b,
                           axis=1, keepdims=True)
        return jnp.any((hops < h) & frontier)

    def body(st):
        return _step(st, ctx_p, adj_k, table_k, b, h, width=table.shape[1],
                     interpret=interpret, **static)

    kst = jax.lax.while_loop(cond, body, kst)
    return from_kernel_state(kst, states[0].shape[0], states[3].shape[1])
