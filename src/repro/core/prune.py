"""Adaptive robust pruning — the "dynamic occlusion criterion" (paper §3.2/§3.3).

An edge (u, v) is pruned when a previously selected witness n satisfies

    alpha(u) * d(n, v) <= d(u, v)            (paper §4.2)

with the *per-node* alpha(u) produced by the mapping function Phi. With
alpha(u) = const this is exactly Vamana's RobustPrune, which is how the
DiskANN baseline is expressed in this framework.

All distances in this module are squared-L2; the criterion is applied as
``alpha^2 * d2(n, v) <= d2(u, v)`` which is equivalent on true distances.

The selection loop is sequential in the candidate rank (each selected witness
can occlude later candidates) — implemented as a ``lax.fori_loop`` over the
(small, O(L+R)) candidate list with vectorised occlusion updates, vmapped over
the node batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array

INVALID = -1


def _dedup_mask(ids: Array) -> Array:
    """True for the first occurrence of each id (ids sorted by priority)."""
    c = ids.shape[0]
    same = ids[None, :] == ids[:, None]  # (C, C)
    earlier = jnp.tril(jnp.ones((c, c), dtype=bool), k=-1)
    dup = (same & earlier).any(axis=1)
    return ~dup


def robust_prune_one(
    cand_ids: Array,
    cand_d2: Array,
    cand_pd2: Array,
    alpha: Array,
    degree: int,
) -> tuple[Array, Array]:
    """Prune one node's candidate pool to <= ``degree`` neighbours.

    Args:
      cand_ids: (C,) candidate ids, INVALID-padded; may contain duplicates.
      cand_d2:  (C,) squared distance of each candidate to the node u
        (``inf`` for invalid entries).
      cand_pd2: (C, C) pairwise squared distances among candidates.
      alpha:    scalar pruning parameter alpha(u) >= 1 (on true distances).
      degree:   max out-degree R.

    Returns:
      (nbr_ids, nbr_d2): each (degree,), selected neighbours sorted ascending
      by distance, INVALID/inf padded.
    """
    c = cand_ids.shape[0]
    valid = (cand_ids != INVALID) & jnp.isfinite(cand_d2)

    order = jnp.argsort(jnp.where(valid, cand_d2, jnp.inf))
    ids = cand_ids[order]
    d2 = jnp.where(valid[order], cand_d2[order], jnp.inf)
    pd2 = cand_pd2[order][:, order]
    valid = valid[order] & _dedup_mask(ids)

    alpha_sq = alpha * alpha

    def body(i, state):
        pruned, selected, count = state
        active = valid[i] & (~pruned[i]) & (count < degree)
        selected = selected.at[i].set(active)
        count = count + active.astype(jnp.int32)
        # Occlude later candidates j: alpha^2 * d2(c_i, c_j) <= d2(u, c_j).
        later = jnp.arange(c) > i
        occluded = later & (alpha_sq * pd2[i, :] <= d2)
        pruned = jnp.where(active, pruned | occluded, pruned)
        return pruned, selected, count

    pruned0 = jnp.zeros((c,), dtype=bool)
    selected0 = jnp.zeros((c,), dtype=bool)
    _, selected, _ = jax.lax.fori_loop(0, c, body, (pruned0, selected0, 0))

    # Compact the selected entries (already distance-sorted) into (degree,).
    rank = jnp.where(selected, jnp.arange(c), c)
    take = jnp.argsort(rank)[:degree]
    out_ids = jnp.where(selected[take], ids[take], INVALID)
    out_d2 = jnp.where(selected[take], d2[take], jnp.inf)
    return out_ids.astype(jnp.int32), out_d2


@functools.partial(jax.jit, static_argnames=("degree",))
def robust_prune_batch(
    x: Array,
    node_ids: Array,
    cand_ids: Array,
    alpha: Array,
    degree: int,
) -> tuple[Array, Array]:
    """Vectorised prune for a batch of nodes.

    Args:
      x:        (N, D) base vectors (distance oracle for the occlusion checks —
        on the real two-tier system these reads come from the fast tier's PQ
        codes during build, full precision here).
      node_ids: (B,) nodes being re-wired.
      cand_ids: (B, C) candidate pools (INVALID-padded, duplicates allowed).
      alpha:    (B,) per-node alpha(u).
      degree:   max out-degree R.

    Returns:
      (adj_rows, adj_d2): (B, degree) pruned neighbour lists + distances.
    """
    safe = jnp.maximum(cand_ids, 0)
    cvecs = x[safe]  # (B, C, D)
    uvecs = x[node_ids]  # (B, D)

    diff = cvecs - uvecs[:, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)  # (B, C)
    # Self-edges and invalid slots are never eligible.
    bad = (cand_ids == INVALID) | (cand_ids == node_ids[:, None])
    d2 = jnp.where(bad, jnp.inf, d2)

    # Pairwise candidate distances for occlusion tests.
    sq = jnp.sum(cvecs * cvecs, axis=-1)  # (B, C)
    dots = jnp.einsum("bcd,bed->bce", cvecs, cvecs,
                      precision=jax.lax.Precision.HIGHEST)
    pd2 = sq[:, :, None] - 2.0 * dots + sq[:, None, :]
    pd2 = jnp.maximum(pd2, 0.0)

    ids = jnp.where(bad, INVALID, cand_ids)
    return jax.vmap(robust_prune_one, in_axes=(0, 0, 0, 0, None))(
        ids, d2, pd2, alpha, degree
    )


def greedy_block_pack(adj, entry: int, nodes_per_block: int):
    """Block-aware slot assignment (the BAMG layout lever): co-locate each
    node's record with its nearest pruned out-neighbours so one I/O-block
    read covers a hop's expansions.

    Nodes are visited in BFS order from the entry point — the order a beam
    walk first touches records — and every still-unassigned node opens a
    *group*: itself plus its nearest unassigned out-neighbours (adjacency
    rows come distance-ascending out of the robust prune, so row order *is*
    nearness order).  Groups fill consecutive record slots and are capped at
    the current I/O block's remaining capacity, so a seed node and the
    neighbours packed with it always share one block — when the walk expands
    the seed, the block read that fetched its adjacency has already paid for
    the neighbours it is most likely to hop to next.  Unreachable nodes are
    appended in id order.

    Host-side numpy (build-time layout, not a kernel).  Returns
    ``slot_of``: (N,) int64 permutation mapping node id -> record slot,
    the form :func:`repro.index.blockstore.write_block_store` takes.
    """
    import numpy as np

    adj = np.asarray(adj)
    n = adj.shape[0]
    npb = int(nodes_per_block)
    if npb <= 1:
        return np.arange(n, dtype=np.int64)

    # BFS from the entry over out-edges; unreached nodes follow in id order.
    order = np.empty(n, dtype=np.int64)
    seen = np.zeros(n, dtype=bool)
    order[0] = int(entry)
    seen[int(entry)] = True
    head, tail = 0, 1
    while head < tail:
        u = order[head]
        head += 1
        for v in adj[u]:
            if v >= 0 and not seen[v]:
                seen[v] = True
                order[tail] = v
                tail += 1
    if tail < n:
        rest = np.flatnonzero(~seen)
        order[tail:] = rest
        seen[rest] = True

    slot_of = np.empty(n, dtype=np.int64)
    assigned = np.zeros(n, dtype=bool)
    next_slot = 0
    for u in order:
        if assigned[u]:
            continue
        group = [int(u)]
        assigned[u] = True
        # Fill only to the end of the current I/O block: the group never
        # straddles a block boundary.
        capacity = npb - (next_slot % npb)
        for v in adj[u]:
            if len(group) >= capacity:
                break
            if v >= 0 and not assigned[v]:
                group.append(int(v))
                assigned[v] = True
        for g in group:
            slot_of[g] = next_slot
            next_slot += 1
    assert next_slot == n
    return slot_of
