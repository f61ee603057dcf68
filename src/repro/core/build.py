"""MCGI index construction — Algorithm 1 (offline) of the paper.

Phase 1 (Geometric Calibration): estimate LID for every point, freeze the
population statistics (mu, sigma), map to per-node alpha(u) via Phi.

Phase 2 (Manifold-Consistent Refinement): Vamana-style synchronous rounds —
each round re-wires every node u from the candidate pool found by a greedy
search towards x_u on the current graph, pruned with the *node-specific*
alpha(u); newly created edges are mirrored (reverse-edge insertion with
re-pruning of overfull destinations), which is what makes the graph navigable
from the medoid.

The loop is host-orchestrated over one jitted program per batch step
(search + prune + mirrored-edge insertion, fixed shapes, no host sync);
batch size trades launches against the (B, C, D) candidate-gather
footprint.

``build_vamana`` (the DiskANN baseline) is the same procedure with the
constant-alpha mapping — the framework's way of isolating the paper's single
moving part.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lid as lid_mod
from repro.core import mapping as mapping_mod
from repro.core import prune as prune_mod
from repro.core import search as search_mod
from repro.core.types import GraphIndex

Array = jax.Array
INVALID = -1


@dataclasses.dataclass(frozen=True)
class BuildConfig:
    """Construction hyper-parameters (paper Table 2 naming)."""

    degree: int = 32            # R — max out-degree
    beam_width: int = 64        # L_build — construction beam
    iters: int = 2              # T — refinement rounds
    lid_k: int = 16             # k-NN size for the LID estimator
    alpha_min: float = mapping_mod.ALPHA_MIN
    alpha_max: float = mapping_mod.ALPHA_MAX
    batch: int = 256            # nodes re-wired per jitted step
    max_hops: int = 256         # search budget during construction
    reverse_cap: int = 16       # reverse-edge candidates accepted per node/step
    seed: int = 0


@functools.partial(jax.jit, static_argnums=(0, 1))
def random_graph(n: int, degree: int, key: Array) -> Array:
    """R-regular random initial graph (Algorithm 1's RandomGraph).

    Rows are duplicate-free (the bit-packed visited set in the searcher
    scatter-adds one bit per neighbour, so a repeated id within a row would
    corrupt the mask)."""
    keys = jax.random.split(key, n)

    def row(k, u):
        ids = jax.random.randint(k, (degree,), 0, n, dtype=jnp.int32)
        ids = jnp.where(ids == u, (ids + 1) % n, ids)  # no self-loops
        # Mark duplicate ids INVALID (order-preserving dedup): an id is a
        # duplicate occurrence if an earlier slot holds the same id.
        earlier_same = (ids[None, :] == ids[:, None]) & (
            jnp.arange(degree)[None, :] < jnp.arange(degree)[:, None]
        )
        return jnp.where(earlier_same.any(axis=1), INVALID, ids)

    return jax.vmap(row)(keys, jnp.arange(n, dtype=jnp.int32))


def _rewire_batch(
    x: Array,
    adj: Array,
    alpha: Array,
    entry: Array,
    node_ids: Array,
    cfg: BuildConfig,
) -> tuple[Array, Array]:
    """The re-wiring half of a refinement step for a batch of nodes.

    Greedy-search each node's own vector on the current graph, pool the beam
    with the node's current neighbours, robust-prune with alpha(u).
    Returns (new_rows, new_d2): (B, R) each.
    """
    queries = x[node_ids]
    beam_ids, _, _ = search_mod.beam_search_exact(
        x, adj, queries, entry,
        beam_width=cfg.beam_width, max_hops=cfg.max_hops, k=cfg.beam_width,
    )
    pool = jnp.concatenate([beam_ids, adj[node_ids]], axis=1)  # (B, L+R)
    return prune_mod.robust_prune_batch(
        x, node_ids, pool, alpha[node_ids], cfg.degree
    )


def _reverse_pairs(
    node_ids: np.ndarray, new_rows: np.ndarray, cap: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side grouping of mirrored edges.

    Every new edge (u -> v) proposes the reverse candidate (v -> u). Groups by
    destination v and pads each group to ``cap`` (overflow is dropped — those
    edges get another chance in the next round, matching batched-Vamana
    practice).

    Returns (dest_ids (V,), cand (V, cap)) as numpy (INVALID padded).
    """
    us = np.repeat(node_ids, new_rows.shape[1])
    vs = new_rows.reshape(-1)
    keep = vs >= 0
    us, vs = us[keep], vs[keep]
    if vs.size == 0:
        return np.empty((0,), np.int32), np.empty((0, cap), np.int32)
    order = np.argsort(vs, kind="stable")
    us, vs = us[order], vs[order]
    dest, start = np.unique(vs, return_index=True)
    cand = np.full((dest.size, cap), INVALID, dtype=np.int32)
    bounds = np.append(start, vs.size)
    for i in range(dest.size):
        grp = us[bounds[i] : bounds[i + 1]][:cap]
        cand[i, : grp.size] = grp
    return dest.astype(np.int32), cand


def _insert_reverse(
    x: Array, adj: Array, alpha: Array, dest: Array, cand: Array, cfg: BuildConfig,
    valid: Array | None = None,
) -> Array:
    """Merge reverse candidates into destination adjacency lists, re-pruning
    overfull nodes with their own alpha(v).

    ``valid`` (optional, (B,) bool) marks real lanes in a shape-padded batch.
    Pad lanes repeat a live destination id (keeping jit shapes fixed), so
    without the mask their re-pruned rows — computed from an all-INVALID
    candidate pool, hence generally *different* from the real lane's row —
    would reach the scatter under a duplicate index, where the winner is
    unspecified.  Masked lanes scatter to row N instead, which ``mode="drop"``
    discards.
    """
    pool = jnp.concatenate([adj[dest], cand], axis=1)
    rows, _ = prune_mod.robust_prune_batch(x, dest, pool, alpha[dest], cfg.degree)
    if valid is None:
        return adj.at[dest].set(rows)
    dest = jnp.where(valid, dest, adj.shape[0])
    return adj.at[dest].set(rows, mode="drop")


def _reverse_pairs_device(
    node_ids: Array, new_rows: Array, cap: int, n: int
) -> tuple[Array, Array, Array]:
    """On-device twin of :func:`_reverse_pairs` (same destinations in the
    same ascending order, same candidate rows), at the fixed length
    E = B*R of the step's edge list so it can run inside the step program.

    Returns (dest (E,), cand (E, cap), count): the first ``count`` entries
    are the real destinations; the rest are filler.
    """
    e = new_rows.size
    us = jnp.repeat(node_ids, new_rows.shape[1])
    vs = new_rows.reshape(-1)
    key = jnp.where(vs >= 0, vs, n)          # dropped edges sort last
    order = jnp.argsort(key, stable=True)
    us, vs = us[order], key[order]
    pos = jnp.arange(e, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), vs[1:] != vs[:-1]])
    group = jnp.cumsum(first, dtype=jnp.int32) - 1
    rank = pos - jax.lax.cummax(jnp.where(first, pos, 0))
    live = vs < n
    count = jnp.sum(first & live, dtype=jnp.int32)
    dest = jnp.zeros((e,), jnp.int32).at[
        jnp.where(first & live, group, e)].set(vs, mode="drop")
    keep = live & (rank < cap)
    cand = jnp.full((e, cap), INVALID, jnp.int32).at[
        jnp.where(keep, group, e), jnp.minimum(rank, cap - 1)
    ].set(us, mode="drop")
    return dest, cand, count


@functools.partial(jax.jit, static_argnames=("cfg",))
def _refine_step(
    x: Array, adj: Array, alpha: Array, entry: Array, node_ids: Array,
    cfg: BuildConfig,
) -> Array:
    """One refinement step as one program: re-wire the batch
    (:func:`_rewire_batch`), then mirror its new edges chunk by chunk
    (:func:`_insert_reverse`, ``cfg.batch`` destinations per chunk, a short
    last chunk padded with its first destination and no candidates) — the
    host never waits on the step, so the build runs at device speed."""
    new_rows, _ = _rewire_batch(x, adj, alpha, entry, node_ids, cfg)
    adj = adj.at[node_ids].set(new_rows)
    dest, cand, count = _reverse_pairs_device(
        node_ids, new_rows, cfg.reverse_cap, adj.shape[0])
    b = cfg.batch
    chunks = (count + b - 1) // b
    last = dest[jnp.maximum(chunks - 1, 0) * b]
    dest = jnp.where(jnp.arange(dest.shape[0]) < count, dest, last)

    def insert_chunk(c, adj):
        return _insert_reverse(
            x, adj, alpha, jax.lax.dynamic_slice_in_dim(dest, c * b, b),
            jax.lax.dynamic_slice_in_dim(cand, c * b, b), cfg)

    return jax.lax.fori_loop(0, chunks, insert_chunk, adj)


def build_with_alpha(
    x: Array,
    alpha: Array,
    cfg: BuildConfig,
    progress: Callable[[str], None] | None = None,
    init_adj: Array | None = None,
) -> Array:
    """Phase 2 (Manifold-Consistent Refinement) given frozen per-node alpha."""
    n = x.shape[0]
    key = jax.random.PRNGKey(cfg.seed)
    adj = random_graph(n, cfg.degree, key) if init_adj is None else init_adj
    entry = search_mod.medoid(x)

    for it in range(cfg.iters):
        perm = np.asarray(
            jax.random.permutation(jax.random.fold_in(key, it + 1), n)
        )
        for start in range(0, n, cfg.batch):
            ids_np = perm[start : start + cfg.batch]
            if ids_np.size < cfg.batch:  # keep jit shapes fixed: wrap-around pad
                ids_np = np.concatenate([ids_np, perm[: cfg.batch - ids_np.size]])
            adj = _refine_step(x, adj, alpha, entry, jnp.asarray(ids_np), cfg)
        if progress:
            adj.block_until_ready()
            progress(f"refinement round {it + 1}/{cfg.iters} done")
    return adj


def build_mcgi(
    x: Array, cfg: BuildConfig = BuildConfig(), progress=None
) -> GraphIndex:
    """Algorithm 1 — full offline MCGI build (calibration + refinement)."""
    profile = lid_mod.estimate_dataset_lid(x, k=cfg.lid_k)
    mapping = mapping_mod.AlphaMapping(
        mu=profile.mu, sigma=profile.sigma,
        alpha_min=cfg.alpha_min, alpha_max=cfg.alpha_max,
    )
    alpha = mapping(profile.lid)
    if progress:
        progress(
            f"calibration: mu={float(profile.mu):.2f} sigma={float(profile.sigma):.2f}"
        )
    adj = build_with_alpha(x, alpha, cfg, progress)
    return GraphIndex(
        adj=adj, entry=search_mod.medoid(x), alpha=alpha,
        lid=profile.lid, mu=profile.mu, sigma=profile.sigma,
    )


def build_vamana(
    x: Array, alpha: float = 1.2, cfg: BuildConfig = BuildConfig(), progress=None
) -> GraphIndex:
    """DiskANN/Vamana baseline: identical pipeline, constant alpha.

    DiskANN builds in two passes (alpha=1 then alpha=target); we reproduce
    that with iters>=2 by using alpha=1 in the first round.
    """
    n = x.shape[0]
    alpha_arr = mapping_mod.constant_alpha(n, alpha)
    if cfg.iters >= 2:
        # DiskANN's first pass runs with alpha=1, the second with the target.
        adj = build_with_alpha(
            x, mapping_mod.constant_alpha(n, 1.0),
            dataclasses.replace(cfg, iters=1), progress,
        )
        adj = build_with_alpha(
            x, alpha_arr, dataclasses.replace(cfg, iters=cfg.iters - 1),
            progress, init_adj=adj,
        )
    else:
        adj = build_with_alpha(x, alpha_arr, cfg, progress)
    return GraphIndex(
        adj=adj, entry=search_mod.medoid(x), alpha=alpha_arr,
        lid=jnp.zeros((n,), jnp.float32), mu=jnp.float32(0), sigma=jnp.float32(0),
    )


def block_layout(graph: GraphIndex, nodes_per_block: int) -> np.ndarray:
    """Build-time block-aware record layout for the on-disk store.

    Thin entry point over :func:`repro.core.prune.greedy_block_pack` taking
    the built :class:`GraphIndex` directly; the returned ``slot_of``
    permutation feeds ``write_block_store(..., nodes_per_block=,
    slot_of=)`` and is recorded in the store manifest (the serializer's
    layout rider), so a reopened store knows how its records were packed.
    """
    return prune_mod.greedy_block_pack(
        np.asarray(graph.adj), int(graph.entry), nodes_per_block)
