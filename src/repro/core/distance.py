"""Distance primitives shared by every index in the framework.

All graph algorithms in :mod:`repro.core` work on *squared* L2 distances (the
monotone transform preserves every comparison the algorithms make and saves a
sqrt per pair).  The LID estimator needs true distances and applies the sqrt
itself (see :mod:`repro.core.lid`).

The pure-jnp implementations here are the reference path; the Pallas kernels in
:mod:`repro.kernels` provide the TPU-optimised drop-ins and are validated
against these functions.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array
HIGHEST = jax.lax.Precision.HIGHEST   # f32 matmuls on the MXU, not bf16

# Metric names accepted across the framework.
L2 = "l2"
IP = "ip"  # inner-product (maximum inner product search, negated to a "distance")
COSINE = "cosine"


def squared_l2(q: Array, x: Array) -> Array:
    """Pairwise squared L2 distances.

    Args:
      q: (Q, D) queries.
      x: (N, D) base points.
    Returns:
      (Q, N) squared distances, computed via the expansion
      ``|q|^2 - 2 q.x + |x|^2`` so the contraction hits the MXU — at full
      f32 precision: the TPU's default one-pass bf16 product loses the
      small gaps between large-norm neighbours the expansion subtracts.
    """
    qn = jnp.sum(q * q, axis=-1, keepdims=True)  # (Q, 1)
    xn = jnp.sum(x * x, axis=-1)  # (N,)
    dot = jnp.matmul(q, x.T, precision=HIGHEST)  # (Q, N)
    d2 = qn - 2.0 * dot + xn[None, :]
    return jnp.maximum(d2, 0.0)


def neg_inner_product(q: Array, x: Array) -> Array:
    """Negated inner product as a distance (smaller = more similar)."""
    return -jnp.matmul(q, x.T, precision=HIGHEST)


def pairwise(q: Array, x: Array, metric: str = L2) -> Array:
    if metric == L2:
        return squared_l2(q, x)
    if metric == IP:
        return neg_inner_product(q, x)
    if metric == COSINE:
        qn = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-12)
        xn = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
        return neg_inner_product(qn, xn)
    raise ValueError(f"unknown metric {metric!r}")


def point_to_points(q: Array, x: Array, metric: str = L2) -> Array:
    """(D,) query vs (M, D) points -> (M,) distances."""
    return pairwise(q[None, :], x, metric)[0]


@functools.partial(jax.jit, static_argnames=("k", "metric", "chunk"))
def brute_force_topk(
    q: Array, x: Array, k: int, metric: str = L2, chunk: int = 4096
) -> tuple[Array, Array]:
    """Exact top-k nearest neighbours by chunked scan over the base set.

    Chunking bounds the (Q, chunk) score buffer so ground-truth computation for
    10^5-point benchmark sets fits comfortably in host memory.

    Returns:
      (dists, ids): each (Q, k), ascending by distance.
    """
    n = x.shape[0]
    nq = q.shape[0]
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    n_chunks = xp.shape[0] // chunk

    init_d = jnp.full((nq, k), jnp.inf, dtype=jnp.float32)
    init_i = jnp.full((nq, k), -1, dtype=jnp.int32)

    def body(carry, ci):
        best_d, best_i = carry
        xs = jax.lax.dynamic_slice_in_dim(xp, ci * chunk, chunk, axis=0)
        d = pairwise(q, xs, metric)  # (Q, chunk)
        ids = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        valid = ids < n
        d = jnp.where(valid[None, :], d, jnp.inf)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (nq, chunk))], axis=1)
        # top_k breaks ties lowest-index-first, exactly as a stable argsort
        # [:k] does, without sorting the whole (Q, k + chunk) block.
        neg_d, order = jax.lax.top_k(-cat_d, k)
        return (-neg_d, jnp.take_along_axis(cat_i, order, axis=1)), None

    (best_d, best_i), _ = jax.lax.scan(
        body, (init_d, init_i), jnp.arange(n_chunks, dtype=jnp.int32)
    )
    return best_d, best_i


def knn_graph(
    x: Array, k: int, metric: str = L2, chunk_q: int = 1024
) -> tuple[Array, Array]:
    """Exact k-NN of every point against the dataset (excluding self).

    Used by the calibration phase (Phase 1 of Algorithm 1) and by the theory
    oracles.  Runs in query chunks to bound memory.

    Returns:
      (dists, ids): each (N, k), ascending; ``dists`` are squared-L2 for the
      l2 metric (callers needing true distances take a sqrt).
    """
    n = x.shape[0]
    outs_d, outs_i = [], []
    topk = jax.jit(
        functools.partial(brute_force_topk, k=k + 1, metric=metric)
    )
    for start in range(0, n, chunk_q):
        qs = x[start : start + chunk_q]
        d, i = topk(qs, x)
        # Drop self-matches: the nearest hit at distance 0 with id == row.
        rows = jnp.arange(start, start + qs.shape[0])[:, None]
        is_self = i == rows
        # Push self to the end, then take first k.
        d = jnp.where(is_self, jnp.inf, d)
        order = jnp.argsort(d, axis=1)[:, :k]
        outs_d.append(jnp.take_along_axis(d, order, axis=1))
        outs_i.append(jnp.take_along_axis(i, order, axis=1))
    return jnp.concatenate(outs_d, axis=0), jnp.concatenate(outs_i, axis=0)


def recall_at_k(pred_ids: Array, true_ids: Array) -> Array:
    """Mean Recall@k between predicted and ground-truth id sets (both (Q, k))."""
    hits = (pred_ids[:, :, None] == true_ids[:, None, :]).any(axis=1)
    return hits.mean()


DistanceFn = Callable[[Array, Array], Array]
