"""IVF-Flat baseline (Faiss-style inverted file, exact in-list distances).

The paper uses memory-mapped Faiss IVF-Flat as the in-memory throughput
roofline (RQ1/RQ2). Structure: a k-means coarse quantiser over ``nlist``
centroids; each base point assigned to its nearest centroid's inverted list;
a query probes the ``nprobe`` closest lists and scans them exactly.

JAX-native layout: inverted lists are padded to the max list length into a
dense (nlist, max_len) id matrix — scans are fixed-shape gathers + one fused
distance matmul, which is also precisely how an MXU wants to consume them.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distance as dist_mod

Array = jax.Array
INVALID = -1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IvfIndex:
    centroids: Array  # (nlist, D)
    lists: Array      # (nlist, max_len) int32, INVALID padded
    list_len: Array   # (nlist,)


def kmeans(
    x: Array, k: int, iters: int = 10, key: Array | None = None, chunk: int = 65536,
    init: Array | None = None,
) -> Array:
    """Batched Lloyd's algorithm (shared with the PQ codebook trainer), from
    ``init`` ((k, d) centroids) or k distinct rows drawn with ``key``."""
    n = x.shape[0]
    if init is None:
        key = jax.random.PRNGKey(0) if key is None else key
        init = x[jax.random.choice(key, n, shape=(k,), replace=False)]
    centroids = init

    @jax.jit
    def assign(c, xs):
        return jnp.argmin(dist_mod.squared_l2(xs, c), axis=1)

    for _ in range(iters):
        parts = [assign(centroids, x[s : s + chunk]) for s in range(0, n, chunk)]
        a = jnp.concatenate(parts)
        sums = jax.ops.segment_sum(x, a, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), a, num_segments=k)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # Re-seed empty clusters at the points farthest from their centroid.
        empty = counts == 0
        centroids = jnp.where(empty[:, None], centroids, new)
    return centroids


def kmeans_pp(x: Array, k: int, first: Array, key: Array) -> Array:
    """k-means++ seeding: (k, d) rows of ``x``, the first ``x[first]``,
    each next drawn with probability proportional to its squared distance
    from the nearest row drawn so far, so a dense region far from the
    others is all but sure to get one."""

    def d2_to(c):
        diff = x - c[None, :]
        return jnp.sum(diff * diff, axis=1)

    def draw(i, st):
        cent, dmin, key = st
        key, sub = jax.random.split(key)
        j = jax.random.categorical(sub, jnp.log(jnp.maximum(dmin, 1e-30)))
        return (cent.at[i].set(x[j]), jnp.minimum(dmin, d2_to(x[j])), key)

    cent = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    return jax.lax.fori_loop(1, k, draw, (cent, d2_to(x[first]), key))[0]


def build_ivf(x: Array, nlist: int = 256, iters: int = 10, seed: int = 0) -> IvfIndex:
    centroids = kmeans(x, nlist, iters=iters, key=jax.random.PRNGKey(seed))
    assign = jnp.argmin(dist_mod.squared_l2(x, centroids), axis=1)
    a = np.asarray(assign)
    n = x.shape[0]
    order = np.argsort(a, kind="stable")
    sorted_ids = np.arange(n, dtype=np.int32)[order]
    counts = np.bincount(a, minlength=nlist)
    max_len = int(counts.max())
    lists = np.full((nlist, max_len), INVALID, dtype=np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for c in range(nlist):
        lists[c, : counts[c]] = sorted_ids[starts[c] : starts[c] + counts[c]]
    return IvfIndex(
        centroids=centroids,
        lists=jnp.asarray(lists),
        list_len=jnp.asarray(counts.astype(np.int32)),
    )


@functools.partial(jax.jit, static_argnames=("nprobe", "k"))
def search_ivf(
    index: IvfIndex, x: Array, queries: Array, nprobe: int = 8, k: int = 10
) -> tuple[Array, Array, Array]:
    """Probe ``nprobe`` lists per query, exact scan, top-k.

    Returns (ids, d2, scanned): (Q, k), (Q, k), (Q,) #points scanned.
    """
    cd = dist_mod.squared_l2(queries, index.centroids)  # (Q, nlist)
    probes = jnp.argsort(cd, axis=1)[:, :nprobe]  # (Q, nprobe)

    def per_query(q, probe):
        ids = index.lists[probe].reshape(-1)  # (nprobe * max_len,)
        valid = ids != INVALID
        vecs = x[jnp.maximum(ids, 0)]
        diff = vecs - q[None, :]
        d2 = jnp.where(valid, jnp.sum(diff * diff, axis=-1), jnp.inf)
        order = jnp.argsort(d2)[:k]
        return ids[order], d2[order], valid.sum()

    return jax.vmap(per_query)(queries, probes)
