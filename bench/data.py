"""On-device data from the seed: a base set and a disjoint query pool.

The ``sift-proxy`` generator draws points on ``clusters`` random
``intrinsic_dim``-dimensional affine subspaces of R^d plus isotropic noise,
so the local intrinsic dimensionality is known (about 14 for SIFT).  One
draw makes base and queries together, so the queries lie on the same
manifolds; a permutation then splits them.  Everything runs in one jitted
call on the device.  The arithmetic follows the program's own proxy
generator (with the einsum at ``Precision.HIGHEST``); it is kept here so
that the benchmark's data does not depend on the code it measures.
"""
from __future__ import annotations

import functools

import numpy as np


def sub_seed(seed: int, purpose: str) -> int:
    """A 31-bit seed for one purpose, from a seed of any size."""
    words = [ord(c) for c in purpose]
    ss = np.random.SeedSequence([int(seed) & 0xFFFFFFFF,
                                 (int(seed) >> 32) & 0xFFFFFFFF, *words])
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _generator(n_total: int, d: int, intrinsic_dim: int, clusters: int,
               noise: float, center_scale: float):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def make(key):
        kg, kp = jax.random.split(key)
        keys = jax.random.split(kg, 4)
        per = n_total // clusters + 1
        basis = jax.random.normal(keys[0], (clusters, d, intrinsic_dim))
        basis = basis / jnp.linalg.norm(basis, axis=1, keepdims=True)
        centers = jax.random.normal(keys[1], (clusters, d)) * center_scale
        coeff = jax.random.normal(keys[2], (clusters, per, intrinsic_dim))
        pts = jnp.einsum("cdi,cpi->cpd", basis, coeff, precision=hi)
        pts = (pts + centers[:, None, :]).reshape(-1, d)[:n_total]
        pts = pts + noise * jax.random.normal(keys[3], pts.shape)
        perm = jax.random.permutation(kp, n_total)
        return pts.astype(jnp.float32)[perm]

    return make


def make_data(cfg: dict, seed: int):
    """(base (n, d), queries (pool, d)) float32 device arrays for ``seed``."""
    import jax

    gen = cfg["data"]
    if gen["generator"] != "sift-proxy":
        raise ValueError(f"unknown data generator {gen['generator']!r}")
    n, pool = int(cfg["n"]), int(cfg["query_pool"])
    make = _generator(n + pool, int(cfg["d"]), int(gen["intrinsic_dim"]),
                      int(gen["clusters"]), float(gen["noise"]),
                      float(gen["center_scale"]))
    pts = make(jax.random.PRNGKey(sub_seed(seed, "data")))
    base, queries = pts[:n], pts[n:]
    return base.block_until_ready(), queries.block_until_ready()
