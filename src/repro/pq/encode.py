"""Vector <-> PQ code transforms."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.pq.codebook import PqCodebook, split_subspaces

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("chunk",))
def _encode_chunked(x: Array, centroids: Array, chunk: int = 16384) -> Array:
    m, k, dsub = centroids.shape
    n = x.shape[0]
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))

    def enc_chunk(xs):
        subs = xs.reshape(xs.shape[0], m, dsub).transpose(1, 0, 2)  # (M, c, dsub)

        def per_sub(sub, cb):
            d2 = (
                jnp.sum(sub * sub, axis=1, keepdims=True)
                - 2.0 * jnp.matmul(sub, cb.T,
                                   precision=jax.lax.Precision.HIGHEST)
                + jnp.sum(cb * cb, axis=1)[None, :]
            )
            return jnp.argmin(d2, axis=1).astype(jnp.uint8)

        return jax.vmap(per_sub)(subs, centroids).T  # (c, M)

    chunks = xp.reshape(-1, chunk, x.shape[1])
    codes = jax.lax.map(enc_chunk, chunks)
    return codes.reshape(-1, m)[:n]


def pq_encode(x: Array, book: PqCodebook, chunk: int = 16384) -> Array:
    """(N, D) -> (N, M) uint8 codes."""
    return _encode_chunked(x, book.centroids, chunk=chunk)


def pq_decode(codes: Array, book: PqCodebook) -> Array:
    """(N, M) codes -> (N, D) reconstructed vectors (codebook centroids)."""
    m = book.m
    gathered = jax.vmap(
        lambda j: book.centroids[j][codes[:, j].astype(jnp.int32)], out_axes=1
    )(jnp.arange(m))  # (N, M, dsub)
    return gathered.reshape(codes.shape[0], -1)
