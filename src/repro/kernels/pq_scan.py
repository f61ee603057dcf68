"""PQ ADC scan Pallas kernel — the fast-tier distance hot-spot.

CPU DiskANN does M byte-gathers per point (AVX2 shuffle loops). Gathers are
VPU-serial on TPU, so the kernel re-expresses the scan as MXU matmuls, one
per sub-quantizer m:

    onehot_m[n, c] = (code[n, m] == c)                 (TN, K), in-register
    dist[q, n]    += lut[q, m, :] . onehot_m[n, :]      (QB, K) x (TN, K)^T

With K=256 each one-hot tile is (128, 256) f32 = 128 KB of VMEM and each
matmul is MXU-shaped.  A program scores QB=8 queries (one f32 sublane tile)
against one base tile, so every block obeys the TPU's (8, 128) tiling rule;
the LUT block (8 queries x M*K f32) stays resident across the base sweep.

Grid: (query tiles, base tiles). Output (Q, N) approximate distances.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp

Array = jax.Array

TILE_N = 128
TILE_Q = 8


def _pq_scan_kernel(lut_ref, codes_ref, o_ref, *, m: int, k: int):
    codes = codes_ref[...].astype(jnp.int32)                   # (TN, M)
    cols = jax.lax.broadcasted_iota(jnp.int32, (TILE_N, k), 1)
    acc = jnp.zeros((TILE_Q, TILE_N), jnp.float32)
    for j in range(m):
        onehot = (codes[:, j:j + 1] == cols).astype(jnp.float32)   # (TN, K)
        acc += jax.lax.dot_general(
            lut_ref[:, j * k:(j + 1) * k], onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                 # (QB, TN)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_scan(luts: Array, codes: Array, *, interpret: bool = False) -> Array:
    """(Q, M, K) LUTs x (N, M) uint8 codes -> (Q, N) ADC distances."""
    q, m, k = luts.shape
    n = codes.shape[0]
    cp = jnp.pad(codes, ((0, (-n) % TILE_N), (0, 0)))
    lp = jnp.pad(luts.reshape(q, m * k), ((0, (-q) % TILE_Q), (0, 0)))
    grid = (lp.shape[0] // TILE_Q, cp.shape[0] // TILE_N)
    out = pl.pallas_call(
        functools.partial(_pq_scan_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_Q, m * k), lambda qi, nj: (qi, 0)),
            pl.BlockSpec((TILE_N, m), lambda qi, nj: (nj, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_Q, TILE_N), lambda qi, nj: (qi, nj)),
        out_shape=jax.ShapeDtypeStruct((lp.shape[0], cp.shape[0]),
                                       jnp.float32),
        name="pq_scan",
        interpret=interpret,
    )(lp, cp)
    return out[:q, :n]
