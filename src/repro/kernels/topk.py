"""Two-phase k-selection Pallas kernel (beam merge / bulk-scan top-k).

Phase 1 (this kernel): per (8-query, base-tile) block, select the local
top-k by k rounds of masked row-min — k is small (<= 128) so the rounds stay
in registers; distances live in VMEM once.  Each tile's k winners are written
into a 128-lane output row (inf/-1 beyond k), so every block is a whole
(8, 128)-aligned tile.

Phase 2 (jnp, negligible): merge the (Q, n_tiles·128) partials with one sort.
This mirrors how TPU top-k is implemented in practice (tile-local selection +
log-merge) while keeping the kernel simple enough to verify in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp

Array = jax.Array

TILE_N = 1024
TILE_Q = 8
_OUT = 128        # output lanes per tile: k <= 128 winners


def _topk_tile_kernel(d_ref, vals_ref, ids_ref, *, k: int, tile: int):
    d = d_ref[...].astype(jnp.float32)                          # (QB, tile)
    ids = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
           + pl.program_id(1) * tile)
    slot = jax.lax.broadcasted_iota(jnp.int32, (TILE_Q, _OUT), 1)
    never = jnp.iinfo(jnp.int32).max

    def round_(i, state):
        d_masked, vals, out_ids = state
        v = jnp.min(d_masked, axis=1, keepdims=True)            # (QB, 1)
        j = jnp.min(jnp.where(d_masked == v, ids, never), axis=1,
                    keepdims=True)                              # lowest id
        vals = jnp.where(slot == i, v, vals)
        out_ids = jnp.where(slot == i, j, out_ids)
        return jnp.where(ids == j, jnp.inf, d_masked), vals, out_ids

    vals0 = jnp.full((TILE_Q, _OUT), jnp.inf, jnp.float32)
    ids0 = jnp.full((TILE_Q, _OUT), -1, jnp.int32)
    _, vals, out_ids = jax.lax.fori_loop(0, k, round_, (d, vals0, ids0))
    vals_ref[...] = vals
    ids_ref[...] = out_ids


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk(d: Array, k: int, *, interpret: bool = False) -> tuple[Array, Array]:
    """(Q, N) distances -> ((Q, k) ascending, (Q, k) int32 ids)."""
    if not 0 < k <= _OUT:
        raise ValueError(f"k={k} outside (0, {_OUT}]")
    q, n = d.shape
    dp = jnp.pad(d, ((0, (-q) % TILE_Q), (0, (-n) % TILE_N)),
                 constant_values=jnp.inf)
    n_tiles = dp.shape[1] // TILE_N
    grid = (dp.shape[0] // TILE_Q, n_tiles)
    out = pl.BlockSpec((TILE_Q, _OUT), lambda i, j: (i, j))
    vals, ids = pl.pallas_call(
        functools.partial(_topk_tile_kernel, k=k, tile=TILE_N),
        grid=grid,
        in_specs=[pl.BlockSpec((TILE_Q, TILE_N), lambda i, j: (i, j))],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((dp.shape[0], n_tiles * _OUT), jnp.float32),
            jax.ShapeDtypeStruct((dp.shape[0], n_tiles * _OUT), jnp.int32),
        ],
        name="topk",
        interpret=interpret,
    )(dp)
    # Phase 2: merge partials.
    order = jnp.argsort(vals[:q], axis=1)[:, :k]
    return (
        jnp.take_along_axis(vals[:q], order, axis=1),
        jnp.take_along_axis(ids[:q], order, axis=1),
    )
