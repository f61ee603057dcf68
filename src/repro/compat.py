"""The two jax API spellings the mesh code shares (jax 0.9).

``make_mesh`` pins Auto axis types (the sharded search relies on GSPMD
propagation, not explicit-sharding types); ``shard_map`` turns off the
varying-manual-axes check, which the shard-local walk programs do not
satisfy.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, names, devices=None):
    """``jax.make_mesh`` with Auto axis types (over ``devices`` if given)."""
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
