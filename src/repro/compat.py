"""Two JAX defaults this repo overrides, in one place.

  * ``make_mesh`` — ``jax.make_mesh`` defaults to Explicit axis types; the
    sharding rules here are written for Auto (GSPMD propagation).
  * ``shard_map`` — ``jax.shard_map`` checks varying-manual-axes by
    default; our wrappers emit io_callbacks the checker cannot reason
    about, so it is off unless asked for.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
