"""The two jax entry points this repo calls with fixed options.

  - ``make_mesh``: ``jax.make_mesh`` with every axis ``Auto`` (the repo
    relies on GSPMD propagation, never on explicit-sharding mode).
  - ``shard_map``: ``jax.shard_map`` with ``check_vma=False`` (the repo
    never relies on varying-manual-axes checks).
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    axis_names = tuple(axis_names)
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names, devices=devices,
                         axis_types=auto)


def shard_map(f, *, mesh, in_specs, out_specs):
    """Unchecked shard_map (the repo never relies on rep/vma checks)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
