"""Mesh and shard_map construction, in one place.

Every mesh in the repo uses Auto axis types (sharding propagates through
``jit`` as in the GSPMD model, no explicit-sharding type checks), and
every ``shard_map`` turns the varying-manual-axes check off by default.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def shard_map(f, *, mesh, in_specs, out_specs, check=False):
    """``jax.shard_map`` with ``check`` as its ``check_vma`` flag."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )
