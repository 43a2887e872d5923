"""The mesh / shard_map calls of the sharded engine, in one place.

All mesh-touching code goes through this module, so the choices made
here (Auto axis types, replication checking off) hold everywhere.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.sharding.set_mesh(mesh)


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (our bodies mix
    replicated and sharded outputs, which the checker rejects)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
