"""One spelling of the mesh and ``shard_map`` constructors.

Every mesh and ``shard_map`` in the repo (engines, benchmarks, subprocess
tests) is built here, so replication checking and axis types are chosen in
one place: ``jax.shard_map`` with ``check_vma=False`` and meshes whose axes
are all ``AxisType.Auto``.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape))
