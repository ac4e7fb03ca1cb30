"""The jax sharding calls the serving and training paths make, in one
place: ``jax.shard_map`` and ``jax.make_mesh`` as jax 0.9 spells them."""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axis_names):
    """A mesh whose axes are all ``AxisType.Auto``."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))
