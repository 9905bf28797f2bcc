"""Mesh and shard_map constructors in one place.

Every mesh of this repo is built with explicit ``Auto`` axis types, and
every ``shard_map`` goes through :func:`shard_map_compat`, so a change of
the JAX mesh API touches this module only.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh_compat(axis_shapes, axis_names):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def abstract_mesh_compat(axis_shapes, axis_names):
    """``jax.sharding.AbstractMesh`` with ``Auto`` axis types."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(axis_names))


def shard_map_compat(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map``; ``check`` maps onto ``check_vma`` (validate the
    replication of outputs)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
