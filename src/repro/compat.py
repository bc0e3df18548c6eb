"""Repo-wide defaults for two jax entry points.

Every ``shard_map`` in the repo runs with the per-shard value-and-mesh
check off, and every mesh is built with ``Auto`` axis types (jax's
default is ``Explicit``, which would turn each resharding into an
explicit ``reshard`` the model code does not spell out).
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the value-and-mesh check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
