"""Wrappers for the device-initiated fused embedding+All-to-All kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.kernels import interpret_mode
from repro.kernels.fused_embedding_a2a.kernel import fused_embedding_a2a_pallas
from repro.parallel.sharding import ParallelContext
from repro.compat import shard_map


def fused_embedding_a2a_kernel_available(mesh=None) -> bool:
    if not interpret_mode():
        return True
    return mesh is not None and len(mesh.axis_names) == 1


def fused_embedding_a2a(ctx: ParallelContext, indices, tables, *,
                        comm_aware=True):
    """Global entry.  indices: [B, T_global, L]; tables: [T_global, V, D]
    sharded over the (1D) mesh axis -> pooled [B, T_global, D], batch
    sharded."""
    axis = ctx.tp_axis
    B, T, L = indices.shape

    def local_fn(idx_l, tab_l):
        my = lax.axis_index(axis)
        n = lax.axis_size(axis)
        with jax.named_scope("fused_embedding_a2a"):
            return fused_embedding_a2a_pallas(
                tab_l, idx_l, my, n_dev=n, L=L, axis_name=axis,
                comm_aware=comm_aware)

    return shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(None, axis, None), P(axis, None, None)),
        out_specs=P(axis, None, None),
        check_vma=False,
    )(indices, tables)
