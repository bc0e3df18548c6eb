"""Wrappers for the device-initiated dispatch All-to-All kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.collectives import feasible_chunks_per_rank
from repro.kernels import clamp_kernel_wire, interpret_mode
from repro.kernels.flatmesh import (WORLD_AXIS, flat_world_mesh,
                                    moe_from_world, moe_to_world,
                                    needs_flat_world)
from repro.kernels.fused_dispatch_a2a.kernel import fused_dispatch_a2a_pallas
from repro.parallel.sharding import ParallelContext


def fused_dispatch_a2a_kernel_available(mesh=None) -> bool:
    """Mosaic on TPU supports any mesh.  The CPU *interpreter* needs a
    known mesh: multi-axis meshes run the kernel's shard_map over a
    flattened single-named-axis view with row-confined logical ids (see
    :mod:`repro.kernels.flatmesh`), so only a missing mesh gates it."""
    if not interpret_mode():
        return True
    return mesh is not None


def fused_dispatch_a2a_shard(xt, axis, *, comm_aware=True, chunks_per_rank=1,
                             skew=0, wire="f32", ring_size=None):
    """Call inside shard_map.  xt: [n, B_loc, E_loc, C, D] stacked by
    destination rank; the PUT ring runs over mesh axis ``axis``.
    ``ring_size`` confines the ring to contiguous groups of that many
    ranks of a larger (flattened) axis — ``None`` means the whole axis.
    ``chunks_per_rank`` is clamped to the largest feasible divisor of the
    capacity axis; ``wire="fp8"`` is clamped to bf16 (one-time warning).

    Differentiable: the dispatch permutation is self-adjoint on this slot
    layout (swapping (source, destination) is an involution), so the VJP
    is the same exchange applied to the cotangent.
    """
    wire = clamp_kernel_wire(wire, "fused_dispatch_a2a")
    world = lax.axis_size(axis)
    n_dev = world if ring_size is None else int(ring_size)
    q = feasible_chunks_per_rank(xt.shape[3], 1, chunks_per_rank)

    def call(v):
        # recompute the ring position per trace: the VJP re-enters this
        # under a fresh trace, and closure-captured index tracers from the
        # forward trace would leak into it
        my_world = lax.axis_index(axis)
        my = lax.rem(my_world, n_dev)
        base = my_world - my
        with jax.named_scope("fused_dispatch_a2a"):
            return fused_dispatch_a2a_pallas(
                v, my, base, n_dev=n_dev, axis_name=axis,
                comm_aware=comm_aware, chunks_per_rank=q, skew=skew,
                wire=wire)

    @jax.custom_vjp
    def a2a(v):
        return call(v)

    def fwd(v):
        return call(v), None

    def bwd(_, g):
        return (call(g),)

    a2a.defvjp(fwd, bwd)
    return a2a(xt)


def _flat_specs(n: int):
    return tuple(P(WORLD_AXIS) for _ in range(n))


def fused_dispatch_a2a(ctx: ParallelContext, x, *, comm_aware=True,
                       chunks_per_rank=1, skew=0, wire="f32"):
    """Standalone global-array entry (tests/benchmarks).

    x: [B, n_ep, E, C, D] global, dim 1 indexing the destination EP
    shard, E sharded over tp — same layout as
    ``moe_dispatch_all_to_all``.  Returns the same global shape with
    source/destination swapped (the FFN+combine kernel's input layout).
    """
    b = x.shape[0]

    def local_fn(xl):
        xt = jnp.moveaxis(xl, 1, 0)  # [n_ep, B_loc, E_loc, C, D]
        out = fused_dispatch_a2a_shard(
            xt, ctx.tp_axis, comm_aware=comm_aware,
            chunks_per_rank=chunks_per_rank, skew=skew, wire=wire)
        return jnp.moveaxis(out, 0, 1)

    if needs_flat_world(ctx.mesh):
        rows, ring = ctx.dp, ctx.tp
        b_sharded = b % rows == 0
        xw = moe_to_world(x, rows, ring, b_sharded=b_sharded)

        def flat_fn(xl):
            xt = jnp.moveaxis(xl[0], 1, 0)
            out = fused_dispatch_a2a_shard(
                xt, WORLD_AXIS, comm_aware=comm_aware,
                chunks_per_rank=chunks_per_rank, skew=skew, wire=wire,
                ring_size=ring)
            return jnp.moveaxis(out, 0, 1)[None]

        yw = shard_map(flat_fn, mesh=flat_world_mesh(ctx.mesh, ctx.tp_axis),
                       in_specs=_flat_specs(1), out_specs=P(WORLD_AXIS),
                       check_vma=False)(xw)
        return moe_from_world(yw, rows, ring, b_sharded=b_sharded)

    dp = ctx.batch_axes if b % ctx.dp == 0 else None
    return shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(dp, None, ctx.tp_axis, None, None),),
        out_specs=P(dp, None, ctx.tp_axis, None, None),
        check_vma=False,
    )(x)
