"""Wrappers for the device-initiated fused expert GEMM + All-to-All kernel.

Also home of the chained MoE entry: the dispatch-side A2A kernel
(:mod:`repro.kernels.fused_dispatch_a2a`) lands tokens in exactly the
by-source slot layout the FFN+combine kernel streams its input from, so
``fused_moe_kernel`` runs dispatch → expert FFN → combine with no XLA
round-trip between the two exchanges.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.kernels import clamp_kernel_wire, interpret_mode
from repro.kernels.flatmesh import (WORLD_AXIS, flat_world_mesh,
                                    moe_from_world, moe_to_world,
                                    needs_flat_world, weights_to_world)
from repro.kernels.fused_dispatch_a2a.ops import fused_dispatch_a2a_shard
from repro.kernels.fused_gemm_a2a.kernel import fused_gemm_a2a_pallas
from repro.kernels.fused_gemm_a2a.ref import expert_ffn_ref
from repro.parallel.sharding import ParallelContext


def fused_gemm_a2a_kernel_available(mesh=None) -> bool:
    """Mosaic on TPU supports any mesh.  The CPU *interpreter* needs a
    known mesh: multi-axis meshes run the kernel's shard_map over a
    flattened single-named-axis view with row-confined logical ids (see
    :mod:`repro.kernels.flatmesh`), so only a missing mesh gates it."""
    if not interpret_mode():
        return True
    return mesh is not None


def _ring_position(axis, ring_size):
    """(n_dev, my, base) for a PUT ring over ``axis`` — the whole axis by
    default, or contiguous ``ring_size`` groups of a flattened world."""
    world = lax.axis_size(axis)
    n_dev = world if ring_size is None else int(ring_size)
    my_world = lax.axis_index(axis)
    my = lax.rem(my_world, n_dev)
    return n_dev, my, my_world - my


def fused_gemm_a2a_shard(xt, w_up, w_gate, w_down, axis, *, act,
                         comm_aware=True, skew=0, tile_k=None, tile_f=None,
                         wire="f32", ring_size=None):
    """Call inside shard_map.  xt: [n, B_loc, E_loc, C, D] stacked by
    combine destination; the PUT ring runs over mesh axis ``axis``
    (``ring_size`` confines it to contiguous groups of a flattened world
    axis).  ``tile_k`` / ``tile_f`` bound the streamed weight panels of
    the up/gate and down GEMM contractions (None = whole depth).
    ``wire`` compresses the combine-PUT payload (kernel path supports
    f32/bf16; fp8 is clamped to bf16 with a one-time warning — the
    per-chunk-scale format is an XLA-path feature).

    Differentiable: ``pallas_call`` has no JVP rule, so the VJP
    differentiates the pure reference of the same math — the gated
    expert FFN followed by the (self-adjoint, kernel-backed) ring A2A
    — rematerialized from the saved operands.  The forward kernel is
    bit-identical to that reference at ``wire="f32"``, so the grads are
    the exact grads of what was computed."""
    wire = clamp_kernel_wire(wire, "fused_gemm_a2a")

    def kernel_call(v, wu, wg, wd):
        n_dev, my, base = _ring_position(axis, ring_size)
        with jax.named_scope("fused_gemm_a2a"):
            return fused_gemm_a2a_pallas(
                v, wu, wg, wd, my, base, n_dev=n_dev, axis_name=axis,
                act=act, comm_aware=comm_aware, skew=skew,
                tile_k=tile_k, tile_f=tile_f, wire=wire)

    def ref_call(v, wu, wg, wd):
        y = expert_ffn_ref(v, wu, wg, wd, act)
        return fused_dispatch_a2a_shard(y, axis, comm_aware=comm_aware,
                                        skew=skew, ring_size=ring_size)

    @jax.custom_vjp
    def gemm_a2a(v, wu, wg, wd):
        return kernel_call(v, wu, wg, wd)

    def fwd(v, wu, wg, wd):
        return kernel_call(v, wu, wg, wd), (v, wu, wg, wd)

    def bwd(res, g):
        _, vjp = jax.vjp(ref_call, *res)
        return vjp(g)

    gemm_a2a.defvjp(fwd, bwd)
    return gemm_a2a(xt, w_up, w_gate, w_down)


def fused_moe_chain_shard(xt, w_up, w_gate, w_down, axis, *, act,
                          comm_aware=True, chunks_per_rank=1, skew=0,
                          tile_k=None, tile_f=None, wire="f32",
                          ring_size=None):
    """Chained dispatch → FFN → combine inside shard_map.

    xt: [n, B_loc, E_loc, C, D] stacked by *dispatch destination*.  The
    dispatch kernel's rx buffer (tokens stacked by source) is consumed
    directly as the FFN+combine kernel's input — the two kernels share
    the by-source slot layout, so nothing round-trips through an XLA
    shuffle between the A2As.  Returns blocks stacked by combine
    destination (= dispatch source): each rank's tokens come home.
    """
    xr = fused_dispatch_a2a_shard(xt, axis, comm_aware=comm_aware,
                                  chunks_per_rank=chunks_per_rank, skew=skew,
                                  wire=wire, ring_size=ring_size)
    return fused_gemm_a2a_shard(xr, w_up, w_gate, w_down, axis, act=act,
                                comm_aware=comm_aware, skew=skew,
                                tile_k=tile_k, tile_f=tile_f, wire=wire,
                                ring_size=ring_size)


def _global_entry(ctx, x, w_up, w_gate, w_down, shard_fn):
    """Shared shard_map plumbing for the global kernel entries: direct
    multi-axis mapping where the backend discharges it, the flattened
    single-named-axis world otherwise (interpret mode on a 2-D mesh)."""
    b = x.shape[0]

    if needs_flat_world(ctx.mesh):
        rows, ring = ctx.dp, ctx.tp
        b_sharded = b % rows == 0
        xw = moe_to_world(x, rows, ring, b_sharded=b_sharded)
        ws = [weights_to_world(w, rows, ring)
              for w in (w_up, w_gate, w_down)]

        def flat_fn(xl, wul, wgl, wdl):
            xt = jnp.moveaxis(xl[0], 1, 0)  # [n_ep, B_loc, E_loc, C, D]
            out = shard_fn(xt, wul[0], wgl[0], wdl[0], WORLD_AXIS, ring)
            return jnp.moveaxis(out, 0, 1)[None]

        yw = shard_map(flat_fn, mesh=flat_world_mesh(ctx.mesh, ctx.tp_axis),
                       in_specs=tuple(P(WORLD_AXIS) for _ in range(4)),
                       out_specs=P(WORLD_AXIS), check_vma=False,
                       )(xw, *ws)
        return moe_from_world(yw, rows, ring, b_sharded=b_sharded)

    dp = ctx.batch_axes if b % ctx.dp == 0 else None

    def local_fn(xl, wu, wg, wd):
        xt = jnp.moveaxis(xl, 1, 0)  # [n_ep, B_loc, E_loc, C, D]
        out = shard_fn(xt, wu, wg, wd, ctx.tp_axis, None)
        return jnp.moveaxis(out, 0, 1)

    return shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(
            P(dp, None, ctx.tp_axis, None, None),
            P(ctx.tp_axis, None, None),
            P(ctx.tp_axis, None, None),
            P(ctx.tp_axis, None, None),
        ),
        out_specs=P(dp, None, ctx.tp_axis, None, None),
        check_vma=False,
    )(x, w_up, w_gate, w_down)


def fused_gemm_a2a(ctx: ParallelContext, x_dispatched, w_up, w_gate, w_down,
                   *, act, comm_aware=True, skew=0, tile_k=None, tile_f=None,
                   wire="f32"):
    """Standalone global-array entry (tests/benchmarks).

    x_dispatched: [B, n_ep, E, C, D] global, E sharded over tp — same
    layout as ``fused_expert_ffn_combine``.  Returns [B, n_ep, E, C, D]
    with the expert outputs returned to their source shards.
    """
    def shard_fn(xt, wu, wg, wd, axis, ring_size):
        return fused_gemm_a2a_shard(xt, wu, wg, wd, axis, act=act,
                                    comm_aware=comm_aware, skew=skew,
                                    tile_k=tile_k, tile_f=tile_f, wire=wire,
                                    ring_size=ring_size)

    return _global_entry(ctx, x_dispatched, w_up, w_gate, w_down, shard_fn)


def fused_moe_kernel(ctx: ParallelContext, x, w_up, w_gate, w_down, *, act,
                     comm_aware=True, chunks_per_rank=1, skew=0, tile_k=None,
                     tile_f=None, wire="f32"):
    """Full device-initiated MoE: dispatch A2A kernel chained with the
    FFN+combine kernel (global-array entry).

    x: [B, n_ep, E, C, D] global, dim 1 indexing the *destination* EP
    shard (``moe_dispatch_all_to_all``'s input layout), E sharded over
    tp.  Equivalent to ``fused_expert_ffn_combine(ctx,
    moe_dispatch_all_to_all(ctx, x), ...)`` with both exchanges device-
    initiated and no HBM round-trip between them.
    """
    def shard_fn(xt, wu, wg, wd, axis, ring_size):
        return fused_moe_chain_shard(xt, wu, wg, wd, axis, act=act,
                                     comm_aware=comm_aware,
                                     chunks_per_rank=chunks_per_rank,
                                     skew=skew, tile_k=tile_k, tile_f=tile_f,
                                     wire=wire, ring_size=ring_size)

    return _global_entry(ctx, x, w_up, w_gate, w_down, shard_fn)
