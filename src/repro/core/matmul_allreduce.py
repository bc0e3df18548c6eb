"""Fused GEMV/GEMM + AllReduce (paper §III-B, Fig. 7).

Megatron row-parallel layer: ``x`` carries the contraction dim sharded
over TP, ``w`` is row-sharded; every rank produces a *partial* full-size
output that must be summed across TP ranks.

  bulk   : y = psum(x_local @ w_local)           (RCCL-baseline analogue)
  fused  : the output is chunked; a matmul-interleaved ring reduce-scatter
           accumulates each chunk while other chunks are still being
           computed, followed by an all-gather of reduced chunks — the
           two phases of the paper's direct AllReduce, with phase one
           fused into the GEMV/GEMM.  Comm-aware scheduling: a rank's own
           output chunk is computed last (Fig. 7b).
  kernel : Pallas device-initiated kernel (remote DMA writes straight
           into the peer's reduction buffer — zero-copy scale-up path).

Chunking dimension is chosen automatically: rows (flattened leading dims)
when they divide the ring, else output columns — decode-shape GEMV
(B·1 rows) always chunks over columns, matching the paper's output-tile
granularity for matrix-vector work.

Granularity (paper Fig. 13): ``chunks_per_rank`` splits each ring step's
payload into sub-chunks, every sub-chunk shipped the moment its partial
matmul finishes.  ``None`` defers to ``FusionConfig.granularity`` (an
int, or ``"auto"`` for the shape-keyed alpha-beta autotuner); infeasible
values are clamped to the largest factor dividing the chunked dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.autotune import resolve_overlap, tune_matmul_allreduce
from repro.core.collectives import (all_gather_wire,
                                    ring_reduce_scatter_compute)
from repro.core.degrade import degrade_mode
from repro.parallel.sharding import ParallelContext
from repro.compat import shard_map


def _bulk(xl, wl, axis):
    return lax.psum(xl @ wl, axis)


def _fused_rows(xl, wl, axis, schedule, q, skew, wire):
    n = lax.axis_size(axis)
    chunk = xl.shape[0] // (n * q)

    def partial(f):
        xi = lax.dynamic_slice_in_dim(xl, f * chunk, chunk, axis=0)
        return xi @ wl

    mine = ring_reduce_scatter_compute(partial, axis, schedule=schedule,
                                       chunks_per_rank=q, sub_axis=0,
                                       skew=skew, wire=wire)
    return all_gather_wire(mine, axis, n, axis=0, wire=wire)


def _fused_cols(xl, wl, axis, schedule, q, skew, wire):
    n = lax.axis_size(axis)
    chunk = wl.shape[1] // (n * q)

    def partial(f):
        wi = lax.dynamic_slice_in_dim(wl, f * chunk, chunk, axis=1)
        return xl @ wi

    mine = ring_reduce_scatter_compute(partial, axis, schedule=schedule,
                                       chunks_per_rank=q, sub_axis=1,
                                       skew=skew, wire=wire)
    return all_gather_wire(mine, axis, n, axis=1, wire=wire)


def matmul_allreduce(
    ctx: ParallelContext,
    x,
    w,
    *,
    mode: str | None = None,
    schedule: str | None = None,
    chunks_per_rank: int | str | None = None,
    skew: int | None = None,
    wire: str | None = None,
):
    """y = AllReduce_tp(x @ w) for row-parallel ``w``.

    x: [..., K] global, K sharded over tp.   w: [K, N] global, row-sharded.
    Returns [..., N] replicated over tp (sharded over dp on leading dims).

    ``chunks_per_rank``: sub-chunk granularity of the fused ring (int or
    "auto"); ``None`` uses ``ctx.fusion.granularity``.  ``skew``: measured
    straggler rotation (Fig. 14); ``None`` uses ``ctx.fusion.skew``.
    ``wire``: ring-payload wire dtype ("f32"/"bf16"/"fp8"/"auto" — the RS
    carry and the phase-2 AG both compress; local accumulation stays f32);
    ``None`` uses ``ctx.fusion.wire``.
    """
    mode = mode or ctx.fusion.resolve("matmul_rs")
    mode = degrade_mode("matmul_allreduce", x.shape[:-1] + w.shape, mode)
    schedule = schedule or ctx.fusion.schedule
    skew = ctx.fusion.skew if skew is None else int(skew)
    axis = ctx.tp_axis
    n = ctx.tp

    lead = x.shape[:-1]
    k, nout = w.shape
    xf = x.reshape((-1, x.shape[-1]))
    rows = xf.shape[0]
    # batch=1 decode shapes cannot shard rows over dp -> replicate there
    dp = ctx.batch_axes if rows % ctx.dp == 0 else None
    rows_local = rows // (ctx.dp if dp is not None else 1)
    use_rows = rows_local % n == 0 and rows_local >= n and mode != "bulk"

    if mode == "kernel":
        # Device-initiated Pallas path (scale-up zero-copy); the kernel is
        # registered lazily to avoid import cycles.
        from repro.kernels.fused_gemv_allreduce.ops import fused_matmul_allreduce_kernel_available

        if not fused_matmul_allreduce_kernel_available(ctx.mesh):
            mode = "fused"

    chunk_dim = rows_local if use_rows else nout
    if mode in ("fused", "kernel"):
        dec = resolve_overlap(
            chunks_per_rank, ctx.fusion.granularity, wire, ctx.fusion.wire,
            lambda fq, w: tune_matmul_allreduce(
                rows_local, k // n, nout, dtype_bytes=x.dtype.itemsize,
                n_dev=n, chunk_dim=chunk_dim, hw=ctx.hw, axis=axis,
                skew=skew, wire=w, fixed_q=fq),
            dim=chunk_dim, ring=n)
        q, wire_dt = dec.q, dec.wire
        if mode == "kernel":
            q = 1  # the kernel's granularity is its own tile pipeline
    else:
        q, wire_dt = 1, "f32"  # bulk: one collective at compute dtype

    def local_fn(xl, wl):
        if mode == "bulk":
            return _bulk(xl, wl, axis)
        if mode == "kernel":
            from repro.kernels.fused_gemv_allreduce.ops import fused_matmul_allreduce_shard

            return fused_matmul_allreduce_shard(xl, wl, axis, wire=wire_dt)
        if use_rows:
            return _fused_rows(xl, wl, axis, schedule, q, skew, wire_dt)
        return _fused_cols(xl, wl, axis, schedule, q, skew, wire_dt)

    yf = shard_map(
        local_fn,
        mesh=ctx.mesh,
        in_specs=(P(dp, ctx.tp_axis), P(ctx.tp_axis, None)),
        out_specs=P(dp, None),
        check_vma=False,
    )(xf, w)
    return yf.reshape(lead + (nout,))
