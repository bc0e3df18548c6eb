"""Wrappers for the device-initiated fused GEMV/GEMM+AllReduce kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.kernels import interpret_mode
from repro.kernels.fused_gemv_allreduce.kernel import fused_matmul_allreduce_pallas
from repro.parallel.sharding import ParallelContext
from repro.compat import shard_map


def fused_matmul_allreduce_kernel_available(mesh=None) -> bool:
    """Mosaic on TPU supports any mesh; the CPU *interpreter* can only
    discharge remote DMAs under a single-named-axis mesh (validation runs
    use a 1D mesh; the production path on CPU falls back to the XLA
    decomposed fusion)."""
    if not interpret_mode():
        return True
    return mesh is not None and len(mesh.axis_names) == 1


def fused_matmul_allreduce_shard(xl, wl, axis, *, comm_aware=True,
                                 tile_n=None, tile_k=None,
                                 vmem_budget_bytes=8 << 20, wire="f32"):
    """Call inside shard_map.  xl: [rows_loc, K_loc]; wl: [K_loc, N].
    The PUT ring runs over mesh axis ``axis``.  ``tile_n`` pins the
    pipeline's output-tile width and ``tile_k`` its contraction-panel
    depth (None = autotuned from the VMEM budget; ``tile_k`` may leave a
    ragged final K panel).  ``wire`` compresses the phase-1 PUT payload
    (kernel path supports f32/bf16; fp8 is clamped to bf16 — the
    per-chunk-scale format is an XLA-path feature)."""
    n_dev = lax.axis_size(axis)
    my = lax.axis_index(axis)
    wire = "bf16" if wire == "fp8" else wire
    with jax.named_scope("fused_gemv_allreduce"):
        return fused_matmul_allreduce_pallas(
            xl, wl, my, n_dev=n_dev, axis_name=axis, comm_aware=comm_aware,
            tile_n=tile_n, tile_k=tile_k,
            vmem_budget_bytes=vmem_budget_bytes, wire=wire)


def fused_matmul_allreduce(ctx: ParallelContext, x, w, *, comm_aware=True,
                           tile_n=None, tile_k=None,
                           vmem_budget_bytes=8 << 20, wire="f32"):
    """Standalone global-array entry (tests/benchmarks).

    x: [..., K] K sharded over tp; w: [K, N] row-sharded -> [..., N]."""
    lead = x.shape[:-1]
    xf = x.reshape((-1, x.shape[-1]))
    rows = xf.shape[0]
    dp = ctx.batch_axes if rows % ctx.dp == 0 else None

    def local_fn(xl, wl):
        return fused_matmul_allreduce_shard(
            xl, wl, ctx.tp_axis, comm_aware=comm_aware, tile_n=tile_n,
            tile_k=tile_k, vmem_budget_bytes=vmem_budget_bytes, wire=wire)

    yf = shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(dp, ctx.tp_axis), P(ctx.tp_axis, None)),
        out_specs=P(dp, None),
        check_vma=False,
    )(xf, w)
    return yf.reshape(lead + (w.shape[1],))
