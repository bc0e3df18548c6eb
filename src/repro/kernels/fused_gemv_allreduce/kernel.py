"""Device-initiated fused GEMV/GEMM + AllReduce (paper §III-B, Fig. 7).

This is the direct TPU analogue of the paper's flagship kernel, rebuilt
as a **tile-granular pipeline** (T3-style track-&-trigger at output-tile
granularity):

* The kernel runs a multi-step grid over (output tile, K panel) pairs.
  ``w`` stays in HBM; each step's ``[tile_k, tile_n]`` weight panel is
  streamed into a VMEM double buffer one step ahead of its use, so VMEM
  holds two panels — not the whole operand and not even a whole
  ``[K, tile_n]`` column strip.  ``N x K`` may exceed VMEM by an
  arbitrary factor in *both* dimensions: ``tile_n`` bounds the output
  width, ``tile_k`` bounds the contraction depth.  Partial products are
  accumulated in a f32 VMEM scratch across K panels; the final K panel
  may be *ragged* (``K % tile_k != 0``) — its copy descriptor and matmul
  are sized to the remainder.
* As soon as a tile's accumulation over its last K panel completes, it
  is PUT into the owning peer's reduction buffer with
  ``pltpu.make_async_remote_copy`` (the ROC_SHMEM non-blocking PUT
  analogue); HBM DMA-in, MXU compute, and remote DMA-out of different
  tiles are all in flight simultaneously.  DMA completion semaphores
  replace the paper's WG_Done bitmask / sliceRdy polling flags.
* Zero-copy: each remote write lands directly in the consumer's
  per-source reduction slot (phase 1) or directly in the consumer's
  *output ref* (phase 2) — no staging buffer or copy kernel on the
  receiver.
* Communication-aware schedule: remote tiles are computed farthest-peer-
  first; the locally-reduced tiles are computed *last* (paper Fig. 7b),
  so local compute hides remote wire time.  The per-rank chunk is further
  split into ``tiles_per_rank`` sub-tiles — the kernel-level face of the
  ``chunks_per_rank`` granularity knob (paper Fig. 13); ``tile_n`` /
  ``tile_k`` are picked by :func:`repro.core.autotune.choose_tile_n` /
  :func:`repro.core.autotune.choose_tile_k` when not pinned.
* Two-phase direct AllReduce (the paper's choice for fully-connected
  scale-up nodes): phase 1 reduce-scatter via the PUTs above; phase 2
  each rank broadcasts its reduced chunk straight into every peer's
  output.

Runs inside shard_map; ``device_id`` is the linearized mesh id, rings run
over the innermost mesh axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotune import choose_tile_k, choose_tile_n, feasible_tile
from repro.kernels import resolve_interpret
from repro.kernels.tile_pipeline import (drain, entry_barrier,
                                         remote_tile_put, step_schedule,
                                         stream_tile_copy)


def _fused_kernel(ids_ref, x_ref, w_hbm, o_ref,
                  w_slots, w_sems, kacc_ref, tx_ref, rx_ref, acc_ref,
                  send_sem, recv_sem, bsend_sem, brecv_sem, *,
                  n_dev, tiles_per_rank, tile_n, tile_k, k_panels, k_rem,
                  barrier, axis_name, id_style):
    my = ids_ref[0]
    i = pl.program_id(0)
    num_tiles = n_dev * tiles_per_rank
    num_steps = num_tiles * k_panels
    bn = tiles_per_rank * tile_n
    ragged = k_rem != tile_k
    # schedule rides in the prefetch operand: ids = [my | offs | subs],
    # indexed by the *tile* a step belongs to
    step_off = lambda t: ids_ref[1 + t]
    step_sub = lambda t: ids_ref[1 + num_tiles + t]

    def wdma(step, last_panel: bool):
        """HBM→VMEM copy descriptor for one [tile_k, tile_n] weight panel.

        ``last_panel`` selects the statically-sized ragged descriptor for
        the final K panel; wait descriptors must rebuild the same variant
        (DMA semaphores account by bytes)."""
        t = lax.div(step, k_panels)
        p = lax.rem(step, k_panels)
        slot = lax.rem(step, 2)
        dest = lax.rem(my + step_off(t), n_dev)
        col = dest * bn + step_sub(t) * tile_n
        if last_panel and ragged:
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds((k_panels - 1) * tile_k, k_rem),
                         pl.ds(col, tile_n)],
                w_slots.at[slot, pl.ds(0, k_rem)],
                w_sems.at[slot],
            )
        return stream_tile_copy(w_hbm, w_slots, w_sems, slot,
                                col, tile_n, row_start=p * tile_k,
                                rows=tile_k)

    def start(step):
        """Start ``step``'s panel copy (ragged-aware when K is ragged)."""
        if not ragged:
            wdma(step, False).start()
            return
        p = lax.rem(step, k_panels)

        @pl.when(p == k_panels - 1)
        def _():
            wdma(step, True).start()

        @pl.when(p != k_panels - 1)
        def _():
            wdma(step, False).start()

    @pl.when(i == 0)
    def _():
        if barrier:
            # no PUT may land before every peer runs this kernel
            entry_barrier(my, n_dev, axis_name, id_style)
        # step 0 is panel 0 of the first tile — ragged only if k_panels==1,
        # which implies tile_k == K and k_rem == tile_k (never ragged)
        wdma(jnp.int32(0), False).start()

    @pl.when(i + 1 < num_steps)
    def _():
        start(i + 1)

    # ---- K-panel pipeline: wait panel in, matmul, accumulate ----------
    p = lax.rem(i, k_panels)
    slot = lax.rem(i, 2)

    def accumulate(partial):
        @pl.when(p == 0)
        def _():
            kacc_ref[...] = partial

        @pl.when(p != 0)
        def _():
            kacc_ref[...] += partial

    if not ragged:
        wdma(i, False).wait()
        accumulate(jnp.dot(x_ref[:, pl.ds(p * tile_k, tile_k)],
                           w_slots[slot],
                           preferred_element_type=jnp.float32))
    else:
        @pl.when(p == k_panels - 1)
        def _():
            wdma(i, True).wait()
            accumulate(jnp.dot(
                x_ref[:, pl.ds((k_panels - 1) * tile_k, k_rem)],
                w_slots[slot, pl.ds(0, k_rem)],
                preferred_element_type=jnp.float32))

        @pl.when(p != k_panels - 1)
        def _():
            wdma(i, False).wait()
            accumulate(jnp.dot(x_ref[:, pl.ds(p * tile_k, tile_k)],
                               w_slots[slot],
                               preferred_element_type=jnp.float32))

    # ---- last K panel of a tile: trigger PUT / place own tile ---------
    t = lax.div(i, k_panels)
    off = step_off(t)
    sub = step_sub(t)
    dest = lax.rem(my + off, n_dev)

    @pl.when((p == k_panels - 1) & (off != 0))
    def _():
        # remote tile: stage in wire dtype, PUT into the peer's per-source
        # slot the moment the accumulation finishes (phase-1 RS)
        tx_ref[t] = kacc_ref[...].astype(tx_ref.dtype)
        remote_tile_put(
            tx_ref.at[t],
            rx_ref.at[my, :, pl.ds(sub * tile_n, tile_n)],
            send_sem, recv_sem, dest, axis_name, id_style,
        ).start()

    @pl.when((p == k_panels - 1) & (off == 0))
    def _():
        # own tiles last: local compute hides the PUTs' wire time (Fig. 7b)
        acc_ref[:, pl.ds(sub * tile_n, tile_n)] = kacc_ref[...]

    # ---- final step: reduce arrivals, write own chunk, broadcast -------
    @pl.when(i == num_steps - 1)
    def _():
        n_remote = (n_dev - 1) * tiles_per_rank
        # sliceRdy analogue: the DMA recv semaphore counts tile arrivals
        # (uniform tile size, so any descriptor of that size accounts one)
        drain(lambda: remote_tile_put(
            tx_ref.at[0], rx_ref.at[0, :, pl.ds(0, tile_n)],
            send_sem, recv_sem, my, axis_name, id_style),
            n_remote, recv=True)
        for s in range(n_dev):
            @pl.when(s != my)
            def _(s=s):
                acc_ref[...] += rx_ref[s].astype(jnp.float32)
        o_ref[:, pl.ds(my * bn, bn)] = acc_ref[...].astype(o_ref.dtype)

        # phase 2: broadcast reduced chunk directly into peers' output
        def bput(dst):
            return remote_tile_put(
                o_ref.at[:, pl.ds(my * bn, bn)],
                o_ref.at[:, pl.ds(my * bn, bn)],   # same slice on peer
                bsend_sem, brecv_sem, dst, axis_name, id_style)

        for off2 in range(1, n_dev):
            bput(lax.rem(my + off2, n_dev)).start()
        drain(lambda: remote_tile_put(
            tx_ref.at[0], rx_ref.at[0, :, pl.ds(0, tile_n)],
            send_sem, recv_sem, my, axis_name, id_style),
            n_remote, recv=False)              # phase-1 sends drained
        drain(lambda: bput(my), n_dev - 1, recv=False)
        drain(lambda: bput(my), n_dev - 1, recv=True)  # peers' chunks in


@functools.partial(jax.jit,
                   static_argnames=("n_dev", "comm_aware", "collective_id",
                                    "interpret", "axis_name",
                                    "id_style", "tile_n", "tile_k",
                                    "vmem_budget_bytes", "wire"))
def fused_matmul_allreduce_pallas(x, w, my_tp, *, n_dev, axis_name,
                                  comm_aware=True, collective_id=7,
                                  interpret=None, id_style=None,
                                  tile_n=None, tile_k=None,
                                  vmem_budget_bytes=8 << 20, wire="f32"):
    """Per-shard tile-pipelined fused GEMV/GEMM+AllReduce.

    x: [B, K_loc]; w: [K_loc, N]; my_tp: int32 scalar (position on the
    ring axis ``axis_name``).  Returns [B, N] fully reduced.

    ``tile_n`` is the output-tile width of the pipeline (the granularity
    knob): ``None`` lets the autotuner size it against the VMEM budget;
    any requested value is clamped to the largest divisor of the per-rank
    chunk ``N // n_dev`` so tiles stay uniform.  ``tile_k`` is the
    contraction-panel depth: ``None`` sizes it so two ``[tile_k, tile_n]``
    panels plus the fixed buffers fit ``vmem_budget_bytes``; it need not
    divide ``K`` — the final panel is ragged.

    ``wire`` is the phase-1 PUT payload dtype: ``"bf16"`` stages the
    finished tiles (already f32-accumulated in the K-panel scratch) in
    bf16 tx/rx buffers so the remote DMA moves half the bytes; the
    receive-side reduction still runs in f32.  The kernel path supports
    ``{"f32", "bf16"}`` — the fp8 per-chunk-scale format is an XLA-path
    feature (callers clamp).  The phase-2 broadcast ships final outputs
    and stays at the output dtype.

    ``interpret=None`` runs the Pallas interpreter exactly when the
    default backend is not a TPU (:func:`repro.kernels.resolve_interpret`).
    """
    interpret = resolve_interpret(interpret)
    if id_style is None:
        id_style = "logical" if interpret else "mesh"
    if wire not in ("f32", "bf16"):
        raise ValueError(f"kernel wire dtype must be 'f32' or 'bf16', "
                         f"got {wire!r}")
    b, k = x.shape
    n = w.shape[1]
    assert n % n_dev == 0, (n, n_dev)
    bn = n // n_dev
    # "f32" = uncompressed: the PUT payload travels at the compute dtype
    wire_dt = (jnp.bfloat16 if wire == "bf16"
               and x.dtype.itemsize > 2 else x.dtype)
    if tile_n is None:
        tile_n = choose_tile_n(b, k, n, n_dev=n_dev,
                               dtype_bytes=x.dtype.itemsize,
                               vmem_budget_bytes=vmem_budget_bytes)
    tile_n = feasible_tile(bn, tile_n)
    if tile_k is None:
        tile_k = choose_tile_k(b, k, n, tile_n, n_dev=n_dev,
                               dtype_bytes=x.dtype.itemsize,
                               vmem_budget_bytes=vmem_budget_bytes)
    tile_k = max(1, min(int(tile_k), k))
    k_panels = -(-k // tile_k)
    k_rem = k - (k_panels - 1) * tile_k
    tiles_per_rank = bn // tile_n
    num_tiles = n_dev * tiles_per_rank

    # the schedule itself rides in the prefetched ids (step_schedule below);
    # the kernel body is schedule-agnostic
    kernel = functools.partial(_fused_kernel, n_dev=n_dev,
                               tiles_per_rank=tiles_per_rank, tile_n=tile_n,
                               tile_k=tile_k, k_panels=k_panels, k_rem=k_rem,
                               barrier=not interpret,
                               axis_name=axis_name, id_style=id_style)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles * k_panels,),
        in_specs=[
            pl.BlockSpec((b, k), lambda i, s: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # w stays in HBM
        ],
        out_specs=pl.BlockSpec((b, n), lambda i, s: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile_k, tile_n), w.dtype),  # streamed w panels
            pltpu.SemaphoreType.DMA((2,)),            # panel double buffer
            pltpu.VMEM((b, tile_n), jnp.float32),     # K-panel accumulator
            # tx staging: remote tiles only — the schedule puts the own
            # (non-staged) tiles last, so remote tiles are t < n_remote.
            # Staged (and received) in the wire dtype: the PUT moves
            # wire-width bytes, the reduction upcasts to f32
            pltpu.VMEM((max((n_dev - 1) * tiles_per_rank, 1), b, tile_n),
                       wire_dt),
            pltpu.VMEM((n_dev, b, bn), wire_dt),      # rx slots (per source)
            pltpu.VMEM((b, bn), jnp.float32),         # reduction accumulator
            pltpu.SemaphoreType.DMA,                  # send
            pltpu.SemaphoreType.DMA,                  # recv
            pltpu.SemaphoreType.DMA,                  # bcast send
            pltpu.SemaphoreType.DMA,                  # bcast recv
        ],
    )
    step_off, step_sub = step_schedule(n_dev, tiles_per_rank, comm_aware)
    ids = jnp.concatenate([
        my_tp.astype(jnp.int32)[None],
        jnp.asarray(step_off + step_sub, jnp.int32),
    ])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n), x.dtype),
        name="fused_gemv_allreduce",
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=interpret,
    )(ids, x, w)
