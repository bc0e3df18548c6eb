"""Device-initiated dispatch-side All-to-All (paper §III + CommFuse).

The dispatch A2A ships each destination rank's capacity chunk of routed
tokens; the XLA combinator path (``moe_dispatch_all_to_all``) decomposes
it into per-peer collective-permutes, but the payload still round-trips
through HBM before the expert FFN can start.  This kernel is the
device-initiated sibling: per-destination token blocks are streamed from
HBM through a VMEM double buffer and every ``chunks_per_rank`` sub-chunk
of the capacity axis is PUT to its peer the moment it is resolved —
CommFuse's sub-collective decomposition of the routing tail, with T3's
producer-tile trigger replaced by DMA completion semaphores.

* Multi-step grid over ``(destination, sub-chunk)`` pairs in comm-aware
  order (farthest peer first, locally-consumed block last; ``skew``
  rotates the remote order by the measured straggler bucket).
* PUTs land directly in the peer's output slot for this source rank —
  the ``[n_dev, B, E_loc, C, D]`` by-source slot layout the FFN+combine
  kernel (:mod:`repro.kernels.fused_gemm_a2a`) streams its input from,
  so the chained form never re-materializes the exchange through XLA.
* ``wire="bf16"`` stages each sub-chunk in a bf16 tx buffer and receives
  into a bf16 rx staging ref upcast at the end (half the remote bytes at
  the cost of the receive-side zero-copy), like the other two kernels.
* Ring confinement on a flattened multi-axis mesh is by logical-id
  arithmetic: peer id = ``ring_base + dest`` (see
  :mod:`repro.kernels.flatmesh`).

Runs inside shard_map over the expert-parallel axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.tile_pipeline import (drain, entry_barrier, remote_tile_put,
                                         step_schedule, stream_block_copy)


def _dispatch_a2a_kernel(ids_ref, x_hbm, o_ref, x_slots, x_sems, tx_ref,
                         rx_ref, send_sem, recv_sem, *, n_dev, q, sub,
                         axis_name, id_style, use_rx, barrier):
    my = ids_ref[0]
    base = ids_ref[1]
    i = pl.program_id(0)
    n_steps = n_dev * q
    step_off = lambda s: ids_ref[2 + s]
    blk = i // q                       # dest-block counter (q subs per dest)
    s_i = lax.rem(i, q)

    def xdma(block, slot):
        dest = lax.rem(my + step_off(block * q), n_dev)
        return stream_block_copy(x_hbm, x_slots, x_sems, slot, dest)

    @pl.when(i == 0)
    def _():
        if barrier:
            # no PUT may land before every peer runs this kernel
            entry_barrier(my, n_dev, axis_name, id_style, base)
        xdma(0, 0).start()

    @pl.when((s_i == 0) & (i + q < n_steps))
    def _():
        # prefetch the next destination's block while this one drains
        xdma(blk + 1, lax.rem(blk + 1, 2)).start()

    @pl.when(s_i == 0)
    def _():
        xdma(blk, lax.rem(blk, 2)).wait()

    off = step_off(i)
    dest = lax.rem(my + off, n_dev)
    c0 = s_i * sub if q > 1 else 0
    chunk = x_slots[lax.rem(blk, 2), :, :, pl.ds(c0, sub)]  # [B, E, sub, D]

    # receive target: the output ref itself (zero-copy) at the exact wire,
    # a wire-dtype rx staging ref otherwise (upcast at the end)
    recv_ref = rx_ref if use_rx else o_ref

    @pl.when(off != 0)
    def _():
        # resolved sub-chunk: PUT straight into the peer's slot for this
        # source rank (data lands in the combine kernel's by-source slot
        # layout; no receive-side shuffle).  Remote steps precede the
        # local block, so i indexes the tx staging directly.
        tx_ref[i] = chunk.astype(tx_ref.dtype)
        remote_tile_put(tx_ref.at[i],
                        recv_ref.at[my, :, :, pl.ds(c0, sub)],
                        send_sem, recv_sem, base + dest, axis_name,
                        id_style).start()

    @pl.when(off == 0)
    def _():
        o_ref[my, :, :, pl.ds(c0, sub)] = chunk

    @pl.when(i == n_steps - 1)
    def _():
        def desc():
            return remote_tile_put(tx_ref.at[0],
                                   recv_ref.at[0, :, :, pl.ds(0, sub)],
                                   send_sem, recv_sem, base + my, axis_name,
                                   id_style)

        drain(desc, (n_dev - 1) * q, recv=True)   # peers' chunks landed
        drain(desc, (n_dev - 1) * q, recv=False)  # our PUTs drained
        if use_rx:
            for src in range(n_dev):
                @pl.when(src != my)
                def _(src=src):
                    o_ref[src] = rx_ref[src].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_dev", "comm_aware", "chunks_per_rank",
                                    "skew", "collective_id", "interpret",
                                    "axis_name", "id_style", "wire"))
def fused_dispatch_a2a_pallas(xt, my_ep, ring_base, *, n_dev, axis_name,
                              comm_aware=True, chunks_per_rank=1, skew=0,
                              collective_id=10, interpret=None,
                              id_style=None, wire="f32"):
    """Per-shard device-initiated dispatch All-to-All.

    xt: [n_dev, B, E_loc, C, D] routed token blocks stacked by
    destination rank; returns the same shape stacked by *source* rank —
    the slot layout ``fused_gemm_a2a_pallas`` consumes directly.
    ``my_ep`` is the int32 ring position, ``ring_base`` the logical id of
    ring position 0 (0 on a 1-D mesh; the row base on a flattened
    multi-axis world, where peer logical id = ``ring_base + dest``).

    ``chunks_per_rank`` must divide the capacity axis C; every
    ``C/chunks_per_rank`` sub-chunk is PUT as soon as it is sliced out
    (Fig. 13 granularity).  ``skew`` rotates the remote destination
    order (Fig. 14).  ``wire`` is the PUT payload dtype — supported
    ``{"f32", "bf16"}`` (fp8 per-chunk scaling is an XLA-path feature;
    callers clamp).

    ``interpret=None`` runs the Pallas interpreter exactly when the
    default backend is not a TPU (:func:`repro.kernels.resolve_interpret`).
    """
    interpret = resolve_interpret(interpret)
    if id_style is None:
        id_style = "logical" if interpret else "mesh"
    if wire not in ("f32", "bf16"):
        raise ValueError(f"kernel wire dtype must be 'f32' or 'bf16', "
                         f"got {wire!r}")
    nd, b, e, c, d = xt.shape
    assert nd == n_dev, (nd, n_dev)
    q = int(chunks_per_rank)
    if q < 1 or c % q:
        raise ValueError(f"chunks_per_rank {q} must divide capacity {c}")
    sub = c // q
    n_steps = n_dev * q
    wire_dt = (jnp.bfloat16 if wire == "bf16" and xt.dtype.itemsize > 2
               else xt.dtype)
    use_rx = wire_dt != xt.dtype
    kernel = functools.partial(_dispatch_a2a_kernel, n_dev=n_dev, q=q,
                               sub=sub, axis_name=axis_name,
                               id_style=id_style, use_rx=use_rx,
                               barrier=not interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # token blocks in HBM
        ],
        out_specs=pl.BlockSpec((nd, b, e, c, d), lambda i, s: (0,) * 5),
        scratch_shapes=[
            pltpu.VMEM((2, b, e, c, d), xt.dtype),    # streamed dest blocks
            pltpu.SemaphoreType.DMA((2,)),            # block double buffer
            # tx staging: one slot per remote (dest, sub) step, at the
            # wire dtype (the local block is stored to the output
            # directly and scheduled last)
            pltpu.VMEM((max((n_dev - 1) * q, 1), b, e, sub, d), wire_dt),
            # rx staging for a narrowed wire (dummy otherwise — PUTs then
            # land zero-copy in the output ref)
            pltpu.VMEM((nd, b, e, c, d) if use_rx else (1,) * 5, wire_dt),
            pltpu.SemaphoreType.DMA,                  # send
            pltpu.SemaphoreType.DMA,                  # recv
        ],
    )
    step_off, _ = step_schedule(n_dev, q, comm_aware, skew)
    ids = jnp.concatenate([my_ep.astype(jnp.int32)[None],
                           ring_base.astype(jnp.int32)[None],
                           jnp.asarray(step_off, jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nd, b, e, c, d), xt.dtype),
        name="fused_dispatch_a2a",
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=interpret,
    )(ids, xt)
