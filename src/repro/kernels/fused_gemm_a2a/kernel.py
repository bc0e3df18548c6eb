"""Device-initiated fused expert GEMM + All-to-All (paper §III, Fig. 10).

The paper's third operator (MoE combine): as soon as an expert shard
finishes the output block destined for one peer, that block is PUT to the
peer while the remaining blocks are still being computed.  This kernel is
the device-initiated sibling of the XLA-level ``fused_expert_ffn_combine``
and shares the tile-pipeline helpers with the rewritten fused
GEMV/GEMM+AllReduce kernel:

* Multi-step grid over combine destinations (comm-aware: farthest peer
  first, locally-consumed block last — paper Fig. 7b's rule applied to
  the A2A).
* The dispatched token blocks stay in HBM; each destination's
  ``[B, E, C, D]`` block is streamed into a VMEM double buffer one step
  ahead, so VMEM holds two blocks — not the whole dispatch buffer.
* The expert weights stay in HBM too: the gated FFN's GEMMs are
  contraction-tiled, streaming ``[tile_k, F]`` up/gate panels and
  ``[tile_f, D]`` down panels through per-stream double buffers and
  accumulating partials in f32 — so VMEM holds two panels per stream
  instead of all ``E_loc`` experts' ``[D, F]`` slabs, and ``D x F``
  scales past VMEM in both dims (the K-panel treatment of the
  GEMV+AllReduce kernel applied to both chained GEMMs).  Panels may be
  ragged in the final step of either contraction.
* The finished block is PUT straight into the peer's *output ref* slot
  for this source rank (zero-copy: the combine A2A needs no receive-side
  shuffle), wire time hidden behind the next block's GEMMs.
* DMA completion semaphores replace the paper's sliceRdy polling.

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


def _panel_copy(hbm, slots, sems, slot, ei, row0, rows, full_rows):
    """Descriptor for one ``[rows, cols]`` weight panel of expert ``ei``
    (``rows < full_rows`` on a ragged final panel).  All indices are
    python-static — the (expert, panel) loops are unrolled."""
    if rows == full_rows:
        dst = slots.at[slot]
    else:
        dst = slots.at[slot, pl.ds(0, rows)]
    return pltpu.make_async_copy(hbm.at[ei, pl.ds(row0, rows)], dst,
                                 sems.at[slot])


def _weight_schedule(e_loc, kp_d, kp_f):
    """Static (stream, expert, panel) order the FFN consumes panels in."""
    items = []
    for ei in range(e_loc):
        items += [("ug", ei, p) for p in range(kp_d)]
        items += [("d", ei, p) for p in range(kp_f)]
    return items


def _gemm_a2a_kernel(ids_ref, x_hbm, wu_hbm, wg_hbm, wd_hbm, o_ref,
                     x_slots, x_sems, wu_slots, wu_sems, wg_slots, wg_sems,
                     wd_slots, wd_sems, tx_ref, rx_ref, send_sem, recv_sem, *,
                     n_dev, e_loc, tile_k, tile_f, dm, f, act,
                     axis_name, id_style, use_rx, barrier):
    my = ids_ref[0]
    base = ids_ref[1]
    i = pl.program_id(0)
    step_off = lambda s: ids_ref[2 + s]
    kp_d = -(-dm // tile_k)
    kp_f = -(-f // tile_f)
    items = _weight_schedule(e_loc, kp_d, kp_f)

    def xdma(step, slot):
        dest = lax.rem(my + step_off(step), n_dev)
        return stream_block_copy(x_hbm, x_slots, x_sems, slot, dest)

    def wcopy(item, occ):
        stream, ei, p = item
        if stream == "ug":
            k0 = p * tile_k
            ksz = min(tile_k, dm - k0)
            return [_panel_copy(wu_hbm, wu_slots, wu_sems, occ % 2, ei,
                                k0, ksz, tile_k),
                    _panel_copy(wg_hbm, wg_slots, wg_sems, occ % 2, ei,
                                k0, ksz, tile_k)]
        f0 = p * tile_f
        fsz = min(tile_f, f - f0)
        return [_panel_copy(wd_hbm, wd_slots, wd_sems, occ % 2, ei,
                            f0, fsz, tile_f)]

    # per-stream double-buffer slot = occurrence count % 2 (python-static)
    occs = []
    counts = {"ug": 0, "d": 0}
    for it in items:
        occs.append(counts[it[0]])
        counts[it[0]] += 1

    @pl.when(i == 0)
    def _():
        if barrier:
            # no PUT may land before every peer runs this kernel
            entry_barrier(my, n_dev, axis_name, id_style, base)
        xdma(0, 0).start()

    @pl.when(i + 1 < n_dev)
    def _():
        xdma(i + 1, (i + 1) % 2).start()

    for c in wcopy(items[0], occs[0]):
        c.start()
    xdma(i, i % 2).wait()
    off = step_off(i)
    dest = lax.rem(my + off, n_dev)
    xs = x_slots[i % 2]                               # [B, E, C, D]
    b, _, cc, _ = xs.shape

    # ---- contraction-tiled gated FFN, weights streamed from HBM -------
    ys = []
    h = g = u = y = None
    for j, (item, occ) in enumerate(zip(items, occs)):
        for c in wcopy(item, occ):
            c.wait()
        if j + 1 < len(items):
            for c in wcopy(items[j + 1], occs[j + 1]):
                c.start()
        stream, ei, p = item
        slot = occ % 2
        xe = xs[:, ei].reshape(b * cc, dm)
        if stream == "ug":
            k0 = p * tile_k
            ksz = min(tile_k, dm - k0)
            xp = xe[:, k0:k0 + ksz]
            hp = jnp.dot(xp, wu_slots[slot, :ksz],
                         preferred_element_type=jnp.float32)
            gp = jnp.dot(xp, wg_slots[slot, :ksz],
                         preferred_element_type=jnp.float32)
            h = hp if p == 0 else h + hp
            g = gp if p == 0 else g + gp
        else:
            if p == 0:
                u = (act(g) * h).astype(xs.dtype)
            f0 = p * tile_f
            fsz = min(tile_f, f - f0)
            yp = jnp.dot(u[:, f0:f0 + fsz], wd_slots[slot, :fsz],
                         preferred_element_type=jnp.float32)
            y = yp if p == 0 else y + yp
            if p == kp_f - 1:
                ys.append(y.reshape(b, 1, cc, dm).astype(o_ref.dtype))
    block = jnp.concatenate(ys, axis=1)               # [B, E, C, D]

    # receive target: the output ref itself (zero-copy) when the wire
    # dtype matches the output, a wire-dtype rx staging ref otherwise
    # (the narrow payload is upcast into the output at the end)
    recv_ref = rx_ref if use_rx else o_ref

    @pl.when(off != 0)
    def _():
        # finished block: PUT straight into the peer's slot for this
        # source rank, staged at the wire dtype (data lands in final
        # layout; no receive-side shuffle)
        tx_ref[i] = block.astype(tx_ref.dtype)
        remote_tile_put(tx_ref.at[i], recv_ref.at[my], send_sem, recv_sem,
                        base + dest, axis_name, id_style).start()

    @pl.when(off == 0)
    def _():
        o_ref[my] = block

    @pl.when(i == n_dev - 1)
    def _():
        def desc():
            return remote_tile_put(tx_ref.at[0], recv_ref.at[0], send_sem,
                                   recv_sem, base + my, axis_name, id_style)

        drain(desc, n_dev - 1, recv=True)   # peers' blocks landed
        drain(desc, n_dev - 1, recv=False)  # our PUTs drained
        if use_rx:
            # upcast the wire-dtype arrivals into the output slots
            for s in range(n_dev):
                @pl.when(s != my)
                def _(s=s):
                    o_ref[s] = rx_ref[s].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_dev", "act", "comm_aware", "skew",
                                    "collective_id", "interpret",
                                    "axis_name", "id_style", "tile_k",
                                    "tile_f", "wire"))
def fused_gemm_a2a_pallas(xt, w_up, w_gate, w_down, my_ep, ring_base, *,
                          n_dev, axis_name, act, comm_aware=True, skew=0,
                          collective_id=8, interpret=None, id_style=None,
                          tile_k=None, tile_f=None, wire="f32"):
    """Per-shard fused expert FFN + combine All-to-All.

    xt: [n_dev, B, E_loc, C, D] dispatched tokens stacked by combine
    destination; w_up/w_gate: [E_loc, D, F]; w_down: [E_loc, F, D];
    my_ep: int32 ring position; ring_base: logical id of ring position 0
    (0 on a 1-D mesh; on a flattened multi-axis world the row base, so a
    PUT to ring position ``dest`` targets logical id ``ring_base + dest``
    and stays row-confined).  ``skew`` rotates the remote send order by
    the measured straggler bucket.  Returns [n_dev, B, E_loc, C, D]
    stacked by *source* rank (the bulk All-to-All's layout).

    ``tile_k`` / ``tile_f`` bound the contraction panels of the up/gate
    and down GEMMs (``None`` = whole depth; values need not divide D or F
    — the final panel of either contraction is ragged).  The weights are
    streamed per (expert, panel) from HBM, so per-expert ``D x F`` and
    the ``E_loc`` multiplier never hit VMEM at once.

    ``wire`` is the combine-PUT payload dtype: ``"bf16"`` stages finished
    blocks (f32-accumulated in the GEMM pipeline) in bf16 tx buffers and
    receives them in a bf16 staging ref upcast into the output at the end
    — the remote DMA moves half the bytes at the cost of the receive-side
    zero-copy.  Supported: ``{"f32", "bf16"}`` (fp8 per-chunk scaling is
    an XLA-path feature; callers clamp).

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
    f = w_up.shape[2]
    assert nd == n_dev, (nd, n_dev)
    tile_k = d if tile_k is None else max(1, min(int(tile_k), d))
    tile_f = f if tile_f is None else max(1, min(int(tile_f), f))
    wire_dt = (jnp.bfloat16 if wire == "bf16" and xt.dtype.itemsize > 2
               else xt.dtype)
    use_rx = wire_dt != xt.dtype
    kernel = functools.partial(_gemm_a2a_kernel, n_dev=n_dev, e_loc=e,
                               tile_k=tile_k, tile_f=tile_f, dm=d, f=f,
                               act=act, axis_name=axis_name,
                               id_style=id_style, use_rx=use_rx,
                               barrier=not interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_dev,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # token blocks in HBM
            pl.BlockSpec(memory_space=pl.ANY),        # w_up in HBM
            pl.BlockSpec(memory_space=pl.ANY),        # w_gate in HBM
            pl.BlockSpec(memory_space=pl.ANY),        # w_down in HBM
        ],
        out_specs=pl.BlockSpec((nd, b, e, c, d), lambda i, s: (0,) * 5),
        scratch_shapes=[
            pltpu.VMEM((2, b, e, c, d), xt.dtype),    # streamed x blocks
            pltpu.SemaphoreType.DMA((2,)),            # block double buffer
            pltpu.VMEM((2, tile_k, f), w_up.dtype),   # streamed up panels
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((2, tile_k, f), w_gate.dtype),  # streamed gate panels
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((2, tile_f, d), w_down.dtype),  # streamed down panels
            pltpu.SemaphoreType.DMA((2,)),
            # tx staging: remote blocks only (own block is written to the
            # output directly and scheduled last, so remote steps are
            # i < n_dev - 1); staged at the wire dtype
            pltpu.VMEM((max(n_dev - 1, 1), b, e, c, d), wire_dt),
            # rx staging for a narrowed wire (a dummy slot otherwise — the
            # PUTs then land zero-copy in the output ref)
            pltpu.VMEM((n_dev, b, e, c, d) if use_rx else (1,) * 5, wire_dt),
            pltpu.SemaphoreType.DMA,                  # send
            pltpu.SemaphoreType.DMA,                  # recv
        ],
    )
    step_off, _ = step_schedule(n_dev, 1, comm_aware, skew)
    ids = jnp.concatenate([my_ep.astype(jnp.int32)[None],
                           ring_base.astype(jnp.int32)[None],
                           jnp.asarray(step_off, jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nd, b, e, c, d), xt.dtype),
        name="fused_gemm_a2a",
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=interpret,
    )(ids, xt, w_up, w_gate, w_down)
