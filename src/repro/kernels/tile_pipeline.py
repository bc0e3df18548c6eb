"""Tile-granular pipeline building blocks for device-initiated kernels.

Shared by the pipelined fused GEMV/GEMM+AllReduce and GEMM+All-to-All
kernels (and reusable by future fused ops).  Three concerns:

* **Weight/activation streaming** — double-buffered HBM→VMEM copies so a
  multi-step grid never stages more than two tiles of a large operand in
  VMEM (removes the whole-operand VMEM capacity cliff of single-shot
  kernels).
* **Remote tile PUTs** — ``pltpu.make_async_remote_copy`` wrappers that
  ship one output tile to a peer the moment its accumulation completes
  (the paper's per-slice RDMA PUT; T3's track-&-trigger unit is likewise
  the output tile).
* **Semaphore bookkeeping** — DMA waits are issued by *descriptor*, so a
  later grid step can drain copies started by earlier steps (grid steps
  share one traced body; python copy objects do not persist across steps,
  matching sizes do).

All helpers are shape-polymorphic over the tile layout; the comm-aware
offset order comes from :mod:`repro.core.scheduling` so XLA-level and
device-initiated paths share one schedule definition.
"""
from __future__ import annotations

from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def device_id_pair(dest, axis_name: str, id_style: str):
    """(device_id, device_id_type) for a remote copy to ring position
    ``dest`` — mesh-coordinate style on real meshes, logical in the
    single-axis interpreter."""
    if id_style == "mesh":
        return {axis_name: dest}, pltpu.DeviceIdType.MESH
    return dest, pltpu.DeviceIdType.LOGICAL


def entry_barrier(my, n_dev: int, axis_name: str, id_style: str, base=0):
    """Wait until every peer of the ring has entered the kernel.

    A PUT lands in the peer's VMEM scratch, which is the peer's own only
    once the peer runs this kernel; before that the same VMEM may belong
    to whatever ran there last.  So each rank signals the barrier
    semaphore of every peer it will PUT to and waits for all of them to
    signal back before its first remote write.  ``base`` is the id of
    ring position 0 (non-zero only on a flattened multi-axis world).
    The kernel's ``collective_id`` names the semaphore.  Kernels call
    this under Mosaic only: the interpreter steps all ranks in lock step
    and has no barrier semaphore."""
    bsem = pltpu.get_barrier_semaphore()
    for off in range(1, n_dev):
        did, dt = device_id_pair(base + lax.rem(my + off, n_dev), axis_name,
                                 id_style)
        pltpu.semaphore_signal(bsem, device_id=did, device_id_type=dt)
    pltpu.semaphore_wait(bsem, n_dev - 1)


def stream_tile_copy(hbm_ref, vmem_slots, sems, slot, col_start, tile_n,
                     row_start=None, rows=None):
    """Descriptor for one HBM→VMEM panel copy into a double-buffer slot.

    With ``row_start``/``rows`` unset the panel spans every row (the
    ``[K, tile_n]`` column strip); setting them streams a
    ``[rows, tile_n]`` sub-panel — the K-dim streaming used by the
    contraction-tiled kernels.  Start it one step ahead; wait with an
    identical descriptor."""
    if row_start is None:
        src = hbm_ref.at[:, pl.ds(col_start, tile_n)]
    else:
        src = hbm_ref.at[pl.ds(row_start, rows), pl.ds(col_start, tile_n)]
    return pltpu.make_async_copy(
        src,
        vmem_slots.at[slot],
        sems.at[slot],
    )


def stream_block_copy(hbm_ref, vmem_slots, sems, slot, index):
    """Descriptor for one HBM→VMEM leading-dim block copy into a double
    buffer slot (the A2A kernels stream per-destination token blocks)."""
    return pltpu.make_async_copy(
        hbm_ref.at[index],
        vmem_slots.at[slot],
        sems.at[slot],
    )


def remote_tile_put(src_ref, dst_ref, send_sem, recv_sem, dest,
                    axis_name: str, id_style: str):
    """Non-blocking PUT of one finished output tile into a peer buffer."""
    did, dt = device_id_pair(dest, axis_name, id_style)
    return pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=did,
        device_id_type=dt,
    )


def drain(descriptor_fn, count: int, *, recv: bool):
    """Wait for ``count`` same-sized remote-copy completions.

    ``descriptor_fn()`` must rebuild a copy descriptor whose src/dst size
    matches the in-flight transfers; DMA semaphores account by bytes, so
    any descriptor of that size retires one arrival/send."""
    for _ in range(count):
        c = descriptor_fn()
        if recv:
            c.wait_recv()
        else:
            c.wait_send()


def step_schedule(n_dev: int, tiles_per_rank: int, comm_aware: bool,
                  skew: int = 0):
    """Static per-grid-step (offset, sub-tile) lists.

    Remote tiles first — farthest peer first under comm-aware scheduling
    (paper Fig. 7b), natural order otherwise — and the locally-reduced
    tiles always last, so local compute hides remote wire time.  ``skew``
    rotates the remote portion of the offset order by the measured
    straggler bucket (Fig. 14), mirroring
    :func:`repro.core.scheduling.ring_offsets`; the local tiles keep
    their final position so the remote-ahead-of-local rule (and the
    kernels' tx-slot indexing, which relies on remote steps preceding the
    local one) is preserved.  The lists are meant to ride in the
    scalar-prefetch operand (a Pallas kernel body cannot capture array
    constants), indexed by the traced ``program_id``.
    """
    offs = (list(range(n_dev - 1, 0, -1)) if comm_aware
            else list(range(1, n_dev))) + [0]
    if skew and n_dev > 1:
        remote = offs[:-1]
        r = skew % len(remote)
        offs = remote[r:] + remote[:r] + [0]
    step_off = []
    step_sub = []
    for off in offs:
        for sub in range(tiles_per_rank):
            step_off.append(off)
            step_sub.append(sub)
    return step_off, step_sub
