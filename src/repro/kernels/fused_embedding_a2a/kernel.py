"""Device-initiated fused embedding pooling + All-to-All (paper §III-A,
Fig. 6 — the scale-out flagship).

One Pallas kernel per chip pools its local tables' bags AND communicates
each destination's batch fragment the moment the fragment's last bag
completes — the TPU analogue of the paper's persistent HIP kernel with
ROC_SHMEM PUTs:

* grid = (destination, batch row, table); the destination axis iterates
  in communication-aware order (farthest peer first, the local fragment
  last — paper Fig. 6b);
* embedding rows are fetched by scalar-prefetched indices driving the
  table BlockSpec (one DMA per lookup — the TPU gather idiom — of the
  8-row aligned group holding the row);
* a fragment accumulates in VMEM; on its last bag it is PUT directly
  into the *destination's output buffer* at this source's table columns
  (zero-copy: the data lands in the layout the interaction op consumes,
  no shuffle kernel — the paper's "no explicit rearrangement" property);
* DMA completion semaphores replace WG_Done/sliceRdy flags; the kernel
  exits after its n-1 inbound fragments have landed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.tile_pipeline import entry_barrier, remote_tile_put


def _kernel(ids_ref, idx_ref, rows_ref, out_ref, frag_ref, stage_ref,
            send_sem, recv_sem, local_sem, *, n_dev, b_loc, t_loc, L, group,
            d, comm_aware, id_style, axis_name, barrier):
    my = ids_ref[0]
    i, b, t, l = (pl.program_id(k) for k in range(4))
    # comm-aware destination order = [n-1, ..., 1, 0] (farthest first,
    # local last) -- pure arithmetic in the grid step index
    off = (n_dev - 1 - i) if comm_aware else i
    dest = lax.rem(my + off, n_dev)
    mine = out_ref.at[:, pl.ds(my * t_loc, t_loc)]   # my table columns

    if barrier:
        @pl.when((i == 0) & (b == 0) & (t == 0) & (l == 0))
        def _():
            # no PUT may land before every peer runs this kernel
            entry_barrier(my, n_dev, axis_name, id_style)

    @pl.when(l == 0)
    def _():
        frag_ref[b, t] = jnp.zeros_like(frag_ref[b, t])

    # the row group holding this lookup's row landed in VMEM; pick it
    row = lax.rem(idx_ref[dest * b_loc + b, t, l], group)
    frag_ref[b, t, :d] += rows_ref[0, pl.ds(row, 1)][0].astype(jnp.float32)

    last_bag = (l == L - 1)

    @pl.when(last_bag)
    def _():
        frag_ref[b, t] = frag_ref[b, t] / L

    frag_done = last_bag & (b == b_loc - 1) & (t == t_loc - 1)

    @pl.when(frag_done)
    def _():
        # each destination's fragment gets its own staging slot: its PUT
        # may still be reading it while the next fragment accumulates
        stage_ref[i] = frag_ref[...].astype(stage_ref.dtype)

    @pl.when(frag_done & (dest != my))
    def _():
        # PUT the fragment straight into dest's output at MY table columns
        remote_tile_put(stage_ref.at[i], mine, send_sem, recv_sem, dest,
                        axis_name, id_style).start()

    @pl.when(frag_done & (dest == my))
    def _():
        # local fragment: a local DMA into our own output slice
        pltpu.make_async_copy(stage_ref.at[i], mine, local_sem).start()

    # final grid step: drain sends, wait for all inbound fragments
    @pl.when((i == n_dev - 1) & frag_done)
    def _():
        wait = remote_tile_put(stage_ref.at[0], mine, send_sem, recv_sem, my,
                               axis_name, id_style)
        for _ in range(n_dev - 1):
            wait.wait_send()
            wait.wait_recv()
        pltpu.make_async_copy(stage_ref.at[0], mine, local_sem).wait()


@functools.partial(jax.jit,
                   static_argnames=("n_dev", "L", "comm_aware",
                                    "collective_id", "interpret",
                                    "id_style", "axis_name"))
def fused_embedding_a2a_pallas(tables, idx, my, *, n_dev, L, axis_name,
                               comm_aware=True, collective_id=9,
                               interpret=None, id_style=None):
    """tables: [T_loc, V, D]; idx: [B_global, T_loc, L] int32.

    Returns [B_loc, n_dev * T_loc, D]: this device's batch fragment of
    the pooled embeddings of ALL devices' tables, fully exchanged.
    ``interpret=None`` runs the Pallas interpreter exactly when the
    default backend is not a TPU (:func:`repro.kernels.resolve_interpret`).
    """
    interpret = resolve_interpret(interpret)
    if id_style is None:
        id_style = "logical" if interpret else "mesh"
    t_loc, v, d = tables.shape
    B, _, _ = idx.shape
    b_loc = B // n_dev
    # rows are fetched in sublane-aligned groups of 8 (Mosaic tiles the
    # second-minor dim by 8): one DMA per lookup, the row picked in VMEM
    group = 8 if v % 8 == 0 else v
    # fragments travel lane-padded: a DMA moves whole 128-lane rows
    dp = -(-d // 128) * 128
    kernel = functools.partial(_kernel, n_dev=n_dev, b_loc=b_loc,
                               t_loc=t_loc, L=L, group=group, d=d,
                               comm_aware=comm_aware,
                               id_style=id_style, axis_name=axis_name,
                               barrier=not interpret)

    def table_index(i, b, t, l, ids_ref, idx_ref):
        off = (n_dev - 1 - i) if comm_aware else i
        dest = (ids_ref[0] + off) % n_dev
        gb = dest * b_loc + b
        return (t, idx_ref[gb, t, l] // group, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_dev, b_loc, t_loc, L),
        in_specs=[pl.BlockSpec((1, group, d), table_index)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((b_loc, t_loc, dp), jnp.float32),  # accumulator
            pltpu.VMEM((n_dev, b_loc, t_loc, dp), tables.dtype),  # staging
            pltpu.SemaphoreType.DMA,                      # send
            pltpu.SemaphoreType.DMA,                      # recv
            pltpu.SemaphoreType.DMA,                      # local copy
        ],
    )
    ids = jnp.stack([my.astype(jnp.int32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="fused_embedding_a2a",
        out_shape=jax.ShapeDtypeStruct((b_loc, n_dev * t_loc, dp),
                                       tables.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            collective_id=collective_id),
        interpret=interpret,
    )(ids, idx, tables)
    return out[..., :d]
