"""Embedding-bag pooling kernel (DLRM EmbeddingBag sum/mean, paper §III-A).

The table stays in HBM; the categorical indices are scalar-prefetched and
drive the table BlockSpec's index_map, so each grid step DMAs exactly the
embedding row it needs into VMEM — the TPU idiom for gather.  Grid is
(batch, L); the bag accumulator for one output row lives in VMEM across
the L loop and is scaled to the mean on the last lookup.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _pool_kernel(idx_ref, row_ref, o_ref, acc_ref):
    ll = pl.program_id(1)
    n_l = pl.num_programs(1)

    @pl.when(ll == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += row_ref[...].astype(jnp.float32)

    @pl.when(ll == n_l - 1)
    def _():
        o_ref[...] = (acc_ref[...] / n_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_pool_pallas(table, idx, *, interpret=None):
    """table: [V, D]; idx: [B, L] int32 -> mean-pooled [B, D]."""
    v, d = table.shape
    b, L = idx.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, L),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, l, idx_ref: (idx_ref[i, l], 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, l, idx_ref: (i, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    return pl.pallas_call(
        _pool_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(idx, table)
