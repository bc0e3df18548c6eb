"""Tiled GEMV kernel (token-phase inference matvec, paper §III-B).

y[n] = sum_k x[k] W[k, n].  Grid is (N/bn, K/bk); each output tile's f32
partial accumulates in VMEM across the K loop — the workgroup-per-output-
tile decomposition the paper's fused GEMV+AllReduce builds on.  x is kept
2D [1, K] (TPU lanes want >= 2D operands).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _gemv_kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def gemv_pallas(x, w, *, bn=256, bk=512, interpret=None):
    (b, k), (k2, n) = x.shape, w.shape
    assert k == k2 and n % bn == 0 and k % bk == 0, (x.shape, w.shape, bn, bk)
    return pl.pallas_call(
        _gemv_kernel,
        grid=(n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((b, bk), lambda j, l: (0, l)),
            pl.BlockSpec((bk, bn), lambda j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((b, bn), lambda j, l: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((b, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, w)
