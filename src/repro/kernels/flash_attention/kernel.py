"""Causal flash-attention kernel (prefill hot-spot).

Grid (batch*heads, q_blocks, kv_blocks), kv innermost; running max /
denominator / output accumulator live in VMEM across the kv loop.  Causal
blocks above the diagonal are skipped by masking (TPU grids are
sequential per core, so `pl.when` on block indices skips the matmuls
entirely for fully-masked blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, bq, bkv):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    should_run = True
    if causal:
        # block fully above the diagonal -> nothing to do
        should_run = qi * bq + bq - 1 >= ki * bkv

    @pl.when(should_run)
    def _():
        q = q_ref[0]                                  # [bq, d]
        k = k_ref[0]                                  # [bkv, d]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "bq", "bkv",
                                             "interpret"))
def flash_attention_pallas(q, k, v, *, scale, causal=True, bq=128, bkv=128,
                           interpret=None):
    """q,k,v: [BH, S, d] (heads pre-folded into batch) -> [BH, S, d]."""
    bh, s, d = q.shape
    assert s % bq == 0 and s % bkv == 0, (s, bq, bkv)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bkv=bkv)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // bq, s // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
