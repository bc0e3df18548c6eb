"""Chunked WKV6 recurrence kernel (RWKV-6 time-mix hot spot).

Grid (B*H, T/chunk) with the chunk dimension sequential; the [N, N]
recurrent state lives in VMEM scratch across chunk steps (the TPU
analogue of a persistent workgroup carrying state).  Within a chunk the
pairwise decay form is used: ratios exp(lc_t - lc_s), s <= t, are
bounded in (0, 1] so any chunk length is numerically safe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *, chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0]            # [c, N]
    k = k_ref[0]
    v = v_ref[0]
    lw = lw_ref[0]          # [c, N] log-decay (<= 0)
    u = u_ref[0]            # [1, N]
    c = r.shape[0]

    lc = jnp.cumsum(lw, axis=0)
    lc_tm1 = lc - lw
    # pairwise per-channel decay exp(lc_{t-1} - lc_s), s < t: bounded (0,1]
    dec = jnp.exp(jnp.clip(lc_tm1[:, None] - lc[None, :], -60.0, 0.0))
    mask = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    att = jnp.einsum("tn,tsn,sn->ts", r, dec * mask[..., None], k)
    o = jnp.dot(att, v, preferred_element_type=jnp.float32)
    # bonus diagonal term
    o = o + (r * u * k).sum(axis=-1, keepdims=True) * v
    # contribution of carried state
    rdec = r * jnp.exp(jnp.clip(lc_tm1, -60.0, 0.0))
    o = o + jnp.dot(rdec, state_ref[...], preferred_element_type=jnp.float32)
    o_ref[0] = o.astype(o_ref.dtype)
    # state update
    lc_end = lc[-1]
    kdec = k * jnp.exp(jnp.clip(lc_end[None, :] - lc, -60.0, 0.0))
    state_ref[...] = jnp.exp(jnp.clip(lc_end, -60.0, 0.0))[:, None] * \
        state_ref[...] + jnp.dot(kdec.T, v, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, lw, u, *, chunk=32, interpret=None):
    """r,k,v,lw: [BH, T, N] (heads folded into batch; lw = log decay);
    u: [BH, 1, N] bonus.  Returns o: [BH, T, N] f32."""
    bh, t, n = r.shape
    assert t % chunk == 0, (t, chunk)
    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, t // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, n), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(r, k, v, lw, u)
