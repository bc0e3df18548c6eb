"""Ragged paged flash attention over a KV block pool left in HBM.

One grid step per batch slot.  The slot's readable blocks arrive as a
compact list (scalar-prefetched): the pool blocks this rank owns that
the slot's queries can see, in table order, with each block's index in
the slot's sequence.  Nothing else of the pool is touched: a slot that
reads nothing costs one grid step, and the table's unused or foreign
entries cost nothing at all.

The list is streamed HBM→VMEM in groups of ``group`` blocks through a
double buffer, one async copy per block of K and of V.  Each block is
copied as ``[block, Hkv*hd]``, so the VMEM tile is lane-dense whatever
the head count.  The copy of the next group is in flight while the
current one is scored, and the last group of a slot starts the first
group of the next slot, so the stream runs on across grid steps.

Scores are computed one KV head at a time: the head's ``g`` query heads
and ``C`` chunk tokens form the rows (``g``-major), so GQA reads each KV
block once for its whole query group.  Masks are the paged path's:
causal by global position, ``p - k < window``, and the softcap; the
online softmax accumulates in f32.  The outputs are the unnormalised
partials ``(o, m, l)``, which the caller merges across ranks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
PAST = 1 << 30        # key position of a list entry past the slot's count


def _paged_kernel(layer_ref, ids_ref, lblk_ref, cnt_ref, pos_ref,
                  qoff_ref, q_ref, k_hbm, v_hbm,
                  o_ref, m_ref, l_ref,
                  kbuf, vbuf, sems, cur_ref, m_sc, l_sc, acc_sc, *,
                  n_kv, hd, block, group, mb, scale, window, softcap):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    keys = group * block

    def copies(slot, s, grp, start):
        """Start (or wait for) the copies of group ``grp`` of slot ``s``'s
        list into buffer ``slot``; entries past the list's count are
        skipped, and their wait with them."""
        n = cnt_ref[s]
        layer = layer_ref[0]
        for j in range(group):
            jj = grp * group + j

            @pl.when(jj < n)
            def _():
                bid = ids_ref[s * mb + jj]
                for w, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    c = pltpu.make_async_copy(
                        hbm.at[layer, bid],
                        buf.at[slot, pl.ds(j * block, block)],
                        sems.at[w, slot])
                    c.start() if start else c.wait()

    @pl.when(b == 0)
    def _():
        cur_ref[0] = 0
        copies(0, 0, 0, True)

    n = cnt_ref[b]
    n_groups = (n + group - 1) // group
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)

    # a slot with nothing to read hands its buffer on to the next slot
    @pl.when((n_groups == 0) & (b + 1 < n_slots))
    def _():
        copies(cur_ref[0], b + 1, 0, True)

    qpos = pos_ref[b] + qoff_ref[...]                       # [R, 1]
    col = lax.broadcasted_iota(jnp.int32, (1, keys), 1)
    row = lax.broadcasted_iota(jnp.int32, (keys, hd), 0)

    def body(grp, slot):
        @pl.when(grp + 1 < n_groups)
        def _():
            copies(1 - slot, b, grp + 1, True)

        @pl.when((grp + 1 == n_groups) & (b + 1 < n_slots))
        def _():
            copies(1 - slot, b + 1, 0, True)

        copies(slot, b, grp, False)
        # global position of every key of the group; entries past the
        # count lie after every query, so the causal mask drops them
        kpos = jnp.full((1, keys), PAST, jnp.int32)
        for j in range(group):
            jj = grp * group + j
            base = jnp.where(jj < n,
                             lblk_ref[b * mb + jnp.minimum(jj, mb - 1)] - j,
                             PAST // block)
            inside = (col >= j * block) & (col < (j + 1) * block)
            kpos = jnp.where(inside, col + base * block, kpos)
        mask = kpos <= qpos                                  # [R, keys]
        if window is not None:
            mask &= qpos - kpos < window
        live = row < (n - grp * group) * block               # [keys, hd]
        for h in range(n_kv):
            lanes = pl.ds(h * hd, hd)
            k = kbuf[slot, :, lanes]
            s = lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_sc[h] = l_sc[h] * corr + p.sum(axis=1, keepdims=True)
            # a buffer row no copy filled this round may hold anything
            v = jnp.where(live, vbuf[slot, :, lanes], 0)
            acc_sc[h] = acc_sc[h] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_sc[h] = m_new
        return 1 - slot

    cur_ref[0] = lax.fori_loop(0, n_groups, body, cur_ref[0])
    o_ref[0] = acc_sc[...]
    m_ref[0] = m_sc[...]
    l_ref[0] = l_sc[...]


@functools.partial(jax.jit, static_argnames=(
    "chunk", "scale", "window", "softcap", "group", "interpret"))
def paged_attention_pallas(q, k_pool, v_pool, layer, ids, lblk, cnt, pos0, *,
                           chunk, scale, window=None, softcap=None,
                           group=8, interpret=None):
    """Unnormalised flash partials of each slot's queries over its listed
    pool blocks.

    q: [B, Hkv, R, hd] with R = g * chunk rows, g-major (row r is query
    head ``r // chunk`` of the KV head's group at chunk token
    ``r % chunk``).  k_pool, v_pool: [L, NB, block, Hkv * hd], left in
    HBM, of which layer ``layer`` (int32 scalar) is read.  ids, lblk:
    [B * MB] int32, slot-major: slot b's first ``cnt[b]`` entries name a
    pool block and its index in the slot's sequence.
    pos0: [B] global position of chunk token 0.  Returns f32
    (o [B, Hkv, R, hd], m [B, Hkv, R, 1], l [B, Hkv, R, 1]).
    """
    B, n_kv, R, hd = q.shape
    _, nb, block, width = k_pool.shape
    assert width == n_kv * hd, (width, n_kv, hd)
    mb = ids.shape[0] // B
    qoff = (jnp.arange(R, dtype=jnp.int32) % chunk)[:, None]
    kernel = functools.partial(
        _paged_kernel, n_kv=n_kv, hd=hd, block=block, group=group, mb=mb,
        scale=scale, window=window, softcap=softcap)
    slot_block = lambda b, *_: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((R, 1), lambda b, *_: (0, 0)),
            pl.BlockSpec((1, n_kv, R, hd), slot_block),
            pl.BlockSpec(memory_space=pl.ANY),        # pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, n_kv, R, hd), slot_block),
            pl.BlockSpec((1, n_kv, R, 1), slot_block),
            pl.BlockSpec((1, n_kv, R, 1), slot_block),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, group * block, width), k_pool.dtype),
            pltpu.VMEM((2, group * block, width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (K|V, buffer)
            pltpu.SMEM((1,), jnp.int32),              # buffer in flight
            pltpu.VMEM((n_kv, R, 1), jnp.float32),
            pltpu.VMEM((n_kv, R, 1), jnp.float32),
            pltpu.VMEM((n_kv, R, hd), jnp.float32),
        ],
    )
    f32 = jnp.float32
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, n_kv, R, hd), f32),
                   jax.ShapeDtypeStruct((B, n_kv, R, 1), f32),
                   jax.ShapeDtypeStruct((B, n_kv, R, 1), f32)),
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids, lblk, cnt, pos0, qoff, q,
      k_pool, v_pool)
