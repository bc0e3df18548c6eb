"""Context-parallel attention with fused KV all-gather (train/prefill) and
sequence-sharded KV caches with partial-softmax merge (decode).

Sharding scheme (see DESIGN.md §5): activations are sequence-sharded over
the tp axis; attention keeps *all* heads on every rank (uniform across the
zoo's awkward head counts) and shards the KV sequence instead.

Train/prefill: rank d owns query chunk d and ring-gathers KV chunks,
running a flash-attention update on each arriving chunk while the next is
on the wire — the fused AllGather x attention operator (the paper's
decomposition applied to the KV gather).  Sliding-window layers
statically bound the number of ring hops (window/chunk), which the bulk
AG baseline cannot do.

Decode: the KV cache stays sequence-sharded; every rank computes a flash
partial over its local slice and one tiny pmax/psum pair merges them
(replaces the paper's sliceRdy polling with the collective itself).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.autotune import resolve_overlap, tune_ring_attention
from repro.core.collectives import (attention_partial_merge, ring_permute,
                                    split_ring_payload, wire_cast,
                                    wire_uncast)
from repro.core.scheduling import sub_chunk_service_order
from repro.kernels.paged_attention import (paged_attention_kernel_supported,
                                          paged_attention_shard)
from repro.parallel.sharding import ParallelContext
from repro.compat import shard_map

NEG_INF = -1e30


def _flash_update(carry, q5, k, v, mask, scale, cap):
    """One flash-attention accumulation step (f32 carries).

    carry = (m, l, o): [b,hk,g,sq], [b,hk,g,sq], [b,hk,g,sq,d]
    q5: [b,sq,hk,g,d]; k,v: [b,sk,hk,d]; mask: [sq,sk] bool, or
    [b,sq,sk] when validity is per batch row (the paged-KV path, where
    each slot masks at its own length/block table).
    """
    m, l, o = carry
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k).astype(jnp.float32) * scale
    if cap is not None:
        s = jnp.tanh(s / cap) * cap
    # additive 2D mask: broadcasts inside the fusion; a select against the
    # full [b,h,g,q,k] score shape would get materialized + loop-hoisted
    bias = jnp.where(mask, 0.0, NEG_INF)
    s = s + (bias[:, None, None] if mask.ndim == 3
             else bias[None, None, None])
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return m_new, l, o


def _span_flash(q5, k, v, qpos, kpos, carry, *, causal, window, scale, cap,
                q_block, kv_block):
    """Accumulate flash carries of q5 against one KV span, blocked so the
    score matrix never exceeds [b, hk, g, q_block, kv_block]."""
    b, sq, hk, g, d = q5.shape
    sk = k.shape[1]
    qb = min(q_block, sq)
    kb = min(kv_block, sk)

    def q_step(qi, mlo):
        m, l, o = mlo
        qs = lax.dynamic_slice_in_dim(q5, qi * qb, qb, axis=1)
        qp = lax.dynamic_slice_in_dim(qpos, qi * qb, qb, axis=0)
        cm = lax.dynamic_slice_in_dim(m, qi * qb, qb, axis=3)
        cl = lax.dynamic_slice_in_dim(l, qi * qb, qb, axis=3)
        co = lax.dynamic_slice_in_dim(o, qi * qb, qb, axis=3)

        def kv_step(ki, mlo_q):
            ks = lax.dynamic_slice_in_dim(k, ki * kb, kb, axis=1)
            vs = lax.dynamic_slice_in_dim(v, ki * kb, kb, axis=1)
            kp = lax.dynamic_slice_in_dim(kpos, ki * kb, kb, axis=0)
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask &= kp[None, :] <= qp[:, None]
            if window is not None:
                mask &= qp[:, None] - kp[None, :] < window
            return _flash_update(mlo_q, qs, ks, vs, mask, scale, cap)

        cm, cl, co = lax.fori_loop(0, sk // kb, kv_step, (cm, cl, co))
        return (lax.dynamic_update_slice_in_dim(m, cm, qi * qb, axis=3),
                lax.dynamic_update_slice_in_dim(l, cl, qi * qb, axis=3),
                lax.dynamic_update_slice_in_dim(o, co, qi * qb, axis=3))

    return lax.fori_loop(0, sq // qb, q_step, carry)


def _init_carry(b, hk, g, sq, d):
    return (jnp.full((b, hk, g, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, hk, g, sq), jnp.float32),
            jnp.zeros((b, hk, g, sq, d), jnp.float32))


def _finalize(carry, b, sq, hq, d):
    m, l, o = carry
    o = o / jnp.maximum(l, 1e-30)[..., None]          # [b,hk,g,sq,d]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)


# ---------------------------------------------------------------------------
# flash backward over one KV span (blocked; recompute-in-backward)
# ---------------------------------------------------------------------------
def _span_flash_bwd(q5, kc, vc, do5, delta, m, l, qpos, kpos, dq5, *,
                    causal, window, scale, cap, q_block, kv_block,
                    dk0=None, dv0=None):
    """Accumulate flash gradients of q5 against one KV span.

    q5/do5/dq5: [b,sq,hk,g,d]; kc,vc: [b,skc,hk,d]; delta,m,l: [b,hk,g,sq].
    dq5 and (dk0, dv0) are running accumulators (the latter travel the
    ring with their chunk).  Scores are recomputed per (q_block, kv_block)
    tile, never materialized whole.
    """
    b, sq, hk, g, dd = q5.shape
    skc = kc.shape[1]
    qb = min(q_block, sq)
    kb = min(kv_block, skc)
    dk = jnp.zeros((b, skc, hk, dd), jnp.float32) if dk0 is None else dk0
    dv = jnp.zeros((b, skc, hk, dd), jnp.float32) if dv0 is None else dv0

    def q_step(qi, carry):
        dq5_, dk_, dv_ = carry
        qs = lax.dynamic_slice_in_dim(q5, qi * qb, qb, axis=1)
        dos = lax.dynamic_slice_in_dim(do5, qi * qb, qb, axis=1)
        qp = lax.dynamic_slice_in_dim(qpos, qi * qb, qb, axis=0)
        ms = lax.dynamic_slice_in_dim(m, qi * qb, qb, axis=3)
        ls = lax.dynamic_slice_in_dim(l, qi * qb, qb, axis=3)
        dls = lax.dynamic_slice_in_dim(delta, qi * qb, qb, axis=3)
        dq_blk = jnp.zeros((b, qb, hk, g, dd), jnp.float32)

        def kv_step(ki, inner):
            dq_b, dk_b, dv_b = inner
            ks = lax.dynamic_slice_in_dim(kc, ki * kb, kb, axis=1)
            vs = lax.dynamic_slice_in_dim(vc, ki * kb, kb, axis=1)
            kp = lax.dynamic_slice_in_dim(kpos, ki * kb, kb, axis=0)
            raw = jnp.einsum("bqhgd,bkhd->bhgqk", qs, ks
                             ).astype(jnp.float32) * scale
            s = raw
            if cap is not None:
                s = jnp.tanh(raw / cap) * cap
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask &= kp[None, :] <= qp[:, None]
            if window is not None:
                mask &= qp[:, None] - kp[None, :] < window
            s = s + jnp.where(mask, 0.0, NEG_INF)[None, None, None]
            p = jnp.exp(s - ms[..., None]) / jnp.maximum(ls, 1e-30)[..., None]
            dv_c = jnp.einsum("bhgqk,bqhgd->bkhd", p,
                              dos.astype(jnp.float32))
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", dos.astype(jnp.float32),
                            vs.astype(jnp.float32))
            ds = p * (dp - dls[..., None])
            if cap is not None:
                t = jnp.tanh(raw / cap)
                ds = ds * (1.0 - t * t)
            ds = ds * scale
            dq_c = jnp.einsum("bhgqk,bkhd->bqhgd", ds, ks.astype(jnp.float32))
            dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qs.astype(jnp.float32))
            dk_b = lax.dynamic_update_slice_in_dim(
                dk_b, lax.dynamic_slice_in_dim(dk_b, ki * kb, kb, 1) + dk_c,
                ki * kb, axis=1)
            dv_b = lax.dynamic_update_slice_in_dim(
                dv_b, lax.dynamic_slice_in_dim(dv_b, ki * kb, kb, 1) + dv_c,
                ki * kb, axis=1)
            return dq_b + dq_c, dk_b, dv_b

        dq_blk, dk_, dv_ = lax.fori_loop(0, skc // kb, kv_step,
                                         (dq_blk, dk_, dv_))
        dq5_ = lax.dynamic_update_slice_in_dim(
            dq5_, lax.dynamic_slice_in_dim(dq5_, qi * qb, qb, 1) + dq_blk,
            qi * qb, axis=1)
        return dq5_, dk_, dv_

    return lax.fori_loop(0, sq // qb, q_step, (dq5, dk, dv))


def _make_ring_attention(axis, n, hops, causal, window, scale, cap,
                         q_block, kv_block, Hq, Hkv, hd, s_loc, n_world,
                         n_sub=1, skew=0, wire="f32"):
    """Ring attention with analytic backward (custom VJP).

    Forward: each arriving KV chunk is flash-consumed while the next hop's
    collective-permute is in flight (the fused AllGather x attention op).
    ``n_sub`` (= ``chunks_per_rank``, paper Fig. 13) splits the local KV
    chunk into sub-chunks that ring *independently*: each sub-chunk is
    forwarded the moment the previous sub-chunk's attention partial has
    been consumed, so sub-chunk ``j``'s wire time hides behind sub-chunk
    ``j-1``'s flash update; the online-softmax stats merge per sub-chunk
    through the shared (m, l, o) carry.
    Backward: KV sub-chunks ring again (recomputed masks/scores, flash-bwd
    per sub-chunk); each sub-chunk's (dk, dv) accumulator travels the ring
    *with* its sub-chunk and is delivered back to its owner in one final
    offset permute.  Peak memory: one score tile — autodiff through the
    unrolled ring would instead save every hop's probability tensors.

    ``skew`` (measured straggler rotation, Fig. 14) rotates the service
    order of the ``n_sub`` independent sub-chunk rings within each hop —
    the straggler-facing sub-ring is forwarded first.  The shared
    online-softmax carry then merges sub-chunks in rotated order, which
    is algebraically the same sum (equal within the usual fp tolerance).

    ``wire`` compresses the ring payloads: KV sub-chunks round once at
    their source (the compressed payload rings unchanged, so remote KV
    sees one rounding regardless of hop count) and the traveling (dk, dv)
    accumulators are cast on every send while the flash-backward
    accumulation stays f32.  ``wire="f32"`` keeps the pre-wire graphs
    bit-identical (the accumulators then travel at the operand dtype, as
    before).
    """
    g = Hq // Hkv
    sub = s_loc // n_sub
    order = sub_chunk_service_order(n_sub, skew)
    compress = wire not in (None, "f32")
    # Without causal/window masking the position arrays are dead code; an
    # unconsumed axis_index leaves a dangling partition-id instruction that
    # the SPMD partitioner refuses, so only trace it when a mask needs it.
    need_pos = causal or window is not None

    def _rank():
        return lax.axis_index(axis) if need_pos else jnp.int32(0)

    @jax.custom_vjp
    def ring_attn(ql, kl, vl):
        o, _, _ = _fwd(ql, kl, vl)
        return o

    def _fwd(ql, kl, vl):
        d = _rank()
        b = ql.shape[0]
        qpos = d * s_loc + jnp.arange(s_loc)
        q5 = ql.reshape(b, s_loc, Hkv, g, hd)
        carry = _init_carry(b, Hkv, g, s_loc, hd)
        # local chunk whole: it is resident at t=0, no wire to hide
        carry = _span_flash(q5, kl, vl, qpos, d * s_loc + jnp.arange(s_loc),
                            carry, causal=causal, window=window, scale=scale,
                            cap=cap, q_block=q_block, kv_block=kv_block)
        # the KV payloads round once at their source (compressed wire
        # rings unchanged; every consumer uncasts the same representation)
        kbufs = [wire_cast(s, wire) for s in split_ring_payload(kl, n_sub)]
        vbufs = [wire_cast(s, wire) for s in split_ring_payload(vl, n_sub)]
        for i in range(1, hops + 1):
            src = (d - i) % n
            for j in order:
                kbufs[j] = ring_permute(kbufs[j], axis, n)
                vbufs[j] = ring_permute(vbufs[j], axis, n)
                carry = _span_flash(
                    q5, wire_uncast(kbufs[j], kl.dtype),
                    wire_uncast(vbufs[j], vl.dtype), qpos,
                    src * s_loc + j * sub + jnp.arange(sub), carry,
                    causal=causal, window=window, scale=scale,
                    cap=cap, q_block=q_block, kv_block=kv_block)
        m, l, _ = carry
        o = _finalize(carry, b, s_loc, Hq, hd)
        return o.astype(ql.dtype), m, l

    def fwd_rule(ql, kl, vl):
        o, m, l = _fwd(ql, kl, vl)
        return o, (ql, kl, vl, o, m, l)

    def bwd_rule(res, do):
        ql, kl, vl, o, m, l = res
        d = _rank()
        b = ql.shape[0]
        qpos = d * s_loc + jnp.arange(s_loc)
        q5 = ql.reshape(b, s_loc, Hkv, g, hd)
        # output is fully sharded (not replicated), so the cotangent
        # arrives unsplit — no world scaling (cf. the CE replicated case)
        do5 = do.astype(jnp.float32).reshape(b, s_loc, Hkv, g, hd)
        o5 = o.reshape(b, s_loc, Hkv, g, hd).astype(jnp.float32)
        # delta = rowsum(do * o): [b,hk,g,sq]
        delta = jnp.einsum("bqhgd,bqhgd->bhgq", do5, o5)
        dq5 = jnp.zeros((b, s_loc, Hkv, g, hd), jnp.float32)

        kpos0 = d * s_loc + jnp.arange(s_loc)
        dq5, dk, dv = _span_flash_bwd(
            q5, kl, vl, do5, delta, m, l, qpos, kpos0, dq5,
            causal=causal, window=window, scale=scale, cap=cap,
            q_block=q_block, kv_block=kv_block)
        # replayed KV rings round once at their source (as in forward)
        kbufs = [wire_cast(s, wire) for s in split_ring_payload(kl, n_sub)]
        vbufs = [wire_cast(s, wire) for s in split_ring_payload(vl, n_sub)]

        def dperm(buf, shift=1):
            """One traveling-accumulator hop: uncompressed wire rides the
            operand dtype (pre-wire behavior, bit-identical); compressed
            wire casts on the send and lands back in f32 for the next
            flash-backward accumulation."""
            if not compress:
                return ring_permute(buf, axis, n, shift=shift)
            return wire_uncast(
                ring_permute(wire_cast(buf, wire), axis, n, shift=shift),
                jnp.float32)

        # traveling (dk, dv) accumulators: local representation is f32
        # under a compressed wire, operand dtype otherwise
        def rest(s, ref):
            return s if compress else s.astype(ref.dtype)

        dkbufs = [rest(s, kl) for s in split_ring_payload(dk, n_sub)]
        dvbufs = [rest(s, vl) for s in split_ring_payload(dv, n_sub)]
        for i in range(1, hops + 1):
            src = (d - i) % n
            for j in order:
                kbufs[j] = ring_permute(kbufs[j], axis, n)
                vbufs[j] = ring_permute(vbufs[j], axis, n)
                dkbufs[j] = dperm(dkbufs[j])
                dvbufs[j] = dperm(dvbufs[j])
                dq5, dkf, dvf = _span_flash_bwd(
                    q5, wire_uncast(kbufs[j], kl.dtype),
                    wire_uncast(vbufs[j], vl.dtype), do5, delta, m, l, qpos,
                    src * s_loc + j * sub + jnp.arange(sub), dq5,
                    causal=causal, window=window, scale=scale, cap=cap,
                    q_block=q_block, kv_block=kv_block,
                    dk0=dkbufs[j].astype(jnp.float32),
                    dv0=dvbufs[j].astype(jnp.float32))
                dkbufs[j] = rest(dkf, kl)
                dvbufs[j] = rest(dvf, vl)
        # deliver accumulated (dk, dv) back to the owning rank: the chunk
        # rests hops ranks ahead of its owner -> one offset permute home
        if hops % n != 0:
            dkbufs = [dperm(s, shift=-hops) for s in dkbufs]
            dvbufs = [dperm(s, shift=-hops) for s in dvbufs]
        dkl = dkbufs[0] if n_sub == 1 else jnp.concatenate(dkbufs, axis=1)
        dvl = dvbufs[0] if n_sub == 1 else jnp.concatenate(dvbufs, axis=1)
        dql = dq5.reshape(b, s_loc, Hq, hd).astype(ql.dtype)
        return dql, dkl.astype(kl.dtype), dvl.astype(vl.dtype)

    ring_attn.defvjp(fwd_rule, bwd_rule)
    return ring_attn


# ---------------------------------------------------------------------------
# train/prefill: ring-gathered context attention
# ---------------------------------------------------------------------------
def context_attention(
    ctx: ParallelContext,
    q, k, v,                  # [B, S, Hq|Hkv, hd] global, S sharded over tp
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    softcap_val: float | None = None,
    mode: str | None = None,
    q_block: int = 256,
    kv_block: int = 1024,
    chunks_per_rank: int | str | None = None,
    skew: int | None = None,
    wire: str | None = None,
):
    """``chunks_per_rank`` sub-chunks the KV ring payload (paper Fig. 13);
    ``None`` defers to ``FusionConfig.granularity`` and ``"auto"`` to the
    shape-keyed alpha-beta tuner (:func:`tune_ring_attention`).  ``skew``
    rotates the sub-ring service order by the measured straggler bucket
    (Fig. 14; ``None`` uses ``ctx.fusion.skew``).  ``wire`` compresses
    the KV ring payloads and the traveling (dk, dv) accumulators (f32
    local accumulation; ``None`` uses ``ctx.fusion.wire``)."""
    mode = mode or ctx.fusion.resolve("kv_ag")
    skew = ctx.fusion.skew if skew is None else int(skew)
    axis, n = ctx.tp_axis, ctx.tp
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    dp = ctx.batch_axes if B % ctx.dp == 0 else None
    scale = scale if scale is not None else hd ** -0.5
    s_loc = S // n
    # sliding-window layers statically bound the ring (fused-mode win):
    # only ceil(window / chunk) previous chunks can contain unmasked keys.
    hops = n - 1
    if window is not None and mode != "bulk" and causal:
        hops = min(n - 1, -(-window // s_loc))

    if mode != "bulk":
        b_loc = B // ctx.dp if dp is not None else B
        # the ring payload is the local KV chunk: only q | s_loc matters
        dec = resolve_overlap(
            chunks_per_rank, ctx.fusion.granularity, wire, ctx.fusion.wire,
            lambda fq, wr: tune_ring_attention(
                b_loc, s_loc, Hq, Hkv, hd, dtype_bytes=k.dtype.itemsize,
                n_dev=n, hops=hops, hw=ctx.hw, axis=axis, skew=skew,
                wire=wr, fixed_q=fq),
            dim=s_loc, ring=1)
        ring_attn = _make_ring_attention(
            axis, n, hops, causal, window, scale, softcap_val,
            q_block, kv_block, Hq, Hkv, hd, s_loc, ctx.mesh.size,
            n_sub=dec.q, skew=skew, wire=dec.wire)

    def local_fn(ql, kl, vl):
        d = lax.axis_index(axis)
        b = ql.shape[0]
        qpos = d * s_loc + jnp.arange(s_loc)

        if mode == "bulk":
            q5 = ql.reshape(b, s_loc, Hkv, g, hd)
            kg = lax.all_gather(kl, axis, axis=1, tiled=True)
            vg = lax.all_gather(vl, axis, axis=1, tiled=True)
            carry = _span_flash(q5, kg, vg, qpos, jnp.arange(S),
                                _init_carry(b, Hkv, g, s_loc, hd),
                                causal=causal, window=window, scale=scale,
                                cap=softcap_val, q_block=q_block,
                                kv_block=kv_block)
            return _finalize(carry, b, s_loc, Hq, hd).astype(ql.dtype)

        # fused: local chunk first (available at t=0), then each arriving
        # ring chunk while the next hop's collective-permute is in flight;
        # analytic backward (see _make_ring_attention).
        return ring_attn(ql, kl, vl)

    return shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(dp, axis, None, None),) * 3,
        out_specs=P(dp, axis, None, None),
        check_vma=False,
    )(q, k, v).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode: sequence-sharded KV cache + partial merge
# ---------------------------------------------------------------------------
def broadcast_pos(pos, B):
    """Normalize a decode position to a per-slot vector [B].

    Accepts the legacy scalar (one shared position — every slot at the
    same offset) or a per-slot ``[B]`` vector; always returns ``[B]``
    int32.  Continuous batching requires the vector form: a slot reused
    by a new request restarts at position 0 while its neighbors keep
    counting."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))


def decode_attention(
    ctx: ParallelContext,
    q,                  # [B, 1, Hq, hd] replicated over tp
    k_cache, v_cache,   # [B, S_max, Hkv, hd] S sharded over tp
    pos,                # [B] (or scalar) int32 per-slot position (kv written)
    *,
    window: int | None = None,
    scale: float | None = None,
    softcap_val: float | None = None,
):
    axis, n = ctx.tp_axis, ctx.tp
    B, S_max, Hkv, hd = k_cache.shape
    Hq = q.shape[2]
    g = Hq // Hkv
    dp = ctx.batch_axes if B % ctx.dp == 0 else None
    scale = scale if scale is not None else hd ** -0.5
    s_loc = S_max // n
    pos = broadcast_pos(pos, B)

    def local_fn(ql, kl, vl, p):
        d = lax.axis_index(axis)
        kpos = d * s_loc + jnp.arange(s_loc)
        b = ql.shape[0]
        q5 = ql.reshape(b, 1, Hkv, g, hd)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, kl).astype(jnp.float32) * scale
        if softcap_val is not None:
            s = jnp.tanh(s / softcap_val) * softcap_val
        valid = kpos[None, :] <= p[:, None]            # [b, s_loc] per slot
        if window is not None:
            valid &= p[:, None] - kpos[None, :] < window
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        m = s.max(axis=-1)
        pr = jnp.exp(s - m[..., None])
        l = pr.sum(axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", pr, vl.astype(jnp.float32))
        o = attention_partial_merge(o, m, l, axis)
        return o.transpose(0, 3, 1, 2, 4).reshape(b, 1, Hq, hd)

    return shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(dp, None, None, None), P(dp, axis, None, None),
                  P(dp, axis, None, None), P(dp)),
        out_specs=P(dp, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, pos).astype(q.dtype)


def cache_update(ctx: ParallelContext, cache, new, pos):
    """Write ``new`` [B, 1, *rest] into a sequence-sharded cache
    [B, S_max, *rest], row ``b`` at its own position ``pos[b]``; only the
    owning rank's slice is touched (zero-copy-style: no gather, no
    staging buffer).  A position at/past ``S_max`` is dropped — the
    engine retires a slot *before* it would reach its cache bound
    (:class:`repro.serve.engine.DecodeEngine`), so an in-graph write past
    the end must not silently rewrite the last row."""
    axis, n = ctx.tp_axis, ctx.tp
    B, S_max = cache.shape[:2]
    rest = (None,) * (cache.ndim - 2)
    dp = ctx.batch_axes if B % ctx.dp == 0 else None
    s_loc = S_max // n
    pos = broadcast_pos(pos, B)

    def local_fn(cl, nl, p):
        d = lax.axis_index(axis)
        local_pos = p - d * s_loc                      # [b]
        # rows outside this rank's slice (or past the cache bound) index
        # out of range and are dropped by the scatter
        rows = jnp.where((local_pos >= 0) & (local_pos < s_loc),
                         local_pos, s_loc)
        b = cl.shape[0]
        return cl.at[jnp.arange(b), rows].set(
            nl[:, 0].astype(cl.dtype), mode="drop")

    return shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(dp, axis, *rest), P(dp, None, *rest), P(dp)),
        out_specs=P(dp, axis, *rest),
        check_vma=False,
    )(cache, new, pos)


# ---------------------------------------------------------------------------
# paged KV: block pool + per-request block tables (continuous batching)
# ---------------------------------------------------------------------------
# The dense decode cache above is [B, S_max, ...] — every slot pays for
# the longest request it might ever serve.  The paged layout instead
# shares one pool of fixed-size blocks ([L, NB, block, Hkv*hd]: every
# layer's blocks stacked, heads flattened into the lane dimension, blocks
# sharded over tp) among all in-flight requests; a per-request *block
# table* [B, MB] maps the request's sequence-block m to the pool block
# that holds it (allocation/free lives host-side in
# :class:`repro.serve.kv_cache.PagedKVCache`).  Ragged sequences then
# cost HBM proportional to their actual lengths, not B x S_max.  The
# step writes and reads each layer's blocks in the stacked pool itself,
# by layer index, so no layer's pool is sliced out and written back.
#
# Sharding: pool blocks are sharded *contiguously* over the tp axis
# (rank d owns global blocks [d*NB/n, (d+1)*NB/n)); each rank writes and
# attends only the blocks it owns and the partials merge through the
# same pmax/psum pair as the dense decode path.  The allocator stripes
# handouts across ranks so load stays balanced.

def paged_cache_update(ctx: ParallelContext, pool, new, tables, pos, valid,
                       layer):
    """Scatter a token chunk into layer ``layer`` of the block pool.

    pool: [L, NB, block, *rest] (blocks sharded over tp); new: [B, C,
    *rest]; tables: [B, MB] global block ids; pos: [B, C] global
    positions; valid: [B, C] bool (False rows — padding past a slot's
    ``n_new``, or idle slots — are dropped).  Writes land only on the
    rank owning the target block; positions whose block index falls
    outside the table are dropped, never clamped."""
    axis, n = ctx.tp_axis, ctx.tp
    NB, block = pool.shape[1:3]
    rest = (None,) * (pool.ndim - 3)
    B, C = pos.shape
    MB = tables.shape[1]
    nb_loc = NB // n

    def local_fn(pl, nl, tbl, p, ok, li):
        d = lax.axis_index(axis)
        blk = p // block                                   # [B, C] seq-block
        ok = ok & (blk < MB)
        g = jnp.take_along_axis(tbl, jnp.clip(blk, 0, MB - 1), axis=1)
        local = g - d * nb_loc
        rows = jnp.where(ok & (local >= 0) & (local < nb_loc), local, nb_loc)
        return pl.at[li, rows.reshape(-1), (p % block).reshape(-1)].set(
            nl.reshape((B * C,) + nl.shape[2:]).astype(pl.dtype), mode="drop")

    return shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(None, axis, None, *rest), P(None, None, *rest), P(), P(),
                  P(), P()),
        out_specs=P(None, axis, None, *rest),
        check_vma=False,
    )(pool, new, tables, pos, valid, jnp.asarray(layer, jnp.int32))


def paged_attention(
    ctx: ParallelContext,
    q,                  # [B, C, Hq, hd] replicated over tp
    pool_k, pool_v,     # [L, NB, block, Hkv*hd] blocks sharded over tp
    tables,             # [B, MB] int32 global block ids
    pos,                # [B, C] global position of each query token
    *,
    layer,              # int32 scalar: the layer of the pools to read
    n_new,              # [B] new tokens per slot (0 = idle)
    window: int | None = None,
    scale: float | None = None,
    softcap_val: float | None = None,
    kv_block: int = 1024,
):
    """Flash attention of a token chunk against a paged KV pool.

    On a TPU, for lane-aligned heads and sublane-aligned blocks, each
    rank runs the ragged paged kernel (:mod:`repro.kernels.paged_attention`):
    it reads, straight from the stacked pool, only the blocks it owns
    that the slot's queries can see.  Elsewhere each rank gathers the
    table blocks it owns of the layer and runs the shared flash-update
    machinery over them span by span.  Either way the masks are per slot
    (causal by global position, window), the chunk's own KV is already in
    the pool, so one pass covers both the cache and intra-chunk
    causality, and the partials merge with the same pmax/psum pair as
    the dense decode path.  C=1 is the pure-decode fast path; C>1 is a
    prefill chunk (continuous batching mixes both in one call via the
    per-slot positions)."""
    axis, n = ctx.tp_axis, ctx.tp
    _, NB, block, width = pool_k.shape
    B, C, Hq, hd = q.shape
    Hkv = width // hd
    g = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5
    MB = tables.shape[1]
    nb_loc = NB // n
    span = max(1, min(MB, kv_block // block))   # table blocks per flash span

    def gather_fn(ql, pkl, pvl, tbl, p, _, li):
        d = lax.axis_index(axis)
        b = ql.shape[0]
        q5 = ql.reshape(b, C, Hkv, g, hd)
        pkl = pkl[li].reshape(nb_loc, block, Hkv, hd)
        pvl = pvl[li].reshape(nb_loc, block, Hkv, hd)
        local = tbl - d * nb_loc                           # [B, MB]
        own = (local >= 0) & (local < nb_loc)
        rows = jnp.where(own, local, 0)
        kg = pkl[rows]                                     # [B, MB, blk, ...]
        vg = pvl[rows]
        carry = _init_carry(b, Hkv, g, C, hd)
        for m0 in range(0, MB, span):
            me = min(MB, m0 + span)
            sk = (me - m0) * block
            ks = kg[:, m0:me].reshape(b, sk, Hkv, hd)
            vs = vg[:, m0:me].reshape(b, sk, Hkv, hd)
            kpos = m0 * block + jnp.arange(sk)             # [sk] global pos
            ownmask = jnp.repeat(own[:, m0:me], block, axis=1)  # [B, sk]
            mask = ownmask[:, None, :] & (kpos[None, None, :] <= p[:, :, None])
            if window is not None:
                mask &= p[:, :, None] - kpos[None, None, :] < window
            carry = _flash_update(carry, q5, ks, vs, mask, scale, softcap_val)
        return carry

    def kernel_fn(ql, pkl, pvl, tbl, p, nn, li):
        return paged_attention_shard(
            ql, pkl, pvl, li, tbl, p[:, 0], nn, lax.axis_index(axis) * nb_loc,
            window=window, scale=scale, softcap=softcap_val)

    partials = (kernel_fn if paged_attention_kernel_supported(block, hd)
                else gather_fn)

    def local_fn(ql, pkl, pvl, tbl, p, nn, li):
        b = ql.shape[0]
        m, l, o = partials(ql, pkl, pvl, tbl, p, nn, li)
        o = attention_partial_merge(o, m, l, axis)         # [b,hk,g,C,d]
        return o.transpose(0, 3, 1, 2, 4).reshape(b, C, Hq, hd)

    pool_spec = P(None, axis, None, None)
    return shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(None, None, None, None), pool_spec, pool_spec, P(), P(),
                  P(), P()),
        out_specs=P(None, None, None, None),
        check_vma=False,
    )(q, pool_k, pool_v, tables, pos, jnp.asarray(n_new, jnp.int32),
      jnp.asarray(layer, jnp.int32)).astype(q.dtype)
