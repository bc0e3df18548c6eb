"""Decomposed compute-collective combinators (TPU-adapted paper core).

The paper's GPU kernels issue a non-blocking RDMA PUT per output slice as
soon as the slice's workgroups finish.  The XLA-level TPU equivalent is a
chunked loop in which each chunk's collective (a ``collective-permute``
ring hop or direct offset permute) is issued immediately after that
chunk's compute, while the loop body continues with the next chunk.  The
loops are *unrolled* in python so XLA's latency-hiding scheduler can hoist
``collective-permute-start`` above the following chunk's compute —
yielding the paper's fine-grained overlap without kernel-boundary sync.

All functions here execute *inside* ``jax.shard_map``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.scheduling import ring_offsets, sub_chunk_service_order


def _ring_perm(n: int, shift: int = 1):
    return [(j, (j + shift) % n) for j in range(n)]


# ---------------------------------------------------------------------------
# wire-fault injection hook (chaos engineering)
# ---------------------------------------------------------------------------
# A trace-time hook applied to every payload leaf as it goes on the wire
# (ring hops and the phase-2 all-gather).  ``None`` — the default — is a
# single Python identity check at *trace* time, so the lowered HLO of a
# clean build is bit-identical whether or not chaos is importable.  The
# chaos runtime (:mod:`repro.runtime.chaos`) installs a corruptor here to
# reproduce flipped-link / NaN-payload faults inside the real rings.
_WIRE_FAULT_HOOK = None


def set_wire_fault_hook(hook):
    """Install (or clear, with ``None``) the wire-fault hook.  Returns the
    previous hook so scoped injectors can restore it."""
    global _WIRE_FAULT_HOOK
    prev = _WIRE_FAULT_HOOK
    _WIRE_FAULT_HOOK = hook
    return prev


def _wire_fault(leaf):
    return leaf if _WIRE_FAULT_HOOK is None else _WIRE_FAULT_HOOK(leaf)


def ring_permute(x, axis_name: str, n: int, shift: int = 1):
    """ppermute with the payload dtype pinned.

    Without the barrier XLA may hoist a downstream bf16->f32 convert
    through the permute ("convert of permute == permute of convert"),
    silently doubling wire bytes; the barrier keeps the narrow dtype on
    the wire.  Accepts a pytree payload (the fp8 wire format rides a
    ``(values, scale)`` pair), barriering and permuting every leaf."""
    return jax.tree.map(
        lambda leaf: lax.ppermute(lax.optimization_barrier(_wire_fault(leaf)),
                                  axis_name, _ring_perm(n, shift)), x)


# ---------------------------------------------------------------------------
# wire-dtype compression (CoCoNet-style fused precision conversion)
# ---------------------------------------------------------------------------
# "f32" is the uncompressed setting: the payload travels at the op's
# compute dtype, exactly as before the wire knob existed (bit-identical).
WIRE_DTYPES = ("f32", "bf16", "fp8")
WIRE_SETTINGS = WIRE_DTYPES + ("auto",)
FP8_MAX = 448.0  # float8_e4m3fn finite max


def wire_itemsize(wire: str, dtype_bytes: int) -> int:
    """Bytes per element on the wire.  The wire is never *widened*: a
    bf16 model under ``wire="bf16"`` already travels at 2 bytes."""
    if wire == "bf16":
        return min(2, int(dtype_bytes))
    if wire == "fp8":
        return min(1, int(dtype_bytes))
    return int(dtype_bytes)


def _passthrough(x, wire: str) -> bool:
    if wire in (None, "f32"):
        return True
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return True  # integer payloads (routing ids, ...) stay exact
    return x.dtype.itemsize <= wire_itemsize(wire, x.dtype.itemsize)


def wire_cast(x, wire: str):
    """Compress one ring/A2A payload chunk for the wire.

    bf16: a plain narrowing cast.  fp8: float8_e4m3fn values with a
    per-chunk max-abs scale riding alongside as a ``(values, scale)``
    pair — the scale is a [1] f32 array so it permutes like any payload.
    ``wire="f32"`` (and any non-narrowing combination) is a passthrough,
    keeping the pre-wire graphs bit-identical.
    """
    if wire not in WIRE_DTYPES and wire is not None:
        raise ValueError(f"unknown wire dtype {wire!r}; expected one of "
                         f"{WIRE_DTYPES}")
    if _passthrough(x, wire):
        return x
    if wire == "bf16":
        return x.astype(jnp.bfloat16)
    amax = lax.stop_gradient(jnp.max(jnp.abs(x.astype(jnp.float32))))
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    q = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    return (q, scale.reshape((1,)))


def wire_uncast(payload, dtype):
    """Decompress a :func:`wire_cast` payload back to ``dtype`` (callers
    pass f32 where the value feeds a local accumulation)."""
    if isinstance(payload, tuple):
        q, scale = payload
        return (q.astype(jnp.float32) * scale[0]).astype(dtype)
    return payload.astype(dtype)


def all_gather_wire(x, axis_name: str, n: int, *, axis: int = 0,
                    wire: str = "f32"):
    """``lax.all_gather(..., tiled=True)`` with the payload compressed to
    the wire dtype per source chunk (the phase-2 all-gather of the fused
    AllReduce).  ``wire="f32"`` is the exact pre-wire gather."""
    if _passthrough(x, wire):
        return lax.all_gather(_wire_fault(x), axis_name, axis=axis,
                              tiled=True)
    p = wire_cast(x, wire)
    if isinstance(p, tuple):
        q, scale = p
        qg = lax.all_gather(lax.optimization_barrier(_wire_fault(q)), axis_name,
                            axis=0,
                            tiled=False)          # [n, ...chunk]
        sg = lax.all_gather(scale, axis_name, axis=0, tiled=False)  # [n, 1]
        shape = (n,) + (1,) * q.ndim
        vals = qg.astype(jnp.float32) * sg.reshape(shape)
        parts = [lax.index_in_dim(vals, s, axis=0, keepdims=False)
                 for s in range(n)]
        return jnp.concatenate(parts, axis=axis).astype(x.dtype)
    g = lax.all_gather(lax.optimization_barrier(_wire_fault(p)), axis_name,
                       axis=axis, tiled=True)
    return g.astype(x.dtype)


def feasible_chunks_per_rank(dim: int, n: int, q: int) -> int:
    """Largest q' <= q such that ``dim`` splits evenly into ``n * q'``
    fine chunks (sub-chunk granularity must divide the chunked dim)."""
    q = max(1, int(q))
    while q > 1 and dim % (n * q) != 0:
        q -= 1
    return q


def split_ring_payload(a, n_sub: int, axis: int = 1):
    """Split a ring payload into ``n_sub`` equal sub-chunks along ``axis``
    so each can ring (and be consumed) independently — the paper's
    Fig. 13 sub-chunk granularity.  ``n_sub`` must divide the axis
    (callers clamp via :func:`feasible_chunks_per_rank` first); an
    indivisible split raises rather than silently truncating the payload.
    """
    if n_sub == 1:
        return [a]
    if a.shape[axis] % n_sub:
        raise ValueError(
            f"sub-chunk factor {n_sub} does not divide ring-payload axis "
            f"{axis} of size {a.shape[axis]}; clamp via "
            f"feasible_chunks_per_rank first")
    sub = a.shape[axis] // n_sub
    return [lax.dynamic_slice_in_dim(a, j * sub, sub, axis=axis)
            for j in range(n_sub)]


# ---------------------------------------------------------------------------
# reduce-scatter fused with per-chunk compute (GEMV/GEMM + AllReduce core)
# ---------------------------------------------------------------------------
def ring_reduce_scatter_compute(
    partial_fn: Callable,
    axis_name: str,
    *,
    schedule: str = "comm_aware",
    chunks_per_rank: int = 1,
    sub_axis: int = 0,
    skew: int = 0,
    wire: str = "f32",
):
    """sum_over_ranks(partial_fn(chunk)) -> own rank's reduced chunk.

    ``partial_fn(f)`` returns this rank's *partial* contribution to fine
    output chunk ``f`` (``f`` is a traced index).  With the default
    ``chunks_per_rank=1`` there are exactly ``n`` fine chunks — one per
    rank — and the semantics match the historical single-chunk ring.  With
    ``chunks_per_rank=q > 1`` the output is split into ``n*q`` fine chunks
    (rank ``r`` owns fine chunks ``r*q .. r*q+q-1``, concatenated along
    ``sub_axis``): each ring step's payload is ``q`` sub-chunks, and every
    sub-chunk is put on the wire the moment it is produced, so XLA can
    hide sub-chunk ``s``'s hop behind sub-chunk ``s+1``'s compute — the
    paper's Fig. 13 granularity knob.

    The comm-aware schedule is the overlapped ring: the carry destined for
    rank ``d`` starts at ``d+1``, each hop adds the local partial for the
    in-flight chunk, and a rank's own chunk is accumulated last — remote
    data is on the wire while local partials are still being computed
    (paper Fig. 7b).

    The oblivious schedule computes *all* partials first (natural order)
    and only then runs the pure ring reduce — communication is exposed at
    the tail exactly like the paper's communication-oblivious baseline.

    ``skew`` (a measured straggler rotation, Fig. 14): the ring-carry
    structure pins which chunk each rank touches at every hop, so skew
    rotates the only free axis — the service order of the ``q``
    independent sub-chunk rings — putting the straggler-facing sub-ring
    on the wire first.  Each sub-ring's compute chain is untouched, so
    the result is bit-identical under any skew.

    ``wire`` compresses the ring *carry* (bf16, or fp8 with a per-chunk
    scale riding alongside): the carry is cast on the send side of every
    hop while all local accumulation runs in f32, so quantization error
    enters only through the wire — the fused-precision-conversion move of
    CoCoNet.  ``wire="f32"`` keeps the pre-wire graph bit-identical
    (payloads travel at the compute dtype, partials accumulate in it).
    """
    n = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    q = chunks_per_rank
    order = sub_chunk_service_order(q, skew)
    compress = wire not in (None, "f32")

    def merge(accs, dtype=None):
        out = accs[0] if q == 1 else jnp.concatenate(accs, axis=sub_axis)
        return out if dtype is None else out.astype(dtype)

    if n == 1:
        return merge([partial_fn(jnp.int32(s)) for s in range(q)])

    def part(f):
        p = partial_fn(f)
        return p.astype(jnp.float32) if compress else p

    def hop(acc):
        if not compress:
            return ring_permute(acc, axis_name, n)
        return wire_uncast(ring_permute(wire_cast(acc, wire), axis_name, n),
                           jnp.float32)

    if schedule == "comm_aware":
        accs: list = [None] * q
        out_dtype = None
        for s in order:
            p = partial_fn(((d - 1) % n) * q + s)
            out_dtype = p.dtype
            accs[s] = p.astype(jnp.float32) if compress else p
        for i in range(1, n):
            c = (d - i - 1) % n
            for s in order:
                accs[s] = hop(accs[s]) + part(c * q + s)
        return merge(accs, out_dtype if compress else None)

    if schedule == "oblivious":
        # All compute up front, then a bare ring reduce-scatter.
        parts = [[partial_fn(((d - 1 - i) % n) * q + s) for s in range(q)]
                 for i in reversed(range(n))]
        out_dtype = parts[0][0].dtype
        # parts[j] is the partial for chunk (d - n + j) mod n; the carry
        # schedule consumes them in reverse creation order so the own
        # chunk was produced first (local-first, the paper's baseline).
        accs = [p.astype(jnp.float32) if compress else p
                for p in parts[-1]]  # chunk (d-1)
        for i in range(1, n):
            for s in order:
                nxt = parts[-(i + 1)][s]
                accs[s] = hop(accs[s]) + (nxt.astype(jnp.float32)
                                          if compress else nxt)
        return merge(accs, out_dtype if compress else None)

    raise ValueError(f"unknown schedule {schedule!r}")


# ---------------------------------------------------------------------------
# all-gather fused with per-chunk consumption (AG + matmul / KV-gather core)
# ---------------------------------------------------------------------------
def ring_all_gather_compute(
    x_local,
    consume_fn: Callable,
    axis_name: str,
    *,
    combine: str = "place",
    out_init=None,
    wire: str = "f32",
):
    """Gather ``x_local`` around the ring, applying ``consume_fn`` to each
    arriving shard immediately (while the next hop is in flight).

    consume_fn(src_index, x_src, acc) -> acc'   (src_index is traced)

    combine="place" is a convenience: consume_fn returns (y_src, position
    placer handled by caller through acc).  The local shard is consumed
    first — it is available at t=0, so its compute hides the first hop.

    ``wire`` compresses the forwarded shard *once at its source* (the
    compressed payload then rings unchanged, so remote shards round
    exactly once regardless of hop count); the local shard is consumed
    uncompressed.  ``wire="f32"`` is the exact pre-wire path.
    """
    n = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    acc = consume_fn(d, x_local, out_init)
    buf = wire_cast(x_local, wire) if wire not in (None, "f32") else x_local
    for i in range(1, n):
        buf = ring_permute(buf, axis_name, n)
        acc = consume_fn((d - i) % n, wire_uncast(buf, x_local.dtype), acc)
    return acc


# ---------------------------------------------------------------------------
# direct all-to-all fused with per-destination compute (GEMM/embedding + A2A)
# ---------------------------------------------------------------------------
def direct_all_to_all_compute(
    produce_fn: Callable,
    out_shape_dtype,
    axis_name: str,
    *,
    schedule: str = "comm_aware",
    chunks_per_rank: int = 1,
    sub_axis: int = 0,
    skew: int = 0,
    wire: str = "f32",
):
    """Fused compute + All-to-All via per-destination direct sends.

    With the default ``chunks_per_rank=1``, ``produce_fn(dest)`` computes
    the full chunk this rank owes rank ``dest`` (traced index).  With
    ``chunks_per_rank=q > 1`` the payload for each destination is split
    into ``q`` sub-chunks along ``sub_axis`` and ``produce_fn(f)`` is
    called with the *fine* index ``f = dest * q + s``; each sub-chunk is
    sent the moment it is produced, so sub-chunk ``s``'s wire time hides
    behind sub-chunk ``s+1``'s compute (paper Fig. 13 granularity knob).
    ``out_shape_dtype`` always describes the full per-destination chunk.

    Each send is a single offset collective-permute — the TPU analogue of
    the paper's per-slice RDMA PUT (one logical point-to-point transaction
    per destination, data moved in final layout, no post-shuffle).

    Returns ``[n, *chunk_shape]`` stacked by *source* rank.

    comm_aware: farthest destination first, own chunk last (paper's
    remote-ahead-of-local rule).  oblivious: natural order (Fig. 14
    baseline).  ``skew`` rotates the remote portion of the destination
    order (a measured straggler rotation — Fig. 14), exactly matching the
    schedule :func:`repro.core.scheduling.sub_chunk_send_events` models;
    per-destination chunks are independent, so the output is bit-identical
    under any skew.

    ``wire`` compresses each remote send (bf16, or fp8 + per-chunk scale)
    on the producer side; the receiver uncasts into the output dtype.
    Every payload is a one-shot point-to-point transaction, so each value
    rounds exactly once.  The locally-consumed chunk never touches the
    wire and stays exact; ``wire="f32"`` is the exact pre-wire path.
    """
    n = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    q = chunks_per_rank
    chunk_shape = tuple(out_shape_dtype.shape)
    out = jnp.zeros((n,) + chunk_shape, out_shape_dtype.dtype)
    if chunk_shape[sub_axis] % q:
        raise ValueError(
            f"sub-chunk factor {q} does not divide destination-chunk axis "
            f"{sub_axis} of size {chunk_shape[sub_axis]}; clamp via "
            f"feasible_chunks_per_rank first")
    sub = chunk_shape[sub_axis] // q

    def place(out, ysub, src, s):
        starts = [jnp.int32(0)] * out.ndim
        starts[0] = src
        starts[sub_axis + 1] = jnp.int32(s * sub)
        return lax.dynamic_update_slice(out, ysub[None], tuple(starts))

    for off in ring_offsets(n, schedule, skew):
        dest = (d + off) % n
        for s in range(q):
            y = produce_fn(dest * q + s) if q > 1 else produce_fn(dest)
            if off == 0:
                recv, src = y, d
            else:
                recv = wire_uncast(
                    ring_permute(wire_cast(y, wire), axis_name, n,
                                 shift=off), y.dtype)
                src = (d - off) % n
            out = place(out, recv, src, s)
    return out


def bulk_all_to_all(x, axis_name: str):
    """Baseline: single All-to-All over leading dim [n, ...] -> [n, ...]."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=False)


# ---------------------------------------------------------------------------
# partial-softmax merge (context-sharded decode attention)
# ---------------------------------------------------------------------------
def attention_partial_merge(o, m, l, axis_name: str):
    """Merge flash-attention partials across a KV-sharded axis.

    o: [..., d] unnormalized partial output (sum of exp(s - m) * v)
    m: [...]    local running max
    l: [...]    local sum of exp(s - m)

    One tiny psum/pmax pair replaces the paper's ``sliceRdy`` polling: the
    collective itself is the readiness signal.
    """
    m_glob = lax.pmax(lax.stop_gradient(m), axis_name)
    corr = jnp.exp(m - m_glob)
    l_glob = lax.psum(l * corr, axis_name)
    o_glob = lax.psum(o * corr[..., None], axis_name)
    return o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
