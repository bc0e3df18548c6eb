"""Wrapper of the ragged paged-attention kernel: the per-rank block list,
the query layout, and the partials in the merge's layout."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.paged_attention.kernel import paged_attention_pallas

KEYS_PER_GROUP = 128     # positions per streamed group of blocks


def paged_attention_kernel_supported(block: int, hd: int) -> bool:
    """The kernel runs on a TPU backend, for lane-aligned heads and
    sublane-aligned blocks; elsewhere the caller keeps its XLA path."""
    return not interpret_mode() and hd % 128 == 0 and block % 8 == 0


def block_list(tables, first, last, lo, nb_loc):
    """Each slot's readable blocks, compacted in table order.

    tables: [B, MB] global block ids (``FREE_BLOCK`` = -1 for none);
    first, last: [B] the slot's sequence blocks its queries can see
    (``last < first`` for none); the rank owns global blocks
    ``[lo, lo + nb_loc)``.  Returns (ids, lblk, cnt): [B, MB] local pool
    ids and sequence-block indices, of which each row's first ``cnt``
    entries are live, and [B] counts."""
    mb = tables.shape[1]
    m = jnp.arange(mb, dtype=jnp.int32)[None]
    local = tables - lo
    keep = ((local >= 0) & (local < nb_loc)
            & (m >= first[:, None]) & (m <= last[:, None]))
    lblk = jnp.argsort(jnp.where(keep, m, mb + m), axis=1).astype(jnp.int32)
    cnt = keep.sum(axis=1, dtype=jnp.int32)
    ids = jnp.where(m < cnt[:, None],
                    jnp.take_along_axis(local, lblk, axis=1), 0)
    return ids, lblk, cnt


def paged_attention_shard(ql, pk, pv, layer, tables, pos0, n_new, lo, *,
                          window=None, scale, softcap=None):
    """Call inside shard_map.  ql: [B, C, Hq, hd]; pk, pv: this rank's
    pool blocks [L, NB_loc, block, Hkv * hd], global ids ``lo ..``, read
    at layer ``layer``; tables [B, MB]; pos0, n_new: [B] first new
    position and new tokens per slot (0 = idle).  Returns the
    unnormalised partials in f32, in the order and layout of the flash
    carry: (m, l [B, Hkv, g, C], o [B, Hkv, g, C, hd])."""
    B, C, Hq, hd = ql.shape
    _, nb_loc, block, width = pk.shape
    n_kv = width // hd
    g = Hq // n_kv
    last = jnp.where(n_new > 0, (pos0 + n_new - 1) // block, -1)
    first = (jnp.zeros_like(pos0) if window is None
             else jnp.maximum(pos0 - window + 1, 0) // block)
    ids, lblk, cnt = block_list(tables, first, last, lo, nb_loc)
    q = ql.reshape(B, C, n_kv, g, hd).transpose(0, 2, 3, 1, 4)
    with jax.named_scope("paged_attention"):
        o, m, l = paged_attention_pallas(
            q.reshape(B, n_kv, g * C, hd), pk, pv, layer,
            ids.reshape(-1), lblk.reshape(-1), cnt, pos0.astype(jnp.int32),
            chunk=C, scale=scale, window=window, softcap=softcap,
            group=max(1, KEYS_PER_GROUP // block))
    return (m.reshape(B, n_kv, g, C), l.reshape(B, n_kv, g, C),
            o.reshape(B, n_kv, g, C, hd))
