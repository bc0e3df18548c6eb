"""Public flash-attention wrapper: folds [B, S, H, d] to [BH, S, d]."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas


def _block(dim, pref):
    for b in (pref, 128, 64, 32, 16, 8):
        if b <= pref and dim % b == 0:
            return b
    return dim


def flash_attention(q, k, v, *, scale=None, causal=True, bq=128, bkv=128):
    """q,k,v: [B, S, H, d] (equal head counts; GQA expansion upstream)."""
    b, s, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = flash_attention_pallas(
        fold(q), fold(k), fold(v), scale=scale, causal=causal,
        bq=_block(s, bq), bkv=_block(s, bkv))
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
