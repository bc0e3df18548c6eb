"""Public GEMV wrapper."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.gemv.kernel import gemv_pallas


def _block(dim, pref):
    for b in (pref, 512, 256, 128, 64, 32, 16, 8):
        if b <= pref and dim % b == 0:
            return b
    return dim


def gemv(x, w, *, bn=256, bk=512):
    """x: [K] or [B, K] small-batch; w: [K, N]."""
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    k, n = w.shape
    out = gemv_pallas(x, w, bn=_block(n, bn), bk=_block(k, bk))
    return out[0] if squeeze else out
