"""Public GEMM wrapper: picks block sizes, pads ragged dims, jits."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.gemm.kernel import gemm_pallas


def _block(dim, pref):
    for b in (pref, 256, 128, 64, 32, 16, 8):
        if b <= pref and dim % b == 0:
            return b
    return dim


def gemm(x, w, *, bm=128, bn=128, bk=128):
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = _block(m, bm), _block(n, bn), _block(k, bk)
    return gemm_pallas(x, w, bm=bm, bn=bn, bk=bk)
