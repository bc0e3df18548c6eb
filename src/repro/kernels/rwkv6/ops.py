"""Public WKV6 wrapper: folds [B, T, H, N] heads into the grid batch."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.rwkv6.kernel import wkv6_pallas


def wkv6(r, k, v, w, u, *, chunk=32):
    """r,k,v,w: [B, T, H, N] (w = decay in (0,1)); u: [H, N] -> [B,T,H,N]."""
    b, t, h, n = r.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, n)
    lw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-8, 1.0))
    uu = jnp.broadcast_to(u[None], (b, h, n)).reshape(b * h, 1, n)
    o = wkv6_pallas(fold(r).astype(jnp.float32), fold(k).astype(jnp.float32),
                    fold(v).astype(jnp.float32), fold(lw), uu,
                    chunk=min(chunk, t))
    return o.reshape(b, h, t, n).transpose(0, 2, 1, 3)
