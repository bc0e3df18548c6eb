"""Pure-jnp oracle for the ragged paged-attention kernel.

Per slot: the keys at the positions its table's blocks hold, up to its
last new token, each query seeing keys at positions ``k <= p`` (and
``p - k < window``); softmax in f32 over one un-sharded pool.
"""
import jax.numpy as jnp


def paged_attention_ref(q, k_pool, v_pool, tables, pos, *, scale,
                        window=None, softcap=None):
    """q: [B, C, Hq, hd]; pools [NB, block, Hkv, hd]; tables [B, MB];
    pos: [B, C] global positions -> [B, C, Hq, hd] f32."""
    B, C, Hq, hd = q.shape
    _, block, n_kv, _ = k_pool.shape
    g = Hq // n_kv
    rows = jnp.maximum(tables, 0)
    k = k_pool[rows].reshape(B, -1, n_kv, hd).astype(jnp.float32)
    v = v_pool[rows].reshape(B, -1, n_kv, hd).astype(jnp.float32)
    kpos = jnp.arange(k.shape[1])
    ok = jnp.repeat(tables >= 0, block, axis=1)[:, None, :]
    ok = ok & (kpos[None, None, :] <= pos[:, :, None])
    if window is not None:
        ok &= pos[:, :, None] - kpos[None, None, :] < window
    q5 = q.reshape(B, C, n_kv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(ok[:, None, None], s, -jnp.inf)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p / p.sum(-1, keepdims=True), v)
    return o.reshape(B, C, Hq, hd)
