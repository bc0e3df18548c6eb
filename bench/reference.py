"""Plain float32 reference of the served GQA transformer.

Written from the layer equations, with nothing of the program imported:
token embedding; per layer RMSNorm, a QKV projection, rotary embedding
(``"2d"``: the first half of each head in interleaved pairs, as GLM;
``"full"``: the whole head in interleaved pairs), causal (optionally
windowed) grouped-query softmax attention, the output projection and a
residual add, then RMSNorm, a SiLU-gated MLP and a residual add; a final
RMSNorm and logits against the embedding table (the LM head is tied).
Every matrix product runs at ``Precision.HIGHEST``.

It runs layer by layer, drawing each layer's weights from the seed
(:mod:`bench.weights`) and dropping them before the next, and attends
and projects the vocabulary in blocks of query rows, so it fits beside
nothing else on one chip at full width.

``gaps`` teacher-forces each request's prompt and served tokens and
reads, at every position that produced a served token, how far that
token's logit lies below the reference's best.  With ``control=True`` it
also runs the same forward with every matrix product's operands rounded
to float8 (e4m3, scaled per tensor for weights and per row for
activations), the step below the served bfloat16, and reads the gap of
the token that this lower precision puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.weights import global_weight, layer_weight, root_key

HI = lax.Precision.HIGHEST
F8_MAX = 448.0
Q_BLOCK = 256


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per ``axis`` slice (None: one
    scale for the tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, low):
    if low:
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, style, theta):
    """x [N, S, H, hd], pos [S]."""
    hd = x.shape[-1]
    rd = hd // 2 if style == "2d" else hd
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = pos.astype(jnp.float32)[:, None] * inv            # [S, rd/2]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0:rd:2], x[..., 1:rd:2]
    rot = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    return jnp.concatenate([rot.reshape(x.shape[:-1] + (rd,)), x[..., rd:]],
                           -1)


def _names(shape):
    d, hd, f = shape.d_model, shape.head_dim, shape.d_ff
    qkv = (shape.n_heads + 2 * shape.n_kv_heads) * hd
    return {"ln1": (d,), "ln2": (d,), "attn/w_qkv": (d, qkv),
            "attn/w_o": (shape.n_heads * hd, d), "ffn/w_gate": (d, f),
            "ffn/w_up": (d, f), "ffn/w_down": (f, d)}


@functools.partial(jax.jit, static_argnums=(2,))
def _layer_weights(root, i, shape):
    return {k: layer_weight(root, f"layers/l0/{k}", i, s, shape.d_model,
                            shape.dtype)
            for k, s in _names(shape).items()}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, shape, low):
    """One transformer layer over x [N, S, D] at positions 0 .. S-1."""
    N, S, _ = x.shape
    Hq, Hkv, hd = shape.n_heads, shape.n_kv_heads, shape.head_dim
    g = Hq // Hkv
    pos = jnp.arange(S)
    h = rms_norm(x, w["ln1"], shape.norm_eps)
    qkv = _mm(h, w["attn/w_qkv"], low)
    q = qkv[..., :Hq * hd].reshape(N, S, Hq, hd)
    k = qkv[..., Hq * hd:(Hq + Hkv) * hd].reshape(N, S, Hkv, hd)
    v = qkv[..., (Hq + Hkv) * hd:].reshape(N, S, Hkv, hd)
    q = rope(q, pos, shape.rope_style, shape.rope_theta)
    k = rope(k, pos, shape.rope_style, shape.rope_theta)
    q = q.reshape(N, S, Hkv, g, hd) * hd ** -0.5
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        qp = pos[q0:q0 + Q_BLOCK]
        s = jnp.einsum("nqkgd,nskd->nkgqs", qb, k, precision=HI)
        mask = pos[None, :] <= qp[:, None]
        if shape.window:
            mask &= qp[:, None] - pos[None, :] < shape.window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("nkgqs,nskd->nqkgd", p, v, precision=HI))
    o = jnp.concatenate(outs, 1).reshape(N, S, Hq * hd)
    x = x + _mm(o, w["attn/w_o"], low)
    h = rms_norm(x, w["ln2"], shape.norm_eps)
    f = jax.nn.silu(_mm(h, w["ffn/w_gate"], low)) * _mm(h, w["ffn/w_up"],
                                                         low)
    return x + _mm(f, w["ffn/w_down"], low)


@functools.partial(jax.jit, static_argnums=(4,))
def _head_gaps(x, x_low, served, root, shape):
    """Per position: the reference's best logit minus its logit of the
    served token, and minus its logit of the lower precision's choice."""
    table = global_weight(root, "embed/table", (shape.vocab, shape.d_model),
                          shape.d_model, shape.dtype)
    norm = global_weight(root, "final_norm", (shape.d_model,),
                         shape.d_model, jnp.float32)
    gaps, low_gaps = [], []
    for q0 in range(0, x.shape[1], Q_BLOCK):
        h = rms_norm(x[:, q0:q0 + Q_BLOCK], norm, shape.norm_eps)
        logits = jnp.einsum("nsd,vd->nsv", h, table, precision=HI)
        best = logits.max(-1)
        tok = served[:, q0:q0 + Q_BLOCK]
        gaps.append(best - jnp.take_along_axis(logits, tok[..., None],
                                               -1)[..., 0])
        if x_low is not None:
            hl = rms_norm(x_low[:, q0:q0 + Q_BLOCK], norm, shape.norm_eps)
            low = jnp.einsum("nsd,vd->nsv", _q8(hl, -1), _q8(table, None),
                             precision=HI)
            pick = low.argmax(-1)
            low_gaps.append(best - jnp.take_along_axis(
                logits, pick[..., None], -1)[..., 0])
    return (jnp.concatenate(gaps, 1),
            jnp.concatenate(low_gaps, 1) if low_gaps else None)


def _bucket(n: int) -> int:
    """Padded length: a power of two of at least 512, so that a few
    program shapes, cached after the first runs, serve every request."""
    return max(512, 1 << (n - 1).bit_length())


def _one(root, shape, prompt, tokens, control):
    ids = list(prompt) + list(tokens)[:-1]
    S = _bucket(len(ids))
    inp = np.zeros((1, S), np.int32)
    served = np.zeros((1, S), np.int32)
    inp[0, :len(ids)] = ids
    served[0, len(prompt) - 1:len(prompt) - 1 + len(tokens)] = tokens
    table = global_weight(root, "embed/table", (shape.vocab, shape.d_model),
                          shape.d_model, shape.dtype)
    x = jnp.take(table, jnp.asarray(inp), axis=0)
    del table
    x_low = x if control else None
    for i in range(shape.n_layers):
        w = _layer_weights(root, jnp.int32(i), shape)
        x = _layer(x, w, shape, False)
        if control:
            x_low = _layer(x_low, w, shape, True)
        del w
    g, gl = _head_gaps(x, x_low, jnp.asarray(served), root, shape)
    sl = slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    return np.asarray(g)[0, sl], (np.asarray(gl)[0, sl] if control
                                  else None)


def gaps(seed: int, shape, seqs, *, control: bool = False):
    """For each (prompt, served tokens) in ``seqs``: an array with, per
    served token, the reference's best logit minus its logit of that
    token; with ``control``, a second list of arrays with the same for
    the token that the float8 forward puts first.  Returns (gaps,
    control_gaps or None)."""
    root = root_key(seed)
    out = [_one(root, shape, p, t, control) for p, t in seqs]
    return [g for g, _ in out], ([c for _, c in out] if control else None)
