"""The dense GQA transformer family: its shape, its work counts and its
plain float32 reference.

Work counts.  Operations and bytes of the served model, from its shapes
alone: the yardstick of every roofline share and utilisation the
benchmark prints.  They are computed from the configuration's ``shape``
group and the live context lengths of a step, never from the compiled
program, so that a change to the program cannot move them.  A step, per
layer: a QKV projection [d_model, (Hq + 2 Hkv) hd], attention over the
context, an output projection [Hq hd, d_model], and a gated MLP of three
[d_model, d_ff] matrices; then a final norm and a tied LM head
[vocab, d_model] for the last new token of each slot.

Reference.  Written from the layer equations, with nothing of the
program imported: token embedding; per layer RMSNorm, a QKV projection,
rotary embedding (``"2d"``: the first half of each head in interleaved
pairs, as GLM; ``"full"``: the whole head in interleaved pairs), causal
(optionally windowed) grouped-query softmax attention, the output
projection and a residual add, then RMSNorm, a SiLU-gated MLP and a
residual add; a final RMSNorm and logits against the embedding table
(the LM head is tied).  Every matrix product runs at
``Precision.HIGHEST``.

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

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.weights import global_weight, layer_weight, root_key
from bench.work import BF16, F32


# -- shape and work counts ---------------------------------------------
@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_style: str = "full"
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    window: int | None = None
    max_seq: int = 4096
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        return cls(**cfg["shape"])

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        qkv = d * (self.n_heads + 2 * self.n_kv_heads) * hd
        wo = self.n_heads * hd * d
        return qkv + wo + 3 * d * self.d_ff

    @property
    def params(self) -> int:
        """Every weight: layers (norms included), final norm, embedding
        table (which is also the LM head)."""
        per_layer = self.layer_matmul_params + 2 * self.d_model
        return (self.n_layers * per_layer + self.d_model
                + self.vocab * self.d_model)

    @property
    def weight_bytes(self) -> int:
        """Bytes one step reads of the weights: every matrix in bf16 and
        the norms in f32 (the embedding rows a step looks up are counted
        with its tokens)."""
        norms = (2 * self.n_layers + 1) * self.d_model
        return ((self.n_layers * self.layer_matmul_params
                 + self.vocab * self.d_model) * BF16 + norms * F32)

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over every layer."""
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim * BF16


def _context(shape: Shape, pos: int) -> int:
    """Keys a query at ``pos`` attends to."""
    n = pos + 1
    return min(n, shape.window) if shape.window else n


def token_flops(shape: Shape, pos: int) -> float:
    """Model FLOPs of one token at position ``pos`` through every layer
    (projections, MLP and attention over its context), without the LM
    head."""
    attn = 4 * _context(shape, pos) * shape.n_heads * shape.head_dim
    return shape.n_layers * (2.0 * shape.layer_matmul_params + attn)


def head_flops(shape: Shape) -> float:
    """The tied LM head for one token."""
    return 2.0 * shape.d_model * shape.vocab


def step_flops(shape: Shape, slots) -> float:
    """FLOPs of one serve step; ``slots`` lists (pos, n_new) of every slot
    that does work in it: n_new tokens at positions pos .. pos+n_new-1,
    and one row of logits."""
    total = 0.0
    for pos, n_new in slots:
        total += sum(token_flops(shape, p) for p in range(pos, pos + n_new))
        total += head_flops(shape)
    return total


def step_bytes(shape: Shape, slots) -> float:
    """Least bytes one serve step moves through HBM: the weights once, the
    K/V each slot's new tokens attend to (its live context, not the whole
    table), the K/V it writes, and its embedding rows."""
    kv = 0
    for pos, n_new in slots:
        kv += _context(shape, pos + n_new - 1) + n_new
    tokens = sum(n for _, n in slots)
    return (shape.weight_bytes + kv * shape.kv_bytes_per_token
            + tokens * shape.d_model * BF16)


# -- reference ---------------------------------------------------------
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
