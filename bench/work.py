"""Operations and bytes of the served model, from its shapes alone.

These are the yardstick of every roofline share and utilisation the
benchmark prints.  They are computed from the configuration's ``shape``
group and the live context lengths of a step, never from the compiled
program, so that a change to the program cannot move them.

A step of the GQA transformer, per layer: a QKV projection
[d_model, (Hq + 2 Hkv) hd], attention over the context, an output
projection [Hq hd, d_model], and a gated MLP of three [d_model, d_ff]
matrices; then a final norm and a tied LM head [vocab, d_model] for the
last new token of each slot.
"""
from __future__ import annotations

import dataclasses

BF16 = 2
F32 = 4


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


def step_least_seconds(shape: Shape, slots, chips: int, peaks) -> tuple:
    """(seconds, bound) of the least time ``chips`` chips can take for one
    serve step, with the work split evenly: the larger of FLOPs over peak
    FLOP/s and bytes over HBM bandwidth."""
    t_flops = step_flops(shape, slots) / chips / peaks.flops_bf16
    t_bytes = step_bytes(shape, slots) / chips / peaks.hbm_bytes_s
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")


def gemv_allreduce_least_seconds(rows: int, k: int, n: int, chips: int,
                                 peaks) -> tuple:
    """(seconds, bound) of one GEMV/GEMM+AllReduce call on each of
    ``chips`` chips: x [rows, k] @ w [k, n] with k split over the chips,
    the [rows, n] bf16 partial sums all-reduced.  Bounds: the weight
    slice and activations through HBM; the all-reduce's 2 (chips-1)/chips
    of the output through the chip's ICI links; the FLOPs."""
    k_loc = k // chips
    hbm = (k_loc * n + rows * k_loc + rows * n) * BF16 / peaks.hbm_bytes_s
    ici = (2 * (chips - 1) / chips * rows * n * BF16 / peaks.ici_bytes_s
           if chips > 1 else 0.0)
    flops = 2.0 * rows * k_loc * n / peaks.flops_bf16
    return max((hbm, "hbm"), (ici, "ici"), (flops, "flops"))
