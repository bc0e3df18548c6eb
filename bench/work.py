"""Least times of the served work, from its operations and bytes.

The operations and bytes of a model's step are its family's
(``bench/families/<family>.py``: ``step_flops``, ``step_bytes``); what
is here belongs to no family: the byte widths, the least time of a step
on given chips, and the least time of one GEMV+AllReduce call.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def step_least_seconds(flops: float, nbytes: float, chips: int,
                       peaks) -> tuple:
    """(seconds, bound) of the least time ``chips`` chips can take for one
    serve step of ``flops`` operations that moves ``nbytes`` through HBM,
    with the work split evenly: the larger of FLOPs over peak FLOP/s and
    bytes over HBM bandwidth."""
    t_flops = flops / chips / peaks.flops_bf16
    t_bytes = nbytes / chips / peaks.hbm_bytes_s
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
