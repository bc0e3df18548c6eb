"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Each kernel package ships three modules:
  kernel.py - pl.pallas_call body with explicit BlockSpec VMEM tiling
  ops.py    - jit'd public wrapper (shape checks, dtype policy, vmap rules)
  ref.py    - pure-jnp oracle used by the allclose test sweeps

Off the TPU the kernels run in the Pallas interpreter (validation); on
the TPU the same calls lower to Mosaic (:func:`resolve_interpret`).  The fused_* kernels use device-initiated remote DMA
(pltpu.make_async_remote_copy) — the TPU analogue of the paper's
GPU-initiated RDMA PUTs.

The fused kernels are *tile-granular pipelines* built on
``repro.kernels.tile_pipeline``: a multi-step grid streams operand
panels HBM→VMEM through a double buffer and PUTs each output tile to its
peer the moment the tile's accumulation completes, so DMA-in, MXU
compute, and remote DMA-out overlap.  Tile width (and the XLA-level
``chunks_per_rank`` sibling knob, see ``FusionConfig.granularity``) is
picked by the shape-keyed autotuner in ``repro.core.autotune``.
"""


def interpret_mode() -> bool:
    import jax

    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel entry's ``interpret`` argument: ``None`` (every entry's
    default) means the interpreter exactly when the default backend is
    not a TPU; a caller that compiles for a described chip passes
    ``False``."""
    return interpret_mode() if interpret is None else interpret


_FP8_CLAMP_WARNED: set = set()


def clamp_kernel_wire(wire: str, op: str) -> str:
    """Device-initiated kernels stage PUT payloads at the wire dtype but
    have no per-chunk-scale path, so ``"fp8"`` is clamped to ``"bf16"``.
    Warns once per op family so ``--wire fp8`` users see the clamp instead
    of silently reading bf16 decisions out of the tune cache."""
    if wire != "fp8":
        return wire
    if op not in _FP8_CLAMP_WARNED:
        _FP8_CLAMP_WARNED.add(op)
        import warnings

        warnings.warn(
            f"{op}: wire='fp8' is an XLA-path feature (per-chunk scale); "
            f"the device-initiated kernel clamps the PUT payload to bf16",
            stacklevel=3)
    return "bf16"
