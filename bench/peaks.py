"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect.  A device that is not in the table is an error:
a share of a peak needs the peak of the chip that ran.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    ici_bytes_s: float     # bytes/s, all of one chip's links together
    hbm_bytes: float       # device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bytes_s=819e9, ici_bytes_s=1600e9 / 8,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
