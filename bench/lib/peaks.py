"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip that is not in the table is an error.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 and
16 GB of HBM at 819 GB/s per chip.  The bf16 peak is the MXU's highest
rate; an f32 contraction at ``Precision.HIGHEST`` takes several bf16
passes, so a share of this peak stays at or below 100%.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peak", "PEAKS", "peak"]


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float      # operations per second
    hbm_bw: float     # bytes per second
    source: str


PEAKS = {
    "TPU v5 lite": Peak(flops=197e12, hbm_bw=819e9,
                        source="Google Cloud, TPU v5e: 197 TFLOP/s bf16, "
                               "819 GB/s HBM"),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
