"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

A copy of `kubeflow_tpu/train/profiling.CHIP_PEAKS`, kept here because
later PRs may edit the program's table and may not edit this one. Every
MFU and roofline share in the benchmark divides by an entry of it; a
device that is not here is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float  # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


CHIP_PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        197e12, 819e9, 16 * 10**9,
        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "16 GB HBM2e at 819 GB/s per chip",
    ),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)}); add the chip with its source"
        ) from None
