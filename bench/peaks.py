"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error: a
share of a peak is never computed against a guess."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float  # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip",
    ),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
