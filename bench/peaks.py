"""Peak rates of each accelerator the benchmark may run on, keyed by JAX's
``device_kind``.  A device that is not listed is an error, never a default."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float            # dense bf16 FLOP/s of one chip
    hbm_bytes_s: float      # HBM bytes/s of one chip
    source: str


_V5E = Peak(flops=197e12, hbm_bytes_s=819e9,
            source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                   "16 GB HBM at 819 GB/s per chip")

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
