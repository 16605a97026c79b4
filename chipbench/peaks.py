"""Published peaks of each chip the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind not in the table is an error.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; have "
                         f"{sorted(PEAKS)}") from None
