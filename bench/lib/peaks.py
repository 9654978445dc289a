"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error: a share of an unknown peak
would be a guess.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]
