"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,          # FLOP/s, dense bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (system "
                  "architecture): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s",
    },
}


def peak(device_kind: str) -> Dict[str, object]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; add it to bench/peaks.py")
    return PEAKS[device_kind]
