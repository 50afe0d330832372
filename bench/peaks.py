"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

TPU v5e: 197 TFLOP/s bf16, 819 GB/s and 16 GiB of HBM (Google Cloud
documentation, "TPU v5e"; JAX names the chip "TPU v5 lite").  A device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
