"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e": 197
TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect. A device that is not in the table is an
error, not a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
