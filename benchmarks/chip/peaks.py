"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def codec_hbm_bytes(elements: int, bits: int) -> int:
    """Bytes the chip must move to quantise (or restore) ``elements``
    float32 values at ``bits`` per code: each value read or written once
    as float32 and once as a code.  This is the work of the field, not of
    any one kernel's tiling: per-block statistics, padding and broadcast
    rows are left out, so a kernel that moves less of them reads higher."""
    return elements * (4 + bits // 8)
