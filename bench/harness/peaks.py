"""Chip peaks, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.

Every predict tier of this system runs its matmuls as bf16 MXU passes:
float32 at default matmul precision is one bf16 pass, and the int8 tier
is fake-quantized (int8 values, float32 storage, bf16 passes).  So the
bf16 peak is the peak for every tier.  A device kind that is not in the
table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       "published numbers to bench/harness/peaks.py") from None
