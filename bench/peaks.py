"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. No
float32 peak is published; float32 matmuls at precision "highest" run as
several bfloat16 passes, so a float32 configuration's share of the bf16
peak is small by construction.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" add them to bench/peaks.py with their source"
                       ) from None
