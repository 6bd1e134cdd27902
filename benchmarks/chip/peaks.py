"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a share of
a peak is only as true as the peak it divides by.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bfloat16 matrix units
        "bytes_per_s": 819e9,  # HBM bandwidth
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, 16 GiB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; add them to peaks.py") from None
