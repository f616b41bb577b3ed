"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM (Google Cloud documentation,
"TPU v5e", system architecture table). A device that is not here is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to bench/peaks.py with their source")
    return PEAKS[device_kind]
