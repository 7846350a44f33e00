"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
16 GB of HBM at 819 GB/s, per chip. A kind not in the table is an error, not
a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16 * 2 ** 30,
                    "source": "Google Cloud, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]
