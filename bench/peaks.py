"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s per chip.  A device kind missing here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" add them to bench/peaks.py with their source")
    return PEAKS[device_kind]
