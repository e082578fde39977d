"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/
docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per
chip.  A device that is not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/core/peaks.py")
    return float(PEAKS[device_kind][key])
