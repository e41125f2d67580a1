"""Published single-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
int8, 819 GB/s HBM). A device that is not in the table is an error, not a
default.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            "to benchmark/peaks.py with its source"
        ) from None
