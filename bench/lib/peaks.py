"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error: a share of a peak that is
not known is not reported.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16 per chip
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; have {sorted(PEAKS)}")
    return PEAKS[device_kind]
