"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  No metric
reads these yet; a roofline share of a kernel on the graph path would.
"""
from __future__ import annotations

V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
       "hbm_bytes_per_s": 819e9,
       "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]
