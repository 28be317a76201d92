"""Published peaks of each chip the benchmark runs on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
819 GB/s HBM bandwidth, 16 GB HBM, 197 TFLOP/s bf16, 393 TOP/s int8.
"""
from __future__ import annotations

_V5E = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
