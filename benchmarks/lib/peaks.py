"""Published peaks of the chips the benchmark knows, keyed by the exact
``device_kind`` JAX reports.  A device that is not here is an error, never
a default.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture
(per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s inter-chip interconnect).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"to benchmarks/lib/peaks.py with its source "
            f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
