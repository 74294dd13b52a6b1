"""Published peaks of the devices the benchmark may run on, keyed by
``device_kind`` as JAX reports it. A device that is not here is an
error, never a default."""

from __future__ import annotations

#: source: Google Cloud documentation, "TPU v5e" system architecture:
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add it to harness/peaks.py "
                       f"with its source")
    return PEAKS[device_kind]
