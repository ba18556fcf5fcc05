"""Published peaks of the chips this benchmark may run on, keyed by the
exact `device_kind` JAX reports. A device that is not here is an error,
never a default: a utilization against a guessed peak is not a number.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmarks/peaks.py has no entry for device_kind "
            f"{device_kind!r}: add its published peaks with their source "
            f"before measuring on it") from None
