"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.  The same numbers
back the system's planning model; they are kept here so that a change to
the system cannot move the yardstick.  A chip not in the table is an
error, never measured against another chip's peaks.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_s": 197e12, "int8_ops_s": 393e12,
                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def peak_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no peaks for device kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
