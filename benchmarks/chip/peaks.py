"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Copied from ``src/repro/launch/hlo_stats.py`` so that the yardstick cannot
move with the program.  A kind with no entry is an error, never a default.

TPU v5e (kind "TPU v5 lite"): Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
