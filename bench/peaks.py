"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

TPU v5e (JAX kind "TPU v5 lite"): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB
HBM per chip — Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e).  A kind that is not here is an
error, never a default.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def for_device(kind: str) -> Dict[str, float]:
    """Peaks of ``kind``; the CPU has none and gets an empty table, which
    every device metric reads as nothing to report."""
    if kind in PEAKS:
        return PEAKS[kind]
    if kind.lower() == "cpu":
        return {}
    raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.py")
