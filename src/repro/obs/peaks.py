"""Peak rates of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip.  A kind that is not in
the table has no peak: callers report no utilization for it rather than
guess one.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["PEAKS", "device_peak"]

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peak(device_kind: str) -> Optional[dict]:
    """The peak rates of ``device_kind``, or None for a kind not in the table."""
    return PEAKS.get(device_kind)
