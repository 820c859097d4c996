"""Peak rates of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
