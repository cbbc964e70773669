"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). JAX names this chip
"TPU v5 lite". A kind missing from the table is an error, never a
default: a roofline share against the wrong peak is worse than none.
"""
from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises ``KeyError`` for a
    kind that is not in the table."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)} ({SOURCE})")
    return PEAKS[device_kind]
