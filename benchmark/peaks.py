"""Published peaks of one chip, keyed by the exact ``device_kind`` JAX reports.

No override and no default: a device that is not in the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9,       # 1,600 Gbit/s chip to chip
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add a row "
            f"to benchmark/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None
