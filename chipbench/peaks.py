"""Published peaks of one chip, keyed by the `device_kind` JAX reports.

A device that is not in the table is an error, never a default: a
roofline share computed against another chip's peaks would be wrong.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of `device_kind`; KeyError for a device not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}") from None
