"""Published peaks of one chip, keyed by ``device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  A kind that
is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
