"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A device missing from the table is an error:
no share of a peak is ever taken against a guessed one.

Source: Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table of one chip; KeyError names the missing kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source") from None
