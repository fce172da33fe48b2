"""Published peaks of each accelerator the benchmark runs on.

Keyed by the ``device_kind`` that JAX reports. A device that is missing
from the table is an error, never a default: a share of a peak that was
guessed is worth nothing.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_s": 197e12,
        "int8_ops_s": 393e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (Cloud TPU "
                  "system architecture): 197 TFLOP/s bf16, 393 TOP/s "
                  "int8, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises KeyError for a
    device the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.py") from None
