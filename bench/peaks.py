"""Published peaks of one chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


def peak(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
