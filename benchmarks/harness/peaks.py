"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, not a default: a share of a
guessed peak is worse than none."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB HBM per chip. "TPU v5 lite" is what libtpu reports on the v5e.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def chip_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises on a kind that has no entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} - add it with its source") from None
