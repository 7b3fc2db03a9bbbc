"""Chip peak FLOPs/sec lookup for MFU denominators.

One table keyed by the ``device_kind`` JAX reports, holding the published
dense bf16 matmul rate per chip. A TPU that is not in the table is an
error, not a default: a utilization against a guessed denominator is worse
than none.
"""

from __future__ import annotations

from typing import Optional

# device_kind -> bf16 dense peak FLOPs/sec per chip.
# "TPU v5 lite": 197 TFLOP/s (Google Cloud documentation, "TPU v5e"); the
# kind string is what libtpu 0.0.34 reports on the v5e (chip_smoke.py).
_PEAKS_BF16 = {
    "TPU v5 lite": 197e12,
}


def chip_peak_flops(device, compute_dtype: str = "bfloat16") -> Optional[float]:
    """Published peak FLOPs/sec of ``device`` for ``compute_dtype``.

    ``None`` where no published figure applies — the CPU, and float32 on a
    TPU (spec sheets state the bf16 rate only) — so MFU is reported as null
    rather than against a made-up denominator. A TPU whose ``device_kind``
    is not in the table raises."""
    if device.platform == "cpu":
        return None
    if device.device_kind not in _PEAKS_BF16:
        raise ValueError(
            f"no published peak for device_kind {device.device_kind!r}; "
            f"known: {sorted(_PEAKS_BF16)} — add it with its source")
    if str(compute_dtype) not in ("bfloat16", "bf16"):
        return None
    return _PEAKS_BF16[device.device_kind]
