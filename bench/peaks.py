"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

A chip that is not in the table is an error, never a default: a roofline
share against the wrong peak is a wrong number.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            "bench/peaks.py with their source"
        ) from None


def bound_seconds(flops: float, bytes_moved: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of operations over
    peak bf16 FLOP/s and bytes over peak HBM bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], bytes_moved / p["hbm_bytes_per_s"])
