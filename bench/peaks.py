"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.

No float64 peak is published: the chip has no float64 units and XLA
emulates float64 arithmetic.  So the float64 admission drain is reported
by its device time alone, with no roofline share.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None
