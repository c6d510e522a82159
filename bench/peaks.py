"""Published peaks of each chip the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not in the table is an error, never a
default: a roofline share against the wrong peak is a wrong number.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud docs, TPU v5e: 197 TFLOP/s bf16, "
                  "819 GB/s HBM, 16 GB",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source")
    return PEAKS[device_kind]


def least_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take for this work: the larger of the
    compute bound and the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
