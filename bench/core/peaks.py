"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16 per chip, 16 GiB of HBM at 819 GB/s.  (The same
numbers as the program's ``core/device.py``, copied so that no PR can
move the yardstick by editing the program.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time(peaks: dict, flops: float, nbytes: float) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bandwidth."""
    return max(flops / peaks["flops"], nbytes / peaks["hbm_bytes_per_s"])
