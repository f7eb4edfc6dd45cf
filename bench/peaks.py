"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
JAX reports a v5e chip's kind as "TPU v5 lite". A kind that is not in the
table is an error: a roofline share against a guessed peak means nothing.
"""

from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table entry for ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)} ({SOURCE})") from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """The least time the chip needs for the work, and which bound sets it."""
    p = peaks(device_kind)
    compute, memory = flops / p["flops"], nbytes / p["bytes_per_s"]
    return (memory, "memory") if memory >= compute else (compute, "compute")
