"""Published peaks of each accelerator the benchmark may run on, keyed by
``device_kind`` as JAX reports it.  A kind that is not here is an error,
never a default.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" (system
architecture table): 197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GiB
of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}


def peak(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; raises KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            f"benchmarks/chip/peaks.py with their source (known: {sorted(PEAKS)})"
        ) from None
