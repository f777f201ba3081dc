"""Published peaks per chip, keyed by `device_kind` as JAX reports it.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9),
}


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a chip missing from the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py") \
            from None
