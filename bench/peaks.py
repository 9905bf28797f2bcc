"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e -- Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect (taken
here as 4 links of 50 GB/s).  A kind without a sourced entry is an error,
never a default.  Copied from ``src/repro/launch/peaks.py``: the program
may change its copy, the benchmark keeps this one.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bytes_s_link": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
