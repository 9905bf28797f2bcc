"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e -- Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect (taken
here as 4 links of 50 GB/s).  A kind without a sourced entry is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bytes_s_link": 50e9},
}

#: the chip the production meshes (``repro.launch.mesh``) model
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_terms(st, device_kind: str = TARGET_KIND) -> dict:
    """Compute / HBM / collective time bounds of an analyzed program
    (``hlo_analysis.analyze``) on one chip of ``device_kind``."""
    pk = peaks(device_kind)
    return {"device_kind": device_kind,
            "t_compute_s": st.flops / pk["flops"],
            "t_memory_s": st.traffic_bytes / pk["hbm_bytes_s"],
            "t_collective_s": (st.total_collective_bytes
                               / pk["ici_bytes_s_link"])}
