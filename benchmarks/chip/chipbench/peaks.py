"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not listed is an error, never a
default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s
    # per chip. No VPU peak is published, so no roofline here has an
    # operations term.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to {__name__}.PEAKS")
    return PEAKS[device_kind][key]
