"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.

A float32 contraction at ``Precision.HIGHEST`` runs as six bf16 passes on
the MXU, so its compute roof is a sixth of the bf16 peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

HIGHEST_F32_PASSES = 6


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/isla_bench/"
                       f"peaks.py with their source")
    return PEAKS[device_kind]


def f32_highest_flops(device_kind: str) -> float:
    """The compute roof of a float32 contraction at Precision.HIGHEST."""
    return peaks(device_kind)["bf16_flops"] / HIGHEST_F32_PASSES
