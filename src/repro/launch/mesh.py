"""Production mesh definitions and per-chip peaks.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before the first
jax initialization). Meshes use Auto axis types: the models constrain
shardings with ``with_sharding_constraint``, which Explicit axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU integration tests (8 forced host devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s inter-chip interconnect = 4 links x 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a kind without published
    peaks here is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
