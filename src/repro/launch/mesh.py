"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:   (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_smoke_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for real (executing) multi-device tests on host devices."""
    return jax.make_mesh(shape, axes)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect over 4 links.
V5E = "TPU v5 lite"
PEAKS = {
    V5E: {
        "peak_flops_bf16": 197e12,  # FLOP/s
        "hbm_bw": 819e9,  # B/s
        "ici_bw_per_link": 1600e9 / 8 / 4,  # B/s per link
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; a kind without published
    numbers in ``PEAKS`` is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS") from None
