"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect.  A device kind that is not in
the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,  # FLOP/s
        "ops_int8": 393e12,  # OP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/peaks.py"
                         ) from None
