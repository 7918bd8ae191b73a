"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh) cell, in seconds:

  compute    = HLO_FLOPs          / (chips × peak_FLOP/s)
  memory     = HLO_bytes_accessed / (chips × HBM_bw)
  collective = collective_bytes   / (chips × link_bw)

FLOPs/bytes/collective-bytes come from ``repro.launch.hlo_analysis`` —
a trip-count-aware walk of the optimized (post-SPMD) HLO, because XLA's
``cost_analysis()`` counts scan bodies once (10-100x undercount).
"""

from __future__ import annotations

from .mesh import V5E, peaks

# the dry run targets the v5e production mesh (``mesh.make_production_mesh``)
HW = peaks(V5E)


def roofline_terms(flops_dev: float, bytes_dev: float,
                   collective_bytes_per_device: float, num_chips: int,
                   f32_upcast_correction: bool = True) -> dict:
    """Per-device-program totals (trip-count-aware, from hlo_analysis).

    The CPU dry-run backend upcasts bf16 dots/activations to f32; on the TPU
    target the data plane is bf16, so with ``f32_upcast_correction`` the
    memory and collective byte totals are halved to reflect target-dtype
    traffic (FLOPs are dtype-independent).  Both raw and corrected values
    are recorded.
    """
    corr = 0.5 if f32_upcast_correction else 1.0
    compute_s = flops_dev / HW["peak_flops_bf16"]
    memory_s = bytes_dev * corr / HW["hbm_bw"]
    collective_s = collective_bytes_per_device * corr / HW["ici_bw_per_link"]
    terms = {
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": float(collective_bytes_per_device),
        "f32_upcast_correction": corr,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    terms["dominant"] = dominant.replace("_s", "")
    # roofline fraction: how much of the step is useful compute if the
    # dominant term fully hides the others
    total = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["roofline_fraction_compute"] = compute_s / total if total else 0.0
    return terms


def model_flops(cfg, shape, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference), N active params."""
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token each
