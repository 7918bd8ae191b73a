"""Model FLOP utilization of the whole serving step over the window:
FLOPs of the tokens actually computed (prompt tokens fed, not those the
prefix cache served, and every decoded token; matmuls and attention over
each token's live context, ``bench/flops.py``) over the window's host-clock
seconds times the chip's peak bf16 FLOP/s."""

from bench import flops, peaks


def read(rec):
    ticks = rec.window_ticks()
    if not ticks:
        return None
    total = flops.step_flops(rec.model, sum(t.tokens for t in ticks),
                             sum(t.keys for t in ticks))
    p = peaks.peaks(rec.device_kind)
    return 100.0 * total / (rec.window_s * p["flops_bf16"])
