"""Share of its roofline that the paged decode-attention kernel reaches in
the traced window.

The work is what attention needs for the live tokens, whatever implements
it: for every token computed, per layer, the K and V rows of the keys it
attends (its position and those before it) read once (bfloat16), q read
and the output written, and ``4 * H * D`` FLOPs per key
(``bench/flops.py``); the harness counts tokens and keys per step from the
engine's public counters (``bench/drivers/serve.py``).  The
least time is the larger of FLOPs over peak FLOP/s and bytes over peak
bandwidth (``bench/peaks.py``); the share is that over the summed device
time of the kernel's events in the trace.  A kernel that walks dead blocks
spends time on no live work, and shows as a lower share."""

from bench import flops, peaks, trace

KERNEL = "paged_decode"  # the Pallas kernel's name in the trace


def read(rec):
    if rec.trace is None:
        return None
    secs, n = trace.kernel_seconds(rec.trace, KERNEL)
    if not n or secs <= 0:
        return None
    lo, hi = rec.traced
    ticks = rec.ticks[lo:hi]
    f, b = flops.paged_attention_work(rec.model,
                                      sum(t.tokens for t in ticks),
                                      sum(t.keys for t in ticks))
    p = peaks.peaks(rec.device_kind)
    least = max(f / p["flops_bf16"], b / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
