"""Mean host-clock time of the window's engine steps that fed prompt
tokens (the tick runs ``prefill_chunk`` micro-steps).  A step ends when its
logits reach the host, so it waits for the device."""


def read(rec):
    ticks = [t for t in rec.window_ticks() if t.prefill]
    if not ticks:
        return None
    return 1e3 * sum(t.end - t.start for t in ticks) / len(ticks)
