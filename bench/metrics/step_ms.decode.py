"""Mean host-clock time of the window's engine steps that fed no prompt
token (one micro-step)."""


def read(rec):
    ticks = [t for t in rec.window_ticks() if not t.prefill]
    if not ticks:
        return None
    return 1e3 * sum(t.end - t.start for t in ticks) / len(ticks)
