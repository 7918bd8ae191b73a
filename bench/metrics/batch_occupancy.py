"""Mean over the window's engine steps of ``slotsBusy / numSlots``, as the
engine reports them (``on_metrics``) when each step's batch is formed."""


def read(rec):
    ticks = rec.window_ticks()
    if not ticks:
        return None
    return 100.0 * sum(t.busy for t in ticks) / (len(ticks) * rec.max_active)
