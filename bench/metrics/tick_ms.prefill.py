"""Mean time of the tick in the window's prefill steps (width C = chunk),
from the engine's own spans: ``serve.dispatch`` (uploads and the tick's
call) plus ``serve.tick_wait`` (until its logits are ready), on the
profiler trace's clock (``bench/spans.py``)."""

from bench import spans


def read(rec):
    prof = spans.of(rec)
    if prof is None:
        return None
    steps = [s for s in prof.window_steps() if s.width > 1]
    if not steps:
        return None
    return 1e3 * sum(s.tick_s for s in steps) / len(steps)
