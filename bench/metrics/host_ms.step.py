"""Mean host time per engine step in the window, from the engine's own
spans: the ``serve.step`` span less its ``serve.dispatch``,
``serve.tick_wait`` and ``serve.hook`` phases (``bench/spans.py``).  It
holds admission, preparing the feed, the logits' copy, sampling and the
prefix-cache insert, and the glue between them; with ``tick_ms.*`` it adds
up to the step less its callbacks."""

from bench import spans


def read(rec):
    prof = spans.of(rec)
    if prof is None:
        return None
    steps = prof.window_steps()
    if not steps:
        return None
    return 1e3 * sum(s.host_s for s in steps) / len(steps)
