"""Median, over the open-loop requests due in the window, of the ticks
from a request's admission to its prompt's last logits, as the engine
counts them (``first`` in the metadata of its ``serve.step`` spans,
``bench/spans.py``).  A request's first token is emitted one tick later.
The trace runs on past the window's close until every due request has its
first token, so late ones are counted too."""

from bench import harness, spans


def read(rec):
    prof = spans.of(rec)
    if prof is None or not rec.open_loop:
        return None
    ticks = {rid: n for s in prof.steps for rid, n in s.first}
    due = [ticks[rid] for rid, r in rec.reqs.items()
           if rec.t0 <= r.due <= rec.t_end and rid in ticks]
    return harness.percentile(due, 50) if due else None
