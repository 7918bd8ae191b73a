"""How late the load generator submitted open-loop requests: 95th
percentile, over the requests due in the window, of submit time minus due
time (host clock).  The generator submits between engine steps, so a long
step makes it late."""

from bench import harness


def read(rec):
    if not rec.open_loop:
        return None
    lags = [(r.submit - r.due) * 1e3 for r in rec.reqs.values()
            if rec.t0 <= r.due <= rec.t_end]
    return harness.percentile(lags, 95) if lags else None
