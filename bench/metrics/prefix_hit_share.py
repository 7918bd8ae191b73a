"""Share of the prompt tokens admitted in the window that the prefix cache
served, from the engine's own ``metrics()["prefixHitRate"]`` counter read
at the window's start and end (with the prompt tokens admitted by then)."""


def read(rec):
    (r0, p0), (r1, p1) = rec.hit0, rec.hit1
    if p1 <= p0:
        return None
    return 100.0 * (r1 * p1 - r0 * p0) / (p1 - p0)
