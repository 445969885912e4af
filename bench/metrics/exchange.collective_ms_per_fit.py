"""Device time of collective operations on chip 0 that no other
operation overlaps, per whole fit (``bench.fit`` span) in the traced
window."""


def read(r):
    n = r.trace.span_count("bench.fit") if r.trace is not None else 0
    if not n or not r.trace.has_collectives(0):
        return None
    return 1e3 * r.trace.collective_exposed_s(0, within="bench.fit") / n
