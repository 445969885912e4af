"""Share of the whole fits' time in the traced window (the ``bench.fit``
spans: each the window's own ``DTSVM.fit`` call) in which no operation
ran on the device, averaged over the cell's chips."""


def read(r):
    if r.trace is None:
        return None
    idle = r.trace.span_idle_share("bench.fit")
    return None if idle is None else 100.0 * idle
