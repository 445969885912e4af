"""Share of the traced serving window in which no operation ran on the
device, averaged over the cell's chips."""


def read(r):
    if r.trace is None or r.trace.idle_share() is None:
        return None
    return 100.0 * r.trace.idle_share()
