"""Megabytes (1e6 bytes) of Z written dense per whole fit: the
program's ``build.densified_bytes`` counter (``repro.obs.counters``)
over the window's whole fits (``bench.fit``), over their number.  A
program without the counter reads nothing."""


def read(r):
    whole = r.result.get("whole")
    if not whole or not whole["fits"] or whole.get("densified_bytes") is None:
        return None
    return whole["densified_bytes"] / 1e6 / whole["fits"]
