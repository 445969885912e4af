"""Host milliseconds per whole fit spent tracing, lowering and compiling
programs: the union of the program's ``compile:<program>`` spans
(``repro.obs.compiles``, placed on the trace's clock by
``benchlib.progspans``) inside the ``bench.fit`` spans of the traced
window, over the number of those fits.  A program that records no
compile spans reads nothing."""
from benchlib import progspans
from benchlib import trace as trace_lib


def read(r):
    if r.trace is None or not r.trace.span_count("bench.fit"):
        return None
    compiles = progspans.on_trace(r.trace, "compile:")
    if compiles is None:
        return None
    inside = []
    for s in r.trace.spans:
        if s.name == "bench.fit":
            inside.extend(trace_lib.clip(compiles, s.start, s.end))
    total_ns = trace_lib.total(trace_lib.union(inside))
    return total_ns / 1e6 / r.trace.span_count("bench.fit")
