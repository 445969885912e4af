"""Host milliseconds per whole fit spent tracing, lowering and compiling
programs: the union of the program's ``compile:<program>`` spans
(``repro.obs.compiles``) inside the ``bench.fit`` spans of the traced
window, over the number of those fits.  The spans come from the
program's recorder, placed on the trace's clock by
``benchlib.progspans``; where no offset is agreed (a backend that
records no program span in the fit), from the ``compile:<program>``
annotations the program writes into the trace beside them.  A program
that records no compile spans reads nothing."""
from benchlib import progspans
from benchlib import trace as trace_lib


def read(r):
    if r.trace is None or not r.trace.span_count("bench.fit"):
        return None
    compiles = progspans.on_trace(r.trace, "compile:")
    if compiles is None:
        if not any(name.startswith("compile:")
                   for name, _, _ in progspans.recorded()):
            return None
        compiles = [(s.start, s.end) for s in r.trace.spans
                    if s.name.startswith("compile:")]
    inside = []
    for s in r.trace.spans:
        if s.name == "bench.fit":
            inside.extend(trace_lib.clip(compiles, s.start, s.end))
    total_ns = trace_lib.total(trace_lib.union(inside))
    return total_ns / 1e6 / r.trace.span_count("bench.fit")
