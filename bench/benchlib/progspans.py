"""The program's own recorded spans (``repro.obs``), placed on a trace's
clock.

The program records its phases (``invariant_build``, ``scan_execute``,
...) and JAX's compile phases (``compile:<program>``) in an in-process
recorder, stamped on the host's realtime clock.  A profiler trace's
event times are that clock less the session's start, which the trace
summary does not keep.  So the offset is found from the program spans
that both hold: each of the trace's program spans is paired with the
recorded spans of the same name and about the same duration, and the
offset that most pairs agree on, within ``AGREE_NS``, is taken.

Nothing is read where the program records no such spans (a program
without them) or where no offset is agreed; this module drives nothing
and imports the program only to read its recorder.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchlib import trace as trace_lib

#: pairs whose durations, or whose offsets, differ by more are not one
AGREE_NS = 50_000


def recorded() -> List[Tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of every span in the program's
    recorder; empty where the program has none."""
    try:
        from repro.obs import spans
    except ImportError:
        return []
    out = []
    for ev in spans.iter_spans():
        start = int(round(float(ev["ts"]) * 1e3))
        out.append((ev["name"], start,
                    start + int(round(float(ev["dur"]) * 1e3))))
    return out


def offset_ns(trace: trace_lib.TraceSummary,
              events: List[Tuple[str, int, int]]) -> Optional[int]:
    """Trace time minus recorder time, agreed by most pairs of one
    program span in both; None where fewer than half the trace's
    program spans (and fewer than two) agree."""
    by_name = {}
    for name, s, e in events:
        by_name.setdefault(name, []).append((s, e))
    mine = [sp for sp in trace.spans if sp.name in by_name]
    cands = sorted(sp.start - s for sp in mine for s, e in by_name[sp.name]
                   if abs((sp.end - sp.start) - (e - s)) <= AGREE_NS)
    best: List[int] = []
    j = 0
    for i in range(len(cands)):
        while cands[i] - cands[j] > AGREE_NS:
            j += 1
        if i + 1 - j > len(best):
            best = cands[j:i + 1]
    if len(best) < max(2, (len(mine) + 1) // 2):
        return None
    return best[len(best) // 2]


def on_trace(trace: trace_lib.TraceSummary,
             prefix: str) -> Optional[List[trace_lib.Interval]]:
    """The recorded spans whose names start with ``prefix``, on the
    trace's clock; None where there are none or no offset is agreed."""
    events = recorded()
    wanted = [(s, e) for name, s, e in events if name.startswith(prefix)]
    if not wanted:
        return None
    off = offset_ns(trace, events)
    if off is None:
        return None
    return [(s + off, e + off) for s, e in wanted]
