"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers use.

- Device planes are ``/device:TPU:<n>``; the operations are the events
  of their ``XLA Ops`` line.  Busy time is the union of those
  intervals, idle time the rest of the window.
- Host spans are the TraceAnnotation events of the host plane: the
  benchmark's own (``bench.*``) and the program's ``obs.spans`` names.
  The window is the ``bench.window`` span.
- An idle gap is named by the innermost kept host span (``HOST_SPANS``)
  that covers its midpoint, so that a gap reads as what the host was
  doing in it: tracing, lowering, compiling, dispatching, or a phase
  of the benchmark or the program.

Times are integer nanoseconds on the profiler's common clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce)", re.IGNORECASE)
#: control flow whose body's operations the trace lists inside it: the
#: loop spans its body and does no work of its own, so it overlaps nothing
CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)?$")
WINDOW_SPAN = "bench.window"
#: host spans kept: the benchmark's own, the program's ``obs.spans``
#: phases and ``compile:<program>`` annotations, and JAX's own trace /
#: lower / compile / dispatch phases
HOST_SPANS = re.compile(
    r"^(bench\.|invariant_build$|plan_compile$|plan_replan$|scan_execute$"
    r"|serve_batch$|store_|compile:|trace_to_jaxpr"
    r"|lower_sharding_computation$|backend_compile|PjitFunction\()")


def instruction(op_name: str) -> str:
    """The instruction an op runs.  A TPU trace names each op by its HLO
    text, ``%name = shape opcode(operands)``, whose operands name other
    instructions (a fusion fed by an all-gather names it)."""
    return op_name.split(" = ", 1)[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` that the merged ``b`` miss."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Span:
    name: str
    start: int
    end: int


@dataclass
class Op:
    name: str
    start: int
    end: int


@dataclass
class TraceSummary:
    window: Interval
    ops: Dict[int, List[Op]] = field(default_factory=dict)   # chip -> ops
    spans: List[Span] = field(default_factory=list)

    # -- device -----------------------------------------------------------
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def busy(self, chip: int, lo: Optional[int] = None,
             hi: Optional[int] = None) -> List[Interval]:
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        return clip(union((o.start, o.end) for o in self.ops.get(chip, [])),
                    lo, hi)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips traced."""
        chips = self.chips()
        if not chips:
            return 0.0
        return sum(total(self.busy(c)) for c in chips) / len(chips) / 1e9

    def idle_share(self) -> Optional[float]:
        w = self.window_s()
        if w <= 0 or not self.chips():
            return None
        return 1.0 - self.busy_s() / w

    def span_device_s(self, name: str, chip: int = 0) -> float:
        """Device busy seconds inside every host span called ``name``."""
        return sum(total(self.busy(chip, s.start, s.end))
                   for s in self.spans if s.name == name) / 1e9

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.spans if s.name == name)

    def _in_window(self, name: Optional[str]) -> List[Interval]:
        """The window, or the parts of it inside host spans ``name``."""
        lo, hi = self.window
        if name is None:
            return [(lo, hi)]
        return union(clip(((s.start, s.end) for s in self.spans
                           if s.name == name), lo, hi))

    def span_idle_share(self, name: str) -> Optional[float]:
        """Share of the time inside host spans called ``name`` in which no
        operation ran on the device, averaged over the chips traced."""
        parts = self._in_window(name)
        length = total(parts)
        chips = self.chips()
        if length <= 0 or not chips:
            return None
        busy = sum(total(self.busy(c, s, e)) for c in chips for s, e in parts)
        return 1.0 - busy / len(chips) / length

    def collective_exposed_s(self, chip: int = 0,
                             within: Optional[str] = None) -> float:
        """Device time of collective operations that no other operation
        on the chip overlaps, inside the window (or inside its host spans
        called ``within``).  A loop or call that holds the collective in
        its body (``CONTAINER``) is not another operation."""
        ops = self.ops.get(chip, [])
        coll = union((o.start, o.end) for o in ops
                     if COLLECTIVE.search(instruction(o.name)))
        rest = union((o.start, o.end) for o in ops
                     if not COLLECTIVE.search(instruction(o.name))
                     and not CONTAINER.match(instruction(o.name)))
        out = 0
        for lo, hi in self._in_window(within):
            out += total(subtract(clip(coll, lo, hi), clip(rest, lo, hi)))
        return out / 1e9

    def has_collectives(self, chip: int = 0) -> bool:
        return any(COLLECTIVE.search(instruction(o.name))
                   for o in self.ops.get(chip, []))

    # -- breakdown ----------------------------------------------------------
    def top_ops(self, n: int = 10, chip: int = 0) -> List[list]:
        lo, hi = self.window
        acc: Dict[str, int] = {}
        for o in self.ops.get(chip, []):
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                acc[o.name] = acc.get(o.name, 0) + d
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def gap_owner(self, s: int, e: int) -> str:
        mid = (s + e) // 2
        best = None
        for sp in self.spans:
            if sp.start <= mid < sp.end and sp.name != WINDOW_SPAN:
                if best is None or sp.end - sp.start < best.end - best.start:
                    best = sp
        return best.name if best is not None else "outside any span"

    def idle_gaps(self, n: int = 10, chip: int = 0) -> List[list]:
        """The longest idle gaps, each named by the host span over it."""
        lo, hi = self.window
        gaps = subtract([(lo, hi)], self.busy(chip))
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        return [[self.gap_owner(s, e), (e - s) / 1e9] for s, e in gaps[:n]]


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read_xplane(path: str) -> TraceSummary:
    """Reduce one trace file to its device operations and host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(chip, []).extend(
                        Op(ev.name, int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Span(ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                             for ev in line.events
                             if HOST_SPANS.match(ev.name))
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if windows:
        window = (min(s.start for s in windows), max(s.end for s in windows))
    else:
        every = [o for lst in ops.values() for o in lst]
        window = ((min(o.start for o in every), max(o.end for o in every))
                  if every else (0, 0))
    keep = [s for s in spans if s.end > window[0] and s.start < window[1]]
    return TraceSummary(window=window, ops=ops, spans=keep)
