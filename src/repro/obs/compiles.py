"""Per-program compile accounting from JAX's own compile events.

JAX reports three host phases for every program it builds through
``jax.monitoring``: tracing to a jaxpr, lowering to an MLIR module and
the backend compile (or persistent-cache load), each with the program's
name (``fun_name``) and, through the time-span listener, its start and
end.  This module registers one listener pair, once, when ``repro.obs``
is imported, and for every phase event:

- counts it by program (``jit(_plan_scan)``, ``jit(_streamed_gram_jit)``,
  ...) and by the innermost ``obs`` span open on the thread
  (``spans.current_span``) — "does my fit recompile every call?" is
  ``compile_counts()["programs"]`` read before and after a fit;
- records it in the span recorder as ``compile:<program>`` (``args``:
  ``phase`` and the enclosing ``span``), on the recorder's clock;
- while a profiler trace is active, also writes it into the trace as a
  ``compile:<program>`` annotation, opened when JAX starts the phase and
  closed when it ends, so the trace names the program of each
  compile gap.

A trace event names the traced function (``scan``); it is counted under
the name its lowering carries (``jit(scan)``).  ``MetricsRegistry``
exposes the counts as its ``compiles`` section (``record_compiles``).
"""
from __future__ import annotations

import threading
from typing import Dict, List

import jax
from jax.profiler import TraceAnnotation

from repro.obs import spans as spans_lib

#: JAX's compile-phase events -> the phase names used here
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: key of compiles that ran outside any ``obs`` span
NO_SPAN = "(no span)"

_LOCK = threading.Lock()
_BY_PROGRAM: Dict[str, Dict[str, List[float]]] = {}
_BY_SPAN: Dict[str, Dict[str, List[float]]] = {}
_OPEN = threading.local()          # per thread: annotations of open phases
_REGISTERED = False


def program_name(phase: str, fun_name) -> str:
    """The program a phase event belongs to: the lowering's own name
    (``jit(scan)``); a trace event's bare function name is wrapped the
    way its lowering names it."""
    name = str(fun_name)
    if phase == "trace" and "(" not in name:
        return f"jit({name})"
    return name


def _open_phases() -> list:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


def _on_phase_start(event: str, value, **kw) -> None:
    """JAX's scalar event at a phase's start: open its annotation."""
    phase = PHASES.get(event)
    if phase is None:
        return
    fun_name = kw.get("fun_name")
    ann = TraceAnnotation("compile:" + program_name(phase, fun_name))
    ann.__enter__()
    _open_phases().append((event, fun_name, ann))


def _close_annotation(event: str, fun_name) -> None:
    stack = _open_phases()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][0] == event and stack[i][1] == fun_name:
            stack.pop(i)[2].__exit__(None, None, None)
            return


def _on_phase_span(event: str, start_s: float, end_s: float, **kw) -> None:
    """JAX's time span at a phase's end: count it and record it."""
    phase = PHASES.get(event)
    if phase is None:
        return
    fun_name = kw.get("fun_name")
    _close_annotation(event, fun_name)
    program = program_name(phase, fun_name)
    within = spans_lib.current_span() or NO_SPAN
    ms = (end_s - start_s) * 1e3
    with _LOCK:
        for table, key in ((_BY_PROGRAM, program), (_BY_SPAN, within)):
            row = table.setdefault(key, {}).setdefault(phase, [0, 0.0])
            row[0] += 1
            row[1] += ms
    start_ns = int(round(start_s * 1e9))
    spans_lib.record("compile:" + program, start_ns,
                     int(round(end_s * 1e9)) - start_ns,
                     {"phase": phase, "span": within})


def register() -> None:
    """Register the listeners with ``jax.monitoring`` (once per
    process; JAX keeps listeners for the life of the process)."""
    global _REGISTERED
    with _LOCK:
        if _REGISTERED:
            return
        _REGISTERED = True
    jax.monitoring.register_scalar_listener(_on_phase_start)
    jax.monitoring.register_event_time_span_listener(_on_phase_span)


def compile_counts() -> dict:
    """``{"programs": {program: {phase: {"count", "ms"}}}, "spans":
    {span: {phase: {"count", "ms"}}}}`` since the last
    :func:`clear_compiles`, phases being ``trace`` / ``lower`` /
    ``compile``."""
    def plain(table):
        return {k: {ph: {"count": int(c), "ms": float(ms)}
                    for ph, (c, ms) in row.items()}
                for k, row in table.items()}

    with _LOCK:
        return {"programs": plain(_BY_PROGRAM), "spans": plain(_BY_SPAN)}


def clear_compiles() -> None:
    """Reset the counts (the recorder keeps its ``compile:`` spans until
    ``spans.clear_spans``)."""
    with _LOCK:
        _BY_PROGRAM.clear()
        _BY_SPAN.clear()

