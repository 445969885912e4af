"""Phase names on both sides: host spans and device scopes.

A *span* wraps one real phase boundary of the system — invariant/K
builds, scan dispatches, session replans, store snapshot/restore, serve
batch windows — and records a wall-clock ``(name, start, duration)``
triple into a process-wide buffer.  The buffer exports as Chrome
trace-event JSON (``to_chrome_trace`` / ``save_trace``), so
``chrome://tracing`` and Perfetto open it directly.

The same name labels the device side: ``span`` also opens a
``jax.named_scope`` of its name, and :func:`scope` opens the scope alone.
Every operation traced inside a scope carries its name in the HLO
``op_name`` metadata of the compiled program (``as_text()``, IR dumps),
so each instruction's phase is known.  (A TPU profile, as
``jax.profiler.ProfileData`` reads it, names each device op by its HLO
text without that metadata.)  Inside traced code (a scan body, a
jitted build, a shard body) use :func:`scope` only: a host span there
would time the trace, not the work.  A scope outside any trace labels
nothing — an eagerly dispatched op is lowered as its own program, under
a fresh name stack.  Scopes are metadata: the lowered instructions and
every output are unchanged.

The recorder is deliberately dumb and cheap: ``time.time_ns`` on
enter/exit, one lock-protected list append, no allocation in the body.
Spans NEVER touch device values — they time host phases only.  When a
``jax.profiler`` trace is active, each span also emits a
``TraceAnnotation`` of its name.  The recorder's clock is the one the
profiler stamps its host events with (the host's realtime clock): an
event's ``ts`` is microseconds since the epoch, and a profile's event
times are the same clock less the session's ``profile_start_time`` (a
stat of its ``Task Environment`` plane), so a recorded span and its
annotation start at the same instant.  ``repro.obs.compiles`` writes
JAX's trace / lower / compile phases into the same recorder as
``compile:<program>`` spans.

Taxonomy (the names the instrumented call sites use):

======================  =================================================
name                    phase
======================  =================================================
``invariant_build``     span: ``engine.invariants.compute_invariants``
                        (the K build, dense or budgeted) and the
                        ``sample_shard`` panel build call; scope: the
                        bodies of the jitted streamed builds, the
                        dense Gram/Lipschitz pair and the panel build
``plan_replan``         span: ``Plan.replan`` (incremental rebuild)
``scan_execute``        span: the ADMM scan call of ``Plan.run`` and of
                        the ``sample_shard`` backend, which times
                        the scan's dispatch (and its trace, lowering and
                        compile on the first run of a shape, or on every
                        run with an ``eval_fn``) — not its execution,
                        which is asynchronous;
                        scope: the scan body, whose device ops are the
                        execution
``qp_solve``            scope: the dual box-QP engine call of
                        ``plan_step`` with its ``X^T Y lam``
``consensus_update``    scope: eqs. (7)-(9), ``plan.consensus_update``
``neighbour_exchange``  scope: every neighbour sum (the ``nbr_reduce``
                        call sites) and the collectives of the sharded
                        backends
``store_snapshot``      span: ``store.snapshot_session``
``store_restore``       span: ``store.restore_session``
``serve_batch``         span: one ``PredictServer`` padded-bucket batch
``compile:<program>``   recorder only: one trace, lowering or backend
                        compile of ``<program>`` (``repro.obs.compiles``)
======================  =================================================

Callers may add their own names freely — the taxonomy is a convention,
not a schema.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Iterator, List, Optional

import jax
from jax.profiler import TraceAnnotation

#: recorder capacity: beyond this many events new spans are counted
#: (``dropped_spans``) but not stored, so a long-lived serve process
#: cannot grow the buffer without bound.
MAX_EVENTS = 100_000

_LOCK = threading.Lock()
_EVENTS: List[dict] = []
_DROPPED = 0
_OPEN = threading.local()          # per thread: names of the open spans


def scope(name: str):
    """The device side of phase ``name``: a ``jax.named_scope``, so that
    every op traced inside it carries ``name`` in its ``op_name``."""
    return jax.named_scope(name)


def _open_spans() -> List[str]:
    names = getattr(_OPEN, "names", None)
    if names is None:
        names = _OPEN.names = []
    return names


def current_span() -> Optional[str]:
    """The innermost span open on the calling thread, or None."""
    names = _open_spans()
    return names[-1] if names else None


def record(name: str, start_ns: int, dur_ns: int,
           attrs: Optional[dict] = None) -> None:
    """Append one complete event stamped on the recorder's clock
    (``time.time_ns``) — what :func:`span` does on exit."""
    global _DROPPED
    ev = {
        "name": name,
        "ph": "X",
        "ts": start_ns / 1e3,               # microseconds since the epoch
        "dur": max(dur_ns, 0) / 1e3,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
    }
    if attrs:
        ev["args"] = attrs
    with _LOCK:
        if len(_EVENTS) < MAX_EVENTS:
            _EVENTS.append(ev)
        else:
            _DROPPED += 1


@contextmanager
def span(name: str, **attrs) -> Iterator[None]:
    """Record one host-side phase as a Chrome-trace complete event, and
    open the phase's device :func:`scope` around it.

    ``attrs`` (plain JSON-able values) land in the event's ``args`` and
    show up in the trace viewer's detail pane::

        with obs.span("scan_execute", iters=30):
            state, hist = plan.run(state, iters=30)
    """
    names = _open_spans()
    names.append(name)
    t0 = time.time_ns()
    try:
        with TraceAnnotation(name), scope(name):
            yield
    finally:
        names.pop()
        record(name, t0, time.time_ns() - t0, attrs)


def iter_spans() -> List[dict]:
    """A copy of the recorded events (Chrome-trace event dicts)."""
    with _LOCK:
        return list(_EVENTS)


def dropped_spans() -> int:
    """Events discarded because the buffer hit :data:`MAX_EVENTS`."""
    with _LOCK:
        return _DROPPED


def clear_spans() -> None:
    """Reset the recorder (buffer and drop counter)."""
    global _DROPPED
    with _LOCK:
        _EVENTS.clear()
        _DROPPED = 0


def to_chrome_trace(events: Optional[List[dict]] = None) -> dict:
    """The recorded (or given) events as a Chrome trace-event document:
    ``{"traceEvents": [...], "displayTimeUnit": "ms"}`` — the JSON-object
    trace format ``chrome://tracing`` / Perfetto load directly."""
    return {
        "traceEvents": iter_spans() if events is None else list(events),
        "displayTimeUnit": "ms",
    }


def save_trace(path: str, events: Optional[List[dict]] = None) -> dict:
    """Write :func:`to_chrome_trace` to ``path`` as JSON; returns the
    written document (validated first, so a bad event fails here, not
    in the viewer)."""
    tree = to_chrome_trace(events)
    validate_chrome_trace(tree)
    with open(path, "w") as fh:
        json.dump(tree, fh, default=str)
    return tree


def validate_chrome_trace(tree: dict) -> None:
    """Raise ``ValueError`` unless ``tree`` is a well-formed complete-
    event Chrome trace (the subset this recorder emits): a dict with a
    ``traceEvents`` list whose entries carry a str ``name``, ``ph`` of
    ``"X"``, non-negative numeric ``ts``/``dur``, and int ``pid``/
    ``tid``."""
    if not isinstance(tree, dict) or not isinstance(
            tree.get("traceEvents"), list):
        raise ValueError("not a Chrome trace: expected a dict with a "
                         "'traceEvents' list")
    for i, ev in enumerate(tree["traceEvents"]):
        if not isinstance(ev, dict):
            raise ValueError(f"traceEvents[{i}] is not a dict")
        if not isinstance(ev.get("name"), str):
            raise ValueError(f"traceEvents[{i}] has no str 'name'")
        if ev.get("ph") != "X":
            raise ValueError(
                f"traceEvents[{i}] ph={ev.get('ph')!r}; this recorder "
                f"emits complete events ('X') only")
        for key in ("ts", "dur"):
            v = ev.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                raise ValueError(
                    f"traceEvents[{i}].{key} must be a non-negative "
                    f"number, got {v!r}")
        for key in ("pid", "tid"):
            if not isinstance(ev.get(key), int):
                raise ValueError(
                    f"traceEvents[{i}].{key} must be an int")
