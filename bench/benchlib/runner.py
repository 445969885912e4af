"""One run of one cell: set-up, the measured window, the check, the
result line.

A traffic kind (``bench/traffic/<kind>.py``) supplies five functions,
each taking the ``Run``:

- ``setup(run)``            make inputs from the seed, build the system
                            under test, warm every shape the window uses;
- ``window(run, seconds)``  drive the timed path; returns a dict with
                            ``attempted``, ``failed``, ``e2e`` (end-to-end
                            values by metric name) and anything the
                            per-layer readers read;
- ``release(run)``          free what the timed path holds;
- ``check(run)``            the compared numbers of what the window
                            produced, against the plain reference;
- ``control(run)``          the same numbers for the reference computed
                            one precision lower, against the reference.

A run with ``control=True`` puts that lower-precision reference in the
program's place on the timed path (``run.control``): the check then has
to come out not correct.  ``bench/calibrate.py`` and the tests make such
runs; ``bench/run.py`` never does.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time
from typing import Dict, Optional

from benchlib import compare, counters, memory, spec as spec_lib
from benchlib import trace as trace_lib


class Run:
    """Everything one run of a cell knows; kinds keep their state on it."""

    def __init__(self, spec: spec_lib.Spec, cell: str, seed: int,
                 trace: bool, overrides: Optional[dict] = None,
                 mix_overrides: Optional[dict] = None, control: bool = False):
        parts = spec_lib.load_all(spec, cell)
        self.spec = spec
        self.cell = cell
        self.config = dict(parts["config"])
        for k, v in (overrides or {}).items():
            self.config[k] = v
        self.mix = dict(parts["mix"], **(mix_overrides or {}))
        self.workload = parts["workload"]
        self.kind = parts["kind"]
        self.readers = parts["readers"]
        self.seed = int(seed)
        self.trace = bool(trace)
        self.control = bool(control)
        self.chips = int(self.workload["chips"])
        import jax

        self.devices = jax.devices()[: self.chips]
        self.counter = counters.CompileCounter().register()
        self.state: Dict[str, object] = {}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def e2e_value(name: str, e2e: dict):
    """The window's value of an end-to-end metric.  A metric named
    ``<quantity>.<group>`` (``fit_s.large``) is the traffic kind's
    ``<quantity>`` under a name, and a bound, of its own for a group of
    cells."""
    if name in e2e:
        return e2e[name]
    return e2e.get(name.split(".", 1)[0])


def _finite(x: float) -> float:
    return float(x) if math.isfinite(x) else 1.0e300


def run_cell(spec: spec_lib.Spec, cell: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, overrides: Optional[dict] = None,
             mix_overrides: Optional[dict] = None,
             trace_dir: Optional[str] = None, control: bool = False) -> dict:
    """Run one cell once; returns the result object (the last line).
    ``overrides`` / ``mix_overrides`` replace top-level keys of the
    configuration / traffic mix (tests run the cells at small sizes);
    ``control`` puts the control in the program's place."""
    import jax

    run = Run(spec, cell, seed, trace, overrides, mix_overrides, control)
    run.seconds = float(seconds)
    kind = run.kind
    programs = memory.ProgramMemory().start()
    try:
        kind.setup(run)
        setup_s = time.perf_counter() - t_start
        log(f"bench: {cell} seed {seed} set-up {setup_s:.3f} s")
        keep = trace_dir is not None
        if trace:
            trace_dir = trace_dir or os.path.join(spec.root, ".bench_trace",
                                                  cell)
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        run.counter.armed = True
        try:
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                res = kind.window(run, seconds)
        finally:
            run.counter.armed = False
            if trace:
                jax.profiler.stop_trace()
    finally:
        programs.stop()
    res["counters"] = run.counter.snapshot()
    summary = None
    if trace:
        summary = trace_lib.read_xplane(trace_lib.find_xplane(trace_dir))
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    mem = memory.peak_bytes(run.devices, programs)
    kind.release(run)
    values = kind.check(run)
    checks = compare.judge(values, run.workload["limits"])
    correct = (res["failed"] == 0 and res["attempted"] > 0
               and compare.all_within(checks))

    metrics = {}
    if trace:
        reading = Reading(run, res, summary)
        for m in spec.per_layer(cell):
            v = run.readers[m["name"]](reading)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in spec.end_to_end(cell):
            v = setup_s if m["name"] == "setup_s" else e2e_value(
                m["name"], res["e2e"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    d0 = run.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s()
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": summary.idle_gaps(10)}
    out["notes"] = dict(res.get("notes", {}), compile=res["counters"],
                        memory=mem)
    out["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                     for k, c in checks.items()}
    for line in compare.check_lines(checks):
        log(line)
    return out


class Reading:
    """What a per-layer reader may read: the run, the window's result
    (counters included) and the trace summary (None without a trace)."""

    def __init__(self, run: Run, result: dict,
                 trace: Optional[trace_lib.TraceSummary]):
        self.run = run
        self.config = run.config
        self.result = result
        self.trace = trace
        self.device_kind = run.devices[0].device_kind


def dumps(result: dict) -> str:
    return json.dumps(result, separators=(",", ":"))
