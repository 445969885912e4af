"""repro.obs — observability: telemetry streams, spans, metrics registry.

Three small, dependency-light pieces (nothing here imports the engine,
so every layer of the system can import obs without cycles):

- ``obs.telemetry`` — device-side per-iteration ADMM diagnostics
  (primal/dual residuals, per-task disagreement, QP box saturation),
  collected inside the fit's own scan and materialized only after it;
  telemetry-on is bitwise telemetry-off on all model outputs.  Enable
  with ``SolverConfig(telemetry=True)``; read ``solver.telemetry_`` /
  ``session.telemetry_``.
- ``obs.spans`` — phase names on both sides: host-side timing
  (invariant builds, scans, snapshots, serve batches) exported as
  Chrome-trace JSON, and the same names as ``jax.named_scope`` device
  scopes (build, QP, update, exchange) in every traced op's metadata.
- ``obs.compiles`` — JAX's trace / lower / compile events counted per
  program and per enclosing span, and recorded as ``compile:<program>``
  spans (registered when ``repro.obs`` is imported).
- ``obs.counters`` — named host-side totals of work done
  (``build.densified_bytes``, ``build.stored_nonzeros``), added from
  static shapes: ``read_counters()`` before and after a fit.
- ``obs.registry`` — ``MetricsRegistry``: one versioned JSON document
  absorbing ``net_report_``, serve stats, ``plan_stats`` and telemetry
  summaries; ``python -m repro.obs report`` renders it.

``obs.timing.timeit`` is the shared benchmark-timing helper (warmup +
``perf_counter`` + ``block_until_ready``).  See docs/observability.md.
"""
from repro.obs.compiles import clear_compiles, compile_counts
from repro.obs.compiles import register as _register_compiles
from repro.obs.counters import clear_counters, count, read_counters
from repro.obs.registry import OBS_SCHEMA_VERSION, MetricsRegistry
from repro.obs.spans import (clear_spans, dropped_spans, iter_spans,
                             save_trace, scope, span, to_chrome_trace,
                             validate_chrome_trace)
from repro.obs.telemetry import (STREAMS, Telemetry, collect_diagnostics,
                                 concat_streams, materialize, summarize)
from repro.obs.timing import Timing, timeit

_register_compiles()

__all__ = [
    "OBS_SCHEMA_VERSION", "MetricsRegistry",
    "clear_compiles", "compile_counts",
    "clear_counters", "count", "read_counters",
    "clear_spans", "dropped_spans", "iter_spans", "save_trace", "scope",
    "span", "to_chrome_trace", "validate_chrome_trace",
    "STREAMS", "Telemetry", "collect_diagnostics", "concat_streams",
    "materialize", "summarize",
    "Timing", "timeit",
]
