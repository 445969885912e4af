"""``python -m repro.analysis`` — the repo's sanitizer CLI.

Runs, in order: the AST lint rules over the given paths, the jaxpr
entry-point audit, the retrace/compile-count guard, the Pallas launch
audit, and the (informational) substrate reachability report.  Exits
non-zero iff any *unsuppressed* finding remains — the CI ``lint``
lane gates on exactly this.

Examples::

    python -m repro.analysis src/repro
    python -m repro.analysis src/repro --format=json --out report.json
    python -m repro.analysis --list-rules
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List

from repro.analysis import linter, rules
from repro.analysis.linter import Finding


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repo-specific static analysis + sanitizers")
    ap.add_argument("paths", nargs="*", default=[],
                    help="files/directories to lint (default: the "
                         "installed repro package)")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text")
    ap.add_argument("--out", help="also write the JSON report here")
    ap.add_argument("--vmem-budget", type=int, default=None,
                    help="Pallas per-step VMEM budget in bytes")
    ap.add_argument("--no-jaxpr", action="store_true",
                    help="skip the jaxpr entry-point audit")
    ap.add_argument("--no-pallas", action="store_true",
                    help="skip the Pallas launch audit")
    ap.add_argument("--no-retrace", action="store_true",
                    help="skip the retrace/compile-count guard")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    return ap


def _default_paths() -> List[str]:
    import os

    import repro
    return [os.path.dirname(os.path.abspath(repro.__file__))]


def retrace_guard() -> List[Finding]:
    """The compile-once invariants, checked live on a tiny problem:
    one ``weighted_gram`` entry per fit, one ``plan_step`` trace for two
    fits of one shape (the scan is a cached program) and per sweep, one
    GEMM compile per serve bucket (and zero for a repeat bucket)."""
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import (_tiny_problem,
                                            jit_cache_size,
                                            trace_counter)
    from repro.engine import plan as engine_plan
    from repro.engine.plan import compile_problem
    from repro.engine.sweep import compile_sweep
    from repro.serve import model as serve_model

    findings: List[Finding] = []

    def expect(name: str, got: int, want: int, what: str) -> None:
        if got != want:
            findings.append(Finding(
                "retrace-guard", name, 0,
                f"{what}: expected exactly {want}, measured {got}"))

    prob = _tiny_problem()
    engine_plan._plan_scan.clear_cache()       # count from a cold scan
    with trace_counter("repro.kernels.ops:weighted_gram",
                       "repro.engine.plan:plan_step") as c:
        for _ in range(2):
            compile_problem(prob, qp_iters=2).run(iters=3)
    expect("fit", c["weighted_gram"], 2,
           "weighted_gram entries over two fits")
    expect("fit", c["plan_step"], 1,
           "plan_step traces over two fits of one shape")

    with trace_counter("repro.kernels.ops:weighted_gram",
                       "repro.engine.sweep:plan_step") as c:
        sw = compile_sweep(prob, [{"C": 0.01}, {"C": 0.1}, {"C": 1.0}],
                           qp_iters=2)
        sw.run(iters=3)
    expect("sweep", c["weighted_gram"], 1,
           "weighted_gram entries per sweep compile")
    expect("sweep", c["plan_step"], 1,
           "plan_step traces per 3-config sweep")

    V, T, p = 2, 2, 4
    model = serve_model.PredictModel.from_r(
        jnp.zeros((V, T, 2 * p + 2), jnp.float32))
    model.decide_rows(jnp.ones((3, p)))          # warm bucket 8
    base = jit_cache_size(serve_model.gemm_rows)
    model.decide_rows(jnp.ones((5, p)))          # same bucket 8
    expect("serve", jit_cache_size(serve_model.gemm_rows) - base, 0,
           "new GEMM compiles for a repeat bucket")
    model.decide_rows(jnp.ones((100, p)))        # new bucket 128
    expect("serve", jit_cache_size(serve_model.gemm_rows) - base, 1,
           "new GEMM compiles for one new bucket")
    return findings


def main(argv=None) -> int:
    args = _parser().parse_args(argv)

    if args.list_rules:
        for rule in rules.all_rules():
            print(f"{rule.id}\n    {rule.summary}\n    "
                  f"history: {rule.history}")
        return 0

    paths = args.paths or _default_paths()
    lint = linter.lint_paths(paths)
    report = {
        "paths": paths,
        "findings": [f.to_dict() for f in lint if not f.suppressed],
        "suppressed": [f.to_dict() for f in lint if f.suppressed],
    }
    gate = [f for f in lint if not f.suppressed]

    if not args.no_jaxpr:
        from repro.analysis.jaxpr_audit import audit_entry_points
        jx = audit_entry_points()
        report["jaxpr"] = [f.to_dict() for f in jx]
        gate += jx
    if not args.no_retrace:
        rt = retrace_guard()
        report["retrace"] = [f.to_dict() for f in rt]
        gate += rt
    if not args.no_pallas:
        from repro.analysis import pallas_audit
        budget = args.vmem_budget or pallas_audit.DEFAULT_VMEM_BUDGET
        pa = pallas_audit.audit_kernels(budget)
        report["pallas"] = [f.to_dict() for f in pa]
        gate += pa

    from repro.analysis.substrate import substrate_report
    report["substrate"] = substrate_report()
    report["summary"] = {
        "unsuppressed": len(gate),
        "suppressed": len(report["suppressed"]),
        "substrate_modules": len(report["substrate"]["substrate"]),
    }

    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2)
    if args.format == "json":
        print(json.dumps(report, indent=2))
    else:
        print(linter.render_text(
            gate + [Finding(**d) for d in report["suppressed"]]))
        sub = report["substrate"]["substrate"]
        top = sorted({m.split(".")[1] for m in sub if "." in m})
        print(f"substrate (quarantined, informational): "
              f"{len(sub)} modules in {top}")
    return 1 if gate else 0


if __name__ == "__main__":
    sys.exit(main())
