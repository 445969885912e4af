"""§Perf hillclimb driver — climbs the QP-engine variant ladder on one
paper-regime DTSVM problem and appends a JSON record per variant to
``results/hillclimb.jsonl``.

Each rung re-times the same fit (same data, same config grid point)
under a different execution strategy of the engine registry, on the
shared ``repro.obs.timing`` clock and inside an ``obs.span`` so the
ladder shows up in the Chrome trace next to the engine's own phase
spans.  Telemetry rides along (bitwise-invisible) to attach a
*convergence guardrail* to every rung: a variant only counts as a perf
win if its final primal/dual residuals and test risk match the
baseline's — a fast kernel that stalls the ADMM outer loop is a loss,
not a win.

    python benchmarks/hillclimb.py [--fast] [--variant pallas_fused]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
try:
    import repro  # noqa: F401  (pip install -e .)
except ModuleNotFoundError:  # fallback: run from a bare checkout
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp
import numpy as np

from common import C, ETA1, ETA2, RESULTS, build, emit
from repro.api import DTSVM, SolverConfig, evaluate
from repro.obs import spans as obs_spans
from repro.obs import timing as obs_timing

#: the ladder: every execution strategy the engine registry exposes for
#: the same ADMM recursion, cheapest-to-build first.  fista is the
#: reference rung every other rung's guardrail compares against.
VARIANTS = [
    ("fista", {"qp_operator": "materialized"}),
    ("pg", {"qp_solver": "pg"}),
    ("pallas_fused", {"qp_solver": "pallas_fused"}),
    ("pallas_fused_multi", {"qp_solver": "pallas_fused_multi",
                            "qp_operator": "materialized"}),
    ("factored", {"qp_solver": "pallas_fused_multi",
                  "qp_operator": "factored"}),
]


def measure(name, kw, data, A, *, iters, qp_iters, repeats):
    """One rung: warm-compile, time ``repeats`` fits, pull the final
    telemetry readings off the last fit.  Returns the jsonl record."""
    cfg = SolverConfig(C=C, eta1=ETA1, eta2=ETA2, iters=iters,
                       qp_iters=qp_iters, telemetry=True, **kw)
    solver = DTSVM(cfg)
    X = jnp.asarray(data["X"], jnp.float32)
    y = jnp.asarray(data["y"], jnp.float32)
    mask = jnp.asarray(data["mask"], jnp.float32)
    jax.block_until_ready(X)

    def fit_once():
        solver.fit(X, y, mask=mask, adj=A)
        return solver.state_

    with obs_spans.span("hillclimb_variant", variant=name):
        t = obs_timing.timeit(fit_once, repeats=repeats, warmup=1)

    tel = solver.telemetry_
    risks = evaluate.risks_of_state(solver.state_, data["X_test"],
                                    data["y_test"])
    return {
        "variant": name,
        "qp_solver": cfg.qp_solver, "qp_operator": cfg.qp_operator,
        "fit_s": t.best_s, "mean_s": t.mean_s,
        "us_per_admm_iter": t.best_s / iters * 1e6,
        "primal_residual": float(np.asarray(tel["primal_residual"])[-1]),
        "dual_residual": float(np.asarray(tel["dual_residual"])[-1]),
        "mean_risk": float(np.mean(np.asarray(risks))),
        "iters": iters, "qp_iters": qp_iters, "repeats": repeats,
    }


def main(fast=True, variant="all"):
    iters = 5 if fast else 30
    qp_iters = 20 if fast else 100
    repeats = 1 if fast else 3
    data, A = build(4, [200, 200], degree=0.8, graph_kind="random",
                    n_test=600 if fast else 1800, seed=0)

    records, baseline = [], None
    for name, kw in VARIANTS:
        if variant != "all" and variant != name:
            continue
        rec = measure(name, kw, data, A, iters=iters, qp_iters=qp_iters,
                      repeats=repeats)
        if baseline is None:
            baseline = rec
        rec["speedup_vs_fista"] = baseline["fit_s"] / rec["fit_s"]
        # the guardrail: perf that stalls the ADMM recursion is not
        # perf.  Inner solvers legitimately differ per-iterate, so the
        # bar is "same test risk, residual no worse than ~2x baseline",
        # not a bitwise trajectory match
        rec["guardrail_ok"] = bool(
            abs(rec["mean_risk"] - baseline["mean_risk"]) < 1e-3
            and rec["primal_residual"]
            <= 2.0 * baseline["primal_residual"] + 1e-3)
        records.append(rec)
        emit(f"hillclimb_{name}", rec["fit_s"] * 1e6,
             f"speedup={rec['speedup_vs_fista']:.2f}x "
             f"guardrail={'ok' if rec['guardrail_ok'] else 'VIOLATED'}")

    if records:
        os.makedirs(RESULTS, exist_ok=True)
        with open(os.path.join(RESULTS, "hillclimb.jsonl"), "a") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
        obs_spans.save_trace(os.path.join(RESULTS, "hillclimb-trace.json"))
    return records


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="shrink repeats/iters, same ladder")
    ap.add_argument("--variant", default="all")
    args = ap.parse_args()
    main(fast=args.fast, variant=args.variant)
