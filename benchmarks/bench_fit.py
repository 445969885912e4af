"""Planned vs unplanned fit(): measures the invariant-hoisting win.

Times a full DTSVM fit two ways over identical inputs, in two regimes —
``paper`` (V=30, T=4, N=256 per (v,t), p=10, 60 ADMM iterations: the
ISSUE config, where the Gram build is a few % of iteration cost and the
planned/unplanned gap sits inside CPU noise) and ``wide_p64`` (same
shapes at p=64, where the N²p Hessian build is a large fraction and the
hoist is directly measurable):

- ``unplanned`` — the seed's per-iteration path: ``dtsvm_step``, which
  rebuilds Z, K, U, the counts and the box every iteration;
- ``planned``   — ``repro.engine.compile_problem`` + ``plan.run``: the
  invariants once, then the light state-dependent body.

Both in two execution modes: ``scan`` (one fused lax.scan per fit —
XLA's loop-invariant code motion already hoists much of the rebuild
there, so the delta is modest) and ``stepwise`` (one eager call per
iteration — the session / direct-``dtsvm_step``-caller pattern, where
no compiler can hoist across calls and the plan's reuse is structural).

A third section, ``sweep``, times the paper's fig4 (C, eps2) grid two
ways — a serial per-config ``compile_problem`` loop (re-tracing and
re-compiling each grid point, the pre-sweep driver pattern) vs ONE
batched ``compile_sweep`` plan — and records the amortization win.

Outputs are verified bit-for-bit identical before timing is reported.
The full (non ``--fast``) run writes ``BENCH_fit.json`` at the repo
root (the perf-trajectory seed); both modes emit the ``run.py`` CSV
contract on stdout.
"""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from common import emit

from repro import engine
from repro.core import dtsvm as core
from repro.core import graph
from repro.data import synthetic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_sweep(iters, qp_iters, *, V=10, n_per_task=(50, 400),
                 degree=0.8667, c_grid=(0.001, 0.01, 0.1),
                 e2_grid=(0.1, 1.0, 10.0, 100.0), seed=0, repeats=2):
    """Serial per-config loop vs one batched SweepPlan on the paper's
    fig4 (C, eps2) grid.  The serial loop re-traces and re-compiles its
    scan per grid point (fresh closures — the fixed cost the sweep
    amortizes); the batched path compiles the whole grid once.  Results
    are asserted bitwise identical before timings are reported."""
    n_train = np.zeros((V, len(n_per_task)), int)
    for t, n in enumerate(n_per_task):
        n_train[:, t] = synthetic.split_counts(n, V)
    data = synthetic.make_multitask_data(V=V, T=len(n_per_task), p=10,
                                         n_train=n_train, n_test=64,
                                         seed=seed)
    A = graph.make_graph("random", V, degree=degree, seed=seed)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A)
    cfgs = [dict(C=c, eps2=e2) for c in c_grid for e2 in e2_grid]
    jax.block_until_ready(prob.X)

    def serial():
        out = []
        for pc in engine.per_config_problems(prob, cfgs):
            pl = engine.compile_problem(pc, qp_iters=qp_iters)
            st, _ = pl.run(iters=iters)
            out.append(st)
        return out

    def batched():
        splan = engine.compile_sweep(prob, cfgs, qp_iters=qp_iters)
        st, _ = splan.run(iters=iters)
        return st

    dt_serial = dt_batched = float("inf")
    last_s = last_b = None
    for _ in range(repeats):
        t0 = time.time()
        last_s = jax.block_until_ready(serial())
        dt_serial = min(dt_serial, time.time() - t0)
        t0 = time.time()
        last_b = jax.block_until_ready(batched())
        dt_batched = min(dt_batched, time.time() - t0)

    for s, st in enumerate(last_s):
        for a, b in zip(jax.tree.leaves(st),
                        jax.tree.leaves(jax.tree.map(lambda x: x[s],
                                                     last_b))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    return {
        "config": {"V": V, "T": len(n_per_task), "N": int(prob.X.shape[2]),
                   "p": int(prob.X.shape[3]), "iters": iters,
                   "qp_iters": qp_iters, "n_configs": len(cfgs),
                   "grid": "fig4 (C, eps2)",
                   "backend": jax.default_backend()},
        "serial_s": dt_serial,
        "batched_s": dt_batched,
        "serial_ms_per_fit": 1e3 * dt_serial / len(cfgs),
        "batched_ms_per_fit": 1e3 * dt_batched / len(cfgs),
        "speedup": dt_serial / dt_batched,
        "bitwise_identical": True,
    }


def _bench_qp_modes(*, V=10, T=2, n_per_vt=128, p=10, iters=40,
                    qp_iters=100, n_test=800, seed=0):
    """Risk-delta table for the QP operating modes: f32 materialized
    (the default contract) vs bf16 streamed-K vs the factored low-rank
    operator, all through the fused multi-iteration engine.  The f32
    multi fit is asserted BITWISE equal to iterating the single-step
    fused engine (the per-dispatch-path contract); bf16 and factored
    are opt-in approximations validated here by their risk deltas."""
    from repro.api import DTSVM, SolverConfig

    n_train = np.full((V, T), n_per_vt, int)
    data = synthetic.make_multitask_data(V=V, T=T, p=p, n_train=n_train,
                                         n_test=n_test, seed=seed)
    A = graph.make_graph("random", V, degree=0.8, seed=seed)
    base = SolverConfig(C=0.01, iters=iters, qp_iters=qp_iters,
                        qp_solver="pallas_fused_multi",
                        qp_operator="materialized")
    modes = {
        "fused_iterated": base.replace(qp_solver="pallas_fused"),
        "f32_materialized": base,
        "bf16_materialized": base.replace(qp_precision="bf16"),
        "f32_factored": base.replace(qp_operator="factored"),
    }
    X = jnp.asarray(data["X"], jnp.float32)
    y = jnp.asarray(data["y"], jnp.float32)
    mask = jnp.asarray(data["mask"], jnp.float32)
    jax.block_until_ready(X)
    out = {"config": {"V": V, "T": T, "N": n_per_vt, "p": p,
                      "iters": iters, "qp_iters": qp_iters,
                      "backend": jax.default_backend()},
           "modes": {}}
    risks, states = {}, {}
    for name, cfg in modes.items():
        solver = DTSVM(cfg)
        t0 = time.time()
        solver.fit(X, y, mask=mask, adj=A)
        jax.block_until_ready(solver.state_.r)
        dt = time.time() - t0
        states[name] = solver.state_
        risks[name] = np.asarray(solver.risks(data["X_test"],
                                              data["y_test"]))
        out["modes"][name] = {"fit_s": round(dt, 3),
                              "mean_risk": float(risks[name].mean())}
    for a, b in zip(jax.tree.leaves(states["f32_materialized"]),
                    jax.tree.leaves(states["fused_iterated"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out["modes"]["f32_materialized"]["bitwise_equals_fused_iterated"] = \
        True
    for name in ("bf16_materialized", "f32_factored"):
        out["modes"][name]["max_abs_risk_delta_vs_f32"] = float(
            np.max(np.abs(risks[name] - risks["f32_materialized"])))
    return out


def _bench_convergence(*, V=10, T=2, n_per_vt=128, p=10, iters=40,
                       qp_iters=100, seed=0):
    """Convergence telemetry per QP engine: the ``repro.obs`` streams
    as curves.  Telemetry is bitwise-invisible (tests/test_obs.py), so
    turning it on here observes exactly the fit the other sections
    time; the recorded trajectories are what ``python -m repro.obs
    report`` renders and what a perf regression that *stalls* ADMM
    (rather than slowing it) would show up in first."""
    from repro.api import DTSVM, SolverConfig
    from repro.obs import timing as obs_timing

    n_train = np.full((V, T), n_per_vt, int)
    data = synthetic.make_multitask_data(V=V, T=T, p=p, n_train=n_train,
                                         n_test=64, seed=seed)
    A = graph.make_graph("random", V, degree=0.8, seed=seed)
    engines = {
        "fista": {"qp_operator": "materialized"},
        "pg": {"qp_solver": "pg"},
        "pallas_fused": {"qp_solver": "pallas_fused"},
        "pallas_fused_multi": {"qp_solver": "pallas_fused_multi",
                               "qp_operator": "materialized"},
        "factored": {"qp_solver": "pallas_fused_multi",
                     "qp_operator": "factored"},
    }
    X = jnp.asarray(data["X"], jnp.float32)
    y = jnp.asarray(data["y"], jnp.float32)
    mask = jnp.asarray(data["mask"], jnp.float32)
    jax.block_until_ready(X)
    out = {"config": {"V": V, "T": T, "N": n_per_vt, "p": p,
                      "iters": iters, "qp_iters": qp_iters,
                      "backend": jax.default_backend()},
           "engines": {}}
    for name, kw in engines.items():
        solver = DTSVM(SolverConfig(C=0.01, iters=iters,
                                    qp_iters=qp_iters, telemetry=True,
                                    **kw))

        def fit_once():
            solver.fit(X, y, mask=mask, adj=A)
            return solver.state_

        t = obs_timing.timeit(fit_once, repeats=1, warmup=0)
        tel = solver.telemetry_
        primal = np.asarray(tel["primal_residual"], np.float64)
        dual = np.asarray(tel["dual_residual"], np.float64)
        out["engines"][name] = {
            "fit_s": round(t.best_s, 3),
            "primal_residual": [round(float(x), 6) for x in primal],
            "dual_residual": [round(float(x), 6) for x in dual],
            "qp_active_frac": [round(float(x), 4) for x in
                               np.asarray(tel["qp_active_frac"])],
            "final_max_disagreement": float(
                np.asarray(tel["disagreement"])[-1].max()),
            "primal_drop": float(primal[0] / max(primal[-1], 1e-12)),
        }
    return out


def _legacy_run(prob, iters, qp_iters, state):
    def body(st, _):
        return core.dtsvm_step(st, prob, qp_iters), jnp.float32(0)
    st, _ = jax.lax.scan(body, state, None, length=iters)
    return st


def _bench_one(V, T, n_per_vt, p, iters, qp_iters):
    n_train = np.full((V, T), n_per_vt, int)
    data = synthetic.make_multitask_data(V=V, T=T, p=p, n_train=n_train,
                                         n_test=64, seed=0)
    A = graph.make_graph("random", V, degree=0.5, seed=0)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    state0 = core.init_state(prob)
    jax.block_until_ready(prob.X)

    def planned():
        # a fit() compiles the plan too — charge it to the planned time
        pl = engine.compile_problem(prob, qp_iters=qp_iters)
        st, _ = pl.run(state=state0, iters=iters)
        return st

    # stepwise mode: one eager dispatch per iteration (no scan to hoist
    # invariants out of) — the online-session / direct-caller pattern
    def stepwise_legacy():
        st = state0
        for _ in range(iters):
            st = core.dtsvm_step(st, prob, qp_iters)
        return st

    def stepwise_planned():
        pl = engine.compile_problem(prob, qp_iters=qp_iters)
        st = state0
        for _ in range(iters):
            st = pl.step(st)
        return st

    variants = {
        "scan_legacy": lambda: _legacy_run(prob, iters, qp_iters, state0),
        "scan_planned": planned,
        "step_legacy": stepwise_legacy,
        "step_planned": stepwise_planned,
        # the hoisted quantity itself: what one invariant build (Z, K,
        # u, counts, box, L) costs — the legacy path pays this EVERY
        # iteration, the plan once per fit
        "invariants": lambda: jax.tree.map(
            jnp.asarray, engine.compute_invariants(prob)),
    }
    # interleave the variants round-robin so slow machine-load drift
    # hits all of them equally; keep per-variant min over repeats
    last, best = {}, {k: float("inf") for k in variants}
    for k, fn in variants.items():                # warm-up (compile)
        last[k] = jax.block_until_ready(fn())
    for _ in range(3):
        for k, fn in variants.items():
            t0 = time.time()
            last[k] = jax.block_until_ready(fn())
            best[k] = min(best[k], time.time() - t0)

    for a, b in zip(jax.tree.leaves(last["scan_legacy"]),
                    jax.tree.leaves(last["scan_planned"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    dt_legacy, dt_plan = best["scan_legacy"], best["scan_planned"]
    dt_step_legacy, dt_step_plan = best["step_legacy"], best["step_planned"]
    dt_inv = best["invariants"]

    rec = {
        "config": {"V": V, "T": T, "N": int(prob.X.shape[2]),
                   "p": int(prob.X.shape[3]), "iters": iters,
                   "qp_iters": qp_iters, "backend": jax.default_backend()},
        "scan": {
            "unplanned_ms_per_iter": 1e3 * dt_legacy / iters,
            "planned_ms_per_iter": 1e3 * dt_plan / iters,
            "speedup": dt_legacy / dt_plan,
        },
        "stepwise": {
            "unplanned_ms_per_iter": 1e3 * dt_step_legacy / iters,
            "planned_ms_per_iter": 1e3 * dt_step_plan / iters,
            "speedup": dt_step_legacy / dt_step_plan,
        },
        # per-fit invariant work: legacy pays iters×, the plan pays 1×
        "invariant_build_ms": 1e3 * dt_inv,
        "invariant_ms_saved_per_fit": 1e3 * dt_inv * (iters - 1),
        "bitwise_identical": True,
    }
    return rec


def run(fast: bool = False):
    if fast:
        return {"paper": _bench_one(8, 2, 32, 10, 10, 50),
                "sweep": _bench_sweep(8, 40, c_grid=(0.01, 0.1),
                                      e2_grid=(1.0, 10.0), repeats=1),
                "qp_modes": _bench_qp_modes(V=4, T=2, n_per_vt=24,
                                            iters=8, qp_iters=30,
                                            n_test=64),
                "convergence": _bench_convergence(V=4, T=2, n_per_vt=24,
                                                  iters=8, qp_iters=30)}
    recs = {
        "paper": _bench_one(30, 4, 256, 10, 60, 100),
        "wide_p64": _bench_one(30, 4, 256, 64, 60, 100),
        "sweep": _bench_sweep(60, 100),
        "qp_modes": _bench_qp_modes(),
        "convergence": _bench_convergence(),
    }
    # fast mode is a smoke run on a toy config — never clobber the
    # committed paper-regime perf-trajectory record with it; a full run
    # rewrites only the sections it owns (roofline.py keeps its own)
    path = os.path.join(ROOT, "BENCH_fit.json")
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        old.update(recs)
        recs = old
    with open(path, "w") as f:
        json.dump(recs, f, indent=2)
        f.write("\n")
    return recs


def main(fast=False):
    recs = run(fast)
    for name, rec in recs.items():
        if name == "roofline":        # owned by roofline.py, preserved
            continue
        if name == "sweep":
            emit("bench_fit_sweep", 1e3 * rec["batched_ms_per_fit"],
                 f"sweep_speedup={rec['speedup']:.2f}x "
                 f"serial_ms_fit={rec['serial_ms_per_fit']:.1f} "
                 f"batched_ms_fit={rec['batched_ms_per_fit']:.1f} "
                 f"configs={rec['config']['n_configs']}")
            continue
        if name == "convergence":
            e = rec["engines"]["fista"]
            emit("bench_fit_convergence", 1e6 * e["fit_s"],
                 f"primal_drop={e['primal_drop']:.1f}x "
                 f"final_dual={e['dual_residual'][-1]:.2e} "
                 f"active_frac={e['qp_active_frac'][-1]:.2f} "
                 f"engines={len(rec['engines'])}")
            continue
        if name == "qp_modes":
            m = rec["modes"]
            emit("bench_fit_qp_modes",
                 1e6 * m["f32_materialized"]["fit_s"],
                 f"bitwise_f32_vs_iterated="
                 f"{m['f32_materialized']['bitwise_equals_fused_iterated']} "
                 f"bf16_risk_delta="
                 f"{m['bf16_materialized']['max_abs_risk_delta_vs_f32']:.4f} "
                 f"factored_risk_delta="
                 f"{m['f32_factored']['max_abs_risk_delta_vs_f32']:.4f} "
                 f"factored_fit_s={m['f32_factored']['fit_s']}")
            continue
        emit(f"bench_fit_{name}",
             1e3 * rec["scan"]["planned_ms_per_iter"],
             f"scan_speedup={rec['scan']['speedup']:.2f}x "
             f"stepwise_speedup={rec['stepwise']['speedup']:.2f}x "
             f"planned_ms_it={rec['scan']['planned_ms_per_iter']:.1f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    main(ap.parse_args().fast)
