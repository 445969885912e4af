"""Shared benchmark utilities: paper-regime data builders + runners.

Every figure benchmark reproduces one experiment of the paper on the
MNIST-proxy generator (DESIGN.md data gate) and reports the figure's
qualitative claim as a derived metric.  ``--fast`` shrinks repeat counts,
not the experimental structure.

All solver execution goes through ``repro.api`` — the figure drivers never
touch problem construction or test-set broadcasting themselves.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    import repro  # noqa: F401  (pip install -e .)
except ModuleNotFoundError:  # fallback: run from a bare checkout
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))

from repro import compile_cache                             # noqa: E402
from repro.api import CSVM, DSVM, DTSVM, SolverConfig      # noqa: E402
from repro.api import dsvm_overrides, evaluate, sweep_fit  # noqa: E402,F401
from repro.core import graph                                # noqa: E402
from repro.data import synthetic                            # noqa: E402
from repro.obs import timing as obs_timing                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(ROOT, "results")
compile_cache.enable(ROOT)

# Paper Section IV defaults
C = 0.01
ETA1 = ETA2 = 1.0


def build(V, n_per_task, *, T=None, degree=0.8, graph_kind="random",
          n_test=1800, relatedness=0.9, noise=1.0, pos_frac=None, seed=0):
    """n_per_task: list of TOTAL training samples per task (paper style —
    split evenly over nodes)."""
    T = T or len(n_per_task)
    n_train = np.zeros((V, T), int)
    for t, n in enumerate(n_per_task):
        n_train[:, t] = synthetic.split_counts(n, V)
    data = synthetic.make_multitask_data(
        V=V, T=T, p=10, n_train=n_train, n_test=n_test,
        relatedness=relatedness, noise=noise, pos_frac=pos_frac, seed=seed)
    A = graph.make_graph(graph_kind, V, degree=degree, seed=seed)
    return data, A


def solver_config(*, iters, eps1=1.0, eps2=1.0, C_=C, qp_iters=100):
    return SolverConfig(C=C_, eps1=eps1, eps2=eps2, eta1=ETA1, eta2=ETA2,
                        iters=iters, qp_iters=qp_iters)


def _timed_fit(solver, data, A, *, active=None, couple=None,
               with_history=True, state=None):
    """Time the ADMM run only: data transfer and test-set broadcast happen
    before t0, so the reported dt/iter stays comparable across PRs.  One
    timed call (compile included — a fit pays it), on the shared
    ``repro.obs.timing.timeit`` clock."""
    V = data["X"].shape[0]
    X = jnp.asarray(data["X"], jnp.float32)
    y = jnp.asarray(data["y"], jnp.float32)
    mask = jnp.asarray(data["mask"], jnp.float32)
    ev = evaluate.risk_eval_fn(V, data["X_test"], data["y_test"]) \
        if with_history else None
    jax.block_until_ready(X)

    def fit_once():
        solver.fit(X, y, mask=mask, adj=A, active=active, couple=couple,
                   state=state, eval_fn=ev)
        return solver.state_

    t = obs_timing.timeit(fit_once, repeats=1, warmup=0)
    hist = None if solver.history_ is None else np.asarray(solver.history_)
    return solver.state_, hist, t.best_s, solver.problem_


def run_dtsvm(data, A, iters, *, eps1=1.0, eps2=1.0, C_=C, qp_iters=100,
              active=None, couple=None, with_history=True, state=None):
    solver = DTSVM(solver_config(iters=iters, eps1=eps1, eps2=eps2, C_=C_,
                                 qp_iters=qp_iters))
    return _timed_fit(solver, data, A, active=active, couple=couple,
                      with_history=with_history, state=state)


def run_dsvm(data, A, iters, *, eps2=1.0, C_=C, qp_iters=100,
             active=None, with_history=True):
    solver = DSVM(solver_config(iters=iters, eps2=eps2, C_=C_,
                                qp_iters=qp_iters))
    return _timed_fit(solver, data, A, active=active,
                      with_history=with_history)


def run_sweep(data, A, cfgs, iters, *, eps1=1.0, eps2=1.0, C_=C,
              qp_iters=100, chain=False, with_history=True):
    """One batched fit of a whole config grid (``repro.api.sweep_fit``).

    Returns ``(SweepResult, dt)`` where dt times the full sweep —
    problem construction, the one shared invariant build, and the
    vmapped ADMM run — matching what ``_timed_fit`` charges a serial
    fit.  Per-config results are bitwise those of looping ``run_dtsvm``
    / ``run_dsvm`` over the same grid (tests/test_sweep.py).
    """
    X = jnp.asarray(data["X"], jnp.float32)
    y = jnp.asarray(data["y"], jnp.float32)
    mask = jnp.asarray(data["mask"], jnp.float32)
    jax.block_until_ready(X)

    def sweep_once():
        res = sweep_fit(
            X, y, cfgs, mask=mask, adj=A,
            base=solver_config(iters=iters, eps1=eps1, eps2=eps2, C_=C_,
                               qp_iters=qp_iters),
            X_test=data["X_test"] if with_history else None,
            y_test=data["y_test"] if with_history else None, chain=chain)
        jax.block_until_ready(res.states.r)
        return res

    t = obs_timing.timeit(sweep_once, repeats=1, warmup=0, block=False)
    return t.result, t.best_s


def run_csvm_per_task(data, *, C_scale=1.0, qp_iters=600):
    """Pooled centralized SVM per task."""
    solver = CSVM(SolverConfig(C=C, qp_iters=qp_iters), C_scale=C_scale)
    solver.fit(data["X"], data["y"], mask=data["mask"])
    return [float(r) for r in solver.risks(data["X_test"], data["y_test"])]


def write_csv(name: str, header: str, rows):
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, name)
    with open(path, "w") as f:
        f.write(header + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    return path


def emit(name: str, us_per_call: float, derived: str):
    """The run.py contract: ``name,us_per_call,derived`` CSV on stdout."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
