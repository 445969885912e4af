"""Large-n scale benchmark: budgeted (chunked/tiled) vs dense invariant
builds, and budgeted fits across QP engines and backends.

The dense invariant build of ``repro.engine`` holds TWO K-sized buffers
live at once — the batched Gram matmul output plus the |K| temporary of
the Gershgorin pass — so at the large-n regime (n_t >= 20k samples per
node-task, p >= 256 features) it needs ~2x the memory the plan actually
keeps.  The ``PlanBudget`` path streams K row-panel by row-panel with
the Gershgorin row sums folded into the same pass, holding one K plus a
bounded panel.  Both are bitwise identical (tests/test_scale.py).

Sections of ``BENCH_scale.json``:

- ``large_build``   the n_t >= 20k, p >= 256 regime.  Dense and
                    budgeted builds run in CPU subprocesses
                    (``JAX_PLATFORMS=cpu``: host memory is what the cap
                    bounds, and a child never needs the chip its parent
                    may hold) under an address-space cap
                    (``RLIMIT_AS``) sized between the two footprints:
                    the dense build OOMs, the budgeted build fits.
                    Uncapped wall-clock and measured peak RSS are
                    recorded for both, plus the analytic
                    workspace-elems accounting per configuration.
- ``large_fit``     full budgeted fits at the same regime across QP
                    engines (``fista``, ``pallas_fused``,
                    ``pallas_fused_multi``) and backends (``vmap``,
                    ``async``), plus a ``qp_operator="factored"`` row
                    that skips the N^2 Gram build entirely (the
                    low-rank headline win); the async identity fabric
                    is asserted bitwise equal to vmap and the multi
                    engine bitwise equal to the iterated fused engine.
- ``equivalence``   a moderate regime where dense still fits: budgeted
                    and dense fits asserted bitwise identical across
                    the same engine/backend grid, with build timings.

``--fast`` shrinks every regime (CI artifact — never clobbers the
committed record unless ``--out`` says so).  Output: the repo-root
``BENCH_scale.json`` on a full run, plus the ``run.py`` CSV contract on
stdout.
"""
import argparse
import json
import os
import resource
import subprocess
import sys
import time

import jax
import numpy as np

from common import emit

from repro import engine
from repro.api import backends
from repro.core import dtsvm as core
from repro.core import graph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Child script for the capped builds: the parent cannot safely OOM
# itself, so each build runs in a subprocess whose virtual address
# space is capped *before* the build starts.  Prints one JSON line.
_CHILD = r"""
import json, os, resource, sys, time
cap, mode, V, T, N, p, max_elems = (int(x) for x in sys.argv[1:8])
if cap > 0:
    resource.setrlimit(resource.RLIMIT_AS, (cap, cap))
sys.path.insert(0, os.path.join(@ROOT@, "src"))
import numpy as np
import jax
from repro import engine
from repro.core import dtsvm as core, graph
rng = np.random.default_rng(0)
X = rng.normal(size=(V, T, N, p)).astype(np.float32)
y = np.sign(rng.normal(size=(V, T, N)))
y = np.where(y == 0, 1.0, y).astype(np.float32)
A = graph.make_graph("ring", V, seed=0)
prob = core.make_problem(X, y, None, A, C=0.01)
jax.block_until_ready(prob.X)
budget = None if mode == 0 else engine.PlanBudget(max_elems=max_elems)
t0 = time.time()
inv = engine.compute_invariants(prob, budget=budget)
jax.block_until_ready(inv.K)
print(json.dumps({
    "seconds": round(time.time() - t0, 3),
    "peak_rss_gb": round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 3),
}))
"""


def _run_build(*, cap_bytes, dense, V, T, N, p, max_elems, timeout=900):
    """One (possibly capped) invariant build in a CPU subprocess."""
    child = _CHILD.replace("@ROOT@", repr(ROOT))
    args = [sys.executable, "-c", child, str(cap_bytes),
            "0" if dense else "1", str(V), str(T), str(N), str(p),
            str(max_elems)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        out = subprocess.run(args, capture_output=True, text=True,
                             timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {"ok": False, "oom": False, "error": "timeout"}
    if out.returncode == 0:
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        rec["ok"], rec["oom"] = True, False
        return rec
    err = (out.stderr or "")[-2000:]
    # only specific allocation-failure signals (plus the kernel's
    # OOM-killer SIGKILL) count as OOM — any other child failure must
    # surface as an error, not fabricate the benchmark's headline claim
    markers = ("MemoryError", "RESOURCE_EXHAUSTED", "std::bad_alloc",
               "Out of memory")
    oom = out.returncode == -9 or any(m in err for m in markers)
    return {"ok": False, "oom": oom,
            "error": err.strip().splitlines()[-1] if err.strip() else
            f"exit code {out.returncode}"}


def _workspace_elems(V, T, N, budget):
    """Analytic Gram-workspace accounting (float32 elements).

    The dense build holds the K output plus the |K| temporary of the
    Gershgorin pass; the budgeted build holds K plus one streamed
    row panel."""
    B = V * T
    k_elems = B * N * N
    if budget is None:
        return {"k_elems": k_elems, "workspace_elems": 2 * k_elems}
    chunk = budget.row_chunk(B, N) or N
    return {"k_elems": k_elems,
            "workspace_elems": k_elems + B * chunk * N,
            "row_chunk": chunk}


def _bench_large_build(*, V=2, T=1, N=20000, p=256, max_elems=2 ** 27):
    """The headline regime: dense OOMs under a cap the budgeted build
    fits, and the budgeted build's uncapped wall-clock/peak-RSS win."""
    budget = engine.PlanBudget(max_elems=max_elems)
    k_bytes = 4 * V * T * N * N
    # cap between the budgeted footprint (~K + panel + runtime) and the
    # dense one (~2K + runtime)
    cap = int(k_bytes * 1.55) + (1 << 30)
    rec = {
        "config": {"V": V, "T": T, "N": N, "p": p,
                   "max_elems": max_elems, "cap_gb": round(cap / 1e9, 2),
                   "backend": "cpu"},
        "dense": _workspace_elems(V, T, N, None),
        "budgeted": _workspace_elems(V, T, N, budget),
    }
    for name, dense in (("dense", True), ("budgeted", False)):
        rec[name]["uncapped"] = _run_build(
            cap_bytes=0, dense=dense, V=V, T=T, N=N, p=p,
            max_elems=max_elems)
        rec[name]["capped"] = _run_build(
            cap_bytes=cap, dense=dense, V=V, T=T, N=N, p=p,
            max_elems=max_elems)
    d, b = rec["dense"], rec["budgeted"]
    rec["dense_oom_under_cap"] = bool(d["capped"].get("oom"))
    rec["budgeted_fits_under_cap"] = bool(b["capped"].get("ok"))
    if d["uncapped"].get("ok") and b["uncapped"].get("ok"):
        rec["build_speedup"] = round(
            d["uncapped"]["seconds"] / b["uncapped"]["seconds"], 3)
        rec["peak_rss_saved_gb"] = round(
            d["uncapped"]["peak_rss_gb"] - b["uncapped"]["peak_rss_gb"], 3)
    return rec


def _make_problem(V, T, N, p, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(V, T, N, p)).astype(np.float32)
    y = np.sign(rng.normal(size=(V, T, N))).astype(np.float32)
    y = np.where(y == 0, 1.0, y).astype(np.float32)
    A = graph.make_graph("ring", V, seed=seed)
    return core.make_problem(X, y, None, A, C=0.01)


_ENGINES = ("fista", "pallas_fused", "pallas_fused_multi")


def _bench_fits(*, V, T, N, p, iters, qp_iters, max_elems,
                assert_dense_equal):
    """Budgeted fits across (qp engine) x (backend) plus the factored
    low-rank operator; optionally assert bitwise equality against the
    dense plan (the moderate regime where dense still fits)."""
    prob = _make_problem(V, T, N, p)
    budget = engine.PlanBudget(max_elems=max_elems)
    jax.block_until_ready(prob.X)
    recs = {"config": {"V": V, "T": T, "N": N, "p": p, "iters": iters,
                       "qp_iters": qp_iters, "max_elems": max_elems,
                       "backend": jax.default_backend()},
            "accounting": _workspace_elems(V, T, N, budget),
            "fits": []}
    states = {}
    for qp_solver in _ENGINES:
        dense_state = None
        if assert_dense_equal:
            st, _ = backends.run(prob, iters, backend="vmap",
                                 qp_iters=qp_iters, qp_solver=qp_solver,
                                 qp_operator="materialized")
            dense_state = jax.block_until_ready(st)
        for backend in ("vmap", "async"):
            t0 = time.time()
            st, _ = backends.run(prob, iters, backend=backend,
                                 qp_iters=qp_iters, qp_solver=qp_solver,
                                 qp_operator="materialized",
                                 budget=budget)
            jax.block_until_ready(st.r)
            dt = time.time() - t0
            states[(qp_solver, backend)] = st
            entry = {"qp_solver": qp_solver, "backend": backend,
                     "fit_s": round(dt, 3)}
            if dense_state is not None:
                for x, z in zip(jax.tree.leaves(dense_state),
                                jax.tree.leaves(st)):
                    np.testing.assert_array_equal(np.asarray(x),
                                                  np.asarray(z))
                entry["bitwise_equals_dense"] = True
            recs["fits"].append(entry)
    # the async identity fabric must reproduce vmap bitwise, budget or
    # not; the fused multi engine must reproduce the iterated fused
    # engine bitwise per backend (the shared f32 oracle dispatch path)
    for qp_solver in _ENGINES:
        for x, z in zip(jax.tree.leaves(states[(qp_solver, "vmap")]),
                        jax.tree.leaves(states[(qp_solver, "async")])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(z))
    for backend in ("vmap", "async"):
        for x, z in zip(
                jax.tree.leaves(states[("pallas_fused", backend)]),
                jax.tree.leaves(states[("pallas_fused_multi", backend)])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(z))
    recs["async_identity_bitwise"] = True
    recs["multi_bitwise_equals_fused"] = True
    # the factored low-rank operator: K = Z diag(a) Z^T is rank <= p+1,
    # so the fit skips the N^2 Gram build entirely (vmap-only mode; not
    # bitwise vs materialized — validated by state deltas instead)
    t0 = time.time()
    st_f, _ = backends.run(prob, iters, backend="vmap",
                           qp_iters=qp_iters,
                           qp_solver="pallas_fused_multi",
                           qp_operator="factored", budget=budget)
    jax.block_until_ready(st_f.r)
    dt_f = time.time() - t0
    st_m = states[("pallas_fused_multi", "vmap")]
    max_dr = float(np.max(np.abs(np.asarray(st_f.r) -
                                 np.asarray(st_m.r))))
    recs["fits"].append({"qp_solver": "pallas_fused_multi",
                         "backend": "vmap", "qp_operator": "factored",
                         "fit_s": round(dt_f, 3),
                         "max_abs_r_delta_vs_materialized": max_dr})
    fused_vmap = next(e["fit_s"] for e in recs["fits"]
                      if e["qp_solver"] == "pallas_fused"
                      and e["backend"] == "vmap")
    recs["factored_speedup_vs_fused_vmap"] = round(fused_vmap / dt_f, 3)
    return recs


def run(fast: bool = False, out: str = None):
    if fast:
        recs = {
            "large_build": _bench_large_build(V=2, T=1, N=4096, p=64,
                                              max_elems=2 ** 23),
            "equivalence": _bench_fits(V=3, T=2, N=256, p=32, iters=3,
                                       qp_iters=30, max_elems=3 * 2 * 64 *
                                       256, assert_dense_equal=True),
        }
    else:
        recs = {
            "large_build": _bench_large_build(),
            "large_fit": _bench_fits(V=2, T=1, N=20000, p=256, iters=2,
                                     qp_iters=10, max_elems=2 ** 27,
                                     assert_dense_equal=False),
            "equivalence": _bench_fits(V=4, T=2, N=1024, p=64, iters=4,
                                       qp_iters=50,
                                       max_elems=4 * 2 * 128 * 1024,
                                       assert_dense_equal=True),
        }
    if out is not None:
        path = out
    elif not fast:
        # fast mode is a smoke config — don't clobber the committed
        # full-regime record unless --out says so explicitly
        path = os.path.join(ROOT, "BENCH_scale.json")
    else:
        path = None
    if path:
        with open(path, "w") as f:
            json.dump(recs, f, indent=2)
            f.write("\n")
    return recs


def main(fast=False, out=None):
    recs = run(fast, out)
    lb = recs["large_build"]
    dense_unc = lb["dense"]["uncapped"]
    budg_unc = lb["budgeted"]["uncapped"]
    fits = recs.get("large_fit") or recs.get("equivalence")
    emit("bench_scale",
         1e6 * budg_unc.get("seconds", float("nan")),
         f"dense_oom_under_cap={lb['dense_oom_under_cap']} "
         f"budgeted_fits_under_cap={lb['budgeted_fits_under_cap']} "
         f"build_speedup={lb.get('build_speedup', 'n/a')} "
         f"peak_rss_dense_gb={dense_unc.get('peak_rss_gb', 'oom')} "
         f"peak_rss_budgeted_gb={budg_unc.get('peak_rss_gb', 'n/a')} "
         f"factored_speedup="
         f"{fits.get('factored_speedup_vs_fused_vmap', 'n/a')}x")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write BENCH_scale.json to this path")
    args = ap.parse_args()
    main(args.fast, args.out)
