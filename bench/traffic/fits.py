"""Traffic kind ``fits``: whole DTSVM fits, back to back, closed loop.

Mix parameters (``traffic/<mix>.json``):

- ``datasets``   how many inputs, made from the seed in set-up; fit i of
                 the window takes dataset i mod ``datasets``, so no fit
                 repeats the one before it;
- ``warm_fits``  fits run in set-up to warm every program.

Each fit is ``repro.api.DTSVM(cfg).fit(X, y, mask=mask, adj=A)`` on
inputs already on the device (sharded over the cell's chips for the
``sample_shard`` backend), with no warm start, ended by
``block_until_ready`` on its state.  ``fit_s`` is the window's wall time
over the fits in it; the window ends with the first fit that finishes
past ``--seconds``, so it holds whole fits only.

With ``--trace 1`` on the ``vmap`` backend the window alternates two
kinds of fit.  A whole fit is the window's own ``DTSVM.fit`` call, inside
a ``bench.fit`` span; the lowering count and the idle share are read
from these alone.  A split fit drives the same fit as its two phases,
``engine.compile_problem`` then ``Plan.run`` (what ``fit`` runs inside),
each blocked inside a ``bench.build`` / ``bench.admm`` span within a
``bench.split`` span; the build and ADMM device times are read from
these.  The traced window stops once it holds the workload's
``trace_fits`` fits of each kind.  Other backends run whole fits only.

With ``run.control`` each fit is the plain reference at three bfloat16
passes (``precision="high"``) in place of the program: the control.

Every fit of the window is compared with the plain reference fit of its
dataset (``benchlib.reference.dtsvm_fit``): the worst leaf of (r, alpha,
beta, lam), as max abs gap over the reference's max abs.
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import compare, datagen, reference

LEAVES = compare.STATE_LEAVES


def _solver_config(run):
    from repro.api import PlanBudget, SolverConfig

    cfg = run.config
    s = cfg["solver"]
    budget = (PlanBudget(max_elems=int(cfg["budget"]["max_elems"]))
              if cfg.get("budget") else None)
    options = {}
    if s["backend"] == "sample_shard":
        options = {"n_shards": run.chips}
    return SolverConfig(C=s["C"], eps1=s["eps1"], eps2=s["eps2"],
                        eta1=s["eta1"], eta2=s["eta2"], iters=cfg["iters"],
                        qp_iters=cfg["qp_iters"], qp_solver=s["qp_solver"],
                        backend=s["backend"], backend_options=options,
                        budget=budget)


def _place(run, d):
    """Put X, y, mask where the cell's backend takes them."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if run.config["solver"]["backend"] != "sample_shard":
        return d
    mesh = Mesh(np.asarray(run.devices), ("samples",))
    rows = NamedSharding(mesh, P(None, None, "samples"))
    out = dict(d)
    out["X"] = jax.device_put(d["X"], NamedSharding(
        mesh, P(None, None, "samples", None)))
    out["y"] = jax.device_put(d["y"], rows)
    out["mask"] = jax.device_put(d["mask"], rows)
    return out


def _fit(run, m: int, split: bool):
    import jax

    from repro import engine
    from repro.api import DTSVM

    d = run.state["data"][m]
    A = run.state["adj"]
    if run.control:
        return _reference(run, m, "high")
    solver = DTSVM(run.state["solver_config"])
    if not split:
        solver.fit(d["X"], d["y"], mask=d["mask"], adj=A)
        jax.block_until_ready(solver.state_)
        return solver.state_
    prob = solver.make_problem(d["X"], d["y"], d["mask"], A)
    with jax.profiler.TraceAnnotation("bench.build"):
        plan = engine.compile_problem(prob, solver.config)
        jax.block_until_ready(plan.inv)
    with jax.profiler.TraceAnnotation("bench.admm"):
        st, _ = plan.run(iters=solver.config.iters)
        jax.block_until_ready(st)
    return st


def setup(run) -> None:
    cfg, mix = run.config, run.mix
    run.state["solver_config"] = _solver_config(run)
    run.state["adj"] = datagen.make_graph(cfg, run.seed)
    run.state["data"] = [_place(run, datagen.fit_data(cfg, run.seed, m))
                         for m in range(int(mix["datasets"]))]
    for i in range(int(mix["warm_fits"])):
        _fit(run, i % len(run.state["data"]), False)
    if _splits(run):
        _fit(run, 0, True)


def _splits(run) -> bool:
    return run.trace and run.config["solver"]["backend"] == "vmap"


def window(run, seconds: float) -> dict:
    import jax

    splits = _splits(run)
    cap = int(run.workload.get("trace_fits", 0)) if run.trace else 0
    n_data = len(run.state["data"])
    fits, errors = [], []
    whole = {"fits": 0, "lowerings": 0}
    n_split = 0
    t0 = time.perf_counter()
    t = t0
    while True:
        m = len(fits) % n_data
        split = splits and len(fits) % 2 == 1
        try:
            if split:
                with jax.profiler.TraceAnnotation("bench.split"):
                    st = _fit(run, m, True)
                n_split += 1
            else:
                low = run.counter.snapshot()["lowerings"]
                with jax.profiler.TraceAnnotation("bench.fit"):
                    st = _fit(run, m, False)
                whole["fits"] += 1
                whole["lowerings"] += run.counter.snapshot()["lowerings"] - low
        except Exception as e:                 # a fit that fails is counted
            errors.append(f"{type(e).__name__}: {e}")
            break
        fits.append((m, st))
        t = time.perf_counter()
        if t - t0 >= seconds or (cap and whole["fits"] >= cap
                                 and (not splits or n_split >= cap)):
            break
    run.state["fits"] = fits
    n = len(fits)
    return {"attempted": n + len(errors), "failed": len(errors),
            "fits": n, "whole": whole, "window_s": t - t0,
            "e2e": {"fit_s": (t - t0) / n} if n else {},
            "notes": {"fits": n, "split_fits": n_split, "errors": errors[:3]}}


def _leaves(st) -> dict:
    get = st.get if isinstance(st, dict) else (lambda k: getattr(st, k))
    return {k: np.asarray(get(k)) for k in LEAVES}


def release(run) -> None:
    run.state["fits"] = [(m, _leaves(st)) for m, st in run.state["fits"]]


def _reference(run, m: int, precision: str):
    import jax

    d = run.state["data"][m]
    dev = run.devices[0]
    args = [jax.device_put(d[k], dev) for k in ("X", "y", "mask")]
    adj = jax.device_put(np.asarray(run.state["adj"]), dev)
    return reference.dtsvm_fit(run.config, *args, adj, precision=precision)


def check(run) -> dict:
    fits = run.state["fits"]
    refs = {m: _reference(run, m, "highest") for m in sorted({m for m, _ in
                                                            fits})}
    err = max((compare.state_rel_err(st, refs[m]) for m, st in fits),
              default=float("inf"))
    run.state["refs"] = refs
    return {"state_rel_err": err}


def control(run) -> dict:
    refs = run.state.get("refs") or {
        m: _reference(run, m, "highest")
        for m in range(len(run.state["data"]))}
    err = max(compare.state_rel_err(_reference(run, m, "high"), ref)
              for m, ref in refs.items())
    return {"state_rel_err": err}
