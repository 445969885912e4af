#!/usr/bin/env python3
"""Drive the DTSVM main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: every single-chip phase
    python chip_smoke.py --chips 4    # four chips: the cross-chip paths only

Everything runs in this one process, through ``repro.api``:

- devices   what JAX sees; anything but a TPU fails at once.
- paper     the paper's regime (V=30 nodes, T=4 tasks, N=256 samples per
            node-task, p=10): DTSVM and DSVM fits on the chip against the
            same fits on the host CPU with the jnp oracle kernels, and the
            identity ``NetConfig()`` fit against the vmap fit, bitwise.
- session   an ``OnlineSession`` that adds one task and drops another,
            against a fresh fit of the final membership.
- large_n   V=2, T=2, N=20000, p=256 under a ``PlanBudget`` (the dual
            Hessian is 6.4 GB): the ``fista``, ``pallas_fused_multi`` and
            factored fits, each against the same fit run with the jnp
            oracle kernels on the chip; the compiled Pallas kernels must be
            in the lowered programs (``tpu_custom_call``).
- serve     ``PredictServer`` answers a few hundred concurrent requests,
            each bitwise equal to ``PredictModel.decide_rows``.

``--chips 4`` runs instead the paths that exist only across chips, each
with what it is compared with: a ``shard_map`` fit with one node per
chip, a ``sample_shard`` fit at N=20000 across the four chips, and
``PredictServer`` round-robin over the four devices.

Data is synthetic, made from ``--seed``.  Any failed check raises, and
the script exits non-zero without printing a result.  The last line of
standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# -- sizes and tolerances --------------------------------------------------
PAPER = dict(V=30, T=4, N=256, p=10, iters=10, qp_iters=100)
SESSION_STAGE_ITERS = 10
LARGE = dict(V=2, T=2, N=20000, p=256, iters=2, qp_iters=20,
             max_elems=2 ** 27)
NODES4 = dict(V=4, T=4, N=256, p=10, iters=10, qp_iters=100)
N_TEST = 1800
SERVE_REQUESTS, SERVE_CLIENTS, SERVE_MAX_ROWS = 320, 8, 16

# Chip against host CPU, and kernel against jnp oracle, differ by float
# rounding only: every contraction is pinned to f32 precision, but the
# Pallas kernels, the oracle and the host CPU each reduce in their own
# order (measured on a TPU v5e: at most 2.5e-5).  A contraction left at
# XLA's default precision, one bf16 pass on a TPU, fails here: it moved
# the paper-regime duals by 3e-1.  States are compared as
# max|a - b| / max|b| per leaf, risks (fractions of the test set) as
# absolute differences; the risk limit also covers fits by different
# QP solvers, which stop at different points of the same problem.
STATE_RTOL = 1e-3
RISK_ATOL = 0.02
# the session warm-starts across membership events, so its final stage
# reaches the fresh fit's risks only as closely as both have converged
SESSION_RISK_ATOL = 0.02


def log(msg: str) -> None:
    print(msg, flush=True)


def check(name: str, ok: bool, detail: str) -> None:
    """Print one check; a failed check raises (exit non-zero)."""
    log(f"  [{'PASS' if ok else 'FAIL'}] {name}: {detail}")
    if not ok:
        raise AssertionError(f"check failed: {name}: {detail}")


def require_tpu(chips: int):
    """The devices phase: print what JAX sees; fail unless it is a TPU
    with at least ``chips`` chips.  Returns the device summary."""
    import jax
    import jaxlib

    devs = jax.devices()
    d0 = devs[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"devices: {devs}")
    log(f"platform={d0.platform} kind={d0.device_kind} count={len(devs)} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d0.platform!r});"
                 f" this check never falls back to the CPU")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# -- shared helpers --------------------------------------------------------
def make_data(V, T, N, p, seed, graph_kind="random"):
    from repro.core import graph
    from repro.data import synthetic

    data = synthetic.make_multitask_data(
        V=V, T=T, p=p, n_train=np.full((V, T), N, int), n_test=N_TEST,
        seed=seed)
    kind = "full" if V <= 2 else graph_kind
    return data, graph.make_graph(kind, V, degree=0.8, seed=seed)


def fit(cls, cfg, data, A, **kw):
    import jax

    m = cls(cfg).fit(data["X"], data["y"], mask=data["mask"], adj=A, **kw)
    jax.block_until_ready(m.state_)
    return m


def state_rel_err(a, b) -> dict:
    """Per-leaf max|a - b| / max|b| of two DTSVM states."""
    out = {}
    for name in ("r", "alpha", "beta", "lam"):
        x = np.asarray(getattr(a, name), np.float64)
        y = np.asarray(getattr(b, name), np.float64)
        out[name] = float(np.max(np.abs(x - y)) / max(np.max(np.abs(y)),
                                                      1e-12))
    return out


def bitwise_equal(a, b) -> bool:
    import jax

    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def finite(state) -> bool:
    import jax

    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(state))


def compare_fits(name, got, ref, data, *, rtol=STATE_RTOL,
                 risk_atol=RISK_ATOL, states=True):
    """Check a fit's state and global risks against a reference fit."""
    check(f"{name} finite", finite(got.state_), "every state leaf finite")
    if states:
        err = state_rel_err(got.state_, ref.state_)
        worst = max(err.values())
        check(f"{name} state", worst <= rtol,
              f"max rel err {worst:.3e} <= {rtol:g} "
              f"({', '.join(f'{k}={v:.2e}' for k, v in err.items())})")
    rg = got.global_risks(data["X_test"], data["y_test"])
    rr = ref.global_risks(data["X_test"], data["y_test"])
    d = float(np.max(np.abs(rg - rr)))
    check(f"{name} risks", d <= risk_atol,
          f"global risks {np.round(rg, 4).tolist()} vs "
          f"{np.round(rr, 4).tolist()}, max |diff| {d:.4f} <= {risk_atol}")


def count_custom_calls(fn, *args, **kw) -> int:
    """``tpu_custom_call`` ops (compiled Pallas kernels) in the program
    ``jax.jit(fn)`` lowers to for these arguments."""
    import jax

    text = jax.jit(functools.partial(fn, **kw)).lower(*args).as_text()
    return text.count("tpu_custom_call")


def run_phase(name, fn, *args):
    log(f"== {name}")
    t0 = time.perf_counter()
    out = fn(*args)
    log(f"   {name} done in {time.perf_counter() - t0:.1f} s "
        f"(compile included; set-up, not a speed figure)")
    return out


# -- one-chip phases -------------------------------------------------------
def phase_paper(seed):
    import jax

    from repro.api import DSVM, DTSVM, NetConfig, SolverConfig
    from repro.kernels import ops as kops

    s = PAPER
    data, A = make_data(s["V"], s["T"], s["N"], s["p"], seed)
    cfg = SolverConfig(C=0.01, iters=s["iters"], qp_iters=s["qp_iters"])
    chip = {c.__name__: fit(c, cfg, data, A) for c in (DTSVM, DSVM)}
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), kops.reference_kernels():
        host = {c.__name__: fit(c, cfg, data, A) for c in (DTSVM, DSVM)}
    for name in chip:
        on = {d.platform for d in chip[name].state_.r.devices()}
        off = {d.platform for d in host[name].state_.r.devices()}
        check(f"{name} placement", on == {"tpu"} and off == {"cpu"},
              f"chip fit on {sorted(on)}, reference on {sorted(off)}")
        compare_fits(f"{name} chip vs host CPU", chip[name], host[name],
                     data)
    net = fit(DTSVM, cfg.replace(net=NetConfig()), data, A)
    same = bitwise_equal(net.state_, chip["DTSVM"].state_)
    err = max(state_rel_err(net.state_, chip["DTSVM"].state_).values())
    check("identity NetConfig() == vmap", same,
          f"async identity fabric bitwise the vmap fit "
          f"(max rel err {err:.3e})")
    return data, A, cfg, chip["DTSVM"]


def phase_session(data, A, cfg):
    from repro.api import DTSVM, OnlineSession

    V, T = data["X"].shape[:2]
    k = SESSION_STAGE_ITERS
    active = np.ones((V, T), np.float32)
    active[:, T - 1] = 0.0                        # the last task joins late
    sess = OnlineSession(data["X"], data["y"], mask=data["mask"], adj=A,
                         config=cfg, active=active)
    sess.run(k)
    sess.add_task(T - 1)
    sess.run(k)
    sess.drop_task(0)
    sess.run(k)
    final = sess.active
    live = final.any(axis=0)
    rs = sess.global_risks(data["X_test"], data["y_test"])
    fresh = fit(DTSVM, cfg.replace(iters=3 * k), data, A, active=final)
    rf = fresh.global_risks(data["X_test"], data["y_test"])
    check("session finite", finite(sess.state) and bool(
        np.isfinite(rs).all()), f"risks {np.round(rs, 4).tolist()}")
    d = float(np.max(np.abs(rs[live] - rf[live])))
    check("session vs fresh fit", d <= SESSION_RISK_ATOL,
          f"live-task risks {np.round(rs[live], 4).tolist()} vs fresh "
          f"{np.round(rf[live], 4).tolist()}, max |diff| {d:.4f} <= "
          f"{SESSION_RISK_ATOL}; plan {sess.plan_stats}")


def kernel_programs(solver, budget, qp_iters):
    """``tpu_custom_call`` counts of the large-n programs, kernel path
    and oracle path: the streamed Gram build, the K-less Lipschitz pass
    and one ADMM step with the fused multi-iteration QP."""
    import jax

    from repro.core import dtsvm as core
    from repro.engine import invariants as inv_lib
    from repro.engine import plan as engine_plan
    from repro.kernels import ops as kops

    prob = solver.problem_
    V, T, N, p = prob.X.shape
    Z = jax.ShapeDtypeStruct((V, T, N, p + 1), np.float32)
    a = jax.ShapeDtypeStruct((V, T, p + 1), np.float32)
    chunk = budget.row_chunk(V * T, N)
    inv = jax.eval_shape(functools.partial(inv_lib.compute_invariants,
                                           budget=budget), prob)
    st = jax.eval_shape(core.init_state, prob)

    def counts():
        pallas = kops._use_pallas()
        return {
            "gram": count_custom_calls(
                inv_lib._streamed_gram_jit, Z, a, Z, chunk=chunk,
                tile=None, _pallas=pallas),
            "lipschitz": count_custom_calls(
                inv_lib._streamed_rowsums_jit, Z, a, chunk=chunk,
                tile=None, _pallas=pallas),
            "qp_multi": count_custom_calls(
                engine_plan.plan_step, prob, inv, st, qp_iters=qp_iters,
                qp_solver="pallas_fused_multi"),
        }

    kernel = counts()
    with kops.reference_kernels():
        oracle = counts()
    return kernel, oracle


def phase_large_n(seed):
    import jax

    from repro.api import DTSVM, PlanBudget, SolverConfig
    from repro.kernels import ops as kops

    s = LARGE
    data, A = make_data(s["V"], s["T"], s["N"], s["p"], seed)
    budget = PlanBudget(max_elems=s["max_elems"])
    cfg = SolverConfig(C=0.01, iters=s["iters"], qp_iters=s["qp_iters"],
                       budget=budget, qp_operator="materialized")
    multi = cfg.replace(qp_solver="pallas_fused_multi")
    factored = multi.replace(qp_operator="factored")
    log(f"   K = {4 * s['V'] * s['T'] * s['N'] ** 2 / 1e9:.1f} GB, "
        f"row chunk {budget.row_chunk(s['V'] * s['T'], s['N'])}")
    with kops.reference_kernels():
        ref_fista = fit(DTSVM, cfg, data, A)
        ref_multi = fit(DTSVM, multi, data, A)
    got = {"fista": fit(DTSVM, cfg, data, A),
           "pallas_fused_multi": fit(DTSVM, multi, data, A),
           "factored": fit(DTSVM, factored, data, A),
           "fista_factored": fit(DTSVM, cfg.replace(qp_operator="factored"),
                                 data, A)}
    compare_fits("fista kernel vs oracle", got["fista"], ref_fista, data)
    compare_fits("pallas_fused_multi kernel vs oracle",
                 got["pallas_fused_multi"], ref_multi, data)
    compare_fits("factored vs multi oracle", got["factored"], ref_multi,
                 data)
    compare_fits("factored fista vs fista oracle", got["fista_factored"],
                 ref_fista, data)
    for name in ("pallas_fused_multi", "factored"):
        compare_fits(f"{name} vs fista oracle (risks)", got[name],
                     ref_fista, data, states=False)
    kernel, oracle = kernel_programs(got["fista"], budget, s["qp_iters"])
    check("compiled Pallas kernels in the programs",
          all(v > 0 for v in kernel.values()),
          f"tpu_custom_call per program {kernel}")
    check("oracle programs hold no kernel",
          not any(oracle.values()), f"tpu_custom_call {oracle}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"   peak_bytes_in_use={peak} "
        f"({'n/a' if peak is None else f'{peak / 1e9:.2f} GB'})")


def serve_load(model, devices, seed):
    """``SERVE_CLIENTS`` concurrent closed-loop clients send
    ``SERVE_REQUESTS`` requests in all; every answer is checked bitwise
    against ``decide_rows``."""
    from repro.serve import PredictServer

    V, T, p = model.shape
    per = SERVE_REQUESTS // SERVE_CLIENTS
    answers = [None] * SERVE_CLIENTS
    with PredictServer(model, window_ms=2.0, devices=devices) as srv:
        def client(i):
            rng = np.random.default_rng(seed * 1000 + i)
            out = []
            for _ in range(per):
                n = int(rng.integers(1, SERVE_MAX_ROWS + 1))
                x = rng.normal(size=(n, p)).astype(np.float32)
                v, t = int(rng.integers(V)), int(rng.integers(T))
                out.append((x, v, t, srv.predict(x, node=v, task=t,
                                                 timeout=60)))
            answers[i] = out

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(SERVE_CLIENTS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        stats = srv.stats()
    done = [a for per_client in answers if per_client for a in per_client]
    bad = sum(not np.array_equal(out, model.decide_rows(x)[:, v * T + t])
              for x, v, t, out in done)
    check("served answers", len(done) == per * SERVE_CLIENTS and bad == 0,
          f"{len(done)} answers, {bad} differ from decide_rows bitwise")
    return stats


def phase_serve(solver, seed):
    import jax

    from repro.serve import PredictModel

    stats = serve_load(PredictModel.from_solver(solver),
                       jax.local_devices()[:1], seed)
    check("batching", stats["batches"] < stats["requests"],
          f"{stats['requests']} requests in {stats['batches']} batches")


# -- four-chip phases ------------------------------------------------------
def phase_shard_map(seed):
    from repro.api import DTSVM, SolverConfig

    s = NODES4
    data, A = make_data(s["V"], s["T"], s["N"], s["p"], seed)
    cfg = SolverConfig(C=0.01, iters=s["iters"], qp_iters=s["qp_iters"])
    ref = fit(DTSVM, cfg, data, A)
    dist = fit(DTSVM, cfg.replace(backend="shard_map"), data, A)
    on = sorted(d.id for d in dist.state_.r.devices())
    check("shard_map placement", len(on) == s["V"],
          f"state spread over devices {on}")
    compare_fits("shard_map vs vmap on chip 0", dist, ref, data)
    log(f"   bitwise: {bitwise_equal(dist.state_, ref.state_)}")
    return dist


def phase_sample_shard(seed):
    import jax

    from repro.api import DTSVM, PlanBudget, SolverConfig

    s = LARGE
    data, A = make_data(s["V"], s["T"], s["N"], s["p"], seed)
    cfg = SolverConfig(C=0.01, iters=s["iters"], qp_iters=s["qp_iters"],
                       budget=PlanBudget(max_elems=s["max_elems"]))
    ref = fit(DTSVM, cfg, data, A)
    n = len(jax.devices())
    got = fit(DTSVM, cfg.replace(backend="sample_shard",
                                 backend_options={"n_shards": n}), data, A)
    on = sorted(d.id for d in got.state_.lam.devices())
    check("sample_shard placement", len(on) == n,
          f"duals spread over devices {on}")
    compare_fits("sample_shard vs vmap on chip 0", got, ref, data)
    log(f"   bitwise: {bitwise_equal(got.state_, ref.state_)}")


def phase_serve_rr(solver, seed):
    import jax

    from repro.serve import PredictModel

    devs = jax.devices()
    stats = serve_load(PredictModel.from_solver(solver), devs, seed)
    check("round-robin", stats["devices"] == len(devs)
          and stats["batches"] >= len(devs),
          f"{stats['batches']} batches over {stats['devices']} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = run_phase("devices", require_tpu, args.chips)
    from repro import compile_cache

    log(f"compile cache: {compile_cache.enable(ROOT)}")
    if args.chips == 4:
        solver = run_phase("shard_map", phase_shard_map, args.seed)
        run_phase("sample_shard", phase_sample_shard, args.seed)
        run_phase("serve_round_robin", phase_serve_rr, solver, args.seed)
    else:
        data, A, cfg, solver = run_phase("paper", phase_paper, args.seed)
        run_phase("session", phase_session, data, A, cfg)
        run_phase("large_n", phase_large_n, args.seed)
        run_phase("serve", phase_serve, solver, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
