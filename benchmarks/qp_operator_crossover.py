"""Where the factored QP operator starts to win: whole-fit time of the
materialized and the factored operator over dense shapes, the readings
behind ``engine.plan.FACTORED_MIN_K_BYTES``.

    python benchmarks/qp_operator_crossover.py [--fast] [--repeats R]
        [--iters I] [--qp-iters Q] [--max-k-bytes B] [--out PATH]

Each shape (V, T, N, p) is drawn on the device from a fixed seed (random
features and labels, a ring graph).  For each operator, with
``qp_solver="fista"`` and the ``large_n`` cell's budget of 2**27
elements, the fit runs once to compile, then ``--repeats`` times, each
timed as its two phases, both blocked: the build
(``engine.compile_problem``) and the ADMM scan (``Plan.run``) of
``--iters`` x ``--qp-iters`` steps (default 2 x 40, the large cells').
One JSON line per shape and operator goes to stdout (medians, in ms),
then a line naming the smallest K, in bytes, from which on the factored
fit was faster at every shape; ``--out`` also writes every line to a
JSON file.  A timing off the TPU says nothing about the chip: run it
there.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

try:
    import repro  # noqa: F401  (pip install -e .)
except ModuleNotFoundError:  # fallback: run from a bare checkout
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402

from repro import engine                                     # noqa: E402
from repro.core import dtsvm as core                         # noqa: E402
from repro.core import graph                                 # noqa: E402

#: (V, T, N, p): from the paper's regime to the large_n cell's, N > 2 (p+1)
SHAPES = [(20, 2, 40, 10), (4, 2, 256, 32), (4, 2, 512, 64),
          (4, 2, 1024, 128), (4, 2, 2048, 256), (4, 2, 2048, 784),
          (4, 2, 4096, 512), (4, 2, 8192, 784), (4, 2, 12000, 784)]
FAST_SHAPES = [(4, 2, 64, 8), (4, 2, 128, 16)]


def problem(V, T, N, p, seed=0):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    X = jax.random.normal(kx, (V, T, N, p), jnp.float32)
    y = jnp.where(jax.random.bernoulli(ky, 0.5, (V, T, N)), 1.0, -1.0)
    return core.make_problem(X, y, None, graph.make_graph("ring", V),
                             C=0.01)


def time_operator(prob, operator, *, iters, qp_iters, repeats):
    budget = engine.PlanBudget(max_elems=1 << 27)

    def fit():
        t0 = time.perf_counter()
        plan = engine.compile_problem(prob, qp_iters=qp_iters,
                                      qp_solver="fista",
                                      qp_operator=operator, budget=budget)
        jax.block_until_ready(plan.inv)
        t1 = time.perf_counter()
        st, _ = plan.run(iters=iters)
        jax.block_until_ready(st)
        t2 = time.perf_counter()
        return (t1 - t0) * 1e3, (t2 - t1) * 1e3

    fit()                                        # compile both programs
    build, admm = zip(*(fit() for _ in range(repeats)))
    return {"build_ms": statistics.median(build),
            "admm_ms": statistics.median(admm),
            "fit_ms": statistics.median(b + a for b, a in zip(build, admm))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fast", action="store_true",
                    help="two tiny shapes (a smoke run, any platform)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--qp-iters", type=int, default=40)
    ap.add_argument("--max-k-bytes", type=float, default=float("inf"),
                    help="skip shapes whose K is larger")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    rows, wins = [], {}
    for V, T, N, p in (FAST_SHAPES if args.fast else SHAPES):
        k_bytes = 4 * V * T * N * N
        if k_bytes > args.max_k_bytes:
            continue
        prob = problem(V, T, N, p)
        fits = {}
        for op in ("materialized", "factored"):
            row = {"V": V, "T": T, "N": N, "p": p, "k_bytes": k_bytes,
                   "operator": op, "device": jax.devices()[0].device_kind,
                   **time_operator(prob, op, iters=args.iters,
                                   qp_iters=args.qp_iters,
                                   repeats=args.repeats)}
            fits[op] = row["fit_ms"]
            rows.append(row)
            print(json.dumps(row), flush=True)
        wins[k_bytes] = wins.get(k_bytes, True) and (
            fits["factored"] < fits["materialized"])
        del prob
    # the smallest K from which on the factored fit wins at every shape
    floor = None
    for k in sorted(wins, reverse=True):
        if not wins[k]:
            break
        floor = k
    print(json.dumps({"smallest_k_bytes_factored_wins": floor}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
