#!/usr/bin/env python3
"""Sweep the offered rate of a ``requests`` cell to find its knee.

    python3 bench/knee.py --workload paper.serve --rates 1000,2000,4000 \\
        --seconds 8 --seed 5 --limit-ms 10

One server, built as the cell builds it, takes the cell's traffic mix
at each rate in turn (open loop, the same generator).  For each rate it
prints p50/p95/p99 latency from the due time, how late the sender ran,
the requests still open when the window closed (the backlog), and the
rows per batch.  The knee is the highest rate whose p95 stays within
``--limit-ms`` with no growing backlog; a cell below the knee runs at
about 0.8 of it.  Exits non-zero off a TPU unless ``--allow-cpu``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--limit-ms", type=float, default=10.0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        sys.exit("knee: JAX found no TPU")
    from repro import compile_cache

    compile_cache.enable(ROOT)
    from benchlib import arrivals, runner, spec as spec_lib

    spec = spec_lib.Spec(ROOT, BENCH_DIR)
    run = runner.Run(spec, args.workload, args.seed, False)
    run.seconds = args.seconds
    run.kind.setup(run)
    cfg = run.config
    knee, below = None, True
    for rate in [float(r) for r in args.rates.split(",")]:
        run.mix = dict(run.mix, rate=rate)
        run.state["sched"] = arrivals.schedule(run.mix, cfg["V"], cfg["T"],
                                               cfg["p"], args.seed,
                                               args.seconds)
        res = run.kind.window(run, args.seconds)
        checks = run.kind.check(run)
        srv = res["server"]
        line = {"rate": rate, "p95_ms": res["e2e"]["request_p95_ms"],
                **res["notes"], "failed": res["failed"],
                "rows_per_batch": srv["rows"] / max(srv["batches"], 1),
                "checks": checks}
        ok = (line["p95_ms"] <= args.limit_ms and res["failed"] == 0
              and line["backlog_at_close"] <= max(0.01 * rate, 10))
        line["within_limit"] = ok
        below = below and ok
        if below:
            knee = rate
        print(json.dumps(line), flush=True)
    run.kind.release(run)
    print(json.dumps({"knee": knee, "limit_ms": args.limit_ms,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
