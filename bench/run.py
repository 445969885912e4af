#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload paper.fit --seed 7 --seconds 10 --trace 0

The cell, its configuration, its traffic and its metrics are found by
name (``bench/workloads/<cell>.json`` and the files it names; the
metrics in ``BENCHMARK.json``).  Set-up makes
the inputs from ``--seed``, builds the system and warms every program
the window uses; the window then drives the timed path for
``--seconds``; after it, what the window produced is compared with the
plain reference.  With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from
a profiler trace of the window and from JAX's compile counters.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), with ``checks`` (each compared number and its
limit) last.  The checks are also the last lines of standard error.

Off a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result: it never falls back to the CPU.  JAX's
persistent compilation cache lives in ``.jax_cache/`` of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _fail(msg: str, code: int) -> "None":
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: read it "
                         "from a scratch directory in the checkout, then "
                         "delete it)")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "__init__.py")):
        _fail(f"no system under test at {src}/repro", 2)
    sys.path[:0] = [BENCH_DIR, src]
    from benchlib import spec as spec_lib

    try:
        spec = spec_lib.Spec(ROOT, BENCH_DIR)
        chips = int(spec.cell(args.workload)["chips"])
    except (spec_lib.SpecError, OSError, KeyError, ValueError) as e:
        _fail(str(e), 2)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        _fail(f"JAX found no TPU (platform {d0.platform!r}); this benchmark "
              f"never falls back to the CPU", 3)
    if len(devices) < chips:
        _fail(f"{args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}", 3)
    import repro

    if not os.path.abspath(repro.__file__).startswith(src + os.sep):
        _fail(f"repro imported from {repro.__file__}, not {src}", 2)
    from repro import compile_cache

    compile_cache.enable(ROOT)
    from benchlib import runner

    result = runner.run_cell(spec, args.workload, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             t_start=T_START, trace_dir=args.trace_dir)
    print(runner.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
