#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload paper.fit --seeds 1,2,3 \\
        --control-seeds 1,2,3 [--seconds 0]

Every reading is a whole run of the cell, ``runner.run_cell`` as
``bench/run.py`` makes it (set-up, a window of ``--seconds``, the
comparison with the plain reference, ``correct``), all in this one
process.  For each seed the program's run; for each control seed the
control's run: the reference one precision lower (three bfloat16 passes
for float32 at highest) put in the program's place on the timed path,
which has to come out not correct.  A limit lies above the largest
program reading and below the smallest control reading.

One JSON line per run, then one summary line.  Like ``run.py`` it exits
non-zero off a TPU, unless ``--allow-cpu`` (a rehearsal).
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--overrides", default="{}",
                    help="JSON of configuration keys to replace (rehearsal)")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        sys.exit("calibrate: JAX found no TPU")
    from repro import compile_cache

    compile_cache.enable(ROOT)
    from benchlib import runner, spec as spec_lib

    spec = spec_lib.Spec(ROOT, BENCH_DIR)
    over = json.loads(args.overrides)
    runs = ([(int(s), False) for s in args.seeds.split(",") if s]
            + [(int(s), True) for s in args.control_seeds.split(",") if s])
    read = {False: [], True: []}
    for seed, control in runs:
        t0 = time.perf_counter()
        out = runner.run_cell(spec, args.workload, seed=seed,
                              seconds=args.seconds, trace=False, t_start=t0,
                              overrides=over, control=control)
        line = {"seed": seed, "run": "control" if control else "program",
                "correct": out["correct"], "attempted": out["attempted"],
                "failed": out["failed"],
                "checks": {k: c["value"] for k, c in out["checks"].items()},
                "metrics": {k: m["value"] for k, m in out["metrics"].items()},
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "seconds": time.perf_counter() - t0}
        read[control].append(line)
        print(json.dumps(line), flush=True)
    summary = {"workload": args.workload,
               "device": jax.devices()[0].device_kind,
               "program_all_correct": all(r["correct"] for r in read[False]),
               "control_none_correct": not any(r["correct"]
                                               for r in read[True])}
    names = sorted({k for r in read[False] + read[True] for k in r["checks"]})
    for k in names:
        prog = [r["checks"][k] for r in read[False]]
        ctrl = [r["checks"][k] for r in read[True]]
        summary[k] = {"program_max": max(prog, default=None),
                      "control_min": min(ctrl, default=None),
                      "program": prog, "control": ctrl}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
