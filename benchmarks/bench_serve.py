"""Serving benchmark: predict latency/throughput vs batching window.

The serving claim of ``repro.serve``: coalescing concurrent predict
requests into padded-bucket GEMM batches buys throughput at a bounded,
configurable latency cost — the ``window_ms`` knob.  This benchmark
measures that trade on a model fitted in the fig2 regime (scarce
target + rich source task):

- a fixed client fleet submits random-size predict requests as fast as
  the server answers, for a fixed duration, at several batching
  windows (0 = greedy dispatch, no waiting);
- every sampled response is asserted EXACTLY equal to the unbatched
  computation (``PredictModel.decide_rows``) — the benchmark proves the
  batching is invisible in the values while it measures it;
- the same sweep runs on one device and, when the process sees more
  than one, round-robin across all of ``jax.devices()`` — in this
  process, so a chip is never needed by a child of a process that holds
  it (on the CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=2``
  gives the multi-device sweep two host devices).

Outputs ``BENCH_serve.json`` (repo root on a full run, ``--out PATH``
anywhere — the CI serve lane uploads the fast variant as an artifact)
with p50/p99 request latency (ms) and requests/sec per window, and the
``run.py`` CSV contract on stdout.
"""
import argparse
import json
import os
import threading
import time

import jax
import numpy as np

from common import build, emit, run_dtsvm

from repro.serve import PredictModel, PredictServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_CLIENTS = 4
MAX_ROWS = 16          # per request
EQUIV_SAMPLES = 50     # responses cross-checked against the direct path


def _fitted_model(fast: bool) -> PredictModel:
    data, A = build(6, [40, 200], degree=0.8, seed=0,
                    n_test=200 if fast else 600)
    state, _, _, _ = run_dtsvm(data, A, 10 if fast else 30,
                               qp_iters=40 if fast else 100,
                               with_history=False)
    return PredictModel.from_state(state)


def _warmup(model: PredictModel) -> None:
    """Compile the GEMM for every bucket the load can hit, so the
    timed section measures serving, not tracing."""
    rng = np.random.default_rng(0)
    b = 8
    while b <= 2 * N_CLIENTS * MAX_ROWS:
        model.decide_rows(rng.normal(
            size=(b, model.shape[2])).astype(np.float32))
        b *= 2


def _load(model: PredictModel, *, window_ms: float, duration_s: float,
          devices=None, seed: int = 0) -> dict:
    """One fixed-duration closed-loop load at one batching window."""
    V, T, P = model.shape
    errs = []
    checked = [0]
    lock = threading.Lock()

    with PredictServer(model, window_ms=window_ms,
                       devices=devices) as srv:
        stop_at = time.perf_counter() + duration_s

        def client(cseed):
            rng = np.random.default_rng(cseed)
            while time.perf_counter() < stop_at:
                n = int(rng.integers(1, MAX_ROWS + 1))
                x = rng.normal(size=(n, P)).astype(np.float32)
                v, t = int(rng.integers(V)), int(rng.integers(T))
                try:
                    out = srv.predict(x, node=v, task=t)
                except Exception as e:
                    errs.append(repr(e))
                    return
                with lock:
                    check = checked[0] < EQUIV_SAMPLES
                    checked[0] += check
                if check and not np.array_equal(
                        out, model.decide_rows(x)[:, v * T + t]):
                    errs.append(f"mismatch at (v={v}, t={t}, n={n})")

        threads = [threading.Thread(target=client, args=(seed * 101 + i,))
                   for i in range(N_CLIENTS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        stats = srv.stats()
    assert not errs, errs[:3]
    assert checked[0] >= min(EQUIV_SAMPLES, stats["requests"])
    return {"window_ms": window_ms, **{
        k: stats[k] for k in ("requests", "rows", "batches",
                              "rows_per_batch", "pad_ratio",
                              "p50_ms", "p99_ms", "rps", "devices")}}


def _sweep(model, windows, duration_s, devices) -> list:
    _warmup(model)
    return [_load(model, window_ms=w, duration_s=duration_s,
                  devices=devices) for w in windows]


def run(fast: bool = False, out: str = None) -> dict:
    windows = (0.0, 2.0) if fast else (0.0, 1.0, 4.0)
    duration_s = 1.0 if fast else 3.0
    model = _fitted_model(fast)

    devices = jax.devices()
    single = _sweep(model, windows, duration_s, devices[:1])
    multi = (_sweep(model, windows, duration_s, devices)
             if len(devices) > 1 else None)

    recs = {
        "config": {"model_shape": list(model.shape),
                   "n_clients": N_CLIENTS, "max_rows": MAX_ROWS,
                   "duration_s": duration_s,
                   "equiv_samples_per_run": EQUIV_SAMPLES,
                   "backend": jax.default_backend()},
        "single_device": single,
        "multi_device": multi,
        "acceptance": {
            # _load asserts sampled responses bitwise == direct; getting
            # here means every run passed
            "batched_equals_direct": True,
            "windows_measured": len(single),
        },
    }
    if out is not None:
        path = out
    elif not fast:
        # fast mode is a smoke config — don't clobber the committed
        # full-regime record unless --out says so explicitly
        path = os.path.join(ROOT, "BENCH_serve.json")
    else:
        path = None
    if path:
        with open(path, "w") as f:
            json.dump(recs, f, indent=2)
            f.write("\n")
    return recs


def main(fast=False, out=None):
    recs = run(fast, out)
    greedy = recs["single_device"][0]
    widest = recs["single_device"][-1]
    emit("bench_serve", greedy["p50_ms"] * 1e3,
         f"exact={recs['acceptance']['batched_equals_direct']} "
         f"w{greedy['window_ms']:g}ms_p50={greedy['p50_ms']:.2f}ms_"
         f"rps={greedy['rps']:.0f} "
         f"w{widest['window_ms']:g}ms_p50={widest['p50_ms']:.2f}ms_"
         f"rps={widest['rps']:.0f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write BENCH_serve.json to this path")
    args = ap.parse_args()
    main(args.fast, args.out)
