"""Traffic kind ``requests``: an open loop of predict requests into
``repro.serve.PredictServer.submit``.

Mix parameters (``traffic/<mix>.json``): ``rate`` (requests/s),
``rows_min``/``rows_max``/``rows_zipf`` (rows per request),
``keys_zipf`` (popularity of the (node, task) pairs), and optionally
``window_ms``/``max_batch`` for the server (absent: its defaults) and
``wait_after_s``, how long past the window's close answers are awaited
(default 60).  The
schedule comes from ``benchlib.arrivals``: every seed the same gaps,
sizes and popularities, in another order.

Independent clients send on schedule whatever the server does: one
sender submits each request at its due time, and a request's latency
runs from its due time to the moment its answer resolves, so a late
sender or a stalled server shows in the tail.  A request that fails or
has no answer a minute after the window closes counts as late past any
limit.  ``request_p95_ms`` is the 95th percentile over every request
of the window.

Weights come from the seed (``benchlib.datagen.serve_weights``).  Every
answer is compared with the reference decision value (float64 on the
host), over its rounding scale.

With ``run.control`` the answers are the reference's at three bfloat16
passes (``precision="high"``), in place of the server's: the control.
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import arrivals, compare, datagen, reference

WAIT_AFTER_S = 60.0


def _server_kwargs(mix: dict) -> dict:
    return {k: mix[k] for k in ("window_ms", "max_batch") if k in mix}


def setup(run) -> None:
    import jax
    import jax.numpy as jnp

    from repro.serve import PredictModel, PredictServer
    from repro.serve.model import gemm_rows, row_bucket

    cfg = run.config
    W, b = datagen.serve_weights(cfg, run.seed)
    run.state["W"], run.state["b"] = W, b
    model = PredictModel(W=jnp.asarray(W), b=jnp.asarray(b))
    server = PredictServer(model, devices=run.devices,
                           **_server_kwargs(run.mix))
    run.state["server"] = server
    # warm every row bucket a batch can take: 8 .. max_batch
    Wf, bf = model.flat()
    for dev in run.devices:
        Wd, bd = jax.device_put(Wf, dev), jax.device_put(bf, dev)
        n = 8
        while True:
            x = jax.device_put(np.zeros((n, cfg["p"]), np.float32), dev)
            np.asarray(gemm_rows(Wd, bd, x))
            if n >= row_bucket(server.max_batch):
                break
            n *= 2
    for f in [server.submit(np.zeros((k, cfg["p"]), np.float32), node=0,
                            task=0) for k in (1, 16)]:
        f.result(timeout=60)
    run.state["sched"] = arrivals.schedule(run.mix, cfg["V"], cfg["T"],
                                           cfg["p"], run.seed, run.seconds)


def window(run, seconds: float) -> dict:
    server = run.state["server"]
    s = run.state["sched"]
    n = len(s["due"])
    if run.control:
        vals = _ref(run, "high")
        run.state["answers"] = [vals[a: a + k] for a, k in
                                zip(s["start"], s["rows"])]
        return {"attempted": n, "failed": 0, "e2e": {},
                "server": {"rows": 0, "batches": 0},
                "notes": {"requests": n}}
    done = np.full(n, np.nan)
    futures = [None] * n
    lag = np.zeros(n)
    before = server.stats()

    def stamp(i):
        def cb(_fut):
            done[i] = time.perf_counter()
        return cb

    X, start, rows = s["X"], s["start"], s["rows"]
    node, task, due = s["node"], s["task"], s["due"]
    t0 = time.perf_counter() + 0.005
    for i in range(n):
        at = t0 + due[i]
        now = time.perf_counter()
        if at > now:
            time.sleep(at - now)
        f = server.submit(X[start[i]: start[i] + rows[i]], node=int(node[i]),
                          task=int(task[i]))
        lag[i] = time.perf_counter() - at
        f.add_done_callback(stamp(i))
        futures[i] = f
    closed = t0 + seconds
    backlog = sum(1 for f in futures if not f.done())
    deadline = closed + float(run.mix.get("wait_after_s", WAIT_AFTER_S))
    answers = [None] * n
    for i, f in enumerate(futures):
        try:
            answers[i] = np.asarray(
                f.result(timeout=max(deadline - time.perf_counter(), 0.0)))
        except Exception:               # failed or never answered: late
            pass
    ok = np.array([a is not None for a in answers]) & np.isfinite(done)
    lat = np.where(ok, (done - (t0 + due)) * 1e3,
                   (deadline - (t0 + due)) * 1e3)
    after = server.stats()
    run.state["answers"] = answers
    p95 = float(np.percentile(lat, 95))
    return {"attempted": n, "failed": int(np.sum(~ok)),
            "e2e": {"request_p95_ms": p95},
            "server": {"rows": after["rows"] - before["rows"],
                       "batches": after["batches"] - before["batches"]},
            "latency_ms": lat,
            "notes": {"requests": n,
                      "p50_ms": float(np.percentile(lat, 50)),
                      "p99_ms": float(np.percentile(lat, 99)),
                      "send_lag_p99_ms": float(np.percentile(lag, 99) * 1e3),
                      "send_lag_max_ms": float(lag.max() * 1e3),
                      "backlog_at_close": int(backlog)}}


def release(run) -> None:
    run.state["server"].close()
    run.state["server"] = None


def _ref(run, precision: str):
    s = run.state["sched"]
    return reference.serve_decisions(run.state["W"], run.state["b"], s["X"],
                                     s["node"], s["task"], s["start"],
                                     s["rows"], precision)


def _scale(run):
    s = run.state["sched"]
    return reference.serve_scale(run.state["W"], s["X"], s["node"],
                                 s["task"], s["rows"], run.state["b"])


def check(run) -> dict:
    answers = run.state["answers"]
    missing = sum(a is None for a in answers)
    got = np.concatenate([np.atleast_1d(a) if a is not None else
                          np.full(int(k), np.nan) for a, k in
                          zip(answers, run.state["sched"]["rows"])])
    have = ~np.isnan(got)        # the unanswered are counted apart
    gap = compare.served_rel_gap(got[have], _ref(run, "highest")[have],
                                 _scale(run)[have])
    return {"served_rel_gap": gap, "unanswered": float(missing)}


def control(run) -> dict:
    gap = compare.served_rel_gap(_ref(run, "high"), _ref(run, "highest"),
                                 _scale(run))
    return {"served_rel_gap": gap, "unanswered": 0.0}
