"""repro.obs: the two hard invariants (telemetry-on is bitwise
telemetry-off; telemetry adds zero retraces), the stream catalog across
every backend, span tracing + Chrome-trace export, the metrics
registry, durable-session carriage of telemetry, and the CLI."""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import repro.obs as obs
from repro import api
from repro.analysis.jaxpr_audit import trace_counter
from repro.api.solvers import SolverConfig
from repro.core import graph
from repro.data import synthetic
from repro.net.policies import NetConfig
from repro.obs import spans as spans_lib
from repro.obs import telemetry as telemetry_lib

from helpers import run_with_devices

V, T, N, P = 3, 2, 12, 6


def _data():
    data = synthetic.make_multitask_data(
        V=V, T=T, p=P, n_train=np.full((V, T), N, int), n_test=8,
        relatedness=0.9, seed=0)
    adj = graph.make_graph("ring", V, seed=0)
    return data["X"], data["y"], data["mask"], adj


def _bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in zip(la, lb))


#: engine-mode matrix for the vmap backend (name -> config kwargs)
ENGINES = {
    "fista": dict(qp_solver="fista"),
    "pg": dict(qp_solver="pg"),
    "pallas_fused": dict(qp_solver="pallas_fused"),
    "pallas_fused_multi": dict(qp_solver="pallas_fused_multi"),
    "factored": dict(qp_solver="pallas_fused_multi",
                     qp_operator="factored"),
}


# ---------------------------------------------------------------------------
# invariant 1: telemetry-on is bitwise telemetry-off, every backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(ENGINES))
def test_telemetry_bitwise_invisible_vmap(name):
    X, y, mask, adj = _data()
    kw = dict(iters=4, qp_iters=8, **ENGINES[name])
    off = api.DTSVM(SolverConfig(**kw)).fit(X, y, mask, adj)
    on = api.DTSVM(SolverConfig(telemetry=True, **kw)).fit(
        X, y, mask, adj)
    assert _bitwise(off.state_, on.state_)
    assert off.telemetry_ is None
    assert set(on.telemetry_) == set(telemetry_lib.STREAMS)


def test_telemetry_bitwise_invisible_async():
    X, y, mask, adj = _data()
    kw = dict(iters=4, qp_iters=8, backend="async", net=NetConfig())
    off = api.OnlineSession(X, y, mask, adj,
                            config=SolverConfig(**kw))
    on = api.OnlineSession(X, y, mask, adj,
                           config=SolverConfig(telemetry=True, **kw))
    off.run(4)
    on.run(4)
    assert _bitwise(off.state, on.state)
    # the async backend folds the fabric's byte counts in as a stream,
    # plus the per-node edge-staleness clock (PR 10)
    assert set(on.telemetry_) == set(telemetry_lib.STREAMS) | {
        "bytes_round", "staleness"}
    assert on.telemetry_["staleness"].shape == (4, len(adj))
    np.testing.assert_array_equal(
        on.telemetry_["bytes_round"],
        np.asarray(on._net_series, np.float32))


def test_telemetry_bitwise_invisible_sample_shard():
    """Single-shard degenerate run in-process (the multi-device case is
    the slow subprocess test below)."""
    X, y, mask, adj = _data()
    kw = dict(iters=4, qp_iters=8, backend="sample_shard")
    off = api.DTSVM(SolverConfig(**kw)).fit(X, y, mask, adj)
    on = api.DTSVM(SolverConfig(telemetry=True, **kw)).fit(
        X, y, mask, adj)
    assert _bitwise(off.state_, on.state_)
    assert set(on.telemetry_) == set(telemetry_lib.STREAMS)


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["shard_map", "sample_shard"])
def test_telemetry_bitwise_invisible_multidevice(backend):
    run_with_devices(f"""
        import numpy as np, jax
        from repro import api
        from repro.api.solvers import SolverConfig
        from repro.core import graph
        from repro.data import synthetic

        data = synthetic.make_multitask_data(
            V=4, T=2, p=6, n_train=np.full((4, 2), 16, int), n_test=8,
            relatedness=0.9, seed=0)
        adj = graph.make_graph("ring", 4, seed=0)
        kw = dict(iters=3, qp_iters=8, backend="{backend}")
        off = api.DTSVM(SolverConfig(**kw)).fit(
            data["X"], data["y"], data["mask"], adj)
        on = api.DTSVM(SolverConfig(telemetry=True, **kw)).fit(
            data["X"], data["y"], data["mask"], adj)
        for a, b in zip(jax.tree.leaves(off.state_),
                        jax.tree.leaves(on.state_)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert on.telemetry_ is not None
        for k, v in on.telemetry_.items():
            assert v.shape[0] == 3 and np.isfinite(v).all(), k
        print("OK")
        """, n_devices=4)


# ---------------------------------------------------------------------------
# invariant 2: zero retraces (exact counts)
# ---------------------------------------------------------------------------
def test_telemetry_adds_zero_retraces():
    """With telemetry on, the fit still builds invariants once and
    traces the step once — and the collector itself traces exactly once,
    inside the same scan body.  The cached scan is keyed on the spec's
    value, so a second fit (a fresh ``Telemetry()``) traces nothing."""
    X, y, mask, adj = _data()
    with trace_counter("repro.kernels.ops:weighted_gram",
                       "repro.engine.plan:plan_step",
                       "repro.obs.telemetry:collect_diagnostics") as c:
        api.DTSVM(iters=4, qp_iters=2, telemetry=True).fit(
            X, y, mask, adj)
        assert c["weighted_gram"] == 1
        assert c["plan_step"] == 1
        assert c["collect_diagnostics"] == 1
        api.DTSVM(iters=4, qp_iters=2, telemetry=True).fit(
            X, y, mask, adj)
    assert c["weighted_gram"] == 2           # one build per fit
    assert c["plan_step"] == 1
    assert c["collect_diagnostics"] == 1


def test_telemetry_off_never_enters_collector():
    X, y, mask, adj = _data()
    with trace_counter("repro.obs.telemetry:collect_diagnostics") as c:
        api.DTSVM(iters=4, qp_iters=2).fit(X, y, mask, adj)
    assert c["collect_diagnostics"] == 0


# ---------------------------------------------------------------------------
# stream semantics
# ---------------------------------------------------------------------------
def test_stream_shapes_dtypes_and_convergence():
    X, y, mask, adj = _data()
    s = api.DTSVM(iters=30, qp_iters=40, telemetry=True).fit(
        X, y, mask, adj)
    t = s.telemetry_
    assert t["primal_residual"].shape == (30,)
    assert t["dual_residual"].shape == (30,)
    assert t["disagreement"].shape == (30, T)
    assert t["qp_active_frac"].shape == (30,)
    for v in t.values():
        assert v.dtype == np.float32 and np.isfinite(v).all()
    assert np.all(t["qp_active_frac"] >= 0)
    assert np.all(t["qp_active_frac"] <= 1)
    # Prop. 1 drives the consensus residuals down over the run
    assert t["dual_residual"][-1] < t["dual_residual"][0]
    assert t["disagreement"].max(1)[-1] < t["disagreement"].max(1)[0]


def test_stream_subset_selection():
    X, y, mask, adj = _data()
    tel = telemetry_lib.Telemetry(streams=("dual_residual",))
    assert tel.streams == ("dual_residual",)
    # a custom spec rides through backend_options; config.telemetry
    # still gates collection (setdefault keeps the explicit spec)
    s = api.DTSVM(iters=3, qp_iters=4, telemetry=True,
                  backend_options={"telemetry": tel})
    s.fit(X, y, mask, adj)
    assert set(s.telemetry_) == {"dual_residual"}
    with pytest.raises(ValueError, match="unknown telemetry streams"):
        telemetry_lib.Telemetry(streams=("nope",))


def test_concat_streams_tolerates_missing_keys():
    a = {"x": np.ones((2,), np.float32)}
    b = {"x": np.zeros((3,), np.float32),
         "bytes_round": np.ones((3,), np.float32)}
    out = telemetry_lib.concat_streams(a, b)
    assert out["x"].shape == (5,)
    assert out["bytes_round"].shape == (3,)
    assert telemetry_lib.concat_streams(None, b)["x"].shape == (3,)


def test_csvm_rejects_telemetry():
    X, y, mask, adj = _data()
    with pytest.raises(ValueError, match="telemetry"):
        api.CSVM(telemetry=True).fit(X, y, mask, adj)


def test_config_roundtrip_and_old_dicts_default_off():
    cfg = SolverConfig(iters=3, telemetry=True)
    d = cfg.to_dict()
    assert d["telemetry"] is True
    assert SolverConfig.from_dict(d).telemetry is True
    d.pop("telemetry")          # a pre-obs config dict
    assert SolverConfig.from_dict(d).telemetry is False


# ---------------------------------------------------------------------------
# sessions: accumulation, save -> restore -> continue, replay
# ---------------------------------------------------------------------------
def test_session_accumulates_streams_across_stages():
    X, y, mask, adj = _data()
    sess = api.OnlineSession(
        X, y, mask, adj, config=SolverConfig(iters=4, qp_iters=8,
                                             telemetry=True))
    sess.run(4)
    assert sess.telemetry_["dual_residual"].shape == (4,)
    sess.run(3)
    assert sess.telemetry_["dual_residual"].shape == (7,)
    assert sess.telemetry_["disagreement"].shape == (7, T)


def test_save_restore_continue_carries_telemetry(tmp_path):
    from repro.store import load_session, save_session

    X, y, mask, adj = _data()
    cfg = SolverConfig(iters=4, qp_iters=8, backend="async",
                       net=NetConfig(), telemetry=True)
    sess = api.OnlineSession(X, y, mask, adj, config=cfg)
    sess.run(4)
    path = os.path.join(str(tmp_path), "s.msgpack")
    save_session(path, sess)
    back = load_session(path)
    for k in sess.telemetry_:
        np.testing.assert_array_equal(back.telemetry_[k],
                                      sess.telemetry_[k])
    back.run(3)
    sess.run(3)
    assert _bitwise(back.state, sess.state)
    for k in sess.telemetry_:
        np.testing.assert_array_equal(back.telemetry_[k],
                                      sess.telemetry_[k])
        assert back.telemetry_[k].shape[0] == 7


def test_v1_snapshot_without_obs_block_migrates(tmp_path):
    """A pre-obs (v1) snapshot loads: the migration defaults the obs
    block to None and the session restores with no telemetry."""
    from repro.store import restore_session, snapshot_session
    from repro.store import schema

    X, y, mask, adj = _data()
    sess = api.OnlineSession(X, y, mask, adj,
                             config=SolverConfig(iters=3, qp_iters=8))
    sess.run(3)
    tree = snapshot_session(sess)
    assert tree["schema_version"] == schema.SCHEMA_VERSION >= 2
    tree.pop("obs")                        # what a v1 writer produced
    tree.pop("membership", None)           # (v3 field, absent in v1 too)
    tree["schema_version"] = 1
    back = restore_session(tree)
    assert back.telemetry_ is None
    assert _bitwise(back.state, sess.state)


def test_replay_reproduces_telemetry():
    from repro.store import EventLog, replay

    X, y, mask, adj = _data()
    log = EventLog()
    cfg = SolverConfig(iters=4, qp_iters=8, telemetry=True)
    sess = api.OnlineSession(X, y, mask, adj, config=cfg, log=log)
    sess.run(4)
    sess.run(2)
    twin = replay(log)
    assert _bitwise(twin.state, sess.state)
    for k in sess.telemetry_:
        np.testing.assert_array_equal(twin.telemetry_[k],
                                      sess.telemetry_[k])


# ---------------------------------------------------------------------------
# spans + Chrome trace export
# ---------------------------------------------------------------------------
def test_spans_cover_phase_boundaries(tmp_path):
    obs.clear_spans()
    X, y, mask, adj = _data()
    with obs.span("fit", tag="test"):
        api.DTSVM(iters=2, qp_iters=4).fit(X, y, mask, adj)
    names = [e["name"] for e in obs.iter_spans()]
    for expected in ("invariant_build", "scan_execute", "fit"):
        assert expected in names, names
    # nesting: the wrapping span closes last, so it is recorded last
    assert names[-1] == "fit"
    ev = obs.iter_spans()[-1]
    assert ev["ph"] == "X" and ev["dur"] >= 0 and ev["args"] == {
        "tag": "test"}


def test_chrome_trace_roundtrips_through_validation(tmp_path):
    obs.clear_spans()
    with obs.span("a", k=1):
        with obs.span("b"):
            pass
    path = os.path.join(str(tmp_path), "trace.json")
    tree = obs.save_trace(path)
    loaded = json.loads(open(path).read())
    obs.validate_chrome_trace(loaded)      # raises on malformed
    assert loaded["displayTimeUnit"] == "ms"
    assert [e["name"] for e in loaded["traceEvents"]] == ["b", "a"]
    assert loaded == json.loads(json.dumps(tree))


def test_trace_validation_rejects_malformed():
    with pytest.raises(ValueError):
        obs.validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        obs.validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "B", "ts": 0,
                              "dur": 0, "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError):
        obs.validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": -1.0,
                              "dur": 0, "pid": 1, "tid": 1}]})


def test_store_and_serve_phases_emit_spans(tmp_path):
    from repro.serve.model import PredictModel
    from repro.serve.server import PredictServer
    from repro.store import load_session, save_session

    obs.clear_spans()
    X, y, mask, adj = _data()
    sess = api.OnlineSession(X, y, mask, adj,
                             config=SolverConfig(iters=2, qp_iters=4))
    sess.run(2)
    path = os.path.join(str(tmp_path), "s.msgpack")
    save_session(path, sess)
    load_session(path)
    model = PredictModel.from_r(np.asarray(sess.state.r))
    srv = PredictServer(model, window_ms=0.0)
    try:
        srv.submit(np.ones((2, P), np.float32), node=0,
                   task=0).result(timeout=30)
    finally:
        srv.close()
    names = {e["name"] for e in obs.iter_spans()}
    assert {"store_snapshot", "store_restore", "serve_batch"} <= names


# ---------------------------------------------------------------------------
# device scopes: the span names in the lowered programs' op metadata
# ---------------------------------------------------------------------------
PHASES = ("scan_execute", "qp_solve", "consensus_update",
          "neighbour_exchange")


@contextlib.contextmanager
def _ir_dump(tmp_path):
    """Dump every module lowered in the block; yields ``texts(program)``
    -> the dumped module texts of that program (``jit_scan``, ...)."""
    d = str(tmp_path / "ir")
    jax.config.update("jax_dump_ir_to", d)
    try:
        yield lambda prog: [open(f).read() for f in sorted(glob.glob(
            os.path.join(d, f"*_{prog}_compile.mlir")))]
    finally:
        jax.config.update("jax_dump_ir_to",
                          os.environ.get("JAX_DUMP_IR_TO", ""))


def _scoped(text, name):
    """``name`` is a scope of some op: it stands in a name-stack
    location path (a function's own name location has no '/')."""
    return re.search(r'loc\("[^"]*\b' + name + '/', text) is not None


def _strip_locations(text):
    lines = [ln for ln in text.splitlines() if not ln.startswith("#loc")]
    return "\n".join(re.sub(r" loc\(#loc\d*\)", "", ln) for ln in lines)


def _instructions(text):
    return sum(1 for ln in text.splitlines() if "stablehlo." in ln)


FITS = {
    "vmap_scan": (dict(iters=2, qp_iters=4), "jit__plan_scan", PHASES),
    "budgeted_build": (dict(iters=2, qp_iters=4,
                            budget=api.PlanBudget(max_elems=64)),
                       "jit__streamed_gram_jit", ("invariant_build",)),
    "factored_build": (dict(iters=2, qp_iters=4,
                            qp_solver="pallas_fused_multi",
                            qp_operator="factored"),
                       "jit__streamed_rowsums_jit", ("invariant_build",)),
}


@pytest.mark.parametrize("case", sorted(FITS))
def test_scopes_name_each_phase_in_the_lowered_program(case, tmp_path):
    kw, program, phases = FITS[case]
    X, y, mask, adj = _data()
    jax.clear_caches()
    with _ir_dump(tmp_path) as texts:
        api.DTSVM(SolverConfig(**kw)).fit(X, y, mask, adj)
        mods = texts(program)
    assert len(mods) == 1, program
    for name in phases:
        assert _scoped(mods[0], name), (program, name)


@pytest.mark.parametrize("case", ["vmap_scan", "budgeted_build"])
def test_scopes_change_no_instruction_and_no_output(case, tmp_path,
                                                    monkeypatch):
    kw, program, phases = FITS[case]
    X, y, mask, adj = _data()
    jax.clear_caches()          # the jitted build lowers again each time
    with _ir_dump(tmp_path / "on") as texts:
        on = api.DTSVM(SolverConfig(**kw)).fit(X, y, mask, adj)
        with_scopes = texts(program)[0]
    monkeypatch.setattr(spans_lib, "scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    with _ir_dump(tmp_path / "off") as texts:
        off = api.DTSVM(SolverConfig(**kw)).fit(X, y, mask, adj)
        without = texts(program)[0]
    assert not _scoped(without, phases[0])
    assert _instructions(with_scopes) == _instructions(without)
    assert _strip_locations(with_scopes) == _strip_locations(without)
    assert _bitwise(on.state_, off.state_)


@pytest.mark.slow
def test_sample_shard_scopes_on_four_devices():
    run_with_devices(r"""
        import glob, os, re, tempfile
        import numpy as np, jax
        from repro import api
        from repro.api.solvers import SolverConfig
        from repro.core import graph
        from repro.data import synthetic

        data = synthetic.make_multitask_data(
            V=3, T=2, p=6, n_train=np.full((3, 2), 16, int), n_test=8,
            relatedness=0.9, seed=0)
        adj = graph.make_graph("ring", 3, seed=0)
        d = tempfile.mkdtemp()
        jax.config.update("jax_dump_ir_to", d)
        api.DTSVM(SolverConfig(iters=2, qp_iters=4, backend="sample_shard",
                               backend_options={"n_shards": 4})).fit(
            data["X"], data["y"], data["mask"], adj)
        # the fit's two programs: the panel build and the scan
        (build,) = glob.glob(os.path.join(d,
                                          "*_jit__panel_build_compile.mlir"))
        (scan,) = glob.glob(os.path.join(d, "*_jit__panel_scan_compile.mlir"))
        build, scan = open(build).read(), open(scan).read()
        scoped = lambda text, name: re.search(
            r'loc\("[^"]*\b' + name + '/', text)
        for name in ("invariant_build", "neighbour_exchange"):
            assert scoped(build, name), name
        for name in ("scan_execute", "qp_solve", "consensus_update",
                     "neighbour_exchange"):
            assert scoped(scan, name), name
        text = build + scan
        # every collective of the fit is named as the exchange
        coll = re.findall(r'loc\("([^"]*/(?:all_gather|pmax|psum))"', text)
        assert coll
        assert all("neighbour_exchange/" in c for c in coll), coll
        print("OK")
        """, n_devices=4)


# ---------------------------------------------------------------------------
# the compile counter and the shared clock
# ---------------------------------------------------------------------------
def _toy_fn():
    def _toy(x):
        return x * 2.0 + 1.0
    return _toy


def test_compile_counter_counts_relowering_per_program_and_span():
    x = jax.numpy.ones(3)
    cached = jax.jit(_toy_fn())
    cached(x)
    obs.clear_compiles()
    obs.clear_spans()
    n = 3
    with obs.span("relower"):
        for _ in range(n):
            # a new function each call, as a new closure is: re-lowered
            jax.jit(_toy_fn())(x)
    with obs.span("cached"):
        for _ in range(n):
            cached(x)                 # found in jit's cache: nothing
    counts = obs.compile_counts()
    prog = counts["programs"]["jit(_toy)"]
    assert prog["lower"]["count"] == n and prog["compile"]["count"] == n
    assert prog["trace"]["count"] == n and prog["lower"]["ms"] > 0
    assert counts["spans"]["relower"]["lower"]["count"] == n
    assert "cached" not in counts["spans"]
    recorded = [e for e in obs.iter_spans()
                if e["name"] == "compile:jit(_toy)"]
    assert len(recorded) == 3 * n
    assert {e["args"]["span"] for e in recorded} == {"relower"}
    assert {e["args"]["phase"] for e in recorded} == {"trace", "lower",
                                                       "compile"}
    reg = obs.MetricsRegistry().record_compiles()
    assert reg.get("compiles")["programs"]["jit(_toy)"]["lower"][
        "count"] == n
    obs.clear_compiles()
    assert obs.compile_counts() == {"programs": {}, "spans": {}}


def test_recorder_and_profiler_share_the_clock(tmp_path):
    from jax.profiler import ProfileData

    obs.clear_spans()
    d = str(tmp_path / "prof")
    jax.profiler.start_trace(d)
    try:
        with obs.span("clock_probe"):
            time.sleep(0.005)
        jax.jit(lambda v: v - 3.0)(jax.numpy.ones(2))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    start = None
    annotations = {}
    for plane in pd.planes:
        st = dict(plane.stats)
        if "profile_start_time" in st:
            start = int(st["profile_start_time"])
        for line in plane.lines:
            for ev in line.events:
                annotations.setdefault(ev.name, (ev.start_ns, ev.end_ns))
    assert start is not None
    ev = [e for e in obs.iter_spans() if e["name"] == "clock_probe"][0]
    a0, a1 = annotations["clock_probe"]
    s0 = ev["ts"] * 1e3 - start
    assert abs(s0 - a0) < 1e6
    assert abs(s0 + ev["dur"] * 1e3 - a1) < 1e6
    # JAX's compile phases reach the trace under the program's name
    assert "compile:jit(<lambda>)" in annotations


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_roundtrip_and_version_guard(tmp_path):
    reg = obs.MetricsRegistry()
    reg.record("custom", {"a": 1, "arr": np.arange(3, dtype=np.float32)})
    d = reg.to_dict()
    assert d["kind"] == "metrics_registry"
    assert d["obs_schema_version"] == obs.OBS_SCHEMA_VERSION
    assert json.loads(json.dumps(d)) == d       # plain JSON throughout
    path = os.path.join(str(tmp_path), "m.json")
    reg.save(path)
    back = obs.MetricsRegistry.load(path)
    assert back.get("custom")["arr"] == [0.0, 1.0, 2.0]
    with pytest.raises(ValueError, match="newer"):
        obs.MetricsRegistry.from_dict(
            dict(d, obs_schema_version=obs.OBS_SCHEMA_VERSION + 1))
    with pytest.raises(ValueError, match="kind"):
        obs.MetricsRegistry.from_dict(dict(d, kind="nope"))


def test_registry_absorbs_session_sources():
    X, y, mask, adj = _data()
    cfg = SolverConfig(iters=3, qp_iters=8, backend="async",
                       net=NetConfig(), telemetry=True)
    sess = api.OnlineSession(X, y, mask, adj, config=cfg)
    sess.run(3)
    reg = obs.MetricsRegistry.from_session(sess).record_spans()
    assert {"plan", "net", "telemetry", "spans"} <= set(reg.sections())
    assert reg.get("telemetry")["dual_residual"]["iters"] == 3
    assert reg.get("net")["msgs_sent"] == sess.net_report_["msgs_sent"]
    rendered = reg.render()
    assert "dual_residual" in rendered and "[net]" in rendered


# ---------------------------------------------------------------------------
# timing helper
# ---------------------------------------------------------------------------
def test_timeit_contract():
    calls = []

    def fn(a, b=1):
        calls.append((a, b))
        return a + b

    t = obs.timeit(fn, 2, b=3, repeats=4, warmup=2)
    assert isinstance(t, obs.Timing)
    assert t.result == 5
    assert len(calls) == 6                  # warmup + timed
    assert len(t.times_s) == 4
    assert t.best_s <= t.mean_s
    with pytest.raises(ValueError):
        obs.timeit(fn, 1, repeats=0)


# ---------------------------------------------------------------------------
# the CLI
# ---------------------------------------------------------------------------
def test_cli_demo_and_report(tmp_path):
    env = dict(os.environ)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                       os.pardir, "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    trace = os.path.join(str(tmp_path), "trace.json")
    metrics = os.path.join(str(tmp_path), "metrics.json")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "demo", "--iters", "2",
         "--trace", trace, "--registry", metrics],
        capture_output=True, text=True, env=env, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    obs.validate_chrome_trace(json.loads(open(trace).read()))
    reg = obs.MetricsRegistry.load(metrics)
    assert {"telemetry", "spans", "compiles"} <= set(reg.sections())
    assert "jit(_plan_scan)" in reg.get("compiles")["programs"]
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "report", metrics],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "dual_residual" in proc.stdout
