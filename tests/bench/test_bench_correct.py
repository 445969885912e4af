"""What decides ``correct``: a run of each cell passes (``paper.fit`` at
its own size, the others cut to fit a CPU); the same run with the timed
path broken underneath does not; and the control (the reference one
precision lower, put in the program's place) does not either.  The
harness's look for a chip is skipped: everything else is the run as
``bench/run.py`` makes it, on the CPU."""
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import datagen, reference, runner  # noqa: E402
from benchlib import compare, spec as spec_lib  # noqa: E402

SEED = 2 ** 40 + 99
SOLVER = {"C": 0.01, "eps1": 1.0, "eps2": 1.0, "eta1": 1.0, "eta2": 1.0,
          "qp_solver": "fista",
          "backend": "vmap"}
SMALL = {
    "paper.fit": {},
    "large_n.fit": dict(V=4, T=2, n_total=[256, 256], N=64, p=8, iters=4,
                        qp_iters=12, solver=SOLVER,
                        budget={"max_elems": 2048}),
    "paper.serve": {},
}
SERVE_MIX = {"rate": 300, "wait_after_s": 2.0}


def _run(cell, **kw):
    spec = spec_lib.Spec(ROOT, BENCH)
    mix = SERVE_MIX if cell.endswith("serve") else None
    return runner.run_cell(spec, cell, seed=SEED, seconds=0.5, trace=False,
                           t_start=time.perf_counter(),
                           overrides=SMALL[cell], mix_overrides=mix, **kw)


@pytest.mark.parametrize("cell", ["paper.fit", "large_n.fit", "paper.serve"])
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    # every end-to-end metric BENCHMARK.json gives the cell, and no other
    spec = spec_lib.Spec(ROOT, BENCH)
    assert set(out["metrics"]) == {m["name"] for m in spec.end_to_end(cell)}
    assert "setup_s" in out["metrics"]
    if cell in spec.cells():
        assert len(out["metrics"]) >= 2


def test_traced_run_reads_its_per_layer_metrics(tmp_path):
    spec = spec_lib.Spec(ROOT, BENCH)
    # a long window: the traced fits (3 of each kind) end it
    out = runner.run_cell(spec, "paper.fit", seed=SEED, seconds=600,
                          trace=True, t_start=time.perf_counter(),
                          overrides=SMALL["paper.fit"],
                          trace_dir=str(tmp_path / "trace"))
    assert out["correct"], out["checks"]
    # the warm fits of set-up lower the fit's two programs (the ADMM scan
    # and the Gram map, each cached per shape), so the window's whole fits
    # lower none; split fits, which feed the build/ADMM spans, alternate
    # with them and are not counted
    assert out["metrics"]["fit.lowerings_per_fit"]["value"] == 0.0
    assert out["notes"]["split_fits"] == 3 and out["notes"]["fits"] == 6
    # no TPU plane on the CPU: the device readers find nothing to read
    assert "fit.device_idle_pct" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _state_unchanged(monkeypatch):
    from repro.engine import plan
    monkeypatch.setattr(plan, "plan_step", lambda prob, inv, state, **k:
                        state)


def _half_batch(monkeypatch):
    from repro.core import dtsvm
    make = dtsvm.make_problem

    def half(X, y, mask=None, *a, **k):
        prob = make(X, y, mask, *a, **k)
        n = prob.mask.shape[-1]
        return prob._replace(mask=prob.mask.at[..., n // 2:].set(0.0))
    monkeypatch.setattr(dtsvm, "make_problem", half)


def _answer_altered(monkeypatch):
    from repro.engine import plan
    update = plan.consensus_update

    def off(*a, **k):
        r, alpha, beta = update(*a, **k)
        return r.at[0, 0, 0].add(1e-3 * (1.0 + abs(r[0, 0, 0]))), alpha, beta
    monkeypatch.setattr(plan, "consensus_update", off)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
@pytest.mark.parametrize("cell", ["paper.fit", "large_n.fit"])
def test_broken_fit_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(cell)
    assert not out["correct"], out["checks"]


def _served_altered(monkeypatch):
    from repro.serve import server
    gemm = server.gemm_rows
    monkeypatch.setattr(server, "gemm_rows",
                        lambda W, b, X: gemm(W, b, X).at[0, :].add(1e-3))


def _served_half(monkeypatch):
    """Half of each batch's rows left out of the GEMM."""
    from repro.serve import server
    gemm = server.gemm_rows

    def half(W, b, X):
        return gemm(W, b, X.at[X.shape[0] // 2:].set(0.0))
    monkeypatch.setattr(server, "gemm_rows", half)


@pytest.mark.parametrize("fault", [_served_altered, _served_half])
def test_broken_serve_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _run("paper.serve")
    assert not out["correct"], out["checks"]


X4 = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax, jax.numpy as jnp
    from benchlib import runner, spec as spec_lib
    if {broken}:
        # the exchange between chips left out: each shard sees only its
        # own rows, repeated, in place of the all-gathered iterate
        def local(x, axis_name, *, axis=0, tiled=False, **k):
            n = jax.lax.psum(1, axis_name)
            return jnp.concatenate([x] * n, axis=axis) if tiled else \\
                jnp.stack([x] * n, axis=axis)
        jax.lax.all_gather = local
    if {fault!r}:
        # a fault of this file, planted as its monkeypatch would plant it
        sys.path.insert(0, {here!r})
        import test_bench_correct as faults

        class _Patch:
            setattr = staticmethod(setattr)
        getattr(faults, {fault!r})(_Patch)
    spec = spec_lib.Spec({root!r}, {bench!r})
    out = runner.run_cell(spec, "large_n_x4.fit", seed={seed}, seconds=0.5,
                          trace=False, t_start=time.perf_counter(),
                          overrides={over!r})
    print(json.dumps(out["checks"]), out["correct"])
""")


def _run_x4(broken=False, fault=""):
    """``large_n_x4.fit`` cut to fit a CPU, on four host devices in a
    child process; its stdout: the checks, then ``correct``."""
    over = dict(V=2, T=2, n_total=[128, 128], N=64, p=8, iters=4,
                qp_iters=12, solver=dict(SOLVER, backend="sample_shard"),
                budget={"max_elems": 2048})
    code = X4.format(bench=BENCH, src=os.path.join(ROOT, "src"), root=ROOT,
                     here=HERE, seed=SEED, over=over, broken=broken,
                     fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip()


@pytest.mark.parametrize("broken", [False, True])
def test_sample_shard_exchange(broken):
    out = _run_x4(broken=broken)
    assert out.endswith(str(not broken)), out


def _consensus_kept(monkeypatch):
    """Eqs. (7)-(9) return the state they were given: the
    ``sample_shard`` step keeps its state (it has its own step, which
    calls ``plan.consensus_update``, not ``plan.plan_step``)."""
    from repro.engine import plan
    monkeypatch.setattr(plan, "consensus_update", lambda prob, state, *a, **k:
                        (state.r, state.alpha, state.beta))


@pytest.mark.parametrize("fault", ["_consensus_kept", "_half_batch",
                                   "_answer_altered"])
def test_broken_sample_shard_fit_is_not_correct(fault):
    out = _run_x4(fault=fault)
    assert out.endswith("False"), out


@pytest.mark.parametrize("cell", ["paper.fit", "large_n.fit", "paper.serve"])
def test_control_run_is_not_correct(cell):
    """The control in the program's place on the timed path, judged as a
    run is judged: every compared number printed, ``correct`` false."""
    out = _run(cell, control=True)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["correct"], out["checks"]
    assert not compare.all_within(out["checks"])


def _limit(cell, name):
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as fh:
        return json.load(fh)["limits"][name]


def _load_cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("cell,cfg_name,over", [
    ("paper.fit", "paper", {}),                     # the cell's own size
    ("large_n.fit", "large_n", {}),                 # the cell's own size
    # the reference never holds K; N=24000 takes a minute on a CPU
    ("large_n_x4.fit", "large_n_x4", {"N": 12000, "n_total": [24000, 24000]}),
])
def test_control_fails_the_fit_limit(cell, cfg_name, over):
    cfg = dict(_load_cfg(cfg_name), **over)
    d = datagen.fit_data(cfg, SEED, 0)
    A = datagen.make_graph(cfg, SEED)
    args = (d["X"], d["y"], d["mask"], A)
    ref = reference.dtsvm_fit(cfg, *args, precision="highest")
    ctl = reference.dtsvm_fit(cfg, *args, precision="high")
    assert compare.state_rel_err(ctl, ref) > _limit(cell, "state_rel_err")


def test_control_split_is_three_bfloat16_passes():
    """The control's operands: a bfloat16 head (the operand rounded to
    nearest) and a bfloat16 tail that is not 0; three passes land well
    inside one bfloat16 pass and well outside float32."""
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(3), (8, 512, 785))
    y = jax.random.normal(jax.random.PRNGKey(4), (8, 785))
    hi, lo = reference._split(x)
    assert hi.dtype == lo.dtype == jnp.bfloat16
    assert bool(jnp.all(hi == x.astype(jnp.bfloat16)))
    assert float(jnp.mean(lo != 0)) > 0.9
    exact = np.einsum("bnd,bd->bn", np.asarray(x, np.float64),
                      np.asarray(y, np.float64))

    def err(v):
        return float(np.abs(np.asarray(v, np.float64) - exact).max()
                     / np.abs(exact).max())
    one = jnp.einsum("bnd,bd->bn", x.astype(jnp.bfloat16),
                     y.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    three = reference.contract("bnd,bd->bn", x, y, "high")
    f32 = reference.contract("bnd,bd->bn", x, y, "highest")
    assert err(f32) < 1e-6 < err(three) < 2e-5 < 10 * err(three) < err(one)


def test_control_fails_the_serve_limit():
    from benchlib import arrivals

    cfg = _load_cfg("paper")
    with open(os.path.join(BENCH, "traffic", "poisson_zipf.json")) as fh:
        mix = json.load(fh)
    W, b = datagen.serve_weights(cfg, SEED)
    s = arrivals.schedule(mix, cfg["V"], cfg["T"], cfg["p"], SEED, 2.0)
    args = (W, b, s["X"], s["node"], s["task"], s["start"], s["rows"])
    ref = reference.serve_decisions(*args, precision="highest")
    ctl = reference.serve_decisions(*args, precision="high")
    scale = reference.serve_scale(W, s["X"], s["node"], s["task"],
                                  s["rows"], b)
    gap = compare.served_rel_gap(ctl, ref, scale)
    assert gap > _limit("paper.serve", "served_rel_gap")
    assert np.isfinite(gap)
