"""Batched sweep engine vs the serial per-config loop.

The contract (the reason the fig3-fig6 drivers could move to
``sweep_fit`` without changing a single output): a ``SweepPlan`` is
BITWISE the serial ``compile_problem`` loop over
``per_config_problems`` — for independent runs, for warm-start chains,
for recorded histories, and for every QP engine including the fused
Pallas kernel under ``REPRO_USE_PALLAS=1``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.api import SolverConfig, dsvm_overrides, evaluate, sweep_fit
from repro.api import backends
from repro.core import dtsvm as core
from repro.core import graph
from repro.data import synthetic
from repro.kernels import ops as kops
from repro.kernels import ref

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                      # optional dep (pip install -e .[test])
    HAS_HYPOTHESIS = False


def _make(V=6, T=2, n=9, seed=0, n_test=60, p=10):
    counts = np.full((V, T), n, int)
    data = synthetic.make_multitask_data(V=V, T=T, p=p, n_train=counts,
                                         n_test=n_test, seed=seed)
    A = graph.make_graph("random", V, degree=0.8, seed=seed)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    return data, prob


def _assert_states_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _serial(prob, cfgs, iters, qp_iters, qp_solver="fista", eval_fn=None,
            chain=False):
    """The reference: loop compile_problem over the per-config problems."""
    states, hists, st = [], [], None
    for pc in engine.per_config_problems(prob, cfgs):
        pl = engine.compile_problem(pc, qp_iters=qp_iters,
                                    qp_solver=qp_solver)
        st, h = pl.run(state=st if chain else None, iters=iters,
                       eval_fn=eval_fn)
        states.append(st)
        hists.append(h)
    return states, hists


GRIDS = {
    "hyper_grid": [dict(C=0.001, eps2=0.1), dict(C=0.01, eps2=1.0),
                   dict(C=0.1, eps2=10.0), dict(eps1=5.0, eta1=2.0)],
    "etas": [dict(eta1=0.7, eta2=0.3), dict(eta2=1.3), dict(eta1=2.0)],
    "masks": [dict(),
              dict(active=(np.arange(12).reshape(6, 2) % 3 != 0)
                   .astype(np.float32)),
              dict(couple=np.zeros(6, np.float32))],
    "dsvm_baseline": [dict(), dsvm_overrides(6)],
    "single": [dict(C=0.05)],
}


@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_sweep_run_matches_serial_bitwise(grid):
    _, prob = _make()
    cfgs = GRIDS[grid]
    serial_states, _ = _serial(prob, cfgs, iters=6, qp_iters=40)
    splan = engine.compile_sweep(prob, cfgs, qp_iters=40)
    states, _ = splan.run(iters=6)
    for s, ref_st in enumerate(serial_states):
        _assert_states_equal(ref_st, jax.tree.map(lambda x: x[s], states))


def test_sweep_shares_one_z():
    """The invariant split: Z has no config axis and is THE one shared
    build; only the a-diagonal family stacks per config."""
    _, prob = _make()
    splan = engine.compile_sweep(prob, GRIDS["hyper_grid"], qp_iters=10)
    V, T, N, p = prob.X.shape
    S = len(GRIDS["hyper_grid"])
    assert splan.inv.Z.shape == (V, T, N, p + 1)          # shared: no S
    for k in ("ntp", "nbr", "u", "a", "K", "hi", "L"):
        assert getattr(splan.inv, k).shape[0] == S, k
    np.testing.assert_array_equal(
        np.asarray(splan.inv.Z),
        np.asarray(engine.compute_z(prob)))


def test_sweep_history_matches_serial():
    data, prob = _make()
    cfgs = GRIDS["hyper_grid"]
    ev = evaluate.risk_eval_fn(prob.X.shape[0], data["X_test"],
                               data["y_test"])
    _, serial_hists = _serial(prob, cfgs, iters=5, qp_iters=30, eval_fn=ev)
    splan = engine.compile_sweep(prob, cfgs, qp_iters=30)
    _, hist = splan.run(iters=5, eval_fn=ev)
    assert hist.shape[:2] == (5, len(cfgs))
    for s, h in enumerate(serial_hists):
        np.testing.assert_array_equal(np.asarray(h), np.asarray(hist[:, s]))


def test_sweep_chain_matches_serial_warm_start():
    """Chain mode == serially carrying the final state into the next
    config's fit (continuation), bitwise."""
    _, prob = _make()
    cfgs = GRIDS["hyper_grid"]
    serial_states, _ = _serial(prob, cfgs, iters=5, qp_iters=30, chain=True)
    splan = engine.compile_sweep(prob, cfgs, qp_iters=30)
    states, _ = splan.run_chain(iters=5)
    for s, ref_st in enumerate(serial_states):
        _assert_states_equal(ref_st, jax.tree.map(lambda x: x[s], states))


def test_sweep_warm_start_state():
    """An explicit stacked warm start resumes each config bitwise."""
    _, prob = _make()
    cfgs = GRIDS["etas"]
    splan = engine.compile_sweep(prob, cfgs, qp_iters=30)
    mid, _ = splan.run(iters=3)
    full, _ = splan.run(iters=7)
    resumed, _ = splan.run(state=mid, iters=4)
    _assert_states_equal(full, resumed)


@pytest.mark.parametrize("qp_solver", ["pg", "pallas_fused"])
def test_sweep_qp_engines_match_serial(qp_solver, monkeypatch):
    """The non-default QP engines stay bitwise under the config axis —
    pallas_fused in interpret mode exercises the kernel's batching."""
    if qp_solver == "pallas_fused":
        monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    _, prob = _make(V=3, T=1, n=5, p=4)
    cfgs = [dict(C=0.05), dict(eps2=3.0), dict(eta2=0.7)]
    iters, qp_iters = 2, 5
    serial_states, _ = _serial(prob, cfgs, iters=iters, qp_iters=qp_iters,
                               qp_solver=qp_solver)
    splan = engine.compile_sweep(prob, cfgs, qp_iters=qp_iters,
                                 qp_solver=qp_solver)
    states, _ = splan.run(iters=iters)
    for s, ref_st in enumerate(serial_states):
        got = jax.tree.map(lambda x: x[s], states)
        if qp_solver == "pallas_fused":
            # under jax 0.9 the config-batched interpret-mode kernel
            # (vmap adds a grid axis) rounds one ulp apart from the
            # serial launch in the first config; the jnp engines stay
            # bitwise
            for la, lb in zip(jax.tree.leaves(ref_st),
                              jax.tree.leaves(got)):
                la, lb = np.asarray(la), np.asarray(lb)
                assert np.max(np.abs(la - lb)) <= 1e-6 * np.max(np.abs(la))
        else:
            _assert_states_equal(ref_st, got)


def test_config_plan_slices_back_to_serial():
    _, prob = _make()
    cfgs = GRIDS["hyper_grid"]
    splan = engine.compile_sweep(prob, cfgs, qp_iters=30)
    pl = splan.config_plan(2)
    st_single, _ = pl.run(iters=4)
    st_sweep, _ = splan.run(iters=4)
    _assert_states_equal(st_single, jax.tree.map(lambda x: x[2], st_sweep))


# ---------------------------------------------------------------------------
# kernels: shared-Z gram broadcast + batched step-size threading
# ---------------------------------------------------------------------------
def test_weighted_gram_shared_z_broadcast():
    rng = np.random.default_rng(0)
    Z = jnp.asarray(rng.normal(size=(4, 2, 7, 5)).astype(np.float32))
    a = jnp.asarray(rng.uniform(0.1, 2.0, size=(3, 4, 2, 5))
                    .astype(np.float32))
    K = kops.weighted_gram(Z, a)
    assert K.shape == (3, 4, 2, 7, 7)
    for s in range(3):
        np.testing.assert_array_equal(
            np.asarray(K[s]), np.asarray(kops.weighted_gram(Z, a[s])))


def test_qp_pg_step_prefix_gamma():
    """A per-config (S,) or (S,V,T) step size leading-aligns against an
    (S,V,T,N) batch instead of misbroadcasting from the right."""
    rng = np.random.default_rng(1)
    S, V, T, N = 3, 2, 2, 5
    A = rng.normal(size=(S, V, T, N, N)).astype(np.float32)
    K = jnp.asarray(A @ np.swapaxes(A, -1, -2) / N)
    lam = jnp.asarray(rng.uniform(0, 1, size=(S, V, T, N)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(S, V, T, N)).astype(np.float32))
    hi = jnp.ones((S, V, T, N), jnp.float32)
    g_s = jnp.asarray(rng.uniform(0.01, 0.1, size=(S,)).astype(np.float32))
    out = ref.qp_pg_step(lam, K, q, hi, g_s)
    full = jnp.broadcast_to(g_s[:, None, None], (S, V, T))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.qp_pg_step(lam, K, q, hi,
                                                            full)))


# ---------------------------------------------------------------------------
# the api surface
# ---------------------------------------------------------------------------
def test_sweep_fit_matches_solver_loop():
    from repro.api import DTSVM
    data, prob = _make()
    cfg = SolverConfig(iters=5, qp_iters=30)
    grid = [dict(eps1=0.1, eps2=10.0), dict(eps1=10.0, eps2=0.1)]
    res = sweep_fit(data["X"], data["y"], grid, mask=data["mask"],
                    adj=prob.adj, base=cfg, X_test=data["X_test"],
                    y_test=data["y_test"])
    assert len(res) == 2
    assert res.history.shape == (5, 2) + prob.active.shape
    for s, over in enumerate(grid):
        sol = DTSVM(cfg.replace(**over)).fit(
            data["X"], data["y"], mask=data["mask"], adj=prob.adj,
            X_test=data["X_test"], y_test=data["y_test"])
        _assert_states_equal(sol.state_, res.state_of(s))
        np.testing.assert_array_equal(np.asarray(sol.history_),
                                      np.asarray(res.history[:, s]))
        np.testing.assert_array_equal(
            np.asarray(sol.risks(data["X_test"], data["y_test"])),
            np.asarray(res.risks(data["X_test"], data["y_test"])[s]))
    np.testing.assert_array_equal(res.final_risks(), res.history[-1])


def test_sweep_fit_dsvm_override_matches_dsvm_solver():
    from repro.api import DSVM
    data, prob = _make()
    V = prob.X.shape[0]
    cfg = SolverConfig(iters=4, qp_iters=30)
    res = sweep_fit(data["X"], data["y"], [dsvm_overrides(V)],
                    mask=data["mask"], adj=prob.adj, base=cfg)
    sol = DSVM(cfg).fit(data["X"], data["y"], mask=data["mask"],
                        adj=prob.adj)
    _assert_states_equal(sol.state_, res.state_of(0))


def test_sweep_validation_errors():
    data, prob = _make(V=3, T=1, n=4, p=4)
    with pytest.raises(ValueError, match="empty config grid"):
        engine.compile_sweep(prob, [])
    with pytest.raises(ValueError, match="unknown sweep override"):
        engine.compile_sweep(prob, [dict(qC=1.0)])
    with pytest.raises(ValueError, match="disagree on static"):
        engine.compile_sweep(prob, [SolverConfig(qp_iters=10),
                                    SolverConfig(qp_iters=20)])
    with pytest.raises(ValueError, match="disagree on static"):
        sweep_fit(data["X"], data["y"],
                  [SolverConfig(iters=3), SolverConfig(iters=4)],
                  mask=data["mask"], adj=prob.adj)
    with pytest.raises(ValueError, match="unknown QP engine"):
        engine.compile_sweep(prob, [dict()], qp_solver="nope")
    splan = engine.compile_sweep(prob, [dict()], qp_iters=5)
    with pytest.raises(ValueError, match="sequential"):
        backends.run_sweep(splan, 1, backend="shard_map", chain=True)
    with pytest.raises(ValueError, match="single-host"):
        backends.run_sweep(splan, 1, backend="shard_map",
                           eval_fn=lambda s: 0.0)


# ---------------------------------------------------------------------------
# hypothesis: random PSD problems x random config grids stay bitwise
# ---------------------------------------------------------------------------
if HAS_HYPOTHESIS:
    _override = st.fixed_dictionaries(
        {},
        optional={
            "C": st.floats(1e-3, 0.5),
            "eps1": st.floats(0.05, 20.0),
            "eps2": st.floats(0.05, 20.0),
            "eta1": st.floats(0.1, 3.0),
            "eta2": st.floats(0.1, 3.0),
            "box_scale": st.floats(0.5, 30.0),
        })

    @settings(max_examples=15, deadline=None)
    @given(V=st.integers(2, 5), T=st.integers(1, 3), n=st.integers(3, 7),
           p=st.integers(2, 6), seed=st.integers(0, 10_000),
           cfgs=st.lists(_override, min_size=1, max_size=4),
           chain=st.booleans())
    def test_property_sweep_bitwise_vs_serial(V, T, n, p, seed, cfgs,
                                              chain):
        """For random problems and random config grids, the batched
        SweepPlan (independent AND warm-start-chained) is bitwise the
        serial compile_problem loop."""
        counts = np.full((V, T), n, int)
        data = synthetic.make_multitask_data(V=V, T=T, p=p, n_train=counts,
                                             n_test=8, seed=seed)
        A = graph.make_graph("random", V, degree=0.7, seed=seed)
        prob = core.make_problem(data["X"], data["y"], data["mask"], A)
        serial_states, _ = _serial(prob, cfgs, iters=3, qp_iters=10,
                                   chain=chain)
        splan = engine.compile_sweep(prob, cfgs, qp_iters=10)
        runner = splan.run_chain if chain else splan.run
        states, _ = runner(iters=3)
        for s, ref_st in enumerate(serial_states):
            _assert_states_equal(ref_st,
                                 jax.tree.map(lambda x: x[s], states))

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 10_000),
           cfgs=st.lists(_override, min_size=1, max_size=3))
    def test_property_sweep_bitwise_pallas(seed, cfgs):
        """Same property through the fused Pallas kernel (interpret
        mode on CPU) — tiny shapes, interpret mode is slow."""
        import os
        old = os.environ.get("REPRO_USE_PALLAS")
        os.environ["REPRO_USE_PALLAS"] = "1"
        try:
            V, T, n, p = 3, 1, 4, 3
            counts = np.full((V, T), n, int)
            data = synthetic.make_multitask_data(V=V, T=T, p=p,
                                                 n_train=counts, n_test=8,
                                                 seed=seed)
            A = graph.ring(V)
            prob = core.make_problem(data["X"], data["y"], data["mask"], A)
            serial_states, _ = _serial(prob, cfgs, iters=2, qp_iters=4,
                                       qp_solver="pallas_fused")
            splan = engine.compile_sweep(prob, cfgs, qp_iters=4,
                                         qp_solver="pallas_fused")
            states, _ = splan.run(iters=2)
            for s, ref_st in enumerate(serial_states):
                _assert_states_equal(ref_st,
                                     jax.tree.map(lambda x: x[s], states))
        finally:
            if old is None:
                os.environ.pop("REPRO_USE_PALLAS", None)
            else:
                os.environ["REPRO_USE_PALLAS"] = old
