"""repro.engine — plan/execute layer vs the legacy per-iteration path.

The contract: ``plan.run`` is bit-for-bit the seed's scan over the
self-contained ``dtsvm_step`` (which rebuilds every invariant each
iteration), the Plan's invariants are state-independent, the Hessian is
built exactly once per fit, and the three QP engines agree.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import brute_force_box_qp
from repro import engine
from repro.engine import invariants as inv_lib
from repro.engine import qp_engines
from repro.api import CSVM, DTSVM, OnlineSession, SolverConfig
from repro.core import csvm as csvm_lib
from repro.core import dtsvm as core
from repro.core import graph
from repro.core import qp as qp_lib
from repro.data import synthetic
from repro.kernels import ops as kops
from repro.kernels import ref


def _make(V=6, T=2, n=9, seed=0, n_test=80):
    counts = np.full((V, T), n, int)
    data = synthetic.make_multitask_data(
        V=V, T=T, p=10, n_train=counts, n_test=n_test, relatedness=0.9,
        seed=seed)
    A = graph.make_graph("random", V, degree=0.8, seed=0)
    return data, A


def _legacy_run(prob, iters, qp_iters, state=None):
    """The SEED's run_dtsvm: lax.scan over the full per-iteration
    dtsvm_step (invariants rebuilt every iteration)."""
    if state is None:
        state = core.init_state(prob)

    def body(st, _):
        return core.dtsvm_step(st, prob, qp_iters), jnp.float32(0)

    st, _ = jax.lax.scan(body, state, None, length=iters)
    return st


def _assert_states_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _assert_states_close(a, b, *, rtol):
    """Every leaf within ``rtol`` of the leaf's own scale, max|b|."""
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        assert np.max(np.abs(la - lb)) <= rtol * np.max(np.abs(lb))


def _rand_box_qp(rng, n, batch=()):
    A = rng.normal(size=batch + (n, n)).astype(np.float32)
    K = (A @ np.swapaxes(A, -1, -2) / n).astype(np.float32)
    q = rng.normal(size=batch + (n,)).astype(np.float32)
    hi = rng.uniform(0.3, 1.0, size=batch + (n,)).astype(np.float32)
    return jnp.asarray(K), jnp.asarray(q), jnp.asarray(hi)


# ---------------------------------------------------------------------------
# plan.run == legacy path, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("config", ["plain", "active", "couple_off",
                                    "ragged_mask", "hypers"])
def test_plan_run_matches_legacy_bitwise(config):
    V, T = 6, 2
    data, A = _make(V, T)
    kw, active, couple, mask = {}, None, None, data["mask"]
    if config == "active":
        active = (np.arange(V * T).reshape(V, T) % 3 != 0).astype(np.float32)
    elif config == "couple_off":
        couple = np.zeros(V, np.float32)
    elif config == "ragged_mask":
        mask = np.array(data["mask"], copy=True)
        mask[:, :, -3:] = 0.0                      # extra padding rows
    elif config == "hypers":
        kw = dict(eps1=5.0, eps2=0.3, eta1=2.0, eta2=0.7)
    prob = core.make_problem(data["X"], data["y"], mask, A, C=0.01,
                             active=active, couple=couple, **kw)
    st_legacy = _legacy_run(prob, 10, 50)
    plan = engine.compile_problem(prob, qp_iters=50)
    st_plan, _ = plan.run(iters=10)
    if config == "hypers":
        # XLA's CPU compiler (jax 0.9) fuses the step-size diagonal u
        # (eq. 10) inside the legacy scan but the plan builds it op by
        # op; with these non-dyadic eps/eta the two u differ by one ulp,
        # which ten ADMM iterations carry to a few ulp of the state.
        _assert_states_close(st_legacy, st_plan, rtol=1e-5)
    else:
        _assert_states_equal(st_legacy, st_plan)


def test_plan_run_matches_legacy_warm_start():
    data, A = _make()
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    warm = _legacy_run(prob, 4, 50)
    st_legacy = _legacy_run(prob, 6, 50, state=warm)
    st_plan, _ = engine.compile_problem(prob, qp_iters=50).run(
        state=warm, iters=6)
    _assert_states_equal(st_legacy, st_plan)


def test_plan_step_matches_legacy_step():
    """Single-iteration equivalence, eager (no scan on either side)."""
    data, A = _make()
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    plan = engine.compile_problem(prob, qp_iters=50)
    st = core.init_state(prob)
    for _ in range(3):
        st_legacy = core.dtsvm_step(st, prob, qp_iters=50)
        st_plan = plan.step(st)
        _assert_states_equal(st_legacy, st_plan)
        st = st_legacy


def test_run_dtsvm_is_plan_backed_and_identical():
    """The public run_dtsvm now routes through the engine; history
    recording keeps the legacy contract."""
    data, A = _make()
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    Xte = jnp.broadcast_to(jnp.asarray(data["X_test"])[None],
                           (6, 2) + data["X_test"].shape[1:])
    yte = jnp.broadcast_to(jnp.asarray(data["y_test"])[None],
                           (6, 2) + data["y_test"].shape[1:])
    ev = lambda st: core.risks(st.r, Xte, yte)
    st, hist = core.run_dtsvm(prob, 5, qp_iters=50, eval_fn=ev)
    assert hist.shape == (5, 6, 2)
    st_legacy = _legacy_run(prob, 5, 50)
    _assert_states_equal(st, st_legacy)


# ---------------------------------------------------------------------------
# invariants are a function of the problem only
# ---------------------------------------------------------------------------
def test_plan_invariants_independent_of_state():
    """Property: recomputing the invariants after any amount of ADMM
    progress (or from any random state) yields the identical pytree —
    they depend on DTSVMProblem alone, never on DTSVMState."""
    data, A = _make()
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    inv0 = inv_lib.compute_invariants(prob)
    plan = engine.compile_problem(prob, qp_iters=40)
    st, _ = plan.run(iters=7)
    inv1 = inv_lib.compute_invariants(prob)       # after running: unchanged
    for a, b in zip(inv0, inv1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # plan.step consults nothing state-derived beyond its arguments:
    # stepping from scrambled states through the same plan equals the
    # legacy full-recompute step from those states.
    rng = np.random.default_rng(0)
    scrambled = core.DTSVMState(
        r=jnp.asarray(rng.normal(size=st.r.shape), jnp.float32),
        alpha=jnp.asarray(rng.normal(size=st.alpha.shape), jnp.float32),
        beta=jnp.asarray(rng.normal(size=st.beta.shape), jnp.float32),
        lam=jnp.asarray(rng.uniform(0, 0.01, size=st.lam.shape), jnp.float32))
    _assert_states_equal(core.dtsvm_step(scrambled, prob, qp_iters=40),
                         plan.step(scrambled))


def test_weighted_gram_built_exactly_once_per_fit(monkeypatch):
    """The acceptance bar: one Hessian build per fit(), not per ADMM
    iteration."""
    calls = {"n": 0}
    real = kops.weighted_gram

    def counting(Z, a, **kw):
        calls["n"] += 1
        return real(Z, a, **kw)

    monkeypatch.setattr(kops, "weighted_gram", counting)
    data, A = _make()
    DTSVM(SolverConfig(C=0.01, iters=12, qp_iters=40)).fit(
        data["X"], data["y"], mask=data["mask"], adj=A)
    assert calls["n"] == 1, calls["n"]


# ---------------------------------------------------------------------------
# QP engine registry
# ---------------------------------------------------------------------------
def test_qp_engine_registry():
    assert set(qp_engines.names()) >= {"fista", "pg", "pallas_fused"}
    with pytest.raises(ValueError, match="unknown QP engine"):
        qp_engines.get("nope")
    with pytest.raises(ValueError, match="unknown QP engine"):
        data, A = _make(V=3, T=1)
        prob = core.make_problem(data["X"], data["y"], data["mask"], A)
        engine.compile_problem(prob, qp_solver="nope")


@pytest.mark.parametrize("name", ["pg", "fista", "pallas_fused"])
def test_qp_engines_match_oracle_on_random_psd(name):
    rng = np.random.default_rng(3)
    K, q, hi = _rand_box_qp(rng, 24)
    lam = qp_engines.get(name)(K, q, hi, iters=3000)
    want = brute_force_box_qp(np.asarray(K), np.asarray(q), np.asarray(hi))
    np.testing.assert_allclose(np.asarray(lam), want, atol=5e-4)


def test_qp_engines_agree_batched():
    """All three engines on the same random PSD box batch (engine-shaped
    leading dims), with and without a precomputed L."""
    rng = np.random.default_rng(4)
    K, q, hi = _rand_box_qp(rng, 16, batch=(3, 2))
    L = qp_lib.gershgorin_lipschitz(K)
    out = {}
    for name in qp_engines.names():
        out[name] = qp_engines.get(name)(K, q, hi, iters=1500, L=L)
        noL = qp_engines.get(name)(K, q, hi, iters=1500)
        np.testing.assert_array_equal(np.asarray(out[name]), np.asarray(noL))
    # pg and the fused kernel iterate the identical update
    np.testing.assert_allclose(np.asarray(out["pg"]),
                               np.asarray(out["pallas_fused"]),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(out["fista"]),
                               np.asarray(out["pg"]), atol=2e-3)


def test_qp_engines_pallas_interpret_mode(monkeypatch):
    """REPRO_USE_PALLAS=1 routes "pallas_fused" through the interpreted
    Pallas kernel; results must match the jnp-oracle route."""
    rng = np.random.default_rng(5)
    K, q, hi = _rand_box_qp(rng, 20, batch=(2,))
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    oracle = qp_engines.get("pallas_fused")(K, q, hi, iters=60)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    fused = qp_engines.get("pallas_fused")(K, q, hi, iters=60)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=3e-5, atol=3e-5)


def test_qp_solvers_accept_precomputed_L():
    """core.qp satellite: a supplied Gershgorin bound reproduces the
    internally-derived one bit-for-bit."""
    rng = np.random.default_rng(6)
    K, q, hi = _rand_box_qp(rng, 30)
    L = qp_lib.gershgorin_lipschitz(K)
    for solver in (qp_lib.solve_box_qp_pg, qp_lib.solve_box_qp_fista):
        np.testing.assert_array_equal(
            np.asarray(solver(K, q, hi, iters=200)),
            np.asarray(solver(K, q, hi, iters=200, L=L)))


def test_pallas_fused_end_to_end_matches_fista_risks():
    """SolverConfig(qp_solver="pallas_fused") runs the whole fit through
    kernels/qp_step.py's update and lands on the same classifier as the
    FISTA engine (fig2-style problem, float32 tolerance on risks)."""
    data, A = _make(V=6, T=2, n=12, seed=1, n_test=200)
    base = SolverConfig(C=0.01, iters=25, qp_iters=300)
    r_fista = DTSVM(base).fit(
        data["X"], data["y"], mask=data["mask"], adj=A).risks(
            data["X_test"], data["y_test"])
    r_fused = DTSVM(base.replace(qp_solver="pallas_fused")).fit(
        data["X"], data["y"], mask=data["mask"], adj=A).risks(
            data["X_test"], data["y_test"])
    np.testing.assert_allclose(np.asarray(r_fused), np.asarray(r_fista),
                               atol=0.02)


# ---------------------------------------------------------------------------
# incremental re-planning (the online Session path)
# ---------------------------------------------------------------------------
def test_replan_recomputes_only_touched_slices():
    V, T = 6, 3
    data, _ = _make(V, T)
    A = graph.ring(V)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    plan = engine.compile_problem(prob, qp_iters=40)
    # node 0 drops task 1: counts change at node 0 (T_v) and at its ring
    # neighbors 1 and V-1 (nbr of task 1) — nodes 2..V-2 keep their K.
    active = np.ones((V, T), np.float32)
    active[0, 1] = 0.0
    plan2 = plan.replan(active=active)
    n_new = plan2.stats["gram_slices_computed"] - \
        plan.stats["gram_slices_computed"]
    assert 0 < n_new < V * T, n_new
    assert plan2.stats["gram_slices_reused"] == V * T - n_new
    # and the incrementally-updated invariants == a from-scratch compile
    fresh = inv_lib.compute_invariants(plan2.prob)
    for a, b in zip(plan2.inv, fresh):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_replan_noop_reuses_everything():
    data, A = _make()
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    plan = engine.compile_problem(prob, qp_iters=40)
    plan2 = plan.replan(active=np.asarray(prob.active),
                        couple=np.asarray(prob.couple))
    assert plan2.stats["gram_slices_computed"] == \
        plan.stats["gram_slices_computed"]
    assert plan2.inv.K is plan.inv.K


def test_session_jit_path_respects_qp_solver():
    """jit=True must route cfg.qp_solver too — an unknown engine fails
    fast instead of silently running FISTA."""
    data, A = _make(V=4, T=2, n=6)
    sess = OnlineSession(data["X"], data["y"], mask=data["mask"], adj=A,
                         jit=True,
                         config=SolverConfig(qp_iters=20, qp_solver="nope"))
    with pytest.raises(ValueError, match="unknown QP engine"):
        sess.run(2)
    # and the fused engine produces the same classifier as eager mode
    cfg = SolverConfig(qp_iters=40, qp_solver="pallas_fused")
    a = OnlineSession(data["X"], data["y"], mask=data["mask"], adj=A,
                      config=cfg)
    b = OnlineSession(data["X"], data["y"], mask=data["mask"], adj=A,
                      jit=True, config=cfg)
    a.run(4)
    b.run(4)
    np.testing.assert_allclose(np.asarray(a.state.r), np.asarray(b.state.r),
                               atol=1e-5, rtol=1e-5)


def test_session_incremental_replan_bitwise_vs_fresh_stages():
    """A session driven through membership events (incremental replan)
    equals per-stage from-scratch compiles, bit for bit."""
    V, T = 6, 3
    n = np.full((V, T), 10, int)
    data = synthetic.make_multitask_data(V=V, T=T, p=10, n_train=n,
                                         n_test=100, seed=2)
    A = graph.make_graph("random", V, degree=0.7, seed=0)
    cfg = SolverConfig(C=0.01, eps2=100.0, qp_iters=40)

    sess = OnlineSession(data["X"], data["y"], mask=data["mask"], adj=A,
                         config=cfg)
    state, active, couple = None, np.ones((V, T), np.float32), \
        np.ones(V, np.float32)            # the session's default masks
    schedule = [lambda: sess.drop_task(1),
                lambda: sess.set_coupling(True),
                lambda: sess.add_task(1, nodes=[0, 1, 2])]
    # stage 0 + three event-driven stages
    sess.run(6)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A,
                             C=0.01, eps2=100.0, active=active,
                             couple=couple)
    state, _ = engine.compile_problem(prob, cfg).run(state=state, iters=6)
    for ev in schedule:
        ev()
        sess.run(6)
        prob = core.make_problem(data["X"], data["y"], data["mask"], A,
                                 C=0.01, eps2=100.0, active=sess.active,
                                 couple=sess.couple)
        state, _ = engine.compile_problem(prob, cfg).run(state=state, iters=6)
    _assert_states_equal(sess.state, state)
    stats = sess.plan_stats
    assert stats["replans"] == 3
    assert stats["gram_slices_reused"] > 0


# ---------------------------------------------------------------------------
# vectorized CSVM (satellite)
# ---------------------------------------------------------------------------
def test_csvm_fit_tasks_matches_per_task_loop_bitwise():
    data, _ = _make(V=5, T=3, n=8, seed=3)
    X = np.asarray(data["X"], np.float32)
    y = np.asarray(data["y"], np.float32)
    mask = np.asarray(data["mask"], np.float32)
    V, T, N, p = X.shape
    w_v, b_v = csvm_lib.csvm_fit_tasks(
        jnp.asarray(X.transpose(1, 0, 2, 3).reshape(T, V * N, p)),
        jnp.asarray(y.transpose(1, 0, 2).reshape(T, V * N)), 0.01,
        jnp.asarray(mask.transpose(1, 0, 2).reshape(T, V * N)),
        qp_iters=200)
    for t in range(T):
        w, b = csvm_lib.csvm_fit(
            jnp.asarray(X[:, t].reshape(-1, p)),
            jnp.asarray(y[:, t].reshape(-1)), 0.01,
            jnp.asarray(mask[:, t].reshape(-1)), qp_iters=200)
        np.testing.assert_array_equal(np.asarray(w), np.asarray(w_v[t]))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(b_v[t]))


def test_csvm_solver_single_dispatch(monkeypatch):
    """CSVM.fit solves all T tasks in ONE vmapped dispatch: the Gram
    kernel is entered once, not once per task."""
    calls = {"n": 0}
    real = kops.weighted_gram

    def counting(Z, a, **kw):
        calls["n"] += 1
        return real(Z, a, **kw)

    monkeypatch.setattr(kops, "weighted_gram", counting)
    data, _ = _make(V=4, T=3, n=8)
    CSVM(SolverConfig(C=0.01, qp_iters=100)).fit(
        data["X"], data["y"], mask=data["mask"])
    assert calls["n"] == 1, calls["n"]


# ---------------------------------------------------------------------------
# batched gamma through kernels.ops (the fused engine's step sizes)
# ---------------------------------------------------------------------------
def test_qp_pg_step_batched_gamma(monkeypatch):
    rng = np.random.default_rng(7)
    K, q, hi = _rand_box_qp(rng, 12, batch=(2, 2))
    lam = jnp.asarray(rng.uniform(0, 0.3, size=(2, 2, 12)).astype(np.float32))
    gamma = jnp.asarray(rng.uniform(0.05, 0.2, size=(2, 2)).astype(np.float32))
    want = np.stack([np.stack([
        np.asarray(ref.qp_pg_step(lam[i, j], K[i, j], q[i, j], hi[i, j],
                                  float(gamma[i, j])))
        for j in range(2)]) for i in range(2)])
    got = np.asarray(kops.qp_pg_step(lam, K, q, hi, gamma))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    got_pallas = np.asarray(kops.qp_pg_step(lam, K, q, hi, gamma))
    np.testing.assert_allclose(got_pallas, want, rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# fused multi-iteration engine + QP operating modes (precision/operator)
# ---------------------------------------------------------------------------
def test_multi_engine_registered_with_capabilities():
    assert "pallas_fused_multi" in qp_engines.names()
    eng = qp_engines.get("pallas_fused_multi")
    assert getattr(eng, "supports_precision", False)
    assert getattr(eng, "supports_fold", False)
    # the legacy engines advertise neither capability
    for name in ("fista", "pg", "pallas_fused"):
        legacy = qp_engines.get(name)
        assert not getattr(legacy, "supports_precision", False)
        assert not getattr(legacy, "supports_fold", False)


def test_multi_engine_bitwise_vs_iterated_on_oracle_path(monkeypatch):
    """The per-dispatch-path bitwise contract: on the jnp-oracle path
    the multi engine IS clip + fori of the single fused step, so its
    f32 answer equals iterating "pallas_fused" bit for bit — including
    from out-of-box random warm starts (the satellite-1 bug class)."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    rng = np.random.default_rng(8)
    for seed in range(6):
        r = np.random.default_rng(seed)
        K, q, hi = _rand_box_qp(r, 16, batch=(2,))
        lam0 = jnp.asarray(
            r.uniform(-1.0, 2.0, size=(2, 16)).astype(np.float32)) * hi
        a = qp_engines.get("pallas_fused")(K, q, hi, lam0, iters=9)
        b = qp_engines.get("pallas_fused_multi")(K, q, hi, lam0, iters=9)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # pg iterates the identical update through a different program
        # shape (vmap of fori vs fori of batched step): allclose only
        c = qp_engines.get("pg")(K, q, hi, lam0, iters=9)
        np.testing.assert_allclose(np.asarray(c), np.asarray(b),
                                   rtol=3e-5, atol=3e-5)
    del rng


def test_multi_engine_interpret_mode_matches_oracle(monkeypatch):
    """REPRO_USE_PALLAS=1 routes the multi engine through the fused
    interpret-mode kernel (one launch per solve)."""
    rng = np.random.default_rng(9)
    K, q, hi = _rand_box_qp(rng, 20, batch=(2,))
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    oracle = qp_engines.get("pallas_fused_multi")(K, q, hi, iters=15)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    fused = qp_engines.get("pallas_fused_multi")(K, q, hi, iters=15)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=3e-5, atol=3e-5)


def test_multi_engine_converges_to_qp_optimum():
    rng = np.random.default_rng(10)
    K, q, hi = _rand_box_qp(rng, 24)
    lam = qp_engines.get("pallas_fused_multi")(K, q, hi, iters=3000)
    want = brute_force_box_qp(np.asarray(K), np.asarray(q), np.asarray(hi))
    np.testing.assert_allclose(np.asarray(lam), want, atol=5e-4)


def test_multi_fit_bitwise_vs_pallas_fused_fit(monkeypatch):
    """SolverConfig(qp_solver="pallas_fused_multi") must land on the
    IDENTICAL state as "pallas_fused": inside one jitted plan the f32
    oracle bodies trace to the same jaxpr (clip + fori of the fused
    step + the same zl contraction), so the whole fit is bitwise.
    Pinned to the oracle dispatch path — bitwise is a per-path
    contract; the interpret/compiled kernels are separate programs and
    match to compiler-contraction tolerance only."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    data, A = _make(V=4, T=2, n=8, seed=2)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    st_a, _ = engine.compile_problem(
        prob, qp_iters=30, qp_solver="pallas_fused").run(iters=8)
    st_b, _ = engine.compile_problem(
        prob, qp_iters=30, qp_solver="pallas_fused_multi").run(iters=8)
    _assert_states_equal(st_a, st_b)


def test_compile_problem_validates_qp_modes():
    data, A = _make(V=3, T=1)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A)
    with pytest.raises(ValueError, match="qp_precision"):
        engine.compile_problem(prob, qp_precision="f16")
    with pytest.raises(ValueError, match="qp_operator"):
        engine.compile_problem(prob, qp_operator="sparse")
    # bf16 needs a precision-capable engine; factored needs fold + f32
    with pytest.raises(ValueError, match="precision"):
        engine.compile_problem(prob, qp_solver="fista",
                               qp_precision="bf16")
    with pytest.raises(ValueError, match="factored"):
        engine.compile_problem(prob, qp_solver="pg",
                               qp_operator="factored")
    with pytest.raises(ValueError, match="factored"):
        engine.compile_problem(prob, qp_solver="pallas_fused_multi",
                               qp_precision="bf16", qp_operator="factored")


@pytest.mark.parametrize("budget", [None, inv_lib.PlanBudget(max_elems=64)])
def test_factored_fista_matches_materialized_and_legacy(budget):
    """qp_solver="fista" with qp_operator="factored": K is never built,
    L is bitwise the materialized plan's (budgeted or not), and the
    state lands within float32 tolerance of the materialized FISTA fit
    and of the legacy ``dtsvm_step`` scan."""
    data, A = _make(V=4, T=2, n=12, seed=3)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    kw = dict(qp_iters=40, qp_solver="fista", budget=budget)
    plan_m = engine.compile_problem(prob, qp_operator="materialized", **kw)
    plan_f = engine.compile_problem(prob, qp_operator="factored", **kw)
    assert plan_f.inv.K is None and plan_m.inv.K is not None
    np.testing.assert_array_equal(np.asarray(plan_f.inv.L),
                                  np.asarray(plan_m.inv.L))
    st_m, _ = plan_m.run(iters=6)
    st_f, _ = plan_f.run(iters=6)
    _assert_states_close(st_f, st_m, rtol=2e-5)
    _assert_states_close(st_f, _legacy_run(prob, 6, 40), rtol=2e-5)


_FISTA = dict(qp_solver="fista", qp_precision="f32")


@pytest.mark.parametrize("kw,want", [
    (dict(x_shape=(20, 2, 40, 10), **_FISTA), "materialized"),    # paper
    (dict(x_shape=(4, 2, 12000, 784), **_FISTA), "factored"),     # large_n
    (dict(x_shape=(4, 2, 12000, 784), qp_solver="pallas_fused_multi",
          qp_precision="f32"), "factored"),
    (dict(x_shape=(4, 2, 12000, 6000), **_FISTA), "materialized"),  # N<2D
    (dict(x_shape=(4, 4, 5788, 47236), sparse_x=True, **_FISTA),
     "materialized"),                                             # rcv1
    (dict(x_shape=(4, 2, 12000, 784), sparse_x=True, **_FISTA),
     "materialized"),
    (dict(x_shape=(4, 2, 12000, 784), qp_solver="pg",
          qp_precision="f32"), "materialized"),
    (dict(x_shape=(4, 2, 12000, 784), qp_solver="pallas_fused_multi",
          qp_precision="bf16"), "materialized"),
    (dict(x_shape=(4, 2, 12000, 784), backend="shard_map", **_FISTA),
     "materialized"),
    (dict(x_shape=(4, 2, 12000, 784), backend="async", **_FISTA),
     "materialized"),
    (dict(x_shape=(2, 2, 24000, 784), backend="sample_shard", **_FISTA),
     "materialized"),                                             # x4 cell
    (dict(x_shape=(20, 2, 40, 10), qp_operator="factored", **_FISTA),
     "factored"),
    (dict(x_shape=(4, 2, 12000, 784), qp_operator="materialized",
          **_FISTA), "materialized"),
])
def test_qp_operator_resolves_from_shape(kw, want):
    from repro.engine import plan as plan_lib
    kw = dict(kw)
    assert plan_lib.resolve_qp_operator(kw.pop("qp_operator", None),
                                        **kw) == want


def test_qp_operator_floor_sits_on_k_bytes():
    """Just below the floor a shape stays materialized, at it factored."""
    from repro.engine import plan as plan_lib
    floor = plan_lib.FACTORED_MIN_K_BYTES
    n = int(np.ceil(np.sqrt(floor / (4 * 8))))        # V*T = 8
    res = lambda N: plan_lib.resolve_qp_operator(
        None, x_shape=(4, 2, N, 15), **_FISTA)
    assert 4 * 8 * (n - 1) ** 2 < floor <= 4 * 8 * n * n
    assert (res(n - 1), res(n)) == ("materialized", "factored")


def test_factored_plans_counter_and_no_lowering_on_second_fit(monkeypatch):
    """plan.factored_plans rises once per factored plan, never for a
    materialized one; a second factored fit of the same shapes lowers
    nothing."""
    from repro import obs
    from repro.engine import plan as plan_lib
    data, A = _make(V=4, T=2, n=30, seed=5)          # N > 2 (p+1)
    data2, _ = _make(V=4, T=2, n=30, seed=6)
    read = lambda: obs.read_counters().get("plan.factored_plans", 0)
    cfg = SolverConfig(C=0.01, iters=3, qp_iters=10)
    n0 = read()
    DTSVM(cfg).fit(data["X"], data["y"], mask=data["mask"], adj=A)
    assert read() == n0                                 # materialized
    # the floor at this toy size: the same fit now resolves to factored
    monkeypatch.setattr(plan_lib, "FACTORED_MIN_K_BYTES", 1)
    fitted = DTSVM(cfg).fit(data["X"], data["y"], mask=data["mask"], adj=A)
    assert read() == n0 + 1
    obs.clear_compiles()
    DTSVM(cfg).fit(data2["X"], data2["y"], mask=data2["mask"], adj=A)
    assert read() == n0 + 2
    lowered = {prog: ph["lower"]["count"]
               for prog, ph in obs.compile_counts()["programs"].items()
               if "lower" in ph}
    assert lowered == {}
    explicit = DTSVM(cfg.replace(qp_operator="factored")).fit(
        data["X"], data["y"], mask=data["mask"], adj=A)
    assert read() == n0 + 3
    _assert_states_equal(fitted.state_, explicit.state_)


def test_backends_run_gates_qp_modes_to_vmap():
    data, A = _make(V=3, T=1)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A)
    from repro.api import backends
    with pytest.raises(ValueError, match="vmap"):
        backends.run(prob, 2, backend="shard_map",
                     qp_solver="pallas_fused_multi", qp_precision="bf16")


def test_factored_operator_skips_gram_and_matches_risks():
    """qp_operator="factored" never materializes K (inv.K is None), the
    streamed Lipschitz bound equals the dense Gershgorin bound bit for
    bit (row panels are bitwise rows of K), and the classifier lands
    within float tolerance of the materialized path."""
    data, A = _make(V=4, T=2, n=10, seed=4, n_test=150)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    plan_m = engine.compile_problem(prob, qp_iters=60,
                                    qp_solver="pallas_fused_multi")
    plan_f = engine.compile_problem(prob, qp_iters=60,
                                    qp_solver="pallas_fused_multi",
                                    qp_operator="factored")
    assert plan_f.inv.K is None and plan_m.inv.K is not None
    np.testing.assert_array_equal(np.asarray(plan_f.inv.L),
                                  np.asarray(plan_m.inv.L))
    st_m, _ = plan_m.run(iters=12)
    st_f, _ = plan_f.run(iters=12)
    np.testing.assert_allclose(np.asarray(st_f.r), np.asarray(st_m.r),
                               rtol=2e-4, atol=2e-4)


def test_factored_fit_end_to_end_matches_fista_risks():
    data, A = _make(V=6, T=2, n=12, seed=1, n_test=200)
    base = SolverConfig(C=0.01, iters=25, qp_iters=300)
    r_fista = DTSVM(base).fit(
        data["X"], data["y"], mask=data["mask"], adj=A).risks(
            data["X_test"], data["y_test"])
    r_fact = DTSVM(base.replace(qp_solver="pallas_fused_multi",
                                qp_operator="factored")).fit(
        data["X"], data["y"], mask=data["mask"], adj=A).risks(
            data["X_test"], data["y_test"])
    np.testing.assert_allclose(np.asarray(r_fact), np.asarray(r_fista),
                               atol=0.02)


def test_bf16_fit_risk_delta_small():
    """The mixed-precision mode is validated by risk deltas (never
    bitwise): paper-style problem, bf16 Hessian tiles."""
    data, A = _make(V=4, T=2, n=12, seed=6, n_test=200)
    base = SolverConfig(C=0.01, iters=20, qp_iters=200,
                        qp_solver="pallas_fused_multi")
    r32 = DTSVM(base).fit(
        data["X"], data["y"], mask=data["mask"], adj=A).risks(
            data["X_test"], data["y_test"])
    r16 = DTSVM(base.replace(qp_precision="bf16")).fit(
        data["X"], data["y"], mask=data["mask"], adj=A).risks(
            data["X_test"], data["y_test"])
    assert float(np.max(np.abs(np.asarray(r16) - np.asarray(r32)))) < 0.05


def test_session_threads_qp_modes_through_plan_path():
    """OnlineSession with a non-default QP mode: jit=True falls back to
    the plan path (the legacy jitted loop only knows materialized f32)
    and both flavors land on the same factored classifier."""
    data, A = _make(V=4, T=2, n=6)
    cfg = SolverConfig(qp_iters=40, qp_solver="pallas_fused_multi",
                       qp_operator="factored")
    a = OnlineSession(data["X"], data["y"], mask=data["mask"], adj=A,
                      config=cfg)
    b = OnlineSession(data["X"], data["y"], mask=data["mask"], adj=A,
                      jit=True, config=cfg)
    a.run(4)
    b.run(4)
    _assert_states_equal(a.state, b.state)


def test_sweep_rejects_non_default_qp_modes():
    from repro.engine import sweep as sweep_lib
    data, A = _make(V=3, T=1)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A)
    cfgs = [SolverConfig(C=0.01, qp_solver="pallas_fused_multi",
                         qp_operator="factored"),
            SolverConfig(C=0.1, qp_solver="pallas_fused_multi",
                         qp_operator="factored")]
    with pytest.raises(ValueError, match="per-fit only"):
        sweep_lib.compile_sweep(prob, cfgs)


def test_plan_fingerprint_distinguishes_qp_modes():
    data, A = _make(V=3, T=1)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A)
    f = lambda **kw: engine.compile_problem(
        prob, qp_solver="pallas_fused_multi", **kw).fingerprint()
    prints = {f(), f(qp_precision="bf16"), f(qp_operator="factored")}
    assert len(prints) == 3
