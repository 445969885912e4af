"""Sparse features (``core.sparse.SparseRows``) through the vmap fit.

A sparse fit solves the same Prop.-1 problem as the dense fit of the
densified X.  It adds in another order: K is summed feature panel by
feature panel, and ``Z g`` / ``Z^T lam`` are segment sums over the
nonzeros where the dense fit runs einsums.  Each contraction then
differs from the dense one by float32 rounding (about 1e-7 relative),
and five ADMM x thirty FISTA steps grow that to a few 1e-6 of a
leaf's largest value.  So every state leaf is held to 2e-5 of its
largest value: ten times what the order of addition gives here, and
well under the 8e-5 a three-bfloat16-pass reference reads on such
data (tests/bench/test_bench_sparse.py)."""
import numpy as np
import pytest

from repro import api, engine, obs
from repro.core import dtsvm as core
from repro.core import sparse
from repro.engine import invariants as inv_lib
from repro.kernels import ref

V, T, N, P, NNZ = 3, 2, 40, 300, 6
TOL = 2e-5
LEAVES = ("r", "alpha", "beta", "lam")


def _data(seed=0):
    """About NNZ nonzeros a row, the last three rows of each block
    padding (masked, empty), a connected three-node graph."""
    rng = np.random.default_rng(seed)
    X = np.zeros((V, T, N, P), np.float32)
    for v in range(V):
        for t in range(T):
            for n in range(N - 3):
                c = rng.choice(P, rng.integers(1, 2 * NNZ), replace=False)
                X[v, t, n, c] = rng.random(len(c)) + 0.1
    y = np.where(rng.random((V, T, N)) < 0.4, 1.0, -1.0).astype(np.float32)
    mask = np.ones((V, T, N), np.float32)
    y[..., -3:] = 0.0
    mask[..., -3:] = 0.0
    adj = np.array([[0, 1, 1], [1, 0, 0], [1, 0, 0]], bool)
    return X, y, mask, adj


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


CFG = api.SolverConfig(iters=5, qp_iters=30)


def test_container_round_trip_and_shape():
    X = _data()[0]
    Xs = sparse.SparseRows.from_dense(X)
    assert Xs.shape == X.shape
    assert Xs.stored == V * T * Xs.vals.shape[-1]
    np.testing.assert_array_equal(np.asarray(Xs.todense()), X)
    import jax
    leaves, tree = jax.tree_util.tree_flatten(Xs)
    assert len(leaves) == 3
    back = jax.tree_util.tree_unflatten(tree, leaves)
    assert back.shape == X.shape


def test_products_match_dense():
    X, y, mask, _ = _data(1)
    Xs = sparse.SparseRows.from_dense(X)
    Z = sparse.compute_z(Xs, y, mask)
    Zd = inv_lib.compute_z(core.make_problem(X, y, mask))
    assert Z.shape == Zd.shape
    rng = np.random.default_rng(2)
    g = rng.standard_normal((V, T, P + 1)).astype(np.float32)
    lam = rng.random((V, T, N)).astype(np.float32)
    np.testing.assert_allclose(sparse.z_dot(Z, g),
                               np.einsum("vtnd,vtd->vtn", Zd, g),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sparse.zt_dot(Z, lam),
                               np.einsum("vtn,vtnd->vtd", lam, Zd),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pallas", ["0", "1"])
def test_panel_gram_matches_dense_gram(pallas, monkeypatch):
    """K and L from the panel build against the dense Gram; with
    ``REPRO_USE_PALLAS=1`` the panels go through the interpreted
    Pallas kernel."""
    monkeypatch.setenv("REPRO_USE_PALLAS", pallas)
    X, y, mask, adj = _data(3)
    prob = core.make_problem(X, y, mask, adj)
    a = inv_lib._masks_part(prob)[3]
    Z = sparse.compute_z(sparse.SparseRows.from_dense(X), y, mask)
    K, L = sparse.gram_and_lipschitz(Z, a)
    Kd = ref.weighted_gram(inv_lib.compute_z(prob), a)
    assert _rel(K, Kd) < 1e-6
    Ld = np.abs(np.asarray(Kd)).sum(-1).max(-1)
    assert _rel(L, Ld) < 1e-6


def test_panels_split_the_features(monkeypatch):
    """A narrow panel (the build's loop over several panels, each
    densified over several chunks) builds the same K."""
    monkeypatch.setattr(sparse, "PANEL", 128)
    monkeypatch.setattr(sparse, "_CHUNK", 64)
    X, y, mask, adj = _data(4)
    prob = core.make_problem(X, y, mask, adj)
    a = inv_lib._masks_part(prob)[3]
    Z = sparse.compute_z(sparse.SparseRows.from_dense(X), y, mask)
    assert sparse.panel_width(P) == 128
    K, _ = sparse.gram_and_lipschitz(Z, a)
    assert _rel(K, ref.weighted_gram(inv_lib.compute_z(prob), a)) < 1e-6


@pytest.mark.parametrize("solver", [api.DTSVM, api.DSVM])
def test_sparse_fit_matches_dense_fit(solver):
    X, y, mask, adj = _data()
    dense = solver(CFG).fit(X, y, mask=mask, adj=adj).state_
    Xs = sparse.SparseRows.from_dense(X)
    got = solver(CFG).fit(Xs, y, mask=mask, adj=adj).state_
    for k in LEAVES:
        assert _rel(getattr(got, k), getattr(dense, k)) < TOL, k


def test_sparse_fit_matches_dense_oracle():
    """Against the plain float32 reference: the densified problem
    through the legacy ``dtsvm_step`` (``core.qp``'s FISTA)."""
    X, y, mask, adj = _data(5)
    Xs = sparse.SparseRows.from_dense(X)
    solver = api.DTSVM(CFG)
    got = solver.fit(Xs, y, mask=mask, adj=adj).state_
    want = sparse.dense_oracle_fit(solver.problem_, CFG.iters, CFG.qp_iters)
    for k in LEAVES:
        assert _rel(getattr(got, k), getattr(want, k)) < TOL, k


def test_sparse_fit_with_risk_history():
    X, y, mask, adj = _data(6)
    Xs = sparse.SparseRows.from_dense(X)
    Xt = X[0, :, :8]
    yt = np.where(y[0, :, :8] == 0, 1.0, y[0, :, :8])
    s = api.DTSVM(CFG).fit(Xs, y, mask=mask, adj=adj, X_test=Xt,
                           y_test=yt)
    d = api.DTSVM(CFG).fit(X, y, mask=mask, adj=adj, X_test=Xt, y_test=yt)
    assert s.history_.shape == (CFG.iters, V, T)
    np.testing.assert_allclose(s.history_, d.history_, atol=1 / 8 + 1e-6)


def test_second_sparse_fit_lowers_nothing():
    X, y, mask, adj = _data(7)
    Xs = sparse.SparseRows.from_dense(X)
    api.DTSVM(CFG).fit(Xs, y, mask=mask, adj=adj)
    X2 = _data(8)[0]
    Xs2 = sparse.SparseRows.from_dense(X2)
    cap = max(Xs.vals.shape[-1], Xs2.vals.shape[-1])
    pad = lambda S: sparse.SparseRows(
        *(np.pad(np.asarray(x), ((0, 0), (0, 0),
                                 (0, cap - x.shape[-1])))
          for x in (S.rows, S.cols, S.vals)), N, P)
    Xs, Xs2 = pad(Xs), pad(Xs2)
    api.DTSVM(CFG).fit(Xs, y, mask=mask, adj=adj)
    obs.clear_compiles()
    api.DTSVM(CFG).fit(Xs2, y, mask=mask, adj=adj)   # same shapes
    lowered = {prog: ph["lower"]["count"]
               for prog, ph in obs.compile_counts()["programs"].items()
               if "lower" in ph}
    assert lowered == {}


def test_counters_per_build():
    X, y, mask, adj = _data(9)
    Xs = sparse.SparseRows.from_dense(X)
    before = obs.read_counters()
    api.DTSVM(CFG).fit(Xs, y, mask=mask, adj=adj)
    after = obs.read_counters()
    # P=300 rounds up to one 384-column panel of every (N, .) block
    assert sparse.panel_width(P) == 384
    dense = after["build.densified_bytes"] - before.get(
        "build.densified_bytes", 0)
    assert dense == 4 * V * T * N * 384
    stored = after["build.stored_nonzeros"] - before.get(
        "build.stored_nonzeros", 0)
    assert stored == Xs.stored == V * T * Xs.vals.shape[-1]
    n0 = obs.read_counters()
    api.DTSVM(CFG).fit(X, y, mask=mask, adj=adj)    # dense: no count
    assert obs.read_counters() == n0


def test_rcv1_densified_bytes():
    """The rcv1_topics deployment: 47 panels of 1024 terms over 16
    blocks of 5788 rows, 17.8 GB written dense a build."""
    shape = (4, 4, 5788, 47236)
    assert sparse.panel_width(47236) == 1024
    assert sparse.densified_bytes(shape) == 4 * 16 * 5788 * 1024 * 47


@pytest.mark.parametrize("backend,opts", [
    ("shard_map", {}), ("async", {}),
    ("sample_shard", {"backend_options": {"n_shards": 1}}),
])
def test_other_backends_refuse_sparse(backend, opts):
    X, y, mask, adj = _data()
    Xs = sparse.SparseRows.from_dense(X)
    with pytest.raises(ValueError, match="sparse"):
        api.DTSVM(CFG.replace(backend=backend, **opts)).fit(
            Xs, y, mask=mask, adj=adj)


@pytest.mark.parametrize("kw", [
    {"qp_operator": "factored", "qp_solver": "pallas_fused_multi"},
    {"qp_precision": "bf16", "qp_solver": "pallas_fused_multi"},
    {"qp_solver": "pallas_fused_multi"},
])
def test_other_qp_modes_refuse_sparse(kw):
    X, y, mask, adj = _data()
    Xs = sparse.SparseRows.from_dense(X)
    with pytest.raises(ValueError, match="sparse"):
        api.DTSVM(CFG.replace(**kw)).fit(Xs, y, mask=mask, adj=adj)


def test_sweeps_and_sessions_refuse_sparse():
    X, y, mask, adj = _data()
    Xs = sparse.SparseRows.from_dense(X)
    with pytest.raises(ValueError, match="sparse"):
        api.sweep_fit(Xs, y, [{"C": 0.01}, {"C": 0.1}], mask=mask, adj=adj)
    with pytest.raises(ValueError, match="sparse"):
        api.OnlineSession(Xs, y, mask, adj, config=CFG)
    plan = engine.compile_problem(core.make_problem(Xs, y, mask, adj), CFG)
    with pytest.raises(ValueError, match="sparse"):
        plan.replan(active=np.ones((V, T), np.float32))
