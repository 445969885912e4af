"""Large-n scale path: budgeted (chunked/tiled) invariant builds and the
sample-sharded backend are BITWISE the dense plan at paper regimes.

The contract (API.md §scale): a ``PlanBudget`` changes how much memory
the K build holds live, never what it computes — streamed row panels,
explicit Pallas tilings, budgeted sweeps, budgeted incremental replans
and the ``sample_shard`` backend's gather mode all reproduce the dense
path bit for bit.  Runs under the default jnp path and under
``REPRO_USE_PALLAS=1`` (the CI pallas lane includes this file).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_with_devices
from repro import engine
from repro.api import OnlineSession, PlanBudget, SolverConfig, backends
from repro.core import dtsvm as core
from repro.core import graph
from repro.data import synthetic
from repro.engine import invariants as inv_lib
from repro.kernels import gram as gram_kernel
from repro.kernels import ops as kops
from repro.kernels import ref


def _make(V=4, T=2, n=24, p=10, seed=0, n_test=40):
    counts = np.full((V, T), n, int)
    data = synthetic.make_multitask_data(
        V=V, T=T, p=p, n_train=counts, n_test=n_test, relatedness=0.9,
        seed=seed)
    A = graph.make_graph("random", V, degree=0.8, seed=seed)
    prob = core.make_problem(data["X"], data["y"], data["mask"], A, C=0.01)
    return prob, data


def _assert_states_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# kernels: rectangular / tiled Gram blocks vs the dense oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,m,d", [(16, 8, 5), (64, 24, 11), (100, 100, 11),
                                   (40, 16, 33)])
def test_ref_gram_rows_is_row_slice_of_dense(n, m, d):
    rng = np.random.default_rng(0)
    Z = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    a = jnp.asarray(rng.uniform(0.1, 2.0, size=(d,)).astype(np.float32))
    dense = ref.weighted_gram(Z, a)
    rows = ref.weighted_gram_rows(Z[:m], a, Z)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(dense)[:m])


@pytest.mark.parametrize("tile", [(8, 128), (16, 128), (64, 128), (8, 256),
                                  (32, 384), (64, 64), (8, 8)])
@pytest.mark.parametrize("n", [64, 100, 256])
def test_tiled_pallas_kernel_bitwise_vs_square_kernel(tile, n):
    """Interpret mode: every (tile_m, tile_n) grid reproduces the square
    DEFAULT_BLOCK kernel bit for bit (the contraction order over the
    padded feature dim is tile-independent).

    Ragged ``n`` (not a multiple of 8) is the exception: the square
    kernel's block is then n rounded up to 8 (104 for n=100), the tiled
    kernel's the tile, and XLA's CPU dot under jax 0.9 rounds those two
    padded output shapes differently.  There the tiles are held to the
    f32 dot-product error bound instead, D * eps * (|Z| a |Z|^T)."""
    rng = np.random.default_rng(1)
    Z = jnp.asarray(rng.normal(size=(n, 11)).astype(np.float32))
    a = jnp.asarray(rng.uniform(0.1, 2.0, size=(11,)).astype(np.float32))
    dense = np.asarray(gram_kernel.weighted_gram_2d(Z, a, interpret=True))
    tiled = np.asarray(gram_kernel.weighted_gram_tiled(Z, a, Z, tile=tile,
                                                       interpret=True))
    if n % 8 == 0:
        np.testing.assert_array_equal(tiled, dense)
    else:
        absZ = np.abs(np.asarray(Z, np.float64))
        bound = Z.shape[1] * np.finfo(np.float32).eps * (
            (absZ * np.asarray(a, np.float64)) @ absZ.T)
        assert np.all(np.abs(tiled - dense) <= bound)


def test_tiled_pallas_row_panels_bitwise(monkeypatch):
    """A row-panel call under REPRO_USE_PALLAS=1 matches the rows of the
    square-kernel dense build exactly."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    rng = np.random.default_rng(2)
    Z = jnp.asarray(rng.normal(size=(3, 2, 64, 11)).astype(np.float32))
    a = jnp.asarray(rng.uniform(0.1, 2.0, size=(3, 2, 11)).astype(
        np.float32))
    dense = kops.weighted_gram(Z, a)
    rows = kops.weighted_gram_rows(Z[..., :24, :], a, Z, tile=(8, 128))
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.asarray(dense)[..., :24, :])


def test_align_tile():
    assert gram_kernel.align_tile((8, 128), 256, 256) == (8, 128)
    assert gram_kernel.align_tile((5, 100), 256, 256) == (8, 128)
    assert gram_kernel.align_tile((1000, 1000), 64, 64) == (64, 128)


# ---------------------------------------------------------------------------
# budgeted plan == dense plan, bitwise (invariants, states, histories)
# ---------------------------------------------------------------------------
def _budgets(prob):
    V, T, N, _ = prob.X.shape
    return [PlanBudget(max_elems=V * T * 8 * N),       # smallest chunks
            PlanBudget(max_elems=V * T * 16 * N),
            PlanBudget(tile=(8, 128)),                 # tile_m as chunk
            PlanBudget(max_elems=10 ** 12)]            # non-binding


@pytest.mark.parametrize("qp_solver", ["fista", "pallas_fused"])
def test_budgeted_plan_bitwise(qp_solver):
    prob, data = _make()
    dense = engine.compile_problem(prob, qp_iters=40, qp_solver=qp_solver)
    ev = lambda st: core.risks(  # noqa: E731
        st.r, jnp.broadcast_to(jnp.asarray(data["X_test"])[None],
                               (4,) + data["X_test"].shape),
        jnp.broadcast_to(jnp.asarray(data["y_test"])[None],
                         (4,) + data["y_test"].shape))
    st_d, hist_d = dense.run(iters=5, eval_fn=ev)
    for budget in _budgets(prob):
        plan = engine.compile_problem(prob, qp_iters=40,
                                      qp_solver=qp_solver, budget=budget)
        np.testing.assert_array_equal(np.asarray(plan.inv.K),
                                      np.asarray(dense.inv.K))
        np.testing.assert_array_equal(np.asarray(plan.inv.L),
                                      np.asarray(dense.inv.L))
        st_b, hist_b = plan.run(iters=5, eval_fn=ev)
        _assert_states_equal(st_d, st_b)
        np.testing.assert_array_equal(np.asarray(hist_d),
                                      np.asarray(hist_b))


def test_budget_via_solver_config():
    prob, _ = _make(V=3, T=2, n=16)
    cfg = SolverConfig(qp_iters=30,
                       budget=PlanBudget(max_elems=3 * 2 * 8 * 16))
    plan = engine.compile_problem(prob, cfg)
    assert plan.budget == cfg.budget
    dense = engine.compile_problem(prob, qp_iters=30)
    np.testing.assert_array_equal(np.asarray(plan.inv.K),
                                  np.asarray(dense.inv.K))


def test_budgeted_sweep_bitwise():
    prob, _ = _make(V=3, T=2, n=20)
    cfgs = [dict(C=c, eps2=e) for c in (0.01, 0.1) for e in (1.0, 10.0)]
    dense = engine.compile_sweep(prob, cfgs, qp_iters=30)
    budget = PlanBudget(max_elems=len(cfgs) * 3 * 2 * 8 * 20)
    budgeted = engine.compile_sweep(prob, cfgs, qp_iters=30, budget=budget)
    np.testing.assert_array_equal(np.asarray(dense.inv.K),
                                  np.asarray(budgeted.inv.K))
    np.testing.assert_array_equal(np.asarray(dense.inv.L),
                                  np.asarray(budgeted.inv.L))
    st_d, _ = dense.run(iters=4)
    st_b, _ = budgeted.run(iters=4)
    _assert_states_equal(st_d, st_b)


def test_budgeted_session_replan_bitwise():
    """A membership event on a budgeted session streams only the touched
    K slices — and stays bitwise the dense session, stage for stage."""
    prob, data = _make(V=4, T=2, n=16)
    kw = dict(mask=data["mask"], adj=prob.adj)
    budget = PlanBudget(max_elems=4 * 2 * 8 * 16)
    s_dense = OnlineSession(data["X"], data["y"], **kw,
                            config=SolverConfig(qp_iters=30))
    s_budget = OnlineSession(data["X"], data["y"], **kw,
                             config=SolverConfig(qp_iters=30, budget=budget))
    for sess in (s_dense, s_budget):
        sess.run(4)
        sess.drop_task(1, nodes=[0])     # localized: most slices reuse
        sess.run(3)
        sess.add_task(1, nodes=[0])
        sess.run(3)
    _assert_states_equal(s_dense.state, s_budget.state)
    assert s_budget.plan_stats["gram_slices_reused"] > 0
    assert s_budget.plan_stats == s_dense.plan_stats


def test_streamed_gram_panel_matches_dense_rows():
    rng = np.random.default_rng(3)
    Z = jnp.asarray(rng.normal(size=(2, 3, 50, 7)).astype(np.float32))
    a = jnp.asarray(rng.uniform(0.1, 2.0, size=(2, 3, 7)).astype(
        np.float32))
    dense = kops.weighted_gram(Z, a)
    # the dense Gershgorin ingredients, via the same XLA row reduction
    want_rs = jnp.sum(jnp.abs(dense), axis=-1)
    for chunk in (8, 16, 24, 48):
        K, rs = inv_lib.streamed_gram_panel(Z, a, Z, chunk)
        np.testing.assert_array_equal(np.asarray(K), np.asarray(dense))
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(want_rs))


def test_row_chunk_semantics():
    b = PlanBudget(max_elems=1000)
    assert b.row_chunk(1, 100) == 8          # floor 8
    assert b.row_chunk(1, 10) is None        # budget doesn't bind
    assert PlanBudget().row_chunk(4, 100) is None
    assert PlanBudget(tile=(32, 128)).row_chunk(4, 100) == 32
    assert PlanBudget(max_elems=10 ** 9).row_chunk(1, 100) is None
    # rectangular: chunk priced against the column count
    assert PlanBudget(max_elems=6400).row_chunk(1, 64, cols=800) == 8


# ---------------------------------------------------------------------------
# hypothesis: random problems x tile/chunk sizes stay bitwise
# ---------------------------------------------------------------------------
def test_budget_property_random_problems():
    hypothesis = pytest.importorskip("hypothesis")     # noqa: F841
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=12, deadline=None)
    @given(V=st.integers(2, 4), T=st.integers(1, 3), n=st.integers(5, 24),
           seed=st.integers(0, 10_000),
           chunk_rows=st.integers(1, 6),               # chunks of 8..48 rows
           tile_m=st.sampled_from([8, 16, 32, 64]),
           tile_n=st.sampled_from([128, 256, 384]),
           use_tile=st.booleans())
    def prop(V, T, n, seed, chunk_rows, tile_m, tile_n, use_tile):
        prob, _ = _make(V=V, T=T, n=n, seed=seed, n_test=8)
        if use_tile:
            budget = PlanBudget(max_elems=V * T * 8 * chunk_rows * n,
                                tile=(tile_m, tile_n))
        else:
            budget = PlanBudget(max_elems=V * T * 8 * chunk_rows * n)
        dense = engine.compile_problem(prob, qp_iters=25)
        budgeted = engine.compile_problem(prob, qp_iters=25, budget=budget)
        np.testing.assert_array_equal(np.asarray(dense.inv.K),
                                      np.asarray(budgeted.inv.K))
        np.testing.assert_array_equal(np.asarray(dense.inv.L),
                                      np.asarray(budgeted.inv.L))
        st_d, _ = dense.run(iters=3)
        st_b, _ = budgeted.run(iters=3)
        _assert_states_equal(st_d, st_b)

    prop()


# ---------------------------------------------------------------------------
# sample-sharded backend (subprocess: forced host devices)
# ---------------------------------------------------------------------------
def test_sample_shard_bitwise_vs_vmap():
    """Gather mode: the sample-sharded fit IS the vmap fit, bit for bit
    (states and histories), including a budgeted in-shard panel build."""
    out = run_with_devices("""
        import os
        os.environ["REPRO_USE_PALLAS"] = "0"   # interpret-mode Pallas inside
        import numpy as np, jax                # shard_map is not under test
        from repro.api import PlanBudget, backends, evaluate
        from repro.core import dtsvm as core, graph
        from repro.data import synthetic
        V, T, N = 3, 2, 64
        n = np.full((V, T), N, int)
        data = synthetic.make_multitask_data(V=V, T=T, p=10, n_train=n,
                                             n_test=32, seed=0)
        A = graph.make_graph("random", V, degree=0.8, seed=0)
        prob = core.make_problem(data["X"], data["y"], data["mask"], A)
        ev = evaluate.risk_eval_fn(V, data["X_test"], data["y_test"])
        for qp_solver in ("fista", "pg"):
            st_v, h_v = backends.run(prob, 5, backend="vmap", qp_iters=50,
                                     qp_solver=qp_solver, eval_fn=ev)
            st_s, h_s = backends.run(prob, 5, backend="sample_shard",
                                     qp_iters=50, qp_solver=qp_solver,
                                     n_shards=4, eval_fn=ev)
            for a, b in zip(jax.tree.leaves(st_v), jax.tree.leaves(st_s)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(h_v), np.asarray(h_s))
        # budgeted in-shard panel build
        st_b, _ = backends.run(prob, 5, backend="sample_shard", qp_iters=50,
                               n_shards=2,
                               budget=PlanBudget(max_elems=V * T * 8 * N))
        st_v, _ = backends.run(prob, 5, backend="vmap", qp_iters=50)
        for a, b in zip(jax.tree.leaves(st_v), jax.tree.leaves(st_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # psum mode: the cheap reduction is equivalent, not bitwise
        st_p, _ = backends.run(prob, 5, backend="sample_shard", qp_iters=50,
                               n_shards=4, reduce="psum")
        for a, b in zip(jax.tree.leaves(st_v), jax.tree.leaves(st_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)
        print("SAMPLE_SHARD_OK")
    """, n_devices=4)
    assert "SAMPLE_SHARD_OK" in out


def test_sample_shard_validation():
    prob, _ = _make(V=3, T=1, n=8)
    with pytest.raises(ValueError, match="fista.*pg"):
        backends.run(prob, 1, backend="sample_shard",
                     qp_solver="pallas_fused")
    with pytest.raises(ValueError, match="reduce"):
        backends.run(prob, 1, backend="sample_shard", reduce="nope")


def test_sample_shard_single_device_matches_vmap():
    """n_shards=1 degenerates to the dense math on one device — bitwise
    vmap without needing forced host devices."""
    prob, _ = _make(V=3, T=2, n=16)
    st_v, _ = backends.run(prob, 4, backend="vmap", qp_iters=40)
    st_s, _ = backends.run(prob, 4, backend="sample_shard", qp_iters=40,
                           n_shards=1)
    _assert_states_equal(st_v, st_s)
