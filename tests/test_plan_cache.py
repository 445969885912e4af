"""The fit's programs are compiled once per shape, not once per fit.

``Plan.run``'s scan is the module-level jit ``engine.plan._plan_scan``
(the ``sample_shard`` backend's are ``api.backends._panel_build`` and
``_panel_scan_jit``) and the Pallas branch of ``kernels.ops.weighted_gram`` maps over its
blocks inside the module-level jit ``kernels.ops._gram_map``.  Both are
fed their arrays as arguments, so a second fit of the same shapes and
QP configuration, on new data, lowers nothing; a changed shape or
configuration lowers its own program.  Counts come from
``obs.compile_counts()`` (JAX's own compile events); every test starts
with both programs cold (``conftest._cold_fit_programs``)."""
import jax
import numpy as np
import pytest

from repro import api, obs
from repro.analysis.jaxpr_audit import jit_cache_size
from repro.core import graph
from repro.data import synthetic
from repro.engine import plan as engine_plan
from repro.kernels import ops as kops
from repro.kernels import ref

V, T, N, P = 2, 2, 8, 4
SCAN = "jit(_plan_scan)"
GRAM_MAP = "jit(_gram_map)"
PANEL_SCAN = "jit(_panel_scan)"


def _data(seed=0, n=N):
    data = synthetic.make_multitask_data(
        V=V, T=T, p=P, n_train=np.full((V, T), n, int), n_test=4,
        relatedness=0.9, seed=seed)
    adj = graph.make_graph("ring", V, seed=0)
    return data["X"], data["y"], data["mask"], adj


def _lowerings(program):
    row = obs.compile_counts()["programs"].get(program, {})
    return row.get("lower", {"count": 0})["count"]


def _first_fit(**kw):
    """The first fit of its shape: one on seed 0, counted from zero."""
    obs.clear_compiles()
    api.DTSVM(**kw).fit(*_data(0))


@pytest.mark.parametrize("backend,program", [
    ("vmap", SCAN),
    ("sample_shard", PANEL_SCAN),        # one device: n_shards=1
])
def test_second_fit_of_a_shape_lowers_nothing(backend, program):
    kw = dict(iters=3, qp_iters=2, backend=backend)
    if backend == "sample_shard":
        kw["backend_options"] = {"n_shards": 1}
    _first_fit(**kw)
    assert _lowerings(program) == 1
    obs.clear_compiles()
    api.DTSVM(**kw).fit(*_data(1))             # same shapes, new data
    lowered = {prog: ph["lower"]["count"]
               for prog, ph in obs.compile_counts()["programs"].items()
               if "lower" in ph}
    assert lowered == {}


@pytest.mark.parametrize("change", [
    {"n": N + 8},
    {"iters": 4},
    {"qp_solver": "pg"},
], ids=["N", "iters", "qp_solver"])
def test_changed_shape_or_config_lowers_a_new_program(change):
    base = dict(iters=3, qp_iters=2, qp_solver="fista")
    _first_fit(**base)
    obs.clear_compiles()
    n = change.pop("n", N)
    api.DTSVM(**dict(base, **change)).fit(*_data(1, n=n))
    assert _lowerings(SCAN) == 1


def test_second_fit_is_bitwise_the_eager_scan():
    """The cached program computes what an eager scan of ``plan_step``
    over the same plan computes, bit for bit."""
    kw = dict(iters=3, qp_iters=2)
    _first_fit(**kw)
    solver = api.DTSVM(**kw).fit(*_data(1))
    assert _lowerings(SCAN) == 1                 # the first fit's only
    plan = engine_plan.compile_problem(solver.problem_, solver.config)

    def body(st, _):
        return engine_plan.plan_step(plan.prob, plan.inv, st,
                                     qp_iters=2), None

    eager, _ = jax.lax.scan(body, plan.init_state(), None, length=3)
    for a, b in zip(jax.tree.leaves(solver.state_),
                    jax.tree.leaves(eager)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_session_stages_reuse_the_scan():
    """A replanned stage keeps every shape, so it dispatches the
    compiled scan of the first stage."""
    X, y, mask, adj = _data()
    sess = api.OnlineSession(X, y, mask, adj,
                             active=np.array([[1, 0], [1, 1]], np.float32),
                             iters=2, qp_iters=2)
    sess.run(2)
    obs.clear_compiles()
    sess.add_task(1)
    sess.run(2)
    assert _lowerings(SCAN) == 0
    assert sess.plan_stats["replans"] == 1


def test_eval_fn_keeps_the_eager_scan():
    """A hook callable is not a cache key: the fit with a test-set risk
    curve scans eagerly and leaves the cached program alone."""
    d = synthetic.make_multitask_data(
        V=V, T=T, p=P, n_train=np.full((V, T), N, int), n_test=4,
        relatedness=0.9, seed=0)
    s = api.DTSVM(iters=3, qp_iters=2).fit(
        d["X"], d["y"], d["mask"], graph.make_graph("ring", V, seed=0),
        X_test=d["X_test"], y_test=d["y_test"])
    assert s.history_.shape[0] == 3
    assert jit_cache_size(engine_plan._plan_scan) == 0


@pytest.mark.parametrize("tile", [None, (8, 128)], ids=["square", "tiled"])
def test_pallas_gram_map_lowers_once(monkeypatch, tile):
    """Under the interpreted Pallas kernels, two batched Gram builds of
    one shape lower the map once; a ``reference_kernels()`` block after
    them still runs the oracle."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    rng = np.random.default_rng(0)
    shape_z, shape_a = (V, T, N, P + 1), (V, T, P + 1)
    obs.clear_compiles()
    outs = []
    for _ in range(2):
        Z = rng.standard_normal(shape_z).astype(np.float32)
        a = rng.uniform(0.5, 1.5, shape_a).astype(np.float32)
        outs.append((Z, a, kops.weighted_gram(Z, a, tile=tile)))
    assert _lowerings(GRAM_MAP) == 1
    for Z, a, K in outs:
        np.testing.assert_allclose(np.asarray(K),
                                   np.asarray(ref.weighted_gram(Z, a)),
                                   rtol=1e-5, atol=1e-5)
    Z, a, _ = outs[-1]
    with kops.reference_kernels():
        K = kops.weighted_gram(Z, a, tile=tile)
    np.testing.assert_array_equal(np.asarray(K),
                                  np.asarray(ref.weighted_gram(Z, a)))
    assert _lowerings(GRAM_MAP) == 1
