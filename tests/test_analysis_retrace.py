"""Compile-once invariants as exact-count regression tests.

The counters come from ``repro.analysis.jaxpr_audit``: a python
function's body runs once per JAX trace, so entry counts of patched
module attributes are trace counts; ``jit_cache_size`` counts compiled
variants of a jitted function.  Each test pins the EXACT number the
architecture promises — a regression here means an accidental retrace
or invariant rebuild, the class of bug the plan/sweep/serve layers
were built to make impossible."""
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.analysis.jaxpr_audit import jit_cache_size, trace_counter
from repro.core import graph
from repro.data import synthetic

V, T, N, P = 2, 2, 8, 4


def _data():
    data = synthetic.make_multitask_data(
        V=V, T=T, p=P, n_train=np.full((V, T), N, int), n_test=4,
        relatedness=0.9, seed=0)
    adj = graph.make_graph("ring", V, seed=0)
    return data["X"], data["y"], data["mask"], adj


def test_fit_builds_invariants_once_and_traces_step_once():
    """One Gram build per fit; the scan is a cached program, so the
    first fit of a shape traces the step once and the next fit of the
    same shape (new data) not at all."""
    X, y, mask, adj = _data()
    with trace_counter("repro.kernels.ops:weighted_gram",
                       "repro.engine.plan:plan_step") as c:
        api.DTSVM(iters=3, qp_iters=2).fit(X, y, mask, adj)
        assert c["weighted_gram"] == 1     # one invariant build per fit
        assert c["plan_step"] == 1         # one trace for the whole scan
        api.DTSVM(iters=3, qp_iters=2).fit(X * 2.0, y, mask, adj)
    assert c["weighted_gram"] == 2
    assert c["plan_step"] == 1             # the second fit traces nothing


def test_sweep_fit_is_one_trace_for_all_configs():
    """The sweep's batched step traces ONCE for the whole config grid
    (the stacked-axis design), and shares the single Gram build."""
    X, y, mask, adj = _data()
    cfgs = [{"C": 0.01}, {"C": 0.1}, {"C": 1.0}]
    with trace_counter("repro.kernels.ops:weighted_gram",
                       "repro.engine.sweep:plan_step") as c:
        api.sweep_fit(X, y, cfgs, mask, adj, iters=3,
                      base=api.SolverConfig(qp_iters=2))
    assert c["weighted_gram"] == 1
    assert c["plan_step"] == 1


def test_session_add_task_replans_incrementally():
    """A membership event must NOT rebuild the plan from scratch: the
    replan enters the Gram kernel exactly once more (for the touched
    slices only) and the stats account for every reused slice."""
    X, y, mask, adj = _data()
    active0 = np.array([[1, 0], [1, 1]], np.float32)
    with trace_counter("repro.kernels.ops:weighted_gram") as c:
        sess = api.OnlineSession(X, y, mask, adj, active=active0,
                                 iters=2, qp_iters=2)
        sess.run(2)
        assert c["weighted_gram"] == 1
        assert sess.plan_stats == {"gram_slices_computed": V * T,
                                   "gram_slices_reused": 0,
                                   "replans": 0}
        sess.add_task(1)
        sess.run(2)
        assert c["weighted_gram"] == 2     # one incremental rebuild
    # activating task 1 touches 3 of the 4 (v,t) weight rows (the new
    # slice plus the ntp-renormalized ones); the untouched slice is
    # carried over bit-for-bit
    assert sess.plan_stats == {"gram_slices_computed": V * T + 3,
                               "gram_slices_reused": 1,
                               "replans": 1}


def test_serve_gemm_compiles_once_per_bucket():
    """PredictServer's GEMM compiles once per padded row bucket: a
    repeat bucket adds zero compiled variants, a new bucket exactly
    one.  (p=7 keeps these signatures private to this test.)"""
    from repro.serve import model as serve_model

    p = 7
    model = serve_model.PredictModel.from_r(
        jnp.zeros((V, T, 2 * p + 2), jnp.float32))
    model.decide_rows(jnp.ones((3, p)))          # warm bucket 8
    base = jit_cache_size(serve_model.gemm_rows)
    model.decide_rows(jnp.ones((6, p)))          # repeat bucket 8
    assert jit_cache_size(serve_model.gemm_rows) == base
    model.decide_rows(jnp.ones((9, p)))          # new bucket 16
    assert jit_cache_size(serve_model.gemm_rows) == base + 1
    model.decide_rows(jnp.ones((16, p)))         # repeat bucket 16
    assert jit_cache_size(serve_model.gemm_rows) == base + 1


def test_serve_server_batches_share_bucket_compiles():
    """End-to-end through PredictServer: many submits coalescing into
    batches reuse the same bucket compile."""
    from repro.serve.model import PredictModel, gemm_rows
    from repro.serve.server import PredictServer

    p = 7
    model = PredictModel.from_r(
        jnp.arange(V * T * (2 * p + 2), dtype=jnp.float32)
        .reshape(V, T, 2 * p + 2) / 100.0)
    srv = PredictServer(model, window_ms=0.0)
    try:
        # same p=7 signatures as the test above may already be cached;
        # measure deltas only
        srv.submit(np.ones((2, p), np.float32), node=0,
                   task=0).result(timeout=30)
        base = jit_cache_size(gemm_rows)
        futs = [srv.submit(np.full((1, p), i, np.float32), node=0,
                           task=1) for i in range(8)]
        for f in futs:
            f.result(timeout=30)
        # every batch (1..8 rows) pads to the already-compiled bucket 8
        assert jit_cache_size(gemm_rows) == base
    finally:
        srv.close()


def test_async_membership_adds_zero_traces():
    """Node churn rides the SAME traced round: membership events are
    data (active-node masks in the scan xs), so a fit with crash /
    recover / leave events traces plan_step exactly as often as the
    event-free async fit — once — and builds the Gram once."""
    from repro.net import Membership, MembershipEvent, NetConfig

    X, y, mask, adj = _data()
    net = NetConfig(schedule="partial:0.75", seed=0)
    mem = Membership(events=(MembershipEvent(2, "crash", 1),
                             MembershipEvent(4, "recover", 1),
                             MembershipEvent(5, "leave", 0)))
    with trace_counter("repro.kernels.ops:weighted_gram",
                       "repro.engine.plan:plan_step") as c:
        api.DTSVM(iters=8, qp_iters=2, net=net).fit(X, y, mask, adj)
        assert c["plan_step"] == 1
        api.DTSVM(iters=8, qp_iters=2, net=net).fit(
            X, y, mask, adj, membership=mem)
    assert c["weighted_gram"] == 2         # one build per fit, no more
    assert c["plan_step"] == 2             # churn fit also traces once


def test_error_feedback_adds_zero_traces_over_int8():
    """Error-feedback compensation is a statically-gated branch of the
    same exchange: turning it on over the int8 wire adds no plan_step
    retrace and no extra Gram build relative to plain int8."""
    from repro.net import LinkPolicy, NetConfig

    X, y, mask, adj = _data()
    for ef in (False, True):
        net = NetConfig(policy=LinkPolicy(quant="int8"), seed=0,
                        error_feedback=ef)
        with trace_counter("repro.kernels.ops:weighted_gram",
                           "repro.engine.plan:plan_step") as c:
            api.DTSVM(iters=4, qp_iters=2, net=net).fit(X, y, mask, adj)
        assert c["weighted_gram"] == 1, f"error_feedback={ef}"
        assert c["plan_step"] == 1, f"error_feedback={ef}"


def test_multi_engine_fit_traces_once():
    """The fused multi-iteration engine keeps the compile-once contract:
    one Gram build, one plan_step trace for the whole fit."""
    X, y, mask, adj = _data()
    with trace_counter("repro.kernels.ops:weighted_gram",
                       "repro.engine.plan:plan_step") as c:
        api.DTSVM(iters=3, qp_iters=2,
                  qp_solver="pallas_fused_multi").fit(X, y, mask, adj)
    assert c["weighted_gram"] == 1
    assert c["plan_step"] == 1


def test_factored_fit_never_builds_gram():
    """qp_operator="factored" must NEVER enter the dense Gram build —
    the streamed Lipschitz pass enters the row-panel kernel exactly
    once and K stays unmaterialized."""
    X, y, mask, adj = _data()
    with trace_counter("repro.kernels.ops:weighted_gram",
                       "repro.kernels.ops:weighted_gram_rows",
                       "repro.engine.plan:plan_step") as c:
        api.DTSVM(iters=3, qp_iters=2, qp_solver="pallas_fused_multi",
                  qp_operator="factored").fit(X, y, mask, adj)
    assert c["weighted_gram"] == 0
    assert c["weighted_gram_rows"] == 1
    assert c["plan_step"] == 1
