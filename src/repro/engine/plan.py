"""Plan/execute: compile a DTSVM problem once, iterate it many times.

The Prop.-1 iteration splits cleanly into

    invariants  —  Z, K, u, a, counts, box, Lipschitz bound: functions of
                   the PROBLEM only (see ``engine.invariants``), and
    step        —  the f^{(k)}-dependent linear term + the dual solve +
                   the primal/multiplier updates: the only part that
                   touches the ADMM state.

``compile_problem`` precomputes the former into a ``Plan``; ``Plan.step``
/ ``Plan.run`` execute the latter.  A fit() therefore builds the dual
Hessian K = Z diag(a) Z^T (the declared hot spot) exactly once instead
of once per ADMM iteration, and the inner QP engine is pluggable
(``engine.qp_engines``: "fista" | "pg" | "pallas_fused").

Results are bit-for-bit identical to scanning the legacy
``core.dtsvm.dtsvm_step`` (tested: tests/test_engine.py) — the step
consumes precomputed values that are bitwise what the legacy path
recomputes each iteration.

``Plan.replan`` is the incremental path behind the online Session
(Fig. 7): membership events rebuild only the invariants they touch.
"""
from __future__ import annotations

import functools
import hashlib
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtsvm as core
from repro.core import sparse
from repro.engine import invariants as inv_lib
from repro.engine import qp_engines
from repro.kernels import ops as kops
from repro.obs import counters as obs_counters
from repro.obs import spans as obs_spans

DEFAULT_QP_SOLVER = "fista"

#: The smallest dual Hessian (K's V*T*N*N float32 bytes) at which
#: ``resolve_qp_operator`` picks the factored operator: below it a QP
#: step is bound by its latency, not by HBM, and the materialized
#: matvec's one read of K does as well as or better than the factored
#: form's two reads of Z.  Read from whole fits of both operators on
#: one TPU v5e chip (``benchmarks/qp_operator_crossover.py``; PERF.md
#: section 6): the smallest measured K at which the factored fit won at
#: 2 x 40 and at 100 x 100 iterations, and at every larger shape.
FACTORED_MIN_K_BYTES = 32 << 20


def resolve_qp_operator(qp_operator: Optional[str], *, qp_solver: str,
                        qp_precision: str, x_shape, sparse_x: bool = False,
                        backend: str = "vmap") -> str:
    """The QP operator a fit runs: ``qp_operator`` when given, else
    chosen from the problem's shape.

    ``None`` resolves to ``"factored"`` only where each QP step then
    reads fewer bytes and bandwidth sets its pace: the ``vmap`` backend
    (the other backends carry their own dual layouts), an engine with a
    factored form (``qp_engines``: ``"fista"``, ``"pallas_fused_multi"``),
    f32, a dense X (``x_shape`` is X's (V, T, N, p)), two reads of Z
    smaller than one of K (N > 2 (p+1)), and K of at least
    :data:`FACTORED_MIN_K_BYTES`.  Everywhere else it resolves to
    ``"materialized"``.
    """
    if qp_operator is not None:
        return qp_operator
    V, T, N, p = x_shape
    factored = (backend == "vmap" and not sparse_x and qp_precision == "f32"
                and getattr(qp_engines.get(qp_solver), "factored", None)
                is not None
                and N > 2 * (p + 1)
                and 4 * V * T * N * N >= FACTORED_MIN_K_BYTES)
    return "factored" if factored else "materialized"


def consensus_update(prob: core.DTSVMProblem, state: core.DTSVMState,
                     u, ntp, nbr, f, zl, nbr_reduce: Callable):
    """Eqs. (7)-(9): the post-dual-solve primal/multiplier updates.

    ``zl = X^T Y lam`` (V, T, p+1) summarizes the dual solve; everything
    else is precomputed invariants plus the carried state.  Returns
    ``(r_new, alpha, beta)``.
    """
    p = prob.X.shape[-1]
    with obs_spans.scope("consensus_update"):
        rhs = jnp.concatenate([zl, zl], axis=-1) - f           # [I,I]^T(..)-f
        r_new = rhs / u                                        # eq. (7)
        act = prob.active[..., None]
        r_new = r_new * act + state.r * (1.0 - act)            # freeze

        # eq. (8): alpha update on the (w0, b0) block, coupled nodes only
        r_act = r_new * act
        task_sum = jnp.sum(r_act, axis=1, keepdims=True) - r_act
        d_alpha = (ntp[..., None] * r_new
                   - task_sum * prob.couple[:, None, None])
        alpha = state.alpha + 0.5 * prob.eta1 * d_alpha[..., : p + 1] * act

        # eq. (9): beta update over active neighbors
        with obs_spans.scope("neighbour_exchange"):
            nbr_sum = nbr_reduce(r_act)
        d_beta = nbr[..., None] * r_new - nbr_sum
        beta = state.beta + 0.5 * prob.eta2 * d_beta * act
    return r_new, alpha, beta


class RowPanels(NamedTuple):
    """The sample-axis hook of :func:`plan_step`: the invariants, duals
    and N-sized problem leaves hold one device's rows (K its row panel
    K[rows, :]), and these collectives join the rows."""
    gather: Callable   # iterate rows (..., N/S) -> (..., N), per matvec
    zl: Callable       # (lam, Z) rows -> X^T Y lam over all samples


def plan_step(prob: core.DTSVMProblem, inv: inv_lib.PlanInvariants,
              state: core.DTSVMState, *, qp_iters: int = 200,
              qp_solver: str = DEFAULT_QP_SOLVER,
              qp_precision: str = "f32",
              qp_operator: str = "materialized",
              nbr_reduce: Optional[Callable] = None,
              row_panels: Optional[RowPanels] = None) -> core.DTSVMState:
    """One Prop.-1 iteration (eqs. 6-9) on precomputed invariants.

    Pure and traceable — every backend steps it: the node-sharded one
    inside shard_map with a collective ``nbr_reduce`` and per-node
    invariant shards, the sample-sharded one with row panels and the
    ``row_panels`` hook (:class:`RowPanels`; ``"fista"``/``"pg"``,
    materialized operator).

    ``qp_precision`` / ``qp_operator`` select the mixed-precision and
    factored-matvec QP modes (validated by ``compile_problem``; both
    default to the exact materialized-f32 path).  Under the factored
    operator the engine's ``factored`` form applies K through ``Z`` and
    ``a`` and returns the zl contraction with the duals.  An engine
    with the ``supports_fold`` capability receives ``Z`` and returns
    the zl contraction from the same fused launch — on the oracle path
    that fold is the identical einsum, so the default-path bitwise
    contract is untouched.
    """
    p = prob.X.shape[-1]
    if nbr_reduce is None:
        nbr_reduce = core._default_nbr_reduce(prob)
    ntp, nbr, u, Z = inv.ntp, inv.nbr, inv.u, inv.Z

    f = core._f_vec(prob, state, ntp, nbr, nbr_reduce)
    g = f[..., : p + 1] / u[..., : p + 1] + f[..., p + 1:] / u[..., p + 1:]
    # a sparse Z (compile_problem admits it with the materialized QP
    # only) takes both of its products over the nonzeros
    is_sparse = isinstance(Z, sparse.SparseZ)
    if is_sparse:
        with obs_spans.scope("sparse_matvec"):
            q = prob.mask + sparse.z_dot(Z, g)
    else:
        # mul+reduce (not einsum): bitwise-stable under an extra vmapped
        # config axis — the sweep engine relies on batched == serial exactly
        q = prob.mask + jnp.sum(Z * g[..., None, :], axis=-1)

    engine = qp_engines.get(qp_solver)
    # the dual solve and its X^T Y lam summary (one launch in the fused
    # engines): the qp_solve phase
    with obs_spans.scope("qp_solve"):
        if row_panels is not None:
            lam = engine(inv.K, q, inv.hi, state.lam, iters=qp_iters,
                         L=inv.L, gather=row_panels.gather)    # eq. (6)
            zl = row_panels.zl(lam, Z)                         # X^T Y lam
        elif qp_operator == "factored":
            lam, zl = engine.factored(Z, inv.a, q, inv.hi, state.lam,
                                      iters=qp_iters, L=inv.L)  # eq. (6)
        elif getattr(engine, "supports_fold", False):
            lam, zl = engine(inv.K, q, inv.hi, state.lam, iters=qp_iters,
                             L=inv.L, precision=qp_precision,
                             Z=Z)                              # eq. (6)
        elif is_sparse:
            lam = engine(inv.K, q, inv.hi, state.lam,
                         iters=qp_iters, L=inv.L)              # eq. (6)
            with obs_spans.scope("sparse_matvec"):
                zl = sparse.zt_dot(Z, lam)                     # X^T Y lam
        else:
            lam = engine(inv.K, q, inv.hi, state.lam,
                         iters=qp_iters, L=inv.L)              # eq. (6)
            # repro: noqa[raw-einsum-in-plan] — deliberate: mul+reduce would materialize a (V,T,N,D) temporary; batching stability is pinned by the fig2-fig7 golden fixtures across all backends
            zl = jnp.einsum("vtn,vtnd->vtd", lam, Z,
                            precision="highest")               # X^T Y lam
    r_new, alpha, beta = consensus_update(prob, state, u, ntp, nbr, f, zl,
                                          nbr_reduce)
    return core.DTSVMState(r=r_new, alpha=alpha, beta=beta, lam=lam)


def _scan(prob: core.DTSVMProblem, inv: inv_lib.PlanInvariants,
          state: core.DTSVMState, *, iters: int, qp_iters: int,
          qp_solver: str, qp_precision: str, qp_operator: str,
          telemetry=None, eval_fn: Optional[Callable] = None,
          nbr_reduce: Optional[Callable] = None):
    """``lax.scan`` of ``iters`` ``plan_step``s from ``state``: returns
    ``(state, outs)``, ``outs`` stacking ``eval_fn(state)`` (0 without
    one) after every iteration, paired with the telemetry streams when
    ``telemetry`` is given.  The body of ``Plan.run``."""
    def body(st, _):
        with obs_spans.scope("scan_execute"):
            new = plan_step(prob, inv, st, qp_iters=qp_iters,
                            qp_solver=qp_solver, qp_precision=qp_precision,
                            qp_operator=qp_operator, nbr_reduce=nbr_reduce)
            out = eval_fn(new) if eval_fn is not None else jnp.float32(0)
            if telemetry is not None:
                out = (out, telemetry.collect(prob, inv.hi, new, st))
        return new, out

    return jax.lax.scan(body, state, None, length=iters)


@functools.partial(jax.jit, static_argnames=(
    "iters", "qp_iters", "qp_solver", "qp_precision", "qp_operator",
    "telemetry", "_pallas"))
def _plan_scan(prob, inv, state, *, iters, qp_iters, qp_solver,
               qp_precision, qp_operator, telemetry, _pallas):
    """``_scan`` as one cached program (``Plan.run``'s default path).
    The Pallas engines read the kernel switch at trace time, so the
    cache is keyed on it (``_pallas``), as the streamed Gram builds'
    is."""
    return _scan(prob, inv, state, iters=iters, qp_iters=qp_iters,
                 qp_solver=qp_solver, qp_precision=qp_precision,
                 qp_operator=qp_operator, telemetry=telemetry)


class Plan:
    """A compiled DTSVM problem: invariants + the light per-iteration body.

    ``stats`` tracks the invariant economy across the plan's lifetime:
    ``gram_slices_computed`` / ``gram_slices_reused`` count (v,t) Gram
    blocks built vs. carried over by ``replan``, ``replans`` the number
    of incremental re-plans.
    """

    def __init__(self, prob: core.DTSVMProblem,
                 inv: inv_lib.PlanInvariants, *, qp_iters: int = 200,
                 qp_solver: str = DEFAULT_QP_SOLVER,
                 qp_precision: str = "f32",
                 qp_operator: str = "materialized",
                 nbr_reduce: Optional[Callable] = None,
                 budget: Optional[inv_lib.PlanBudget] = None,
                 stats: Optional[dict] = None):
        self.prob = prob
        self.inv = inv
        self.qp_iters = qp_iters
        self.qp_solver = qp_solver
        self.qp_precision = qp_precision
        self.qp_operator = qp_operator
        self.budget = budget
        self._nbr_reduce = nbr_reduce
        V, T = prob.X.shape[:2]
        self.stats = stats if stats is not None else {
            "gram_slices_computed": V * T,
            "gram_slices_reused": 0,
            "replans": 0,
        }

    # -- execution ---------------------------------------------------------
    def init_state(self) -> core.DTSVMState:
        return core.init_state(self.prob)

    def step(self, state: core.DTSVMState) -> core.DTSVMState:
        """One ADMM iteration on the precomputed invariants."""
        return plan_step(self.prob, self.inv, state, qp_iters=self.qp_iters,
                         qp_solver=self.qp_solver,
                         qp_precision=self.qp_precision,
                         qp_operator=self.qp_operator,
                         nbr_reduce=self._nbr_reduce)

    def run(self, state: Optional[core.DTSVMState] = None, iters: int = 1,
            eval_fn: Optional[Callable] = None, telemetry=None):
        """Scan ``iters`` iterations.  Returns (state, history) where
        history stacks ``eval_fn(state)`` after every iteration (or
        None) — the same contract as the legacy ``run_dtsvm``.

        With ``telemetry`` (a ``repro.obs.Telemetry``) the scan
        additionally stacks per-iteration convergence diagnostics and
        the return becomes ``(state, history, streams)`` — the state
        carry is untouched (extra scan *outputs* only), so the model
        outputs are bitwise identical to the telemetry-None call, and
        the collector traces once inside the same scan body (zero extra
        retraces).  The streams are still on device; materialize them
        after the scan (``repro.obs.materialize``).

        Without ``eval_fn`` or a plan-level ``nbr_reduce`` the scan is
        the module-level jit ``_plan_scan``, fed the problem, invariants
        and state as arguments and keyed on their shapes, the QP
        configuration, ``iters``, the telemetry spec and the Pallas
        switch: the first run of a shape traces, lowers and compiles it,
        every later run (any plan of that shape, e.g. the next fit on new
        data or a replanned session stage) dispatches the compiled
        program.  A hook callable would key the cache on its identity (a
        per-call closure misses every time and keeps its arrays alive in
        the cache), so with one the scan runs eagerly, traced anew each
        call."""
        if state is None:
            state = self.init_state()
        kw = dict(iters=int(iters), qp_iters=self.qp_iters,
                  qp_solver=self.qp_solver, qp_precision=self.qp_precision,
                  qp_operator=self.qp_operator, telemetry=telemetry)
        span_args = {} if telemetry is None else {"telemetry": True}
        with obs_spans.span("scan_execute", iters=int(iters), **span_args):
            if eval_fn is None and self._nbr_reduce is None:
                state, outs = _plan_scan(self.prob, self.inv, state, **kw,
                                         _pallas=kops._use_pallas())
            else:
                state, outs = _scan(self.prob, self.inv, state,
                                    eval_fn=eval_fn,
                                    nbr_reduce=self._nbr_reduce, **kw)
        if telemetry is None:
            return state, (outs if eval_fn is not None else None)
        hist, streams = outs
        return state, (hist if eval_fn is not None else None), streams

    # -- identity --------------------------------------------------------
    def fingerprint(self) -> str:
        """A content hash of everything that determines the plan's
        execution: every problem and invariant leaf (dtype, shape, raw
        bytes) plus the QP configuration.  Two plans with equal
        fingerprints step bitwise-identically, so the durable session
        layer (``repro.store``) stores this hash instead of the (large,
        deterministically rebuildable) invariants and asserts the
        rebuilt plan matches on restore."""
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves((self.prob, self.inv)):
            arr = np.asarray(leaf)
            h.update(f"{arr.dtype}|{arr.shape}|".encode())
            h.update(arr.tobytes())
        h.update(f"|{self.qp_iters}|{self.qp_solver}"
                 f"|{self.qp_precision}|{self.qp_operator}".encode())
        return h.hexdigest()

    # -- incremental re-planning (the online Session path) -----------------
    def replan(self, *, active=None, couple=None) -> "Plan":
        """A new Plan for changed membership masks, reusing every
        invariant the change does not touch (host-side; see
        ``invariants.update_invariants``).  The plan's ``budget``
        carries over, so rebuilt K slices stream through the same
        bounded row panels as the original build."""
        with obs_spans.span("plan_replan"):
            prob, inv, n = inv_lib.update_invariants(
                self.prob, self.inv, active=active, couple=couple,
                budget=self.budget)
        V, T = prob.X.shape[:2]
        stats = dict(self.stats)
        stats["replans"] += 1
        stats["gram_slices_computed"] += n
        stats["gram_slices_reused"] += V * T - n
        return Plan(prob, inv, qp_iters=self.qp_iters,
                    qp_solver=self.qp_solver,
                    qp_precision=self.qp_precision,
                    qp_operator=self.qp_operator,
                    nbr_reduce=self._nbr_reduce,
                    budget=self.budget, stats=stats)


def compile_problem(prob: core.DTSVMProblem, cfg=None, *,
                    qp_iters: Optional[int] = None,
                    qp_solver: Optional[str] = None,
                    qp_precision: Optional[str] = None,
                    qp_operator: Optional[str] = None,
                    nbr_reduce: Optional[Callable] = None,
                    nbr_counts=None,
                    budget: Optional[inv_lib.PlanBudget] = None,
                    backend: str = "vmap") -> Plan:
    """Precompute every loop-invariant of Prop. 1 into a ``Plan``.

    Parameters
    ----------
    prob : core.DTSVMProblem
        The problem to compile (data/graph/masks/hyper-parameters).
    cfg : object, optional
        Any object with ``qp_iters`` / ``qp_solver`` / ``budget``
        attributes (e.g. ``repro.api.SolverConfig``); explicit keywords
        override it.
    qp_iters : int, optional
        Inner box-QP iterations per ADMM step (default 200).
    qp_solver : str, optional
        QP engine name (``"fista" | "pg" | "pallas_fused" |
        "pallas_fused_multi"``).
    qp_precision : str, optional
        ``"f32"`` (default, exact) or ``"bf16"`` — mixed-precision K
        tiles with f32 iterates; requires an engine with the
        ``supports_precision`` capability (``"pallas_fused_multi"``).
        Validated by risk deltas (BENCH_fit), never claimed bitwise.
    qp_operator : str, optional
        ``"materialized"`` or ``"factored"`` — the low-rank O(N D)
        matvec ``Z (a (Z^T lam))``; K is never built (the invariants
        carry ``K=None`` and the Gershgorin bound streams through
        discarded row panels).  Factored needs an engine with a
        factored form (``"fista"``, ``"pallas_fused_multi"``), f32 and
        a dense X.  ``None`` (the default) chooses from the shapes
        (:func:`resolve_qp_operator`); each plan that resolves to
        factored adds 1 to the ``plan.factored_plans`` counter
        (``repro.obs.counters``).
    nbr_reduce : callable, optional
        Neighbor-sum hook for SPMD execution.
    nbr_counts : jnp.ndarray, optional
        Precomputed (V, T) active-neighbor counts (SPMD shards pass
        their collective counts).
    budget : invariants.PlanBudget, optional
        Memory budget for the K build: streams the Gram construction
        through bounded row panels instead of one batched matmul —
        bitwise identical to the dense build (the large-n scale path).
    backend : str, optional
        The backend that will step the plan (default ``"vmap"``; the
        ``"async"`` fabric steps the materialized operator only), for
        :func:`resolve_qp_operator`.

    Returns
    -------
    Plan
        Compiled invariants plus the light per-iteration body.  Pure
        jnp — safe to call under jit (the incremental ``Plan.replan``
        is the only host-side part of the engine).
    """
    if qp_iters is None:
        qp_iters = getattr(cfg, "qp_iters", 200)
    if qp_solver is None:
        qp_solver = getattr(cfg, "qp_solver", DEFAULT_QP_SOLVER)
    if qp_precision is None:
        qp_precision = getattr(cfg, "qp_precision", "f32")
    if qp_operator is None:
        qp_operator = getattr(cfg, "qp_operator", None)
    if budget is None:
        budget = getattr(cfg, "budget", None)
    engine = qp_engines.get(qp_solver)   # fail fast on unknown engines
    if qp_precision not in ("f32", "bf16"):
        raise ValueError(f"unknown qp_precision {qp_precision!r}; "
                         f"expected 'f32' or 'bf16'")
    qp_operator = resolve_qp_operator(
        qp_operator, qp_solver=qp_solver, qp_precision=qp_precision,
        x_shape=prob.X.shape,
        sparse_x=isinstance(prob.X, sparse.SparseRows), backend=backend)
    if qp_operator not in ("materialized", "factored"):
        raise ValueError(f"unknown qp_operator {qp_operator!r}; "
                         f"expected 'materialized' or 'factored'")
    if qp_precision != "f32" and not getattr(engine, "supports_precision",
                                             False):
        raise ValueError(
            f"qp_precision={qp_precision!r} needs a mixed-precision "
            f"engine (qp_solver='pallas_fused_multi'); got {qp_solver!r}")
    if qp_operator == "factored":
        if getattr(engine, "factored", None) is None:
            raise ValueError(
                f"qp_operator='factored' needs an engine with a factored "
                f"form (qp_solver='fista' or 'pallas_fused_multi'); "
                f"got {qp_solver!r}")
        if qp_precision != "f32":
            raise ValueError("qp_operator='factored' is f32-only "
                             "(the low-rank matvec never streams K "
                             "tiles, so bf16 K has nothing to apply to)")
    if isinstance(prob.X, sparse.SparseRows) and (
            qp_operator != "materialized" or qp_precision != "f32"
            or getattr(engine, "supports_fold", False)):
        sparse.require_dense(prob.X, f"qp_solver={qp_solver!r} with "
                             f"qp_precision={qp_precision!r} and "
                             f"qp_operator={qp_operator!r}")
    if qp_operator == "factored":
        obs_counters.count("plan.factored_plans", 1)
    inv = inv_lib.compute_invariants(
        prob, nbr_counts=nbr_counts, budget=budget,
        materialize_k=(qp_operator != "factored"))
    return Plan(prob, inv, qp_iters=qp_iters, qp_solver=qp_solver,
                qp_precision=qp_precision, qp_operator=qp_operator,
                nbr_reduce=nbr_reduce, budget=budget)
