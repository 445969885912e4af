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

import hashlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtsvm as core
from repro.engine import invariants as inv_lib
from repro.engine import qp_engines
from repro.obs import spans as obs_spans

DEFAULT_QP_SOLVER = "fista"


def consensus_update(prob: core.DTSVMProblem, state: core.DTSVMState,
                     u, ntp, nbr, f, zl, nbr_reduce: Callable):
    """Eqs. (7)-(9): the post-dual-solve primal/multiplier updates.

    ``zl = X^T Y lam`` (V, T, p+1) summarizes the dual solve; everything
    else is precomputed invariants plus the carried state.  Returns
    ``(r_new, alpha, beta)``.  Shared by ``plan_step`` and the
    sample-sharded backend (whose duals live on row panels but whose
    consensus math is replicated) — one copy of ops that must stay
    bitwise-identical across execution paths.
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


def plan_step(prob: core.DTSVMProblem, inv: inv_lib.PlanInvariants,
              state: core.DTSVMState, *, qp_iters: int = 200,
              qp_solver: str = DEFAULT_QP_SOLVER,
              qp_precision: str = "f32",
              qp_operator: str = "materialized",
              nbr_reduce: Optional[Callable] = None) -> core.DTSVMState:
    """One Prop.-1 iteration (eqs. 6-9) on precomputed invariants.

    Pure and traceable — the SPMD backend calls this inside shard_map
    with a collective ``nbr_reduce`` and per-node invariant shards.

    ``qp_precision`` / ``qp_operator`` select the mixed-precision and
    factored-matvec QP modes (validated by ``compile_problem``; both
    default to the exact materialized-f32 path).  An engine with the
    ``supports_fold`` capability receives ``Z`` and returns the zl
    contraction from the same fused launch — on the oracle path that
    fold is the identical einsum, so the default-path bitwise contract
    is untouched.
    """
    p = prob.X.shape[-1]
    if nbr_reduce is None:
        nbr_reduce = core._default_nbr_reduce(prob)
    ntp, nbr, u, Z = inv.ntp, inv.nbr, inv.u, inv.Z

    f = core._f_vec(prob, state, ntp, nbr, nbr_reduce)
    g = f[..., : p + 1] / u[..., : p + 1] + f[..., p + 1:] / u[..., p + 1:]
    # mul+reduce (not einsum): bitwise-stable under an extra vmapped
    # config axis — the sweep engine relies on batched == serial exactly
    q = prob.mask + jnp.sum(Z * g[..., None, :], axis=-1)

    engine = qp_engines.get(qp_solver)
    # the dual solve and its X^T Y lam summary (one launch in the fused
    # engines): the qp_solve phase
    with obs_spans.scope("qp_solve"):
        if qp_operator == "factored":
            lam, zl = qp_engines.solve_factored_multi(
                Z, inv.a, q, inv.hi, state.lam, iters=qp_iters,
                L=inv.L)                                       # eq. (6)
        elif getattr(engine, "supports_fold", False):
            lam, zl = engine(inv.K, q, inv.hi, state.lam, iters=qp_iters,
                             L=inv.L, precision=qp_precision,
                             Z=Z)                              # eq. (6)
        else:
            lam = engine(inv.K, q, inv.hi, state.lam,
                         iters=qp_iters, L=inv.L)              # eq. (6)
            # repro: noqa[raw-einsum-in-plan] — deliberate: mul+reduce would materialize a (V,T,N,D) temporary; batching stability is pinned by the fig2-fig7 golden fixtures across all backends
            zl = jnp.einsum("vtn,vtnd->vtd", lam, Z,
                            precision="highest")               # X^T Y lam
    r_new, alpha, beta = consensus_update(prob, state, u, ntp, nbr, f, zl,
                                          nbr_reduce)
    return core.DTSVMState(r=r_new, alpha=alpha, beta=beta, lam=lam)


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
        after the scan (``repro.obs.materialize``)."""
        if state is None:
            state = self.init_state()
        if telemetry is None:
            def body(st, _):
                with obs_spans.scope("scan_execute"):
                    st = self.step(st)
                    out = (eval_fn(st) if eval_fn is not None
                           else jnp.float32(0))
                return st, out

            with obs_spans.span("scan_execute", iters=int(iters)):
                state, hist = jax.lax.scan(body, state, None, length=iters)
            return state, (hist if eval_fn is not None else None)

        def body(st, _):
            with obs_spans.scope("scan_execute"):
                new = self.step(st)
                out = eval_fn(new) if eval_fn is not None else jnp.float32(0)
                tel = telemetry.collect(self.prob, self.inv.hi, new, st)
            return new, (out, tel)

        with obs_spans.span("scan_execute", iters=int(iters),
                            telemetry=True):
            state, (hist, streams) = jax.lax.scan(body, state, None,
                                                  length=iters)
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
                    budget: Optional[inv_lib.PlanBudget] = None) -> Plan:
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
        ``"materialized"`` (default) or ``"factored"`` — the low-rank
        O(N D) matvec ``Z (a (Z^T lam))``; K is never built (the
        invariants carry ``K=None`` and the Gershgorin bound streams
        through discarded row panels).  Requires
        ``qp_solver="pallas_fused_multi"`` and f32.
    nbr_reduce : callable, optional
        Neighbor-sum hook for SPMD execution.
    nbr_counts : jnp.ndarray, optional
        Precomputed (V, T) active-neighbor counts (SPMD shards pass
        their collective counts).
    budget : invariants.PlanBudget, optional
        Memory budget for the K build: streams the Gram construction
        through bounded row panels instead of one batched matmul —
        bitwise identical to the dense build (the large-n scale path).

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
        qp_operator = getattr(cfg, "qp_operator", "materialized")
    if budget is None:
        budget = getattr(cfg, "budget", None)
    engine = qp_engines.get(qp_solver)   # fail fast on unknown engines
    if qp_precision not in ("f32", "bf16"):
        raise ValueError(f"unknown qp_precision {qp_precision!r}; "
                         f"expected 'f32' or 'bf16'")
    if qp_operator not in ("materialized", "factored"):
        raise ValueError(f"unknown qp_operator {qp_operator!r}; "
                         f"expected 'materialized' or 'factored'")
    if qp_precision != "f32" and not getattr(engine, "supports_precision",
                                             False):
        raise ValueError(
            f"qp_precision={qp_precision!r} needs a mixed-precision "
            f"engine (qp_solver='pallas_fused_multi'); got {qp_solver!r}")
    if qp_operator == "factored":
        if not getattr(engine, "supports_fold", False):
            raise ValueError(
                f"qp_operator='factored' is validated only with the "
                f"fused multi engine (qp_solver='pallas_fused_multi'); "
                f"got {qp_solver!r}")
        if qp_precision != "f32":
            raise ValueError("qp_operator='factored' is f32-only "
                             "(the low-rank matvec never streams K "
                             "tiles, so bf16 K has nothing to apply to)")
    inv = inv_lib.compute_invariants(
        prob, nbr_counts=nbr_counts, budget=budget,
        materialize_k=(qp_operator != "factored"))
    return Plan(prob, inv, qp_iters=qp_iters, qp_solver=qp_solver,
                qp_precision=qp_precision, qp_operator=qp_operator,
                nbr_reduce=nbr_reduce, budget=budget)
