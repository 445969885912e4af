"""One fit/predict surface over the paper's three solvers.

``SolverConfig`` carries every hyper-parameter of Prop. 1 plus the
execution choice (backend + options); ``CSVM``, ``DSVM`` and ``DTSVM``
all implement the same ``Solver`` protocol over it, so swapping the
algorithm — the thing every figure of the paper does — is a one-line
change:

    cfg = SolverConfig(C=0.01, eps2=1.0, iters=60)
    DTSVM(cfg).fit(X, y, mask=mask, adj=adj).risks(X_test, y_test)
    DSVM(cfg).fit(X, y, mask=mask, adj=adj).risks(X_test, y_test)
    CSVM(cfg).fit(X, y, mask=mask).risks(X_test, y_test)

Data layout is the repo-wide convention: X (V, T, N, p), y/mask (V, T, N)
in {-1,+1}/{0,1}, test sets (T, n, p) shared across nodes.  The solvers
wrap — never replace — the math in ``repro.core``; everything here is
plumbing, bookkeeping and defaults.

Looping ``fit()`` over a hyper-parameter GRID re-traces and re-compiles
every point — use ``repro.api.sweep_fit`` instead: the whole grid runs
as one batched plan, bitwise identical per config (``repro.api.sweep``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Protocol, Tuple, runtime_checkable

import jax.numpy as jnp
import numpy as np

from repro.api import backends, evaluate
from repro.core import csvm as csvm_lib
from repro.core import dsvm as dsvm_lib
from repro.core import dtsvm as core
from repro.engine.invariants import PlanBudget
from repro.net.policies import NetConfig
from repro.obs.telemetry import Telemetry


@dataclass(frozen=True)
class SolverConfig:
    """Hyper-parameters + execution strategy for every solver.

    The algorithmic fields mirror the paper's Section-IV defaults; the
    execution fields select how ``fit`` runs, not what it computes.

    Parameters
    ----------
    C : float
        SVM error penalty (paper Section IV sweeps it, Fig. 4).
    eps1, eps2 : float
        Shared / task-specific regularization weights of Prop. 1.
    eta1, eta2 : float
        Task- and node-consensus ADMM weights.
    iters : int
        ADMM iterations per ``fit()``.
    qp_iters : int
        Inner box-QP iterations per ADMM step.
    qp_solver : str
        Dual QP engine: ``"fista" | "pg" | "pallas_fused" |
        "pallas_fused_multi"`` (``repro.engine.qp_engines``).
    qp_precision : str
        ``"f32"`` (default, exact) or ``"bf16"`` — mixed-precision K
        tiles with f32 iterates/accumulators in the fused multi
        engine.  Requires ``qp_solver="pallas_fused_multi"``;
        validated by the BENCH_fit risk-delta table, never claimed
        bitwise.
    qp_operator : str, optional
        ``"materialized"`` or ``"factored"`` — evaluate the QP matvec
        as ``Z (a (Z^T lam))`` in O(N D) without ever building K (the
        large-n fast path; K is rank <= p+1).  ``None`` (default)
        chooses from the shapes (``engine.plan.resolve_qp_operator``):
        factored on the ``vmap`` backend with ``qp_solver="fista"`` or
        ``"pallas_fused_multi"``, f32 and a dense X where N > 2 (p+1)
        and K would hold at least ``engine.plan.FACTORED_MIN_K_BYTES``;
        materialized everywhere else.  An explicit value is honoured;
        factored needs one of those engines, f32, a dense X and the
        ``vmap`` backend.
    box_scale : float, optional
        The paper's multiplier on ``C`` in the QP box (auto: ``V*T``).
    backend : str
        Execution strategy: ``"vmap" | "shard_map" | "async" |
        "sample_shard"`` (``repro.api.backends``).
    backend_options : dict
        Backend extras, e.g. ``{"topology": "ring"}`` (shard_map) or
        ``{"n_shards": 4, "reduce": "psum"}`` (sample_shard).
    net : repro.net.NetConfig, optional
        Communication model; setting it routes the default backend to
        ``"async"`` — the identity ``NetConfig()`` reproduces the vmap
        trajectory bitwise, now metered.
    budget : repro.engine.PlanBudget, optional
        Memory budget for the invariant (K) build: streams the Gram
        construction through bounded row panels — bitwise identical to
        the dense build (the large-n scale path; API.md §scale).
    telemetry : bool
        Collect per-iteration convergence streams (``repro.obs``:
        primal/dual residuals, per-task disagreement, QP box
        saturation) inside the fit's own scan; read them from
        ``solver.telemetry_``.  Guaranteed bitwise-invisible on all
        model outputs and retrace-free (docs/observability.md).
    """
    C: float = 0.01
    eps1: float = 1.0
    eps2: float = 1.0
    eta1: float = 1.0
    eta2: float = 1.0
    iters: int = 60                  # ADMM iterations per fit()
    qp_iters: int = 200              # inner box-QP iterations
    qp_solver: str = "fista"         # "fista" | "pg" | "pallas_fused"
    #                                  | "pallas_fused_multi"
    qp_precision: str = "f32"        # "f32" | "bf16" (multi engine only)
    qp_operator: Optional[str] = None   # "materialized" | "factored";
    #                                     None: chosen from the shapes
    box_scale: Optional[float] = None   # paper's V*T multiplier (auto)
    backend: str = "vmap"            # "vmap" | "shard_map" | "async"
    backend_options: Dict[str, Any] = field(default_factory=dict)
    # e.g. {"topology": "ring"} or {"mesh": ..., "axis": "nodes"}
    net: Optional[NetConfig] = None  # communication model (repro.net);
    # setting it routes the default backend to "async" — the identity
    # NetConfig() reproduces the vmap trajectory bitwise, now metered
    budget: Optional[PlanBudget] = None   # large-n K-build streaming
    telemetry: bool = False          # per-iteration obs streams (repro.obs)

    def replace(self, **kw) -> "SolverConfig":
        """A copy with the given fields replaced (frozen dataclass)."""
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        """Plain-python form for the durable-session schema
        (``repro.store``); ``from_dict`` inverts it exactly.

        ``net`` serializes via ``NetConfig.to_dict``, ``budget`` as its
        two ints.  ``backend_options`` must already be plain data —
        device meshes / callables have no declarative form and raise a
        ``TypeError`` naming the offending key.
        """
        for k, v in self.backend_options.items():
            if not isinstance(v, (int, float, str, bool, type(None))):
                raise TypeError(
                    f"SolverConfig.to_dict: backend_options[{k!r}] is a "
                    f"{type(v).__name__}, which has no serializable form "
                    f"(meshes/callables are runtime objects — rebuild "
                    f"them after from_dict instead)")
        return {
            "C": float(self.C), "eps1": float(self.eps1),
            "eps2": float(self.eps2), "eta1": float(self.eta1),
            "eta2": float(self.eta2), "iters": int(self.iters),
            "qp_iters": int(self.qp_iters), "qp_solver": self.qp_solver,
            "qp_precision": self.qp_precision,
            "qp_operator": self.qp_operator,
            "box_scale": None if self.box_scale is None
            else float(self.box_scale),
            "backend": self.backend,
            "backend_options": dict(self.backend_options),
            "net": None if self.net is None else self.net.to_dict(),
            "budget": None if self.budget is None else
            {"max_elems": None if self.budget.max_elems is None
             else int(self.budget.max_elems),
             "tile": None if self.budget.tile is None
             else [int(t) for t in self.budget.tile]},
            "telemetry": bool(self.telemetry),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "SolverConfig":
        """Rebuild a SolverConfig from ``to_dict``'s plain form."""
        d = dict(d)
        if d.get("net") is not None:
            d["net"] = NetConfig.from_dict(d["net"])
        if d.get("budget") is not None:
            b = d["budget"]
            d["budget"] = PlanBudget(
                max_elems=b["max_elems"],
                tile=None if b["tile"] is None else tuple(b["tile"]))
        return cls(**d)


@runtime_checkable
class Solver(Protocol):
    """What every solver exposes; see module docstring for the data layout."""

    config: SolverConfig

    def init_state(self, prob):
        """Zero state for ``prob`` (a ``core.DTSVMState`` for the
        consensus solvers)."""

    def step(self, state, prob):
        """One algorithm iteration ``state -> state``."""

    def fit(self, X, y, mask=None, adj=None, **kw) -> "Solver":
        """Train on X (V, T, N, p) / y (V, T, N); returns self."""

    def predict(self, X):
        """Predicted labels in {-1, +1} for test inputs."""

    def risks(self, X_test, y_test):
        """Misclassification rates on a shared (T, n, p) test set."""

    def residuals(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(task, node) consensus-constraint violations of the fit."""


def _as_solver_config(config, overrides) -> SolverConfig:
    cfg = config if config is not None else SolverConfig()
    if overrides:
        cfg = cfg.replace(**overrides)
    return cfg


def effective_backend(cfg: SolverConfig) -> str:
    """The backend a config actually runs: a communication model
    (``cfg.net``) promotes the default "vmap" to "async" and is invalid
    with any other backend.  Shared by solvers and the OnlineSession so
    the resolution policy lives in one place."""
    if cfg.net is not None:
        if cfg.backend == "vmap":
            return "async"
        if cfg.backend != "async":
            raise ValueError(f"SolverConfig.net is an async-backend "
                             f"feature; got backend={cfg.backend!r}")
    return cfg.backend


class _ConsensusSolver:
    """Shared machinery for the two decentralized solvers."""

    def __init__(self, config: Optional[SolverConfig] = None, **overrides):
        self.config = _as_solver_config(config, overrides)
        self.problem_: Optional[core.DTSVMProblem] = None
        self.state_: Optional[core.DTSVMState] = None
        self.history_ = None
        self.net_report_: Optional[Dict[str, Any]] = None   # async backend
        self.telemetry_: Optional[Dict[str, Any]] = None    # obs streams

    # -- problem construction (the one subclass hook) ----------------------
    def make_problem(self, X, y, mask=None, adj=None, *, active=None,
                     couple=None) -> core.DTSVMProblem:
        raise NotImplementedError

    # -- protocol ----------------------------------------------------------
    def init_state(self, prob: core.DTSVMProblem) -> core.DTSVMState:
        return core.init_state(prob)

    def step(self, state: core.DTSVMState,
             prob: core.DTSVMProblem) -> core.DTSVMState:
        """One Prop.-1 ADMM iteration (vmap path), honoring the
        configured QP engine.  One-shot: compiles the problem's
        invariants per call — loops should hold a plan instead
        (``repro.engine.compile_problem`` + ``plan.step``)."""
        from repro import engine
        return engine.compile_problem(prob, self.config).step(state)

    def fit(self, X, y, mask=None, adj=None, *, active=None, couple=None,
            iters: Optional[int] = None, state: Optional[core.DTSVMState]
            = None, eval_fn=None, X_test=None, y_test=None,
            membership=None):
        """Run ADMM on (X, y).  Returns self; state/history are stored on
        ``state_`` / ``history_`` (and, with ``config.telemetry``, the
        per-iteration convergence streams on ``telemetry_``).  Passing
        ``state`` warm-starts (the online setting); ``X_test``/``y_test``
        record a per-iteration risk curve without any manual
        broadcasting; ``membership`` (a ``repro.net.Membership``)
        schedules node enter/leave/crash/recover events over the fit —
        an async-backend feature (docs/churn.md)."""
        prob = self.make_problem(X, y, mask, adj, active=active,
                                 couple=couple)
        if eval_fn is None and X_test is not None:
            eval_fn = evaluate.risk_eval_fn(prob.X.shape[0], X_test, y_test)
        cfg = self.config
        backend, options = effective_backend(cfg), dict(cfg.backend_options)
        if membership is not None:
            if backend != "async":
                raise ValueError(
                    "membership= models node churn over the communication "
                    "fabric; configure SolverConfig(net=NetConfig(...)) "
                    "or backend='async'")
            options["membership"] = membership
        if cfg.net is not None:
            options.setdefault("net", cfg.net)
        if cfg.budget is not None:
            options.setdefault("budget", cfg.budget)
        if backend == "async":
            options.setdefault("meter_out", {})
        if cfg.telemetry:
            options.setdefault("telemetry", Telemetry())
            options.setdefault("telemetry_out", {})
        self.state_, self.history_ = backends.run(
            prob, iters if iters is not None else cfg.iters,
            backend=backend, qp_iters=cfg.qp_iters,
            qp_solver=cfg.qp_solver, qp_precision=cfg.qp_precision,
            qp_operator=cfg.qp_operator, state=state,
            eval_fn=eval_fn, **options)
        self.net_report_ = options.get("meter_out", {}).get("report")
        self.telemetry_ = options.get("telemetry_out", {}).get("streams")
        self.problem_ = prob
        return self

    # -- inference ---------------------------------------------------------
    def _require_fit(self) -> core.DTSVMState:
        if self.state_ is None:
            raise RuntimeError("call fit() first")
        return self.state_

    def decision(self, X) -> jnp.ndarray:
        """Decision values g_vt(x).  X: (T, n, p) shared, or (V, T, n, p)."""
        st = self._require_fit()
        X = jnp.asarray(X, jnp.float32)
        if X.ndim == 3:
            X = jnp.broadcast_to(X[None], (st.r.shape[0],) + X.shape)
        return core.decision_values(st.r, X)

    def predict(self, X) -> jnp.ndarray:
        """Predicted labels in {-1, +1}, shape (V, T, n)."""
        return jnp.sign(self.decision(X))

    def risks(self, X_test, y_test) -> jnp.ndarray:
        """(V, T) per-node test risks on the shared test set."""
        return evaluate.risks_of_state(self._require_fit(), X_test, y_test)

    def global_risks(self, X_test, y_test) -> np.ndarray:
        """(T,) network-average risks (what the figures plot)."""
        return evaluate.global_risks(self.risks(X_test, y_test))

    def residuals(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(task, node) consensus residuals of the fitted state."""
        st = self._require_fit()
        return core.consensus_residuals(st, self.problem_)


class DTSVM(_ConsensusSolver):
    """Prop. 1: decentralized multi-task transfer SVM."""

    def make_problem(self, X, y, mask=None, adj=None, *, active=None,
                     couple=None) -> core.DTSVMProblem:
        """The full Prop.-1 problem tensor from user arrays.

        X: (V, T, N, p) float32, y/mask: (V, T, N), adj: (V, V) bool;
        ``active`` (V, T) / ``couple`` (V,) mask mixed networks
        (Fig. 6).  Hyper-parameters come from ``self.config``.
        """
        cfg = self.config
        return core.make_problem(
            X, y, mask, adj, C=cfg.C, eps1=cfg.eps1, eps2=cfg.eps2,
            eta1=cfg.eta1, eta2=cfg.eta2, box_scale=cfg.box_scale,
            active=active, couple=couple)


class DSVM(_ConsensusSolver):
    """Forero et al. single-task consensus SVM — the paper's baseline [7].

    Per-task independent training; ``couple`` is forced to 0 and the
    shared term is disabled (see ``repro.core.dsvm``).  ``eps1``/``eta1``
    from the config are ignored by construction.
    """

    def make_problem(self, X, y, mask=None, adj=None, *, active=None,
                     couple=None) -> core.DTSVMProblem:
        """The same problem tensor with task coupling forced off and
        Forero's V*C box — the paper's single-task baseline [7].
        ``couple`` is ignored by construction."""
        cfg = self.config
        return dsvm_lib.make_dsvm_problem(
            X, y, mask, adj, C=cfg.C, eps2=cfg.eps2, eta2=cfg.eta2,
            active=active)


class CSVM:
    """Centralized pooled SVM per task — the paper's baseline [13].

    Same surface, different math: all nodes' data for a task is pooled
    and one box-QP solved per task.  ``fit`` accepts the identical
    (V, T, N, p) layout (plus plain (N, p) single-task data) so swapping
    CSVM for DTSVM in an experiment is still a one-line change.
    """

    def __init__(self, config: Optional[SolverConfig] = None, *,
                 C_scale: float = 1.0, **overrides):
        self.config = _as_solver_config(config, overrides)
        self.C_scale = C_scale
        self.w_: Optional[jnp.ndarray] = None      # (T, p)
        self.b_: Optional[jnp.ndarray] = None      # (T,)
        self.history_ = None

    def init_state(self, prob=None):
        """The fitted (w (T, p), b (T,)) pair — CSVM has no ADMM state."""
        return (self.w_, self.b_)

    def step(self, state, prob):
        """CSVM is a direct (single-shot) solver — always raises."""
        raise NotImplementedError(
            "CSVM is a direct (single-shot) solver; use fit()")

    def fit(self, X, y, mask=None, adj=None, **_ignored) -> "CSVM":
        """Pool all nodes' data per task and solve one box QP per task.

        Accepts the identical (V, T, N, p) layout (plus plain (N, p)
        single-task data); ``adj`` is accepted and ignored so swapping
        CSVM for DTSVM stays a one-line change.  Returns self.
        """
        if self.config.net is not None:
            raise ValueError("SolverConfig.net models a decentralized "
                             "network; CSVM is centralized (no links to "
                             "model) — drop net or use DSVM/DTSVM")
        if self.config.telemetry:
            raise ValueError("SolverConfig.telemetry streams the ADMM "
                             "loop's consensus diagnostics; CSVM is a "
                             "direct (single-shot) solver — drop "
                             "telemetry or use DSVM/DTSVM")
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float32)
        if X.ndim == 2:                       # single task, pooled already
            X = X[None, None]
            y = y[None, None]
        V, T, N, p = X.shape
        if mask is None:
            mask = np.ones((V, T, N), np.float32)
        mask = np.asarray(mask, np.float32)
        # pool nodes per task, then one vmapped solve over all T tasks
        # (bit-for-bit the per-task loop it replaces — tested)
        self.w_, self.b_ = csvm_lib.csvm_fit_tasks(
            jnp.asarray(X.transpose(1, 0, 2, 3).reshape(T, V * N, p)),
            jnp.asarray(y.transpose(1, 0, 2).reshape(T, V * N)),
            self.config.C * self.C_scale,
            jnp.asarray(mask.transpose(1, 0, 2).reshape(T, V * N)),
            qp_iters=self.config.qp_iters)
        return self

    def _require_fit(self):
        if self.w_ is None:
            raise RuntimeError("call fit() first")

    def decision(self, X) -> jnp.ndarray:
        """X: (T, n, p) -> (T, n) decision values."""
        self._require_fit()
        X = jnp.asarray(X, jnp.float32)
        if X.ndim == 2:
            X = X[None]
        return (jnp.einsum("tnp,tp->tn", X, self.w_, precision="highest")
                + self.b_[:, None])

    def predict(self, X) -> jnp.ndarray:
        """Predicted labels in {-1, +1}: (T, n) for (T, n, p) inputs."""
        return jnp.sign(self.decision(X))

    def risks(self, X_test, y_test) -> jnp.ndarray:
        """(T,) per-task test risks (no node axis: the model is pooled)."""
        self._require_fit()
        y_test = jnp.asarray(y_test, jnp.float32)
        if y_test.ndim == 1:
            y_test = y_test[None]
        g = self.decision(X_test)
        return jnp.mean((jnp.sign(g) != jnp.sign(y_test)).astype(jnp.float32),
                        axis=-1)

    def global_risks(self, X_test, y_test) -> np.ndarray:
        """(T,) risks as numpy — already network-global (pooled model)."""
        return np.asarray(self.risks(X_test, y_test))

    def residuals(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """A centralized model is trivially in consensus."""
        z = jnp.float32(0.0)
        return z, z
