"""OnlineSession — the paper's Fig. 7 setting as a first-class object.

Tasks enter and leave a LIVE consensus network without restarting: only
the ``active`` (V, T) and ``couple`` (V,) masks change between stages,
while the ADMM state (r, alpha, beta, warm-started duals) carries over.
The session owns exactly that bookkeeping:

    sess = OnlineSession(X, y, mask=mask, adj=adj,
                         config=SolverConfig(eps2=100.0, qp_iters=100))
    sess.run(30)                       # stage 1: all tasks independent
    sess.drop_task(1); sess.set_coupling(True)
    sess.run(30)                       # stage 2: task 0 couples with 2
    ...
    sess.risks(X_test, y_test)

Membership masks are DATA, not problem structure: every stage sees a
``DTSVMProblem`` with identical array shapes/dtypes, so the compiled
ADMM scan is reused across stages (jax's compilation cache keys on the
computation, which never changes) instead of re-lowering per stage.

The session plans incrementally (``repro.engine``): the first ``run``
compiles the problem's loop-invariants into a ``Plan``; afterwards each
membership event only invalidates the invariants it touches — counts,
U/a diagonals, the QP box, and the K Gram slices of the (v,t) pairs
whose ``a`` row actually changed — while every untouched Gram block
carries over bit-for-bit (``plan_stats`` counts the reuse).  This is
the enter/leave story of Fig. 7 without ever rebuilding the problem
from scratch.

Replaying a stage schedule through a session is bit-for-bit identical to
the hand-rolled per-stage ``make_problem`` + ``run_dtsvm`` loop it
replaces (tested).  ``jit=True`` additionally wraps each ``run`` in one
``jax.jit`` call — fastest across many short stages, numerically
equivalent but not bitwise (XLA fuses differently inside jit).

With a communication model (``SolverConfig(net=NetConfig(...))`` or
``backend="async"``) the session becomes fabric-aware: mailboxes, delay
rings and byte counters persist across ``run`` calls (one continuous
message stream — drops are keyed on the absolute round), and every
membership event warm-fills the affected tasks' mailboxes from the
neighbors' current variables before the next round (the Fig.-7 join
story), metered separately as ``warmfill_msgs``.  The identity
NetConfig reproduces the vmap session bitwise, stage for stage
(tested); ``net_report_`` holds the cumulative byte accounting.

The NODE set is elastic too (``repro.net.elastic``; docs/churn.md):
``node_enter`` / ``node_leave`` / ``node_crash`` / ``node_recover``
schedule membership events at the session's current absolute round —
a dead node freezes and publishes nothing, a graceful leaver's
mailbox columns are garbage-collected, a joiner/recoverer warm-fills,
and ``node_recover(v, from_state=restored.state)`` grafts the node's
rows from a durable ``repro.store`` snapshot (the crash-recovery
story).  Event emission is continuation-safe, so a churn session
split across stages — or saved and restored mid-stream — stays
bitwise one long run.

Sessions are durable (``repro.store``): ``SessionStore.save`` snapshots
the whole thing — state, masks, plan fingerprint, live fabric — and the
restored session continues bitwise; ``OnlineSession(..., log=EventLog())``
additionally records every constructor/membership/run decision so
``repro.store.replay`` rebuilds the session from history alone.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import backends, evaluate
from repro.api.solvers import SolverConfig, _as_solver_config, \
    effective_backend
from repro.core import dtsvm as core
from repro.core import sparse
from repro.engine import plan as engine_plan
from repro.obs import telemetry as obs_telemetry


@functools.partial(jax.jit, static_argnames=("iters", "qp_iters",
                                             "with_eval", "qp_solver"))
def _run_jitted(prob, state, Xte, yte, iters, qp_iters, with_eval,
                qp_solver="fista"):
    ev = (lambda st: core.risks(st.r, Xte, yte)) if with_eval else None
    return core.run_dtsvm(prob, iters, qp_iters, state=state, eval_fn=ev,
                          qp_solver=qp_solver)


def _node_index(nodes, V: int):
    return slice(None) if nodes is None else np.asarray(nodes, int)


class OnlineSession:
    """Carry ADMM state across task enter/leave events (paper Fig. 7)."""

    def __init__(self, X, y, mask=None, adj=None, *,
                 config: Optional[SolverConfig] = None,
                 active=None, couple=None, X_test=None, y_test=None,
                 jit: bool = False, log=None, **overrides):
        self.config = _as_solver_config(config, overrides)
        sparse.require_dense(X, "OnlineSession (its replans)")
        self._X = jnp.asarray(X, jnp.float32)
        self._y = jnp.asarray(y, jnp.float32)
        V, T, N, p = self._X.shape
        self._mask = (jnp.ones((V, T, N), jnp.float32) if mask is None
                      else jnp.asarray(mask, jnp.float32))
        self._adj = (jnp.zeros((V, V), bool) if adj is None
                     else jnp.asarray(adj, bool))
        self.V, self.T = V, T
        self._active = (np.ones((V, T), np.float32) if active is None
                        else np.array(active, np.float32, copy=True))
        self._couple = (np.ones((V,), np.float32) if couple is None
                        else np.array(couple, np.float32, copy=True))
        self._jit = jit
        self._test = None
        if X_test is not None:
            self._test = evaluate.broadcast_test_set(X_test, y_test, V)
        self.state: Optional[core.DTSVMState] = None
        self.iteration = 0
        self.history = []            # one (iters, V, T) risk block per run()
        self._plan: Optional[engine_plan.Plan] = None
        self._masks_dirty = False    # membership changed since last plan
        # node-level membership (repro.net.elastic): the absolute-round
        # event list is continuation-safe — every run passes the WHOLE
        # list and the fabric replays past events into its start status
        self._node_events = []
        # fabric-aware (async backend) bookkeeping: live mailboxes/delay
        # rings/counters and the absolute round of the message stream
        self._net_fabric = None
        self._net_state = None
        self._net_series = []        # per-round bytes, across all stages
        self.net_report_: Optional[dict] = None
        # obs convergence streams, concatenated across run() calls when
        # config.telemetry is set (repro.obs; iteration axis = rounds)
        self.telemetry_: Optional[dict] = None
        if jit and self._effective_backend() == "async":
            raise ValueError("jit=True is a vmap-session feature; the "
                             "async fabric already scans its rounds — "
                             "drop jit or the net config")
        # event log (repro.store.events): duck-typed — anything with an
        # append(event, **payload) method; the init record captures the
        # constructor so replay() can rebuild the session from history
        self._log = log
        self._emit("init", X=self._X, y=self._y, mask=self._mask,
                   adj=self._adj, config=self.config.to_dict(),
                   active=self._active.copy(), couple=self._couple.copy(),
                   jit=jit,
                   X_test=None if X_test is None
                   else np.asarray(X_test, np.float32),
                   y_test=None if y_test is None
                   else np.asarray(y_test, np.float32))

    def _emit(self, event: str, **payload) -> None:
        """Append one record to the session's event log, if any."""
        if self._log is not None:
            self._log.append(event, **payload)

    # ------------------------------------------------------------------
    # membership events
    # ------------------------------------------------------------------
    @property
    def active(self) -> np.ndarray:
        """(V, T) activity mask (copy; mutate via the event methods)."""
        return self._active.copy()

    @property
    def couple(self) -> np.ndarray:
        """(V,) task-coupling mask (copy)."""
        return self._couple.copy()

    def add_task(self, task: int, nodes: Optional[Sequence[int]] = None
                 ) -> "OnlineSession":
        """Activate ``task`` at ``nodes`` (default: everywhere)."""
        self._active[_node_index(nodes, self.V), task] = 1.0
        self._masks_dirty = True
        self._emit("add_task", task=int(task), nodes=None if nodes is None
                   else [int(n) for n in nodes])
        return self

    def drop_task(self, task: int, nodes: Optional[Sequence[int]] = None
                  ) -> "OnlineSession":
        """Deactivate ``task``; its per-node state freezes but persists,
        so the task re-enters later exactly where it left off."""
        self._active[_node_index(nodes, self.V), task] = 0.0
        self._masks_dirty = True
        self._emit("drop_task", task=int(task), nodes=None if nodes is None
                   else [int(n) for n in nodes])
        return self

    def set_active(self, active) -> "OnlineSession":
        """Replace the whole (V, T) activity mask at once (bulk form of
        ``add_task``/``drop_task``)."""
        self._active = np.array(active, np.float32, copy=True).reshape(
            self.V, self.T)
        self._masks_dirty = True
        self._emit("set_active", active=self._active.copy())
        return self

    def set_coupling(self, on: Union[bool, float, np.ndarray],
                     nodes: Optional[Sequence[int]] = None
                     ) -> "OnlineSession":
        """Turn cross-task consensus on/off, per node or globally."""
        if np.ndim(on) == 0:
            self._couple[_node_index(nodes, self.V)] = float(on)
        else:
            if nodes is not None:
                raise ValueError(
                    "pass either a full (V,) couple mask OR a scalar with "
                    "nodes=, not both")
            self._couple = np.array(on, np.float32, copy=True).reshape(self.V)
        self._masks_dirty = True
        self._emit("set_coupling",
                   on=float(on) if np.ndim(on) == 0
                   else np.array(on, np.float32),
                   nodes=None if nodes is None
                   else [int(n) for n in nodes])
        return self

    # ------------------------------------------------------------------
    # node-level membership (repro.net.elastic)
    # ------------------------------------------------------------------
    def _membership(self):
        from repro.net import elastic
        if not self._node_events:
            return None
        return elastic.Membership(events=tuple(self._node_events))

    def _node_event(self, kind: str, node: int) -> None:
        if self._effective_backend() != "async":
            raise ValueError(
                "node membership events are a fabric feature — configure "
                "a communication model (SolverConfig(net=NetConfig(...))) "
                "or backend='async' first")
        from repro.net import elastic
        self._node_events.append(elastic.MembershipEvent(
            round=self.iteration, kind=kind, node=int(node)))
        # a buffer-mode (identity fast path) fabric has no per-receiver
        # mailboxes to GC/fill: drop it so the next run rebuilds in
        # mailbox mode, warm from the current state (byte counters
        # restart — churn sessions should start under a lossy/explicit
        # mailbox config when cumulative accounting matters)
        if self._net_fabric is not None and self._net_fabric.mode == "buffer":
            self._net_fabric = None
            self._net_state = None

    def node_enter(self, node: int) -> "OnlineSession":
        """A NEW node joins the live network at the current round: it
        starts computing and its incident mailboxes warm-fill (metered
        as ``warmfill_msgs``).  Idempotent on an already-live node."""
        self._node_event("enter", node)
        self._emit("node_enter", node=int(node))
        return self

    def node_leave(self, node: int) -> "OnlineSession":
        """A GRACEFUL departure: neighbors withdraw the node's links and
        garbage-collect its mailbox contributions immediately."""
        self._node_event("leave", node)
        self._emit("node_leave", node=int(node))
        return self

    def node_crash(self, node: int) -> "OnlineSession":
        """An ABRUPT death: neighbors don't know — they keep spending
        bytes into its mailbox and its stale values linger until the
        bounded-staleness policy (``NetConfig.stale_limit``) ages them
        out."""
        self._node_event("crash", node)
        self._emit("node_crash", node=int(node))
        return self

    def node_recover(self, node: int, from_state: Optional[
            core.DTSVMState] = None) -> "OnlineSession":
        """The crashed node rejoins; its incident mailboxes warm-fill
        like an enter.  ``from_state`` (e.g. the ``.state`` of a session
        restored from a ``repro.store`` snapshot) grafts that state's
        row ``node`` over the local one — the crash-recovery story: the
        node restarts from its last durable checkpoint."""
        if from_state is not None and self.state is None:
            raise RuntimeError("run() the session before recovering "
                               "a node from a snapshot state")
        self._node_event("recover", node)
        rows = None
        if from_state is not None:
            self.state = core.DTSVMState(*(
                jnp.asarray(cur).at[node].set(jnp.asarray(src)[node])
                for cur, src in zip(self.state, from_state)))
            rows = {k: np.asarray(v[node])
                    for k, v in zip(core.DTSVMState._fields, from_state)}
        self._emit("node_recover", node=int(node), rows=rows)
        return self

    @property
    def node_status(self) -> dict:
        """Current per-node membership: ``{"alive": (V,) bool mask,
        "events": [event dicts fired so far]}``."""
        mem = self._membership()
        alive = (np.ones(self.V, bool) if mem is None
                 else mem.alive_at(self.V, self.iteration) > 0)
        return {"alive": alive,
                "events": [] if mem is None
                else [e.to_dict() for e in mem.events]}

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def problem(self) -> core.DTSVMProblem:
        """The current-stage problem: same arrays, fresh masks.

        The masks are COPIED here: jnp.asarray may alias numpy memory on
        CPU, and the membership events mutate ``_active``/``_couple`` in
        place — possibly while an async dispatched run still reads them.
        """
        cfg = self.config
        return core.make_problem(
            self._X, self._y, self._mask, self._adj, C=cfg.C,
            eps1=cfg.eps1, eps2=cfg.eps2, eta1=cfg.eta1, eta2=cfg.eta2,
            box_scale=cfg.box_scale, active=self._active.copy(),
            couple=self._couple.copy())

    def _current_plan(self) -> engine_plan.Plan:
        """The stage's Plan: compiled once, then incrementally re-planned
        — a membership event recomputes only the invariants it touched
        (the untouched Gram slices are reused bit-for-bit)."""
        if self._plan is None:
            self._plan = engine_plan.compile_problem(
                self.problem(), self.config,
                backend=self._effective_backend())
        elif self._masks_dirty:
            self._plan = self._plan.replan(active=self._active.copy(),
                                           couple=self._couple.copy())
        self._masks_dirty = False
        return self._plan

    @property
    def plan_stats(self) -> dict:
        """Invariant-reuse counters of the incremental planner (empty
        before the first ``run``)."""
        return {} if self._plan is None else dict(self._plan.stats)

    def _effective_backend(self) -> str:
        return effective_backend(self.config)

    def _async_net_kwargs(self, was_dirty: bool, old_active,
                          plan: engine_plan.Plan) -> dict:
        """Carried fabric state for the async backend, with the Fig.-7
        warm-fill applied when membership changed since the last run."""
        cfg = self.config
        if (was_dirty and self._net_state is not None
                and old_active is not None):
            changed = np.asarray(plan.prob.active) != old_active
            if changed.any():
                payload = self.state.r * plan.prob.active[..., None]
                self._net_state = self._net_fabric.warm_fill(
                    self._net_state, payload,
                    jnp.asarray(changed, jnp.float32))
        out = {}
        kw = dict(plan=plan, fabric=self._net_fabric,
                  fabric_state=self._net_state, round0=self.iteration,
                  meter_out=out)
        if cfg.net is not None:
            kw["net"] = cfg.net
        mem = self._membership()
        if mem is not None:
            kw["membership"] = mem
        return kw

    def run(self, iters: Optional[int] = None, *, record: bool = True):
        """Advance the live network ``iters`` ADMM iterations under the
        CURRENT membership masks.  Returns the (iters, V, T) risk curve
        when a test set was given (and ``record``), else None."""
        cfg = self.config
        backend = self._effective_backend()
        iters = iters if iters is not None else cfg.iters
        self._emit("run", iters=int(iters), record=bool(record))
        with_eval = record and self._test is not None
        default_qp_mode = (cfg.qp_precision == "f32"
                           and cfg.qp_operator in (None, "materialized"))
        # the legacy jitted fast path runs the core loop, which only
        # knows the materialized f32 operator — non-default QP modes
        # and telemetry collection take the plan path below, which
        # threads them through.
        if self._jit and backend == "vmap" and default_qp_mode \
                and not cfg.telemetry:
            Xte, yte = self._test if with_eval else (None, None)
            prob = self.problem()
            if self.state is None:
                self.state = core.init_state(prob)
            self.state, hist = _run_jitted(prob, self.state, Xte, yte,
                                           iters, cfg.qp_iters, with_eval,
                                           cfg.qp_solver)
            if not with_eval:
                hist = None
        else:
            ev = None
            if with_eval:
                Xte, yte = self._test
                ev = lambda st: core.risks(st.r, Xte, yte)  # noqa: E731
            was_dirty = self._masks_dirty
            old_active = (None if self._plan is None
                          else np.asarray(self._plan.prob.active))
            use_plan = backend in ("vmap", "async")
            plan = self._current_plan() if use_plan else None
            prob = plan.prob if plan is not None else self.problem()
            if self.state is None:
                self.state = core.init_state(prob)
            options = dict(cfg.backend_options)
            if plan is not None:
                options["plan"] = plan
            elif cfg.budget is not None:
                # plan-less backends compile per call — keep the K
                # build streamed there too
                options.setdefault("budget", cfg.budget)
            if backend == "async":
                options.update(self._async_net_kwargs(was_dirty,
                                                      old_active, plan))
            if cfg.telemetry:
                options["telemetry"] = obs_telemetry.Telemetry()
                options["telemetry_out"] = {}
            self.state, hist = backends.run(
                prob, iters, backend=backend, qp_iters=cfg.qp_iters,
                qp_solver=cfg.qp_solver, qp_precision=cfg.qp_precision,
                qp_operator=cfg.qp_operator, state=self.state, eval_fn=ev,
                **options)
            if backend == "async":
                out = options["meter_out"]
                self._net_fabric = out["fabric"]
                self._net_state = out["fabric_state"]
                self._net_series.extend(
                    out["report"]["bytes_round_series"])
            if cfg.telemetry:
                streams = options["telemetry_out"].get("streams")
                if streams is not None:
                    self.telemetry_ = obs_telemetry.concat_streams(
                        self.telemetry_, streams)
        self.iteration += iters
        if backend == "async":
            from repro.net import meter
            # cumulative accounting: the fabric counters carry across
            # stages, so re-derive against the total round count
            self.net_report_ = meter.report(
                self._net_fabric, self._net_state, rounds=self.iteration,
                bytes_per_round=np.asarray(self._net_series))
            mem = self._membership()
            if mem is not None:
                self.net_report_["membership"] = {
                    "events": [e.to_dict() for e in mem.events],
                    "final_alive": [float(a) for a in
                                    mem.alive_at(self.V, self.iteration)],
                }
        if hist is not None:
            self.history.append(np.asarray(hist))
        return None if hist is None else np.asarray(hist)

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------
    def _require_state(self) -> core.DTSVMState:
        if self.state is None:
            raise RuntimeError("run() the session first")
        return self.state

    def risks(self, X_test=None, y_test=None) -> jnp.ndarray:
        """(V, T) risks on the given (or construction-time) test set."""
        st = self._require_state()
        if X_test is None:
            if self._test is None:
                raise ValueError("no test set given")
            Xte, yte = self._test
            return core.risks(st.r, Xte, yte)
        return evaluate.risks_of_state(st, X_test, y_test)

    def global_risks(self, X_test=None, y_test=None) -> np.ndarray:
        """(T,) network-average risks."""
        return evaluate.global_risks(self.risks(X_test, y_test))

    def residuals(self):
        """(task, node) consensus residuals under the current masks."""
        return core.consensus_residuals(self._require_state(), self.problem())
