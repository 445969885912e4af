"""Asynchronous Prop.-1 ADMM over a Fabric: stale mailboxes, real bytes.

The synchronous engine step (``repro.engine.plan_step``) touches the
network in exactly two places, both through its ``nbr_reduce`` hook:

    1. the f^{(k)} linear term sums the neighbors' PREVIOUS decision
       variables (eq. 11), and
    2. the beta multiplier update sums their FRESH ones (eq. 9).

``run_async`` re-executes the untouched ``plan_step`` with a fabric-
backed ``nbr_reduce``: call 1 reads the mailboxes as they stand (stale,
quantized, whatever the links delivered), call 2 publishes the node's
new variables through the fabric — one metered exchange per round — and
reads the post-delivery mailboxes.  Per-round activation masks from the
schedule gate both the state update (inactive nodes freeze, exactly the
``active``-mask semantics of the core) and the sends.

Because the identity fabric's reduce IS the synchronous dense-adjacency
einsum over exactly the values the vmap path would sum, the lossless /
zero-delay / trivial-schedule configuration reproduces
``compile_problem``'s trajectory BIT FOR BIT (tests/test_net.py) — the
async fabric is a strict generalization, not a parallel implementation.

The whole loop is one ``lax.scan``; fabric state (mailboxes, delay
rings, byte counters) is part of the carry, so a run can be split
across calls (the OnlineSession does) without changing the stream:
drops are keyed on the absolute round counter carried in the state.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtsvm as core
from repro.engine import plan as engine_plan
from repro.net import elastic as elastic_lib
from repro.net import fabric as fabric_lib
from repro.net import meter as meter_lib
from repro.net import schedule as schedule_lib
from repro.net.policies import NetConfig
from repro.obs import telemetry as obs_telemetry


class AsyncResult(NamedTuple):
    state: core.DTSVMState
    history: Optional[jnp.ndarray]    # (iters, ...) eval_fn outputs or None
    fabric_state: fabric_lib.FabricState
    report: dict                      # byte/message accounting (meter)
    fabric: fabric_lib.Fabric
    #: materialized per-round convergence streams (+ ``bytes_round``)
    #: when a ``telemetry=`` spec was passed, else None
    telemetry: Optional[dict] = None


def _fabric_step(plan: engine_plan.Plan, fab: fabric_lib.Fabric,
                 state: core.DTSVMState, fst: fabric_lib.FabricState,
                 act, links, task_counts):
    """One async round: the untouched ``plan_step`` against a fabric-
    backed ``nbr_reduce``, then the schedule's freeze merge."""
    calls = {"n": 0}
    cell = {}

    def nbr_reduce(arr):
        calls["n"] += 1
        if calls["n"] == 1:
            # eq. (11): last-received neighbor variables, as they stand
            return fab.reduce(fst)
        # eq. (9): publish this round's fresh variables, then read what
        # the links actually delivered
        fst2, bytes_now = fab.exchange(fst, arr, act, links,
                                       task_counts=task_counts)
        cell["fst"] = fst2
        cell["bytes"] = bytes_now
        return fab.reduce(fst2)

    new = engine_plan.plan_step(plan.prob, plan.inv, state,
                                qp_iters=plan.qp_iters,
                                qp_solver=plan.qp_solver,
                                nbr_reduce=nbr_reduce)
    if calls["n"] != 2:
        raise AssertionError(
            f"plan_step called nbr_reduce {calls['n']} times, expected 2 "
            f"(f-term + beta update); the fabric hook needs updating")
    # schedule freeze: a node that did not compute this round keeps its
    # whole state (same semantics as the core's task-level active mask)
    on = act > 0
    merged = core.DTSVMState(
        r=jnp.where(on[:, None, None], new.r, state.r),
        alpha=jnp.where(on[:, None, None], new.alpha, state.alpha),
        beta=jnp.where(on[:, None, None], new.beta, state.beta),
        lam=jnp.where(on[:, None, None], new.lam, state.lam),
    )
    return merged, cell["fst"], cell["bytes"]


def run_async(prob: core.DTSVMProblem, iters: int, *,
              net: Optional[NetConfig] = None,
              plan: Optional[engine_plan.Plan] = None,
              fabric: Optional[fabric_lib.Fabric] = None,
              fabric_state: Optional[fabric_lib.FabricState] = None,
              qp_iters: int = 200, qp_solver: str = "fista",
              state: Optional[core.DTSVMState] = None,
              eval_fn: Optional[Callable] = None,
              round0: int = 0, budget=None, telemetry=None,
              membership: Optional[elastic_lib.Membership] = None
              ) -> AsyncResult:
    """Run ``iters`` asynchronous rounds of Prop. 1 over the fabric.

    ``net`` declares the communication model (default: identity — the
    synchronous trajectory, now with byte metering).  ``budget``
    (``engine.PlanBudget``) streams the plan's K build through bounded
    row panels when no prebuilt ``plan`` is given.  ``plan`` /
    ``fabric`` / ``fabric_state`` let callers carry compiled invariants
    and live mailboxes across calls (the OnlineSession path); ``round0``
    enters the schedule stream at that absolute round (and, when
    ``fabric_state`` is None, starts the fabric's round counter there —
    a carried fabric_state keeps its own).

    ``membership`` (a ``repro.net.elastic.Membership``) makes the NODE
    set elastic: its alive mask multiplies the schedule's activations
    (dead nodes freeze — the scan shape never changes), its gone mask
    withdraws a graceful leaver's links, and its gc/fill masks fire the
    fabric's mailbox maintenance on the event round
    (``Fabric.apply_membership``).  A trivial membership (no events,
    all alive) is exactly ``membership=None`` — the identity contract
    is untouched.  Any real event forces mailbox mode.

    ``telemetry`` (a ``repro.obs.Telemetry``) collects per-round
    convergence diagnostics inside the same scan — extra scan outputs
    only, so the state/mailbox trajectory is bitwise the telemetry-None
    run — and folds the fabric's per-round byte counts in as a
    ``bytes_round`` stream, the per-node staleness clock as
    ``staleness`` (rounds, V), and (under a membership) the live-node
    count as ``nodes_alive``; the materialized dict lands on
    ``AsyncResult.telemetry``.
    """
    net = net if net is not None else NetConfig()
    if plan is None:
        plan = engine_plan.compile_problem(prob, qp_iters=qp_iters,
                                           qp_solver=qp_solver,
                                           budget=budget, backend="async")
    if state is None:
        state = core.init_state(prob)
    V = prob.X.shape[0]

    mem = membership
    if mem is not None and mem.is_trivial:
        mem = None                       # identity: exactly no membership
    sched = schedule_lib.resolve(net.schedule, seed=net.seed)
    acts, links = sched.emit(V, iters, adj=np.asarray(prob.adj),
                             round0=round0)
    mm = None
    if mem is not None:
        mm = mem.masks(V, iters, round0=round0)
        acts = np.asarray(acts) * mm["alive"]
        links = elastic_lib.combine_links(links, mm, np.asarray(prob.adj))
    acts = jnp.asarray(acts, jnp.float32)                  # (iters, V)
    has_links = links is not None
    if fabric is None:
        fabric = fabric_lib.build_fabric(prob, net,
                                         force_mailbox=has_links)
    elif has_links and fabric.mode == "buffer":
        raise ValueError("a link-varying schedule (or membership with "
                         "events) needs a mailbox-mode fabric; build it "
                         "with force_mailbox=True")
    if fabric_state is None:
        payload0 = state.r * prob.active[..., None]
        fabric_state = fabric.init_state(payload0, round0=round0)
    task_counts = jnp.sum(prob.active, axis=1)             # (V,) live rows

    xs = (acts,
          jnp.asarray(links) if has_links else jnp.zeros(
              (iters, 1), bool),
          jnp.asarray(mm["gc"]) if mem is not None else jnp.zeros(
              (iters, 1), bool),
          jnp.asarray(mm["fill"]) if mem is not None else jnp.zeros(
              (iters, 1), bool))

    def body(carry, x):
        st, fst = carry
        act, lnk, gcm, film = x
        lnk = lnk if has_links else None
        if mem is not None:
            # membership maintenance fires BEFORE the round's exchange:
            # GC a leaver's columns, warm-fill a joiner's edges from
            # everyone's current (masked) decision variables
            payload = st.r * plan.prob.active[..., None]
            fst = fabric.apply_membership(fst, gcm, film, payload)
        new, fst, bytes_now = _fabric_step(plan, fabric, st, fst, act, lnk,
                                           task_counts)
        ev = eval_fn(new) if eval_fn is not None else jnp.float32(0)
        # None is an empty pytree node: the telemetry-off scan carries
        # exactly the original outputs (bitwise contract)
        tel = (None if telemetry is None
               else telemetry.collect(plan.prob, plan.inv.hi, new, st))
        # per-node staleness: extra scan OUTPUT only — never in the
        # carry, so the state/mailbox trajectory stays bitwise
        stale = jnp.max(fst.silence, axis=1)
        return (new, fst), (ev, bytes_now, stale, tel)

    (state, fabric_state), (hist, bytes_rounds, stale_rounds, tel_streams) \
        = jax.lax.scan(body, (state, fabric_state), xs, length=iters)
    report = meter_lib.report(fabric, fabric_state, rounds=iters,
                              bytes_per_round=bytes_rounds)
    if mem is not None:
        fired = elastic_lib.events_in(mem, iters, round0)
        report["membership"] = {
            "events": [e.to_dict() for e in fired],
            "final_alive": ([] if iters == 0
                            else [float(a) for a in mm["alive"][-1]]),
            "epochs": len(mem.epochs(V, iters, round0=round0)),
        }
    tel_out = None
    if telemetry is not None:
        tel_out = obs_telemetry.materialize(tel_streams)
        tel_out["bytes_round"] = np.asarray(bytes_rounds, np.float32)
        tel_out["staleness"] = np.asarray(stale_rounds, np.float32)
        if mem is not None:
            tel_out["nodes_alive"] = mm["alive"].sum(axis=1).astype(
                np.float32)
    return AsyncResult(state=state,
                       history=hist if eval_fn is not None else None,
                       fabric_state=fabric_state, report=report,
                       fabric=fabric, telemetry=tel_out)
