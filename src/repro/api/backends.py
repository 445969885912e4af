"""Execution backends behind ``Solver.fit``: one registry, one signature.

A backend is a callable

    run(prob, iters, *, qp_iters, qp_solver, state, eval_fn, **options)
        -> (DTSVMState, history | None)

over the SAME ``DTSVMProblem``; switching backends changes how the
Prop.-1 iteration executes, never what it computes.  Every backend goes
through the plan/execute engine (``repro.engine``): loop-invariants are
compiled once per fit, then the light per-iteration body runs.

- ``"vmap"``       single-host, dense-adjacency einsum neighbor sums —
                   the default.  Accepts a prebuilt ``plan=`` (the
                   online Session passes its incrementally re-planned
                   one) and builds one otherwise.
- ``"shard_map"``  one device per network node, neighbor sums as
                   collectives (``repro.core.dtsvm_dist``), the plan
                   compiled per node inside the shard; accepts
                   ``topology="graph" | "ring"`` and an optional ``mesh``.
- ``"async"``      the communication fabric (``repro.net``): the SAME
                   compiled plan stepped against per-node mailboxes
                   behind lossy/delayed/quantized links and activation
                   schedules, with byte metering.  Accepts ``net=``
                   (a ``repro.net.NetConfig``), a prebuilt ``plan=`` /
                   ``fabric_state=`` / ``round0=`` (the online Session
                   carries both across stages), and ``meter_out=`` — a
                   dict the backend fills with the run's byte report and
                   final fabric state (the ``(state, history)`` return
                   contract leaves no slot for them).

- ``"sample_shard"`` a node's local samples split across devices (the
                   large-n path, API.md §scale): each device owns an
                   N/S row panel of every (v,t) dual Hessian; the
                   ``row_panels`` hook of ``plan_step`` all-gathers the
                   iterate per QP step and reduces the dual linear
                   term across the sample axis (``reduce="gather"`` is
                   bitwise the vmap fit; ``"psum"`` is the cheap
                   equivalent).  Accepts ``n_shards=`` / ``mesh=`` and
                   a ``budget=`` for streamed panel builds.

Every backend steps the one ``engine.plan_step``, in a build program
and a scan program compiled once per shape; they differ in where the
invariants live and in the step's hooks.  All are numerically equivalent in their lossless configurations — the
async backend's identity fabric and the sample-sharded gather mode are
bitwise the vmap path (tested); pick by config, not by import.
``qp_solver`` selects the inner dual engine ("fista" | "pg" |
"pallas_fused" — ``repro.engine.qp_engines``); ``budget=``
(``engine.PlanBudget``) streams every backend's invariant build.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtsvm as core
from repro.core import dtsvm_dist, sparse
from repro.dist import sharding as shard_lib
from repro.engine import invariants as inv_lib
from repro.engine import plan as engine_plan
from repro.kernels import ops as kops
from repro.net import async_admm
from repro.obs import spans as obs_spans
from repro.obs import telemetry as obs_telemetry

_REGISTRY: Dict[str, Callable] = {}


def register(name: str):
    """Register a backend runner under ``name`` (decorator)."""
    def deco(fn: Callable) -> Callable:
        _REGISTRY[name] = fn
        return fn
    return deco


def get(name: str) -> Callable:
    """The registered backend runner for ``name`` (ValueError if absent)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def names():
    """Sorted names of every registered fit backend."""
    return sorted(_REGISTRY)


@register("vmap")
def _run_vmap(prob: core.DTSVMProblem, iters: int, *, qp_iters: int = 200,
              qp_solver: str = "fista", qp_precision: str = "f32",
              qp_operator: Optional[str] = None,
              state: Optional[core.DTSVMState] = None, eval_fn=None,
              plan: Optional[engine_plan.Plan] = None, budget=None,
              telemetry=None, telemetry_out: Optional[dict] = None,
              **_ignored):
    """Single-host backend: one compiled plan, one scanned fit.

    Parameters
    ----------
    prob : core.DTSVMProblem
        The problem to fit.
    iters : int
        ADMM iterations.
    qp_iters, qp_solver, qp_precision, qp_operator
        Inner dual solve configuration (``engine.qp_engines``); a
        ``qp_operator`` of None is chosen from the shapes
        (``engine.plan.resolve_qp_operator``).
    state : core.DTSVMState, optional
        Warm start (zeros when omitted).
    eval_fn : callable, optional
        Per-iteration hook ``state -> array``; stacked into the history.
    plan : engine.Plan, optional
        Prebuilt plan (the online Session passes its incrementally
        re-planned one); must agree with ``prob``/``qp_iters``/
        ``qp_solver``.
    budget : engine.PlanBudget, optional
        Streams the invariant (K) build through bounded row panels —
        bitwise identical to the dense build (ignored when ``plan`` is
        prebuilt).
    telemetry : repro.obs.Telemetry, optional
        Collect per-iteration convergence diagnostics inside the fit's
        scan (extra scan outputs — the model outputs stay bitwise).
    telemetry_out : dict, optional
        Receives ``{"streams": {name: np.ndarray}}`` (materialized after
        the scan) — the ``(state, history)`` return contract leaves no
        slot for the streams.

    Returns
    -------
    (core.DTSVMState, history or None)
    """
    if plan is None:
        plan = engine_plan.compile_problem(prob, qp_iters=qp_iters,
                                           qp_solver=qp_solver,
                                           qp_precision=qp_precision,
                                           qp_operator=qp_operator,
                                           budget=budget)
    elif (plan.prob is not prob or plan.qp_iters != qp_iters
          or plan.qp_solver != qp_solver
          or plan.qp_precision != qp_precision
          or qp_operator not in (None, plan.qp_operator)):
        raise ValueError(
            "prebuilt plan= disagrees with the call: pass prob=plan.prob "
            "and matching qp_iters/qp_solver/qp_precision/qp_operator "
            "(or omit plan=)")
    if telemetry is None:
        return plan.run(state=state, iters=iters, eval_fn=eval_fn)
    st, hist, streams = plan.run(state=state, iters=iters, eval_fn=eval_fn,
                                 telemetry=telemetry)
    if telemetry_out is not None:
        telemetry_out["streams"] = obs_telemetry.materialize(streams)
    return st, hist


@register("shard_map")
def _run_shard_map(prob: core.DTSVMProblem, iters: int, *,
                   qp_iters: int = 200, qp_solver: str = "fista",
                   state: Optional[core.DTSVMState] = None, eval_fn=None,
                   topology: str = "graph", mesh=None, axis: str = "nodes",
                   budget=None, telemetry=None,
                   telemetry_out: Optional[dict] = None):
    """One device per network node; neighbor sums as collectives.

    ``topology`` selects ``"graph"`` (all_gather + adjacency mask) or
    ``"ring"`` (two ppermute exchanges); ``budget``
    (``engine.PlanBudget``) streams each node's local K build.  Same
    ``(state, history)`` contract as ``"vmap"``.  ``telemetry`` routes
    through the planned-runner host loop (like ``eval_fn``) and
    collects the diagnostics from each round's committed state — the
    per-round states are bitwise the scanned path's, so the streams
    are too.
    """
    if topology not in ("graph", "ring"):
        raise ValueError(f"unknown topology {topology!r}; "
                         f"expected 'graph' or 'ring'")
    if eval_fn is None and telemetry is None:
        st = dtsvm_dist.run_dtsvm_dist(prob, iters, mesh=mesh, axis=axis,
                                       topology=topology, qp_iters=qp_iters,
                                       state=state, qp_solver=qp_solver,
                                       budget=budget)
        return st, None
    # per-iteration history/telemetry: compile the node-sharded plan
    # invariants ONCE, then step against them between host evaluations.
    # The decentralized deployment would log locally instead.
    if mesh is None:
        mesh = dtsvm_dist.make_node_mesh(prob.X.shape[0], axis)
    compile_fn, run1 = dtsvm_dist.build_planned_runner(
        mesh, axis=axis, topology=topology, qp_iters=qp_iters, iters=1,
        qp_solver=qp_solver, budget=budget)
    inv = compile_fn(prob)
    st = core.init_state(prob) if state is None else state
    hi = None
    if telemetry is not None:
        hi = inv_lib._masks_part(prob)[4]
    hist, tel_rows = [], []
    for _ in range(iters):
        prev = st
        st = run1(st, prob, inv)
        if eval_fn is not None:
            hist.append(eval_fn(st))
        if telemetry is not None:
            tel_rows.append(telemetry.collect(prob, hi, st, prev))
    if telemetry_out is not None and tel_rows:
        telemetry_out["streams"] = {
            k: np.stack([np.asarray(row[k], np.float32)
                         for row in tel_rows])
            for k in tel_rows[0]}
    return st, (jnp.stack(hist) if eval_fn is not None else None)


@register("async")
def _run_async(prob: core.DTSVMProblem, iters: int, *, qp_iters: int = 200,
               qp_solver: str = "fista",
               state: Optional[core.DTSVMState] = None, eval_fn=None,
               net=None, plan: Optional[engine_plan.Plan] = None,
               fabric=None, fabric_state=None, round0: int = 0,
               meter_out: Optional[dict] = None, budget=None,
               telemetry=None, telemetry_out: Optional[dict] = None,
               membership=None):
    """The communication fabric (``repro.net``): the same compiled plan
    stepped against per-node mailboxes behind lossy/delayed/quantized
    links, with byte metering.  ``net`` is a ``repro.net.NetConfig``;
    ``meter_out`` (a dict) receives the byte report and final fabric
    state; ``budget`` streams the plan's K build when no prebuilt
    ``plan`` is passed; ``telemetry`` / ``telemetry_out`` collect the
    per-round convergence streams (plus ``bytes_round``) from the same
    scan; ``membership`` (a ``repro.net.Membership``) schedules node
    enter/leave/crash/recover events over the run (docs/churn.md).
    """
    if plan is not None and (plan.prob is not prob
                             or plan.qp_iters != qp_iters
                             or plan.qp_solver != qp_solver):
        raise ValueError(
            "prebuilt plan= disagrees with the call: pass prob=plan.prob "
            "and matching qp_iters/qp_solver (or omit plan=)")
    res = async_admm.run_async(
        prob, iters, net=net, plan=plan, fabric=fabric,
        fabric_state=fabric_state, qp_iters=qp_iters, qp_solver=qp_solver,
        state=state, eval_fn=eval_fn, round0=round0, budget=budget,
        telemetry=telemetry, membership=membership)
    if meter_out is not None:
        meter_out["report"] = res.report
        meter_out["fabric"] = res.fabric
        meter_out["fabric_state"] = res.fabric_state
    if telemetry_out is not None and res.telemetry is not None:
        telemetry_out["streams"] = res.telemetry
    return res.state, res.history


def _panel_specs(axis: str):
    """``(prob, state, invariants)`` specs of the sample-sharded plan:
    every N-sized leaf splits over ``axis``, the rest replicate."""
    prob_spec, state_spec = shard_lib.sample_specs(axis)
    repl = shard_lib.P()
    return prob_spec, state_spec, inv_lib.PlanInvariants(
        ntp=repl, nbr=repl, u=repl, a=repl, Z=prob_spec.X, K=prob_spec.X,
        hi=prob_spec.y, L=repl)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "budget",
                                             "_pallas"))
def _panel_build(prob, *, mesh, axis, budget, _pallas):
    """Each device's invariants as one cached program: counts/u/a
    replicated, Z/hi/K as its row panels, L the global Gershgorin bound;
    plus Z over all samples (``reduce="gather"`` contracts the gathered
    duals with it).  ``_pallas`` keys the cache on the kernel switch
    ``weighted_gram_rows`` reads at trace time."""
    prob_spec, _, inv_spec = _panel_specs(axis)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(prob_spec,),
                       out_specs=(inv_spec, shard_lib.P()), check_vma=False)
    def build(pr):
        with obs_spans.scope("invariant_build"):
            ntp, nbr, u, a, hi = inv_lib._masks_part(pr)
            Z = inv_lib.compute_z(pr)                      # (V,T,Nl,p+1)
            with obs_spans.scope("neighbour_exchange"):
                Z_full = jax.lax.all_gather(Z, axis, axis=2, tiled=True)
            V, T, Nl = Z.shape[:3]
            chunk = None if budget is None else \
                budget.row_chunk(V * T, Nl, cols=Z_full.shape[2])
            tile = None if budget is None else budget.tile
            if chunk is None:
                K = kops.weighted_gram_rows(Z, a, Z_full, tile=tile)
                rs = jnp.sum(jnp.abs(K), axis=-1)
            else:
                K, rs = inv_lib.streamed_gram_panel(Z, a, Z_full, chunk, tile)
            # global Gershgorin bound: max over ALL rows (max is exact)
            with obs_spans.scope("neighbour_exchange"):
                L = jnp.maximum(jax.lax.pmax(jnp.max(rs, axis=-1), axis),
                                1e-12)
        return inv_lib.PlanInvariants(ntp=ntp, nbr=nbr, u=u, a=a, Z=Z, K=K,
                                      hi=hi, L=L), Z_full

    return build(prob)


def _panel_scan(state, prob, inv, Z_full, *, mesh, axis, iters, qp_iters,
                qp_solver, reduce, telemetry, eval_fn=None):
    """``iters`` ``plan_step``s on each device's row panels, the
    sample-axis collectives as its ``row_panels`` hook."""
    prob_spec, state_spec, inv_spec = _panel_specs(axis)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(state_spec, prob_spec, inv_spec,
                                 shard_lib.P()),
                       out_specs=(state_spec, shard_lib.P()),
                       check_vma=False)
    def scan(st, pr, iv, Z_full):
        def gather(y):              # local rows -> all rows, exact concat
            with obs_spans.scope("neighbour_exchange"):
                return jax.lax.all_gather(y, axis, axis=y.ndim - 1,
                                          tiled=True)

        def zl(lam, Z):
            if reduce == "gather":
                return jnp.einsum("vtn,vtnd->vtd", gather(lam), Z_full,
                                  precision="highest")
            part = jnp.einsum("vtn,vtnd->vtd", lam, Z, precision="highest")
            with obs_spans.scope("neighbour_exchange"):
                return jax.lax.psum(part, axis)

        panels = engine_plan.RowPanels(gather=gather, zl=zl)

        def body(s, _):
            with obs_spans.scope("scan_execute"):
                new = engine_plan.plan_step(pr, iv, s, qp_iters=qp_iters,
                                            qp_solver=qp_solver,
                                            row_panels=panels)
                out = (eval_fn(new) if eval_fn is not None
                       else jnp.float32(0))
                # None is an empty pytree node: telemetry-off scans carry
                # exactly the original outputs (bitwise contract)
                tel = (None if telemetry is None
                       else obs_telemetry.collect_shard_diagnostics(
                           pr, iv.hi, new, s, telemetry.streams, axis))
            return new, (out, tel)

        return jax.lax.scan(body, st, None, length=iters)

    return scan(state, prob, inv, Z_full)


#: the scan as one cached program, keyed on the shapes and the static
#: configuration (``engine.plan._plan_scan``'s counterpart)
_panel_scan_jit = jax.jit(_panel_scan, static_argnames=(
    "mesh", "axis", "iters", "qp_iters", "qp_solver", "reduce", "telemetry"))


@register("sample_shard")
def _run_sample_shard(prob: core.DTSVMProblem, iters: int, *,
                      qp_iters: int = 200, qp_solver: str = "fista",
                      state: Optional[core.DTSVMState] = None, eval_fn=None,
                      mesh=None, n_shards: Optional[int] = None,
                      axis: str = "samples", reduce: str = "gather",
                      budget=None, telemetry=None,
                      telemetry_out: Optional[dict] = None, **_ignored):
    """Split every node's local samples across devices (the large-n path).

    Each device owns an N/S row slice of the (V, T, N, p) problem tensor
    and builds ONLY its row panel K[rows, :] of every (v,t) dual Hessian
    (``kernels.ops.weighted_gram_rows``, optionally streamed under
    ``budget``) — per-device Gram memory drops from N² to N²/S.  The
    scan steps ``engine.plan_step`` on those panels with the sample-axis
    collectives as its ``row_panels`` hook: one all-gather of the
    (V, T, N) iterate per inner QP step, and the dual linear term
    reduced across the sample axis; the O(p)-sized consensus math is
    replicated.  Build and scan are cached programs (``_panel_build``,
    ``_panel_scan_jit``); a call with an ``eval_fn`` traces anew.

    Parameters
    ----------
    mesh : jax.sharding.Mesh, optional
        1-D mesh named ``axis`` (default:
        ``dist.sharding.make_sample_mesh``).
    n_shards : int, optional
        Devices to split the sample axis over (when ``mesh`` is None).
    reduce : {"gather", "psum"}
        How the dual linear term X^T Y lam is reduced across the sample
        axis: ``"gather"`` gathers lam and reduces densely — BITWISE
        identical to the ``"vmap"`` backend (tested); ``"psum"`` sums
        per-shard partials — one (p+1)-vector of traffic instead of N,
        numerically equivalent but not bitwise (float addition
        reassociates).
    budget : engine.PlanBudget, optional
        Streams each device's K panel build through bounded row chunks.

    Notes
    -----
    ``qp_solver`` must be ``"fista"`` or ``"pg"`` (the fused Pallas
    engine assumes the square single-device Hessian).  ``eval_fn`` runs
    inside the shard and must depend only on the replicated state leaves
    (``r``/``alpha``/``beta``) — the standard risk hook does.
    ``telemetry`` collects inside the shard too: the state streams come
    from the replicated ``r``, the box-face fraction from per-shard
    partial sums combined with one psum
    (``obs.telemetry.collect_shard_diagnostics``).
    """
    if qp_solver not in ("fista", "pg"):
        raise ValueError(
            f"sample_shard supports qp_solver 'fista' | 'pg', got "
            f"{qp_solver!r} (the fused Pallas engine assumes the square "
            f"single-device Hessian)")
    if reduce not in ("gather", "psum"):
        raise ValueError(f"unknown reduce {reduce!r}; "
                         f"expected 'gather' or 'psum'")
    N = prob.X.shape[2]
    if mesh is None:
        mesh = shard_lib.make_sample_mesh(N, n_shards, axis=axis)
    n_dev = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if N % n_dev:
        raise ValueError(f"{N} samples do not tile evenly over {n_dev} "
                         f"'{axis}' devices")
    with obs_spans.span("invariant_build", budgeted=budget is not None):
        inv, Z_full = _panel_build(prob, mesh=mesh, axis=axis,
                                   budget=budget, _pallas=kops._use_pallas())
    if state is None:
        state = core.init_state(prob)
    kw = dict(mesh=mesh, axis=axis, iters=int(iters), qp_iters=qp_iters,
              qp_solver=qp_solver, reduce=reduce, telemetry=telemetry)
    if eval_fn is None:
        scan = functools.partial(_panel_scan_jit, **kw)
    else:                         # a per-call closure: traced anew, uncached
        scan = jax.jit(functools.partial(_panel_scan, eval_fn=eval_fn, **kw))
    with obs_spans.span("scan_execute", iters=int(iters)):
        st, (hist, tel) = scan(state, prob, inv, Z_full)
    if telemetry_out is not None and tel is not None:
        telemetry_out["streams"] = obs_telemetry.materialize(tel)
    return st, (hist if eval_fn is not None else None)


def run(prob: core.DTSVMProblem, iters: int, *, backend: str = "vmap",
        qp_iters: int = 200, qp_solver: str = "fista",
        qp_precision: str = "f32", qp_operator: Optional[str] = None,
        state=None, eval_fn=None, **options):
    """Dispatch one fit through the named backend.

    ``backend`` is a registry name (``names()`` lists them:
    ``"vmap" | "shard_map" | "async" | "sample_shard"``); ``options``
    pass through to the backend runner (e.g. ``topology=``, ``net=``,
    ``n_shards=``, ``budget=``, ``telemetry=``/``telemetry_out=`` —
    every backend collects the obs convergence streams).  Returns
    ``(state, history | None)``.

    The mixed-precision / factored-operator QP modes
    (``qp_precision="bf16"`` / ``qp_operator="factored"``) are a
    single-host plan feature: only the ``"vmap"`` backend threads them
    (any other backend raises on a non-default value — the sharded
    paths carry their own dual layouts).  So is a sparse X
    (``core.sparse.SparseRows``): any other backend raises on it.
    ``qp_operator=None`` lets the vmap plan choose the operator from the
    shapes (``engine.plan.resolve_qp_operator``); the other backends run
    it as materialized.
    """
    if backend != "vmap":
        sparse.require_dense(prob.X, f"the {backend!r} backend")
        if qp_precision != "f32" or qp_operator not in (None,
                                                        "materialized"):
            raise ValueError(
                f"qp_precision/qp_operator are vmap-backend features; "
                f"backend={backend!r} runs the exact materialized-f32 "
                f"dual path only")
    else:
        options = dict(options, qp_precision=qp_precision,
                       qp_operator=qp_operator)
    return get(backend)(prob, iters, qp_iters=qp_iters, qp_solver=qp_solver,
                        state=state, eval_fn=eval_fn, **options)


# -- batched sweeps ---------------------------------------------------------
_SWEEP_REGISTRY: Dict[str, Callable] = {}


def register_sweep(name: str):
    """Register a sweep runner: ``run(plan, iters, *, state, eval_fn,
    chain, **options) -> (states, history | None)`` over a prebuilt
    ``repro.engine.SweepPlan`` (decorator)."""
    def deco(fn: Callable) -> Callable:
        _SWEEP_REGISTRY[name] = fn
        return fn
    return deco


@register_sweep("vmap")
def _run_sweep_vmap(plan, iters: int, *, state=None, eval_fn=None,
                    chain: bool = False, **_ignored):
    if chain:
        return plan.run_chain(state=state, iters=iters, eval_fn=eval_fn)
    return plan.run(state=state, iters=iters, eval_fn=eval_fn)


@register_sweep("shard_map")
def _run_sweep_shard_map(plan, iters: int, *, state=None, eval_fn=None,
                         chain: bool = False, mesh=None,
                         sweep_axis: str = "sweep", node_axis=None,
                         topology: str = "graph"):
    if chain:
        raise ValueError("warm-start chains are sequential in the config "
                         "axis — use backend='vmap' for chain=True")
    if eval_fn is not None:
        raise ValueError("per-iteration histories are a single-host "
                         "feature; run the sharded sweep without "
                         "X_test/eval_fn and evaluate the final states")
    st = plan.run_sharded(iters, mesh=mesh, sweep_axis=sweep_axis,
                          node_axis=node_axis, topology=topology,
                          state=state)
    return st, None


def run_sweep(plan, iters: int, *, backend: str = "vmap", state=None,
              eval_fn=None, chain: bool = False, **options):
    """Dispatch one batched sweep through the named sweep backend."""
    try:
        fn = _SWEEP_REGISTRY[backend]
    except KeyError:
        raise ValueError(f"unknown sweep backend {backend!r}; available: "
                         f"{sorted(_SWEEP_REGISTRY)}") from None
    return fn(plan, iters, state=state, eval_fn=eval_fn, chain=chain,
              **options)
