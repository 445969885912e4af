"""Batched sweep engine: one compiled plan, many hyper-parameter configs.

The paper's headline experiments (Figs. 3-6) are *sweeps* — grids over C,
eps1/eps2, label-imbalance scenarios, mixed-network masks — yet a serial
driver pays the full ``compile_problem`` + trace/compile cost once per
grid point for problems that differ only in a handful of scalars.  This
module stacks a config axis S over ONE shared invariant build:

    shared      —  Z (the label-signed data) depends only on (X, y, mask)
                   and is built exactly once for the whole sweep;
    per-config  —  the a-diagonal, u, counts, QP box and Gershgorin step
                   size are tiny per-config leaves, and the Gram
                   re-weighting K = Z diag(a) Z^T runs as ONE batched
                   kernel call over the stacked a instead of S calls.

Execution is a single vmapped ``plan_step`` scanned over the ADMM
iterations, so the whole grid traces and compiles once.  Results are
bitwise identical to the serial ``compile_problem`` loop over
``per_config_problems`` (tested: tests/test_sweep.py) — the per-config
scalar constants are rounded to float32 host-side in exactly the order
the serial path rounds them.

Three execution paths:

    plan = compile_sweep(prob, cfgs, qp_iters=..., qp_solver=...)
    states, hist = plan.run(iters=60, eval_fn=ev)       # vmapped, default
    states, hist = plan.run_chain(iters=60)             # warm-start chain
    states = plan.run_sharded(60, mesh=mesh)            # configs on devices

``run_chain`` scans the config axis sequentially, warm-starting config
s from config s-1's final state (the annealing/continuation pattern),
still against the one shared invariant build.  ``run_sharded`` tiles the
config axis across devices via shard_map — optionally ALONGSIDE the node
axis (a 2-D (sweep, nodes) mesh reusing ``core.dtsvm_dist``'s collective
neighbor sums), matching the single-host path bitwise (tests/test_dist).
"""
from __future__ import annotations

import functools
from typing import Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtsvm as core
from repro.engine import invariants as inv_lib
from repro.engine import qp_engines
from repro.engine.plan import DEFAULT_QP_SOLVER, Plan, plan_step

# Hyper-parameters a config may override (everything in DTSVMProblem that
# is a scalar); ``active`` / ``couple`` masks may also vary per config.
SWEEP_FIELDS = ("C", "eps1", "eps2", "eta1", "eta2", "box_scale")
_MASK_FIELDS = ("active", "couple")

# vmap axis trees for one config slice: data/graph leaves are shared
# (None), hyper-parameter and mask leaves carry the config axis.
_PROB_AXES = core.DTSVMProblem(
    X=None, y=None, mask=None, adj=None, C=0, eps1=0, eps2=0, eta1=0,
    eta2=0, box_scale=0, active=0, couple=0)
_INV_AXES = inv_lib.PlanInvariants(ntp=0, nbr=0, u=0, a=0, Z=None, K=0,
                                   hi=0, L=0)


def _overrides_of(cfg) -> dict:
    """Normalize one sweep entry to a dict of DTSVMProblem field
    overrides.  A mapping is a PARTIAL override (missing keys keep the
    base problem's values); a SolverConfig-like object is a COMPLETE
    spec — every scalar hyper-parameter it carries is taken (a dataclass
    cannot distinguish user-set fields from defaults)."""
    if isinstance(cfg, Mapping):
        d = dict(cfg)
        unknown = set(d) - set(SWEEP_FIELDS) - set(_MASK_FIELDS)
        if unknown:
            raise ValueError(
                f"unknown sweep override(s) {sorted(unknown)}; "
                f"sweepable: {SWEEP_FIELDS + _MASK_FIELDS}")
        return d
    # SolverConfig (or any object with the hyper-parameter attributes)
    d = {k: getattr(cfg, k) for k in SWEEP_FIELDS if hasattr(cfg, k)}
    return d


def per_config_problems(prob: core.DTSVMProblem,
                        cfgs: Sequence) -> list:
    """The S problems a serial driver would build — one ``DTSVMProblem``
    per config, sharing the data/graph arrays of ``prob``; scalar
    overrides get the same 0-d float32 canonicalization
    ``core.make_problem`` applies.  This is both the construction the
    sweep compiler stacks AND the reference the equivalence tests loop
    ``compile_problem`` over.
    """
    if not len(cfgs):
        raise ValueError("empty config grid")
    out = []
    for cfg in cfgs:
        d = _overrides_of(cfg)
        pc = prob
        # same scalar canonicalization as core.make_problem (0-d float32)
        scalars = {k: jnp.asarray(float(v), jnp.float32)
                   for k, v in d.items()
                   if k in SWEEP_FIELDS and v is not None}
        if scalars:
            pc = pc._replace(**scalars)
        for k in _MASK_FIELDS:
            if d.get(k) is not None:
                pc = pc._replace(**{k: jnp.asarray(d[k], jnp.float32)})
        out.append(pc)
    return out


def _check_static(cfgs, qp_iters, qp_solver):
    """Per-fit statics (loop lengths, engine choice) cannot vary along a
    batched axis — validate and resolve them once for the whole sweep."""
    for key, explicit, default in (("qp_iters", qp_iters, 200),
                                   ("qp_solver", qp_solver,
                                    DEFAULT_QP_SOLVER)):
        vals = {getattr(c, key) for c in cfgs if hasattr(c, key)}
        if len(vals) > 1:
            raise ValueError(
                f"configs disagree on static {key!r} ({sorted(map(str, vals))}); "
                f"a sweep shares one compiled loop — split the grid or pass "
                f"{key}= explicitly")
        if explicit is None:
            explicit = vals.pop() if vals else default
        if key == "qp_iters":
            qp_iters = int(explicit)
        else:
            qp_solver = str(explicit)
    return qp_iters, qp_solver


class SweepPlan:
    """A compiled sweep: S configs stacked over one shared invariant build.

    ``prob`` is the batched problem (hyper-parameter leaves are (S,)
    float32 arrays, ``active``/``couple`` carry a leading S axis; the
    data/graph leaves are the original shared arrays), ``inv`` the
    batched invariants (Z shared — no S axis — everything else stacked).
    """

    def __init__(self, base: core.DTSVMProblem, prob: core.DTSVMProblem,
                 inv: inv_lib.PlanInvariants, config_problems: list, *,
                 qp_iters: int = 200, qp_solver: str = DEFAULT_QP_SOLVER,
                 budget: Optional[inv_lib.PlanBudget] = None):
        self.base = base
        self.prob = prob
        self.inv = inv
        self.config_problems = config_problems
        self.n_configs = len(config_problems)
        self.qp_iters = qp_iters
        self.qp_solver = qp_solver
        self.budget = budget

    # -- execution (single host, vmapped) ----------------------------------
    def init_state(self) -> core.DTSVMState:
        """Zero ADMM state with a leading config axis: leaves (S, V, T, ...)."""
        st = core.init_state(self.base)
        return jax.tree.map(
            lambda x: jnp.zeros((self.n_configs,) + x.shape, x.dtype), st)

    def _step1(self, nbr_reduce: Optional[Callable] = None) -> Callable:
        return lambda pr, iv, st: plan_step(
            pr, iv, st, qp_iters=self.qp_iters, qp_solver=self.qp_solver,
            nbr_reduce=nbr_reduce)

    def step(self, state: core.DTSVMState) -> core.DTSVMState:
        """One ADMM iteration for every config at once (vmapped)."""
        return jax.vmap(self._step1(),
                        in_axes=(_PROB_AXES, _INV_AXES, 0))(
            self.prob, self.inv, state)

    def run(self, state: Optional[core.DTSVMState] = None, iters: int = 1,
            eval_fn: Optional[Callable] = None):
        """Scan ``iters`` iterations of the whole grid.  Returns
        ``(states, history)`` with per-config leading axes: state leaves
        (S, V, T, ...), history (iters, S, ...) stacking
        ``eval_fn(state_s)`` per config (or None)."""
        if state is None:
            state = self.init_state()
        vstep = jax.vmap(self._step1(), in_axes=(_PROB_AXES, _INV_AXES, 0))

        def body(st, _):
            st = vstep(self.prob, self.inv, st)
            out = jax.vmap(eval_fn)(st) if eval_fn is not None \
                else jnp.float32(0)
            return st, out

        state, hist = jax.lax.scan(body, state, None, length=iters)
        return state, (hist if eval_fn is not None else None)

    # -- warm-start chain --------------------------------------------------
    def run_chain(self, state: Optional[core.DTSVMState] = None,
                  iters: int = 1, eval_fn: Optional[Callable] = None):
        """Run the configs SEQUENTIALLY, config s warm-starting from
        config s-1's final state (continuation/annealing sweeps), as one
        scan over the config axis — still a single trace/compile.

        ``state`` is a single unbatched warm start for config 0 (zeros
        when omitted).  Returns ``(states, history)`` shaped exactly like
        ``run``: the per-config FINAL states stacked on axis 0, history
        (iters, S, ...).  Bitwise identical to serially looping
        ``compile_problem(...).run(state=prev, iters=iters)``.
        """
        if state is None:
            state = core.init_state(self.base)
        base, Z = self.base, self.inv.Z
        qp_iters, qp_solver = self.qp_iters, self.qp_solver
        xs = (
            tuple(getattr(self.prob, k) for k in SWEEP_FIELDS),
            (self.prob.active, self.prob.couple),
            tuple(getattr(self.inv, k)
                  for k in ("ntp", "nbr", "u", "a", "K", "hi", "L")),
        )

        def chain_body(st, xs_s):
            scalars, (act, cpl), (ntp, nbr, u, a, K, hi, L) = xs_s
            pr = base._replace(**dict(zip(SWEEP_FIELDS, scalars)),
                               active=act, couple=cpl)
            iv = inv_lib.PlanInvariants(ntp=ntp, nbr=nbr, u=u, a=a, Z=Z,
                                        K=K, hi=hi, L=L)

            def body(s, _):
                s = plan_step(pr, iv, s, qp_iters=qp_iters,
                              qp_solver=qp_solver)
                out = eval_fn(s) if eval_fn is not None else jnp.float32(0)
                return s, out

            st, hist = jax.lax.scan(body, st, None, length=iters)
            return st, (st, hist)

        _, (states, hist) = jax.lax.scan(chain_body, state, xs)
        if eval_fn is None:
            return states, None
        return states, jnp.swapaxes(hist, 0, 1)        # -> (iters, S, ...)

    # -- multi-device tiling ----------------------------------------------
    def run_sharded(self, iters: int, *, mesh=None, sweep_axis: str = "sweep",
                    node_axis: Optional[str] = None, topology: str = "graph",
                    state: Optional[core.DTSVMState] = None):
        """Tile the config axis across devices (shard_map), optionally
        ALONGSIDE the node axis on a 2-D (sweep, nodes) mesh where the
        neighbor sums run as collectives (``topology="graph" | "ring"``,
        same contract as ``core.dtsvm_dist``).  Returns the final stacked
        states; per-iteration histories stay a single-host feature.
        Numerically identical to ``run`` (tested under forced host
        devices for both topologies)."""
        from jax.sharding import PartitionSpec as P

        from repro.core import dtsvm_dist

        if topology not in ("graph", "ring"):
            raise ValueError(f"unknown topology {topology!r}; "
                             f"expected 'graph' or 'ring'")
        V = self.base.X.shape[0]
        if mesh is None:
            mesh = make_sweep_mesh(self.n_configs,
                                   V if node_axis is not None else None,
                                   sweep_axis=sweep_axis,
                                   node_axis=node_axis or "nodes")
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        missing = {sweep_axis} | ({node_axis} if node_axis else set())
        missing -= set(shape)
        if missing:
            raise ValueError(
                f"mesh axes {tuple(mesh.axis_names)} lack {sorted(missing)}; "
                f"pass a mesh with matching sweep_axis/node_axis names "
                f"(e.g. make_sweep_mesh(n_configs, V))")
        if self.n_configs % shape[sweep_axis]:
            raise ValueError(
                f"{self.n_configs} configs do not tile evenly over "
                f"{shape[sweep_axis]} '{sweep_axis}' devices")
        if node_axis is not None and V % shape[node_axis]:
            raise ValueError(f"{V} nodes do not tile evenly over "
                             f"{shape[node_axis]} '{node_axis}' devices")

        sw = P(sweep_axis)
        nd = P(node_axis) if node_axis else P()
        swnd = P(sweep_axis, node_axis) if node_axis else sw
        prob_spec = core.DTSVMProblem(
            X=nd, y=nd, mask=nd, adj=P(), C=sw, eps1=sw, eps2=sw, eta1=sw,
            eta2=sw, box_scale=sw, active=swnd, couple=swnd)
        inv_spec = inv_lib.PlanInvariants(ntp=swnd, nbr=swnd, u=swnd,
                                          a=swnd, Z=nd, K=swnd, hi=swnd,
                                          L=swnd)
        state_spec = core.DTSVMState(r=swnd, alpha=swnd, beta=swnd,
                                     lam=swnd)
        qp_iters, qp_solver = self.qp_iters, self.qp_solver

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(state_spec, prob_spec, inv_spec, nd),
                           out_specs=state_spec, check_vma=False)
        def run_shard(st, pr, iv, adj_rows):
            if node_axis is not None:
                nbr_reduce = dtsvm_dist._nbr_reduce_for(
                    adj_rows.astype(jnp.float32), axis=node_axis,
                    topology=topology)
            else:
                adjf = adj_rows.astype(jnp.float32)
                nbr_reduce = lambda arr: jnp.einsum("vu,utd->vtd", adjf,
                                                      arr, precision="highest")
            step1 = lambda p_, i_, s_: plan_step(
                p_, i_, s_, qp_iters=qp_iters, qp_solver=qp_solver,
                nbr_reduce=nbr_reduce)
            vstep = jax.vmap(step1, in_axes=(_PROB_AXES, _INV_AXES, 0))

            def body(s, _):
                return vstep(pr, iv, s), None

            st, _ = jax.lax.scan(body, st, None, length=iters)
            return st

        if state is None:
            state = self.init_state()
        return jax.jit(run_shard)(state, self.prob, self.inv,
                                  self.base.adj)

    # -- per-config views --------------------------------------------------
    def config_plan(self, s: int) -> Plan:
        """The serial ``Plan`` of config ``s``, sharing this sweep's
        invariant slices (no recompute) — handy for drilling into one
        grid point with the single-problem API."""
        iv = inv_lib.PlanInvariants(*[
            getattr(self.inv, k) if k == "Z" else getattr(self.inv, k)[s]
            for k in inv_lib.PlanInvariants._fields])
        return Plan(self.config_problems[s], iv, qp_iters=self.qp_iters,
                    qp_solver=self.qp_solver, budget=self.budget)


def make_sweep_mesh(n_configs: int, n_nodes: Optional[int] = None, *,
                    sweep_axis: str = "sweep", node_axis: str = "nodes"):
    """A mesh tiling configs (and optionally nodes) over the available
    devices: 1-D ``(sweep,)`` or 2-D ``(sweep, nodes)``.  The sweep axis
    takes the largest divisor of ``n_configs`` that fits the device
    budget, so configs always tile evenly over as many devices as
    possible."""
    from repro.dist.sharding import largest_divisor_leq

    n_dev = len(jax.devices())
    if n_nodes is None:
        n_sweep = largest_divisor_leq(n_configs, n_dev)
        devs = np.asarray(jax.devices()[:n_sweep])
        return jax.sharding.Mesh(devs, (sweep_axis,))
    n_sweep = largest_divisor_leq(n_configs, n_dev // n_nodes)
    need = n_sweep * n_nodes
    if n_dev < need:
        raise ValueError(f"need {need} devices, have {n_dev}")
    devs = np.asarray(jax.devices()[:need]).reshape(n_sweep, n_nodes)
    return jax.sharding.Mesh(devs, (sweep_axis, node_axis))


def compile_sweep(prob: core.DTSVMProblem, cfgs: Sequence, *,
                  qp_iters: Optional[int] = None,
                  qp_solver: Optional[str] = None,
                  nbr_counts: Optional[jnp.ndarray] = None,
                  budget: Optional[inv_lib.PlanBudget] = None) -> SweepPlan:
    """Compile S hyper-parameter configs over ``prob``'s data into one
    batched ``SweepPlan``.

    Parameters
    ----------
    prob : core.DTSVMProblem
        The base problem whose data/graph every config shares.
    cfgs : sequence
        Override mappings (keys among ``SWEEP_FIELDS`` +
        ``active``/``couple``) or SolverConfig-like objects.  Statics
        (``qp_iters``, ``qp_solver``) must agree across the grid.
    qp_iters, qp_solver : optional
        Explicit statics for the whole sweep (resolved against the
        configs by ``_check_static``).
    nbr_counts : jnp.ndarray, optional
        Precomputed (V, T) active-neighbor counts.
    budget : invariants.PlanBudget, optional
        Memory budget for the stacked (S, V, T, N, N) Gram build — the
        sweep's K is S times the single-fit K, so this is where the
        dense build runs out of memory first.  Streaming is bitwise
        identical to the dense batched call.

    Returns
    -------
    SweepPlan
        The shared Z is built once; u/a/counts/box are stacked from the
        exact host-side per-config arithmetic the serial path performs
        (keeping results bitwise identical), and the Gram re-weighting
        runs as one batched ``weighted_gram`` over the stacked
        a-diagonal (or as budgeted row panels).
    """
    qp_iters, qp_solver = _check_static(cfgs, qp_iters, qp_solver)
    qp_engines.get(qp_solver)            # fail fast on unknown engines
    for key, default in (("qp_precision", "f32"),
                         ("qp_operator", "materialized")):
        bad = {getattr(c, key) for c in cfgs
               if getattr(c, key, default) not in (None, default)}
        if bad:
            raise ValueError(
                f"compile_sweep shares one stacked materialized-K build; "
                f"{key}={sorted(bad)} is per-fit only — use "
                f"compile_problem/SolverConfig for non-default QP modes")
    probs = per_config_problems(prob, cfgs)
    Z = inv_lib.compute_z(prob)

    parts = [inv_lib._masks_part(pc, nbr_counts) for pc in probs]
    ntp, nbr, u, a, hi = (jnp.stack([p[i] for p in parts])
                          for i in range(5))
    K, L = inv_lib.gram_and_lipschitz(Z, a, budget)   # Z shared under a
    inv = inv_lib.PlanInvariants(ntp=ntp, nbr=nbr, u=u, a=a, Z=Z, K=K,
                                 hi=hi, L=L)

    def stack_f32(field):
        return jnp.asarray([getattr(pc, field) for pc in probs],
                           jnp.float32)

    sweep_prob = prob._replace(
        **{k: stack_f32(k) for k in SWEEP_FIELDS},
        active=jnp.stack([pc.active for pc in probs]),
        couple=jnp.stack([pc.couple for pc in probs]))
    return SweepPlan(prob, sweep_prob, inv, probs, qp_iters=qp_iters,
                     qp_solver=qp_solver, budget=budget)
