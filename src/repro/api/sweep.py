"""``sweep_fit`` — the whole hyper-parameter grid as ONE fit call.

The paper's Figs. 3-6 all sweep something (eps grids, C grids, imbalance
scenarios, mixed-network masks) over fixed data.  A serial driver loops
``fit()`` per grid point and re-traces/re-compiles S near-identical
problems; ``sweep_fit`` compiles the grid once through
``repro.engine.sweep`` and runs every config in a single vmapped scan
(or tiled across devices), with per-config results bitwise identical to
the serial loop:

    res = sweep_fit(X, y, [{"eps1": e1, "eps2": e2} for e1 in G for e2 in G],
                    mask=mask, adj=adj, base=SolverConfig(iters=60),
                    X_test=X_test, y_test=y_test)
    res.final_global_risks()        # (S, T) — what the figures plot
    res.history                     # (iters, S, V, T) risk curves

Each config is a mapping of PARTIAL overrides (keys: C, eps1, eps2,
eta1, eta2, box_scale, active, couple) applied on top of ``base``, or a
full ``SolverConfig`` — which is a COMPLETE spec: all six scalar
hyper-parameters come from it (a dataclass cannot tell user-set fields
from defaults), ``base`` then only supplies the statics and the
active/couple masks.  Statics (iters, qp_iters, qp_solver, backend)
cannot vary inside one sweep.  ``dsvm_overrides`` expresses the
paper's DSVM baseline as a config, so a DTSVM-vs-DSVM comparison on
shared data (Figs. 5/6) is a 2-config sweep instead of two fits.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import backends, evaluate
from repro.api.solvers import SolverConfig
from repro.core import dsvm as dsvm_lib
from repro.core import dtsvm as core
from repro.core import sparse
from repro.engine import sweep as sweep_lib


def dsvm_overrides(V: int, *, active=None) -> Dict[str, Any]:
    """The DSVM baseline (Forero et al.) as sweep-config overrides:
    coupling off, the shared term forced to zero, Forero's V*C box —
    the same field values ``core.dsvm.make_dsvm_problem`` applies
    (single definition: ``dsvm_problem_fields``)."""
    d = dict(dsvm_lib.dsvm_problem_fields(V))
    if active is not None:
        d["active"] = active
    return d


@dataclass
class SweepResult:
    """Stacked outcome of one sweep: every array carries a leading
    config axis S (in ``history`` it is axis 1: (iters, S, V, T))."""
    configs: List
    states: core.DTSVMState              # leaves (S, V, T, ...)
    history: Optional[np.ndarray]        # (iters, S, V, T) risks or None
    plan: sweep_lib.SweepPlan
    chained: bool = False

    def __len__(self) -> int:
        return self.plan.n_configs

    def state_of(self, s: int) -> core.DTSVMState:
        """The final ADMM state of config ``s`` (unbatched leaves)."""
        return jax.tree.map(lambda x: x[s], self.states)

    def risks(self, X_test, y_test) -> jnp.ndarray:
        """(S, V, T) per-config/node/task risks on the shared test set."""
        return evaluate.risks_of_state(self.states, X_test, y_test)

    def global_risks(self, X_test, y_test) -> np.ndarray:
        """(S, T) network-average risks per config."""
        return np.asarray(self.risks(X_test, y_test)).mean(axis=-2)

    def final_risks(self) -> np.ndarray:
        """(S, V, T) last-iteration risks from the recorded curve."""
        if self.history is None:
            raise ValueError("no history: pass X_test/y_test to sweep_fit")
        return np.asarray(self.history[-1])

    def final_global_risks(self) -> np.ndarray:
        """(S, T) last-iteration network-average risks from the curve."""
        return self.final_risks().mean(axis=-2)


def _split_grid(cfgs: Sequence, base: Optional[SolverConfig]):
    """Resolve the statics (iters/qp/backends) and the per-config
    override list from a mixed grid of mappings / SolverConfigs."""
    base = base if base is not None else SolverConfig()
    solver_cfgs = [c for c in cfgs if isinstance(c, SolverConfig)]
    if base.net is not None or any(c.net is not None for c in solver_cfgs):
        raise ValueError(
            "SolverConfig.net is a single-fit (async backend) feature; "
            "the batched sweep runs the synchronous engine — fit lossy "
            "configs one at a time through DTSVM(cfg.replace(net=...))")
    for key in ("iters", "qp_iters", "qp_solver", "backend"):
        vals = {getattr(c, key) for c in solver_cfgs}
        vals.add(getattr(base, key))
        if len(vals) > 1:
            raise ValueError(
                f"configs disagree on static {key!r} ({sorted(map(str, vals))});"
                f" a sweep shares one compiled loop — split the grid")
    return base, list(cfgs)


def sweep_fit(X, y, cfgs: Sequence, mask=None, adj=None, *,
              base: Optional[SolverConfig] = None, active=None, couple=None,
              iters: Optional[int] = None, X_test=None, y_test=None,
              chain: bool = False, state: Optional[core.DTSVMState] = None,
              backend: Optional[str] = None,
              backend_options: Optional[Dict[str, Any]] = None
              ) -> SweepResult:
    """Fit every config of a hyper-parameter grid in one batched run.

    Data layout is the repo-wide convention (X (V,T,N,p), y/mask (V,T,N),
    test sets (T,n,p) shared across nodes); ``base`` fills hyper-
    parameters a mapping config leaves out and supplies the statics (a
    ``SolverConfig`` config instead specifies all six scalars itself —
    see the module docstring).  ``chain``
    runs the grid sequentially with warm starts (config s starts from
    config s-1's final state) instead of independently.  ``backend``
    "vmap" (default) or "shard_map" (``backend_options``: mesh /
    sweep_axis / node_axis / topology) — tiles the config axis across
    devices; histories are a vmap-backend feature.  ``base.budget``
    (``PlanBudget``) streams the stacked (S, V, T, N, N) Gram build
    through bounded row panels — the sweep's K is S times a single
    fit's, so large grids hit memory first (API.md §scale).
    """
    sparse.require_dense(X, "sweep_fit")
    base, cfgs = _split_grid(cfgs, base)
    prob = core.make_problem(
        X, y, mask, adj, C=base.C, eps1=base.eps1, eps2=base.eps2,
        eta1=base.eta1, eta2=base.eta2, box_scale=base.box_scale,
        active=active, couple=couple)
    plan = sweep_lib.compile_sweep(prob, cfgs, qp_iters=base.qp_iters,
                                   qp_solver=base.qp_solver,
                                   budget=base.budget)
    eval_fn = None
    if X_test is not None:
        eval_fn = evaluate.risk_eval_fn(prob.X.shape[0], X_test, y_test)
    states, hist = backends.run_sweep(
        plan, iters if iters is not None else base.iters,
        backend=backend if backend is not None else base.backend,
        state=state, eval_fn=eval_fn, chain=chain,
        **(backend_options if backend_options is not None
           else base.backend_options))
    return SweepResult(configs=cfgs, states=states,
                       history=None if hist is None else np.asarray(hist),
                       plan=plan, chained=chain)
