"""Decentralized SPMD execution of DTSVM: one mesh axis = the node graph.

The single-host path computes neighbor sums by a dense-adjacency einsum.
Here the V nodes live on V devices of a ``nodes`` mesh axis, each holding
ONLY its own data shard — the paper's deployment model — and the neighbor
sum becomes a collective (DESIGN.md §3 hardware mapping):

- ``topology="graph"``: one ``all_gather`` of the (2p+2)-sized decision
  vectors followed by an adjacency-row mask.  Neighbor-only *information
  flow* is preserved by masking; decision vectors are tiny, so on a pod
  this is latency-bound and cheaper than emulated point-to-point.
- ``topology="ring"``:  two ``ppermute`` neighbor exchanges — the native
  ICI pattern, bit-exact for ring graphs.

Both reuse the exact Prop.-1 math via the ``nbr_reduce`` hook, so the SPMD
run is numerically identical to the single-host reference (tested).

Execution shards the *plan* (repro.engine): ``_node_build`` computes
each node's loop-invariants (Z, K, u, counts, box, step size) once, and
``_node_scan`` scans ``engine.plan_step`` against them.  Both are
module-level jits keyed on the shapes and the static configuration, so
a second run of one shape lowers nothing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import dtsvm
from repro.engine import invariants as inv_lib
from repro.engine import plan as engine_plan
from repro.kernels import ops as kops


def make_node_mesh(V: int, axis: str = "nodes") -> Mesh:
    devs = np.asarray(jax.devices()[:V])
    if len(devs) < V:
        raise ValueError(f"need {V} devices for {V} nodes, have {len(devs)}")
    return jax.sharding.Mesh(devs, (axis,))


def _nbr_reduce_for(adjf, *, axis: str, topology: str):
    """The collective neighbor sum for (V_local, ...) shards."""
    if topology == "ring":
        def nbr_reduce(arr):                                 # (Vl,T,D), Vl==1
            n = jax.lax.psum(1, axis)
            fwd = [(i, (i + 1) % n) for i in range(n)]
            bwd = [(i, (i - 1) % n) for i in range(n)]
            left = jax.lax.ppermute(arr, axis, fwd)
            right = jax.lax.ppermute(arr, axis, bwd)
            return left + right
    else:
        def nbr_reduce(arr):
            full = jax.lax.all_gather(arr, axis, axis=0, tiled=True)  # (V,T,D)
            return jnp.einsum("vu,utd->vtd", adjf, full, precision="highest")
    return nbr_reduce


def _node_specs(axis: str):
    """Sharding specs: state/problem/invariants over the node axis."""
    node = P(axis)
    repl = P()
    state_spec = dtsvm.DTSVMState(r=node, alpha=node, beta=node, lam=node)
    prob_spec = dtsvm.DTSVMProblem(
        X=node, y=node, mask=node, adj=repl,
        C=None, eps1=None, eps2=None, eta1=None, eta2=None, box_scale=None,
        active=node, couple=node)
    prob_spec = jax.tree.map(lambda s: s if isinstance(s, P) else repl,
                             prob_spec,
                             is_leaf=lambda x: isinstance(x, P) or x is None)
    inv_spec = inv_lib.PlanInvariants(ntp=node, nbr=node, u=node, a=node,
                                      Z=node, K=node, hi=node, L=node)
    return node, repl, state_spec, prob_spec, inv_spec


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "budget",
                                             "_pallas"))
def _node_build(prob, *, mesh, axis, budget, _pallas):
    """Each node's plan invariants (its local K build) as one cached
    program; ``_pallas`` keys the cache on the kernel switch the Gram
    kernels read at trace time."""
    node, repl, _, prob_spec, inv_spec = _node_specs(axis)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(prob_spec, node, repl), out_specs=inv_spec,
                       check_vma=False)
    def build(pr, adj_r, act_g):
        # adj rows shard over nodes; the active table stays global
        nbr_counts = jnp.einsum("vu,ut->vt", adj_r.astype(jnp.float32), act_g)
        return inv_lib.compute_invariants(pr, nbr_counts=nbr_counts,
                                          budget=budget)

    return build(prob, prob.adj, prob.active)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis", "topology", "qp_iters", "iters", "qp_solver",
    "_pallas"))
def _node_scan(state, prob, inv, *, mesh, axis, topology, qp_iters, iters,
               qp_solver, _pallas):
    """``iters`` ``plan_step``s on each node's shard, the neighbour sum a
    collective, as one cached program (keyed on ``_pallas`` as
    ``engine.plan._plan_scan`` is: the Pallas engines read the switch at
    trace time)."""
    node, _, state_spec, prob_spec, inv_spec = _node_specs(axis)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(state_spec, prob_spec, inv_spec, node),
                       out_specs=state_spec, check_vma=False)
    def scan(st, pr, iv, adj_r):
        nbr_reduce = _nbr_reduce_for(adj_r.astype(jnp.float32), axis=axis,
                                     topology=topology)

        def body(s, _):
            return engine_plan.plan_step(pr, iv, s, qp_iters=qp_iters,
                                         qp_solver=qp_solver,
                                         nbr_reduce=nbr_reduce), None

        return jax.lax.scan(body, st, None, length=iters)[0]

    return scan(state, prob, inv, prob.adj)


def build_planned_runner(mesh: Mesh, *, axis: str = "nodes",
                         topology: str = "graph", qp_iters: int = 200,
                         iters: int = 1, qp_solver: str = "fista",
                         budget=None):
    """Two-phase decentralized execution: ``(compile_fn, step_fn)``.

    ``inv = compile_fn(prob)`` builds the node-sharded plan invariants
    (one weighted-Gram Hessian build per fit); ``step_fn(state, prob,
    inv)`` then advances ``iters`` ADMM iterations against them (the
    host-eval history path: ``iters=1`` between evaluations).  Both
    dispatch the cached programs ``_node_build`` / ``_node_scan``.
    """
    def compile_fn(pr):
        return _node_build(pr, mesh=mesh, axis=axis, budget=budget,
                           _pallas=kops._use_pallas())

    def step_fn(st, pr, inv):
        return _node_scan(st, pr, inv, mesh=mesh, axis=axis,
                          topology=topology, qp_iters=qp_iters, iters=iters,
                          qp_solver=qp_solver, _pallas=kops._use_pallas())

    return compile_fn, step_fn


def run_dtsvm_dist(prob: dtsvm.DTSVMProblem, iters: int,
                   mesh: Optional[Mesh] = None, axis: str = "nodes",
                   topology: str = "graph", qp_iters: int = 200,
                   state: Optional[dtsvm.DTSVMState] = None,
                   qp_solver: str = "fista", budget=None):
    """Decentralized run.  Shards every (V, ...) array over the node
    axis: the invariant build, then ``iters`` iterations against it."""
    V = prob.X.shape[0]
    if mesh is None:
        mesh = make_node_mesh(V, axis)
    if state is None:
        state = dtsvm.init_state(prob)
    compile_fn, step_fn = build_planned_runner(
        mesh, axis=axis, topology=topology, qp_iters=qp_iters, iters=iters,
        qp_solver=qp_solver, budget=budget)
    return step_fn(state, prob, compile_fn(prob))
