"""DTSVM — Proposition 1 of the paper, exactly, vectorized over (V, T).

Decision vector layout (size 2p+2):  r = [w0 (p), b0, wt (p), bt].

The paper's operators all act diagonally in this basis, which we exploit:

    M1   = diag(1_p, 0, 0_p, 0)                    (selects w0)
    M2   = diag(0_p, 0, 1_p, 0)                    (selects wt)
    P0   = [I,0]^T [I,0] = diag(1_{p+1}, 0_{p+1})  (selects w0, b0)
    U_vt = eps1*M1 + eps2*M2 + 2*eta1*(T-1)*P0 + 2*eta2*|B_v|*I   — diagonal

    [I,I] r           = r[:p+1] + r[p+1:]            (the working classifier)
    [I,I] U^{-1} [I,I]^T = diag(a),  a_i = 1/U_i + 1/U_{p+1+i}

so the dual Hessian of QP (6) is the *weighted Gram matrix*

    K = (Y X~) diag(a) (Y X~)^T,       X~ = [X, 1]   (augmented data)

— the compute hot spot, served by ``repro.kernels.gram`` on TPU.

K (with Z, U, the counts, the QP box and its Lipschitz bound) depends
only on the problem, never on the ADMM state, so ``run_dtsvm`` executes
through ``repro.engine``: invariants are compiled once per fit and only
the state-dependent body runs per iteration.  ``dtsvm_step`` below is
the self-contained single-iteration reference (recomputes everything
each call) — kept as the correctness oracle the engine is tested
against bit-for-bit, and for one-off step-debugging.

Generalizations needed by the paper's own experiments (all default to the
plain algorithm):

- ``active`` (V, T) mask — which tasks a node trains (Fig. 6 mixed networks,
  Fig. 7 online enter/leave).  Inactive (v,t) keep their state frozen and
  are excluded from every consensus sum.
- ``couple`` (V,) mask — whether a node runs the *task* consensus (DTSVM)
  or not (plain DSVM), reproducing Fig. 6's mixed DSVM/DTSVM training.
- per-sample ``mask`` — ragged N_vt via padding; padded rows get a zero
  box so their duals stay 0.

Isolated bias note: when a (v,t) has no neighbors and no task coupling, the
paper's U is singular in the bias rows (b is unregularized in a bare SVM).
We floor the diagonal at ``_U_FLOOR`` — a tiny ridge on b, the standard
penalty-trick; tests confirm it recovers the CSVM solution.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qp as qp_lib
from repro.kernels import ops as kops
from repro.obs import spans as obs_spans

_U_FLOOR = 1e-6


class DTSVMState(NamedTuple):
    r: jnp.ndarray        # (V, T, 2p+2)
    alpha: jnp.ndarray    # (V, T, p+1)
    beta: jnp.ndarray     # (V, T, 2p+2)
    lam: jnp.ndarray      # (V, T, N)   warm-started duals


class DTSVMProblem(NamedTuple):
    X: jnp.ndarray        # (V, T, N, p)
    y: jnp.ndarray        # (V, T, N)  in {-1, +1}
    mask: jnp.ndarray     # (V, T, N)  in {0, 1}
    adj: jnp.ndarray      # (V, V) bool
    C: jnp.ndarray        # () float32 — scalar hyper-parameters are
    eps1: jnp.ndarray     # () stored as 0-d arrays, NOT Python floats:
    eps2: jnp.ndarray     # a Python float closed over a lax.scan embeds
    eta1: jnp.ndarray     # as an HLO literal while the sweep engine's
    eta2: jnp.ndarray     # per-config slices are loop operands, and XLA
    box_scale: jnp.ndarray  # compiles the two differently (ULP drift).
    # box_scale: the paper's V*T multiplier on C.  (In a SweepPlan these
    # six leaves carry a leading (S,) config axis instead.)
    active: jnp.ndarray   # (V, T)
    couple: jnp.ndarray   # (V,)


def make_problem(X, y, mask=None, adj=None, *, C=0.01, eps1=1.0, eps2=1.0,
                 eta1=1.0, eta2=1.0, box_scale=None, active=None,
                 couple=None) -> DTSVMProblem:
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    V, T, N, p = X.shape
    if mask is None:
        mask = jnp.ones((V, T, N), jnp.float32)
    if adj is None:
        adj = jnp.zeros((V, V), bool)
    if active is None:
        active = jnp.ones((V, T), jnp.float32)
    if couple is None:
        couple = jnp.ones((V,), jnp.float32)
    if box_scale is None:
        box_scale = float(V * T)
    f32 = lambda v: jnp.asarray(float(v), jnp.float32)
    return DTSVMProblem(X, y, jnp.asarray(mask, jnp.float32),
                        jnp.asarray(adj), f32(C), f32(eps1), f32(eps2),
                        f32(eta1), f32(eta2), f32(box_scale),
                        jnp.asarray(active, jnp.float32),
                        jnp.asarray(couple, jnp.float32))


def init_state(prob: DTSVMProblem) -> DTSVMState:
    V, T, N, p = prob.X.shape
    return DTSVMState(
        r=jnp.zeros((V, T, 2 * p + 2), jnp.float32),
        alpha=jnp.zeros((V, T, p + 1), jnp.float32),
        beta=jnp.zeros((V, T, 2 * p + 2), jnp.float32),
        lam=jnp.zeros((V, T, N), jnp.float32),
    )


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------
def _default_nbr_reduce(prob: DTSVMProblem):
    """Sum an (V,T,D) array over each node's active neighbors (dense adj)."""
    adjf = prob.adj.astype(jnp.float32)
    # repro: noqa[raw-einsum-in-plan] — deliberate: this einsum DEFINES the neighbor-sum semantics every backend (incl. Fabric.reduce) must match bitwise; golden fixtures pin it
    return lambda arr: jnp.einsum("vu,utd->vtd", adjf, arr,
                                  precision="highest")


def _counts(prob: DTSVMProblem, nbr_counts: Optional[jnp.ndarray] = None):
    """Per-(v,t) coupling pair count and active-neighbor count."""
    active = prob.active                                   # (V,T)
    T_v = jnp.sum(active, axis=1, keepdims=True)           # (V,1)
    ntp = (T_v - 1.0) * prob.couple[:, None] * active      # (V,T)
    ntp = jnp.maximum(ntp, 0.0)
    if nbr_counts is None:
        # repro: noqa[raw-einsum-in-plan] — deliberate: integer-valued count contraction, exact in f32 for any summation order
        nbr_counts = jnp.einsum("vu,ut->vt", prob.adj.astype(jnp.float32),
                                active)
    nbr = nbr_counts * active                              # inactive rows: 0
    return ntp, nbr


def _u_diag(prob: DTSVMProblem, ntp, nbr):
    """Diagonal of U_vt, eq. (10): (V, T, 2p+2)."""
    p = prob.X.shape[-1]
    w0 = prob.eps1 + 2 * prob.eta1 * ntp[..., None] + 2 * prob.eta2 * nbr[..., None]
    b0 = 2 * prob.eta1 * ntp[..., None] + 2 * prob.eta2 * nbr[..., None]
    wt = prob.eps2 + 2 * prob.eta2 * nbr[..., None]
    bt = 2 * prob.eta2 * nbr[..., None]
    u = jnp.concatenate([
        jnp.broadcast_to(w0, ntp.shape + (p,)),
        b0,
        jnp.broadcast_to(wt, ntp.shape + (p,)),
        bt,
    ], axis=-1)
    return jnp.maximum(u, _U_FLOOR)


def _f_vec(prob: DTSVMProblem, state: DTSVMState, ntp, nbr, nbr_reduce):
    """f_vt^{(k)}, eq. (11): (V, T, 2p+2)."""
    p = prob.X.shape[-1]
    r, alpha, beta = state.r, state.alpha, state.beta
    active = prob.active[..., None]                        # (V,T,1)
    # task sums: sum over other active tasks at the node (coupled nodes only)
    r_act = r * active
    task_sum = jnp.sum(r_act, axis=1, keepdims=True) - r_act   # (V,T,D)
    task_term = ntp[..., None] * r + task_sum * prob.couple[:, None, None]
    task_term = task_term.at[..., p + 1:].set(0.0)          # P0 projection
    # neighbor sums: sum over active neighbors of the same task
    with obs_spans.scope("neighbour_exchange"):
        nbr_sum = nbr_reduce(r_act)
    nbr_term = nbr[..., None] * r + nbr_sum

    pad = jnp.zeros((*alpha.shape[:-1], p + 1), alpha.dtype)
    alpha_full = jnp.concatenate([alpha, pad], axis=-1)     # [I,0]^T alpha
    f = 2.0 * alpha_full + 2.0 * beta \
        - prob.eta1 * task_term - prob.eta2 * nbr_term
    return f


def _qp_inputs(prob: DTSVMProblem, u, f):
    """Weighted Gram Hessian K, linear term q, box hi — for QP (6)."""
    V, T, N, p = prob.X.shape
    Xa = jnp.concatenate([prob.X, jnp.ones((V, T, N, 1), jnp.float32)], -1)
    Z = prob.y[..., None] * Xa * prob.mask[..., None]       # (V,T,N,p+1)
    a = 1.0 / u[..., : p + 1] + 1.0 / u[..., p + 1:]        # (V,T,p+1)
    K = kops.weighted_gram(Z, a)                            # (V,T,N,N)
    g = f[..., : p + 1] / u[..., : p + 1] + f[..., p + 1:] / u[..., p + 1:]
    # mul+reduce (not einsum) to stay bitwise-identical to the batched
    # sweep path, whose vmapped dot_general would reassociate differently
    q = prob.mask + jnp.sum(Z * g[..., None, :], axis=-1)
    hi = prob.box_scale * prob.C * prob.mask * prob.active[..., None]
    return Z, K, q, hi


def dtsvm_step(state: DTSVMState, prob: DTSVMProblem,
               qp_iters: int = 200, nbr_reduce=None,
               nbr_counts: Optional[jnp.ndarray] = None) -> DTSVMState:
    """One full Proposition-1 iteration (eqs. 6-9), self-contained.

    ``nbr_reduce`` abstracts the neighbor sum so the same math runs both
    vmapped on one host (dense-adjacency einsum, the default) and SPMD
    inside shard_map (all_gather/ppermute — repro.core.dtsvm_dist).

    This is the LEGACY per-iteration path: it rebuilds every loop
    invariant (Z, K, u, counts, box) on each call.  Multi-iteration runs
    should go through ``run_dtsvm`` / ``repro.engine.compile_problem``,
    which hoist those invariants out of the loop and produce bit-for-bit
    identical states (migration note: API.md §engine).
    """
    p = prob.X.shape[-1]
    if nbr_reduce is None:
        nbr_reduce = _default_nbr_reduce(prob)
    ntp, nbr = _counts(prob, nbr_counts)
    u = _u_diag(prob, ntp, nbr)
    f = _f_vec(prob, state, ntp, nbr, nbr_reduce)
    Z, K, q, hi = _qp_inputs(prob, u, f)

    solve = jax.vmap(jax.vmap(
        lambda Kvt, qvt, hivt, l0: qp_lib.solve_box_qp_fista(
            Kvt, qvt, hivt, iters=qp_iters, lam0=l0)))
    lam = solve(K, q, hi, state.lam)                        # eq. (6)

    # repro: noqa[raw-einsum-in-plan] — deliberate: legacy oracle mirrors engine/plan.py's zl contraction exactly; tests assert oracle == engine bitwise
    zl = jnp.einsum("vtn,vtnd->vtd", lam, Z, precision="highest")  # X^T Y lam
    rhs = jnp.concatenate([zl, zl], axis=-1) - f            # [I,I]^T (...) - f
    r_new = rhs / u                                          # eq. (7)
    act = prob.active[..., None]
    r_new = r_new * act + state.r * (1.0 - act)             # freeze inactive

    # eq. (8): alpha update on the (w0, b0) block, coupled nodes only
    r_act = r_new * act
    task_sum = jnp.sum(r_act, axis=1, keepdims=True) - r_act
    d_alpha = (ntp[..., None] * r_new - task_sum * prob.couple[:, None, None])
    alpha = state.alpha + 0.5 * prob.eta1 * d_alpha[..., : p + 1] * act

    # eq. (9): beta update over active neighbors
    with obs_spans.scope("neighbour_exchange"):
        nbr_sum = nbr_reduce(r_act)
    d_beta = nbr[..., None] * r_new - nbr_sum
    beta = state.beta + 0.5 * prob.eta2 * d_beta * act

    return DTSVMState(r=r_new, alpha=alpha, beta=beta, lam=lam)


def run_dtsvm(prob: DTSVMProblem, iters: int, qp_iters: int = 200,
              state: Optional[DTSVMState] = None,
              eval_fn: Optional[Callable[[DTSVMState], jnp.ndarray]] = None,
              qp_solver: str = "fista"):
    """Run ADMM iterations.  Returns (state, history) where history stacks
    ``eval_fn(state)`` after every iteration (or None).

    Executes through the plan/execute engine: the loop-invariants of
    Prop. 1 (Z, K, u, counts, box, step size) are compiled once by
    ``repro.engine.compile_problem`` and only the state-dependent body
    runs per iteration — bit-for-bit identical to scanning
    ``dtsvm_step`` (tested), ~the Hessian build cheaper per iteration.
    ``qp_solver`` selects the inner dual engine ("fista" | "pg" |
    "pallas_fused", see ``repro.engine.qp_engines``).
    """
    from repro.engine import plan as engine_plan   # deferred: avoids cycle
    pl = engine_plan.compile_problem(prob, qp_iters=qp_iters,
                                     qp_solver=qp_solver)
    return pl.run(state=state, iters=iters, eval_fn=eval_fn)


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------
def decision_values(r: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """g_vt(x) = [x^T, 1] [I,I] r_vt, eq. (12).  X: (..., N, p)."""
    p = X.shape[-1]
    w = r[..., :p] + r[..., p + 1: 2 * p + 1]
    b = r[..., p] + r[..., 2 * p + 1]
    return (jnp.einsum("...np,...p->...n", X, w, precision="highest")
            + b[..., None])


def risks(r: jnp.ndarray, X: jnp.ndarray, y: jnp.ndarray,
          mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Per-(v,t) misclassification rate on a test set."""
    g = decision_values(r, X)
    wrong = (jnp.sign(g) != jnp.sign(y)).astype(jnp.float32)
    if mask is None:
        return jnp.mean(wrong, axis=-1)
    return jnp.sum(wrong * mask, axis=-1) / jnp.maximum(jnp.sum(mask, -1), 1)


def consensus_residuals(state: DTSVMState, prob: DTSVMProblem):
    """Max violation of the two consensus constraint families (test metric)."""
    p = prob.X.shape[-1]
    r = state.r
    act = prob.active[..., None]
    w0b0 = r[..., : p + 1] * act
    # across tasks within a node
    mean_t = jnp.sum(w0b0, 1, keepdims=True) / jnp.maximum(
        jnp.sum(act, 1, keepdims=True), 1)
    task_res = jnp.max(jnp.abs((w0b0 - mean_t) * act))
    # across neighboring nodes per task
    A = prob.adj.astype(jnp.float32)
    r_act = r * act
    deg = jnp.maximum(jnp.einsum(
        "vu,ut->vt", A, prob.active), 1)[..., None]
    nbr_mean = jnp.einsum("vu,utd->vtd", A, r_act, precision="highest") / deg
    node_res = jnp.max(jnp.abs((r - nbr_mean) *
                               act * (deg > 0)))
    return task_res, node_res
