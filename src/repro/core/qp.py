"""Box-constrained quadratic programs (the dual sub-problem (6) of Prop. 1).

    maximize   -1/2 lam^T K lam + q^T lam
    subject to 0 <= lam <= hi        (elementwise; hi may be a vector,
                                      hi=0 rows encode padding/inactive data)

Solvers (all fixed-iteration ``jax.lax`` loops, jit/vmap-friendly):

- ``solve_box_qp_pg``       projected-gradient ascent, Gershgorin step size
- ``solve_box_qp_fista``    Nesterov-accelerated projected gradient
- ``kkt_residual``          optimality measure used by tests

Each algorithm is one recursion (``_pg``, ``_fista``) over a ``matvec``
that applies K; every solver and engine (``engine.qp_engines``) runs
these two, on K itself, on a row panel of K (the sample-sharded
backend) or on the factors of K = Z diag(a) Z^T (``factored_matvec``).

K is PSD by construction (a Gram matrix), so the Gershgorin row-sum bound
dominates the spectral norm and 1/L steps are safe.  Both solvers accept
a precomputed ``L`` — K is loop-invariant across a fit, so the engine's
Plan derives the bound once (``gershgorin_lipschitz``) instead of every
solve.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def gershgorin_lipschitz(K: jnp.ndarray) -> jnp.ndarray:
    """Gershgorin upper bound on ||K||_2 for PSD K, batched:
    (..., N, N) -> (...)."""
    return jnp.maximum(jnp.max(jnp.sum(jnp.abs(K), axis=-1), axis=-1), 1e-12)


_lipschitz = gershgorin_lipschitz


def _project(lam, hi):
    return jnp.clip(lam, 0.0, hi)


def _pg(matvec, q, hi, iters, lam0, L):
    """The projected-gradient recursion on one box QP, K applied by
    ``matvec``."""
    step = 1.0 / L
    lam = jnp.zeros_like(q) if lam0 is None else lam0
    lam = _project(lam, hi)

    def body(_, lam):
        grad = q - matvec(lam)
        return _project(lam + step * grad, hi)

    return jax.lax.fori_loop(0, iters, body, lam)


def solve_box_qp_pg(K: jnp.ndarray, q: jnp.ndarray, hi: jnp.ndarray,
                    iters: int = 200, lam0=None,
                    L: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Projected-gradient ascent with constant step 1/L (L: optional
    precomputed Gershgorin bound)."""
    if L is None:
        L = gershgorin_lipschitz(K)
    return _pg(lambda y: jnp.matmul(K, y, precision="highest"),
               q, hi, iters, lam0, L)


def _fista(matvec, q, hi, iters, lam0, L):
    """The FISTA recursion on one box QP, K applied by ``matvec``."""
    step = 1.0 / L
    lam = jnp.zeros_like(q) if lam0 is None else _project(lam0, hi)
    state = (lam, lam, jnp.float32(1.0))

    def body(_, state):
        lam, y, t = state
        grad = q - matvec(y)
        lam_new = _project(y + step * grad, hi)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_new = lam_new + ((t - 1.0) / t_new) * (lam_new - lam)
        return (lam_new, y_new, t_new)

    lam, _, _ = jax.lax.fori_loop(0, iters, body, state)
    return lam


def solve_box_qp_fista(K: jnp.ndarray, q: jnp.ndarray, hi: jnp.ndarray,
                       iters: int = 200, lam0=None,
                       L: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """FISTA-style accelerated projected gradient (monotone restart-free).
    ``L``: optional precomputed Gershgorin bound."""
    if L is None:
        L = gershgorin_lipschitz(K)
    return _fista(lambda y: jnp.matmul(K, y, precision="highest"),
                  q, hi, iters, lam0, L)


def factored_matvec(Z: jnp.ndarray, a: jnp.ndarray):
    """``y -> K y`` for K = Z diag(a) Z^T (Z: (N, D), a: (D,)) applied
    through its factors, ``Z (a * (Z^T y))``: O(N D) instead of
    O(N^2)."""
    def matvec(y):
        zt = jnp.matmul(y, Z, precision="highest")
        return jnp.matmul(Z, a * zt, precision="highest")

    return matvec


def qp_objective(K, q, lam):
    Klam = jnp.matmul(K, lam, precision="highest")
    return (-0.5 * jnp.matmul(lam, Klam, precision="highest")
            + jnp.matmul(q, lam, precision="highest"))


def kkt_residual(K, q, hi, lam) -> jnp.ndarray:
    """|| lam - proj(lam + grad) ||_inf — zero iff lam is optimal."""
    grad = q - jnp.matmul(K, lam, precision="highest")
    return jnp.max(jnp.abs(lam - _project(lam + grad, hi)))
