"""CSVM — centralized linear soft-margin SVM (the paper's [13] baseline).

Solved in the dual with the same box-QP machinery as DTSVM:

    max_lam  1^T lam - 1/2 lam^T (Y X~ diag(ainv) X~^T Y) lam,
    0 <= lam <= C,
    ainv = [1,...,1, 1/eps_b]

The unregularized bias of the textbook SVM introduces an equality
constraint in the dual; we use the standard penalty trick (tiny ridge
eps_b on b), consistent with DTSVM's _U_FLOOR treatment — see
core/dtsvm.py docstring.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import qp as qp_lib
from repro.kernels import ops as kops

_EPS_B = 1e-3


def csvm_fit(X: jnp.ndarray, y: jnp.ndarray, C: float,
             mask: jnp.ndarray = None, qp_iters: int = 500,
             eps_b: float = _EPS_B) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fit on pooled data.  X: (N, p), y: (N,).  Returns (w, b)."""
    N, p = X.shape
    if mask is None:
        mask = jnp.ones((N,), jnp.float32)
    Xa = jnp.concatenate([X, jnp.ones((N, 1), jnp.float32)], axis=-1)
    Z = y[:, None] * Xa * mask[:, None]
    ainv = jnp.concatenate([jnp.ones((p,), jnp.float32),
                            jnp.asarray([1.0 / eps_b], jnp.float32)])
    K = kops.weighted_gram(Z, ainv)
    q = mask
    hi = C * mask
    lam = qp_lib.solve_box_qp_fista(K, q, hi, iters=qp_iters)
    w_aug = jnp.matmul((Z * ainv[None, :]).T, lam,
                       precision="highest")          # diag(ainv) Z^T lam
    return w_aug[:p], w_aug[p]


def csvm_fit_tasks(X: jnp.ndarray, y: jnp.ndarray, C: float,
                   mask: jnp.ndarray = None, qp_iters: int = 500,
                   eps_b: float = _EPS_B) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``csvm_fit`` vmapped over a leading task axis: one dispatched solve
    for all tasks.  X: (T, N, p), y/mask: (T, N).  Returns
    (w (T, p), b (T,)) — bit-for-bit what the per-task loop produces
    (tested)."""
    if mask is None:
        mask = jnp.ones(X.shape[:-1], jnp.float32)
    fit1 = lambda Xt, yt, mt: csvm_fit(Xt, yt, C, mt, qp_iters=qp_iters,
                                       eps_b=eps_b)
    return jax.vmap(fit1)(X, y, mask)


def csvm_decision(w: jnp.ndarray, b: jnp.ndarray, X: jnp.ndarray):
    return jnp.matmul(X, w, precision="highest") + b


def csvm_risk(w, b, X, y) -> jnp.ndarray:
    g = csvm_decision(w, b, X)
    return jnp.mean((jnp.sign(g) != jnp.sign(y)).astype(jnp.float32))
