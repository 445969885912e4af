"""The serving-side view of a fitted linear SVM network.

Training carries the stacked primal vector r = [w0; b0; w; b] per
(node, task); inference only ever needs the effective hyperplanes

    w_vt = w0 + w_vt,   b_vt = b0 + b_vt

— V*T tiny (p+1)-vectors.  ``PredictModel`` freezes exactly that: a
(V, T, p) weight block and a (V, T) bias block, extracted once from a
state / solver / session and immutable afterwards (a NamedTuple of
arrays), which is what makes hot-swapping a server's model a single
reference assignment.

The decision values here are computed as ONE flat GEMM against all
V*T hyperplanes — ``G = X @ W_flat.T + b_flat`` — and gathered per
request.  Rows of a GEMM are bitwise independent of the other rows
(each output element is its own dot product), so a request's answers
do not depend on what it was batched with — the exactness contract the
server's padded-bucket batching relies on (asserted in
tests/test_serve.py).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class PredictModel(NamedTuple):
    """Frozen per-(node, task) hyperplanes of a fitted network.

    ``W`` (V, T, p) and ``b`` (V, T) are the effective parameters
    w0 + w_vt / b0 + b_vt — everything inference needs, nothing ADMM
    carries."""
    W: jnp.ndarray
    b: jnp.ndarray

    @property
    def shape(self) -> Tuple[int, int, int]:
        """(V, T, p)."""
        return tuple(self.W.shape)

    @classmethod
    def from_r(cls, r) -> "PredictModel":
        """Extract the hyperplanes from a stacked primal block r
        (..., V, T, 2p+2) — same slicing as
        ``core.dtsvm.decision_values``."""
        r = jnp.asarray(r, jnp.float32)
        p = (r.shape[-1] - 2) // 2
        W = r[..., :p] + r[..., p + 1: 2 * p + 1]
        b = r[..., p] + r[..., 2 * p + 1]
        return cls(W=W, b=b)

    @classmethod
    def from_state(cls, state) -> "PredictModel":
        """From a ``core.DTSVMState`` (uses ``state.r``)."""
        return cls.from_r(state.r)

    @classmethod
    def from_session(cls, sess) -> "PredictModel":
        """From a (run) ``OnlineSession`` — the publish hook a serving
        deployment calls after every stage."""
        if sess.state is None:
            raise RuntimeError("run() the session before publishing")
        return cls.from_state(sess.state)

    @classmethod
    def from_solver(cls, solver) -> "PredictModel":
        """From a fitted solver (``DTSVM``/``DSVM``; uses ``state_``)."""
        if getattr(solver, "state_", None) is None:
            raise RuntimeError("fit() the solver before publishing")
        return cls.from_state(solver.state_)

    # ------------------------------------------------------------------
    def flat(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(V*T, p) weights and (V*T,) biases — the GEMM layout; the
        hyperplane of (v, t) is row ``v * T + t``."""
        V, T, p = self.W.shape
        return self.W.reshape(V * T, p), self.b.reshape(V * T)

    def decision(self, X) -> jnp.ndarray:
        """Decision values for X (T, n, p) shared or (V, T, n, p):
        (V, T, n) — the offline-evaluation form, matching
        ``core.decision_values`` on the originating state."""
        V, T, p = self.W.shape
        X = jnp.asarray(X, jnp.float32)
        if X.ndim == 3:
            X = jnp.broadcast_to(X[None], (V,) + X.shape)
        return (jnp.einsum("vtnp,vtp->vtn", X, self.W, precision="highest")
                + self.b[..., None])

    def predict(self, X) -> jnp.ndarray:
        """Labels in {-1, +1}, shape (V, T, n)."""
        return jnp.sign(self.decision(X))

    def decide_rows(self, X) -> np.ndarray:
        """Decision values of rows X (n, p) against ALL V*T hyperplanes
        at once: (n, V*T) — one bucket-padded GEMM, the exact
        computation the server runs on its batches.  Padding to the
        row bucket is part of the contract: row values are bitwise
        stable across all bucket shapes, but the UNPADDED tiny-n GEMM
        lowers to a different (matrix-vector) kernel with a different
        reduction — so the canonical form always pads."""
        X = np.asarray(X, np.float32)
        Wf, bf = self.flat()
        Xp = np.zeros((row_bucket(X.shape[0]), X.shape[1]), np.float32)
        Xp[:X.shape[0]] = X
        return np.asarray(gemm_rows(Wf, bf, jnp.asarray(Xp)))[:X.shape[0]]


def row_bucket(n: int) -> int:
    """Smallest power-of-two row count >= n (floor 8) — the static
    batch shapes every GEMM in this package runs at, so the kernel
    compiles once per bucket and every path lowers identically."""
    b = 8
    while b < n:
        b *= 2
    return b


@jax.jit
def gemm_rows(Wf: jnp.ndarray, bf: jnp.ndarray,
              X: jnp.ndarray) -> jnp.ndarray:
    """The server's kernel: X (B, p) against every hyperplane —
    (B, V*T).  Jitted once per (B, p, V*T) bucket shape; runs on
    whatever device its (committed) inputs live on, which is how the
    server pins batches to devices."""
    return jnp.matmul(X, Wf.T, precision="highest") + bf[None, :]
