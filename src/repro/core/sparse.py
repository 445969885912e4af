"""Sparse features: X stored as its nonzeros, per (node, task) block.

Linear SVMs run mostly on sparse text (tf-idf rows over tens of
thousands of terms, a few dozen nonzeros a row), where the dense
(V, T, N, p) layout does not fit a chip.  ``SparseRows`` holds such an
X as fixed-shape coordinate lists:

    rows (V, T, E) int32    the nonzero's sample index, in [0, N)
    cols (V, T, E) int32    its feature index, in [0, p)
    vals (V, T, E) float32  its value

E is the largest nonzero count over the blocks; shorter blocks are
padded with zero values (at any valid index).  Entries may come in any
order, and repeated (row, col) pairs add.  ``.shape`` is the dense
(V, T, N, p), so every reader of ``X.shape`` works unchanged.

``DTSVM`` / ``DSVM`` fits take it on the ``vmap`` backend only
(``DTSVM(SolverConfig(backend="vmap")).fit(X_sparse, y, ...)``):

- the label-signed data Z = Y [X, 1] stays sparse (``SparseZ``: the
  signed nonzeros, and the bias column s = y * mask kept dense);
- the dual Hessian K = Z diag(a) Z^T is built exactly, feature panel by
  feature panel (``gram_and_lipschitz``): each panel's columns of every
  block are scattered into a dense (V*T, N, panel) tile whose weighted
  Gram (the Pallas kernel) is added into K; dense Z is never held whole;
- the ADMM step's two products with Z, ``q = mask + Z g`` and
  ``zl = Z^T lam``, are gathers and segment sums over the nonzeros
  (``z_dot`` / ``zt_dot``); the QP iterates on the materialised K as in
  the dense fit.

Every other path (``shard_map``, ``async``, ``sample_shard``, sweeps,
session replans, the factored and mixed-precision QP modes) raises a
``ValueError`` on sparse input.  ``dense_oracle_fit`` is the plain
reference of the sparse fit: the same problem densified, through the
legacy ``core.dtsvm_step``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.obs import spans as obs_spans

#: feature columns densified per step of the K build
PANEL = 1024


@jax.tree_util.register_pytree_node_class
class SparseRows:
    """X (V, T, N, p) as per-block coordinate lists; see the module
    docstring.  A pytree: ``rows``, ``cols``, ``vals`` are its leaves,
    ``N`` and ``p`` static."""

    def __init__(self, rows, cols, vals, N: int, p: int):
        self.rows = rows
        self.cols = cols
        self.vals = vals
        self.N = int(N)
        self.p = int(p)

    @property
    def shape(self):
        return tuple(self.vals.shape[:2]) + (self.N, self.p)

    @property
    def stored(self) -> int:
        """Entries stored, padding included: V * T * E."""
        return int(np.prod(self.vals.shape))

    def tree_flatten(self):
        return (self.rows, self.cols, self.vals), (self.N, self.p)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @classmethod
    def from_dense(cls, X) -> "SparseRows":
        """The nonzeros of a dense (V, T, N, p) array (host side), each
        block padded to the largest block's count."""
        X = np.asarray(X, np.float32)
        V, T, N, p = X.shape
        blocks = [np.nonzero(X[v, t]) for v in range(V) for t in range(T)]
        E = max(max(len(r) for r, _ in blocks), 1)
        rows = np.zeros((V * T, E), np.int32)
        cols = np.zeros((V * T, E), np.int32)
        vals = np.zeros((V * T, E), np.float32)
        for b, (r, c) in enumerate(blocks):
            rows[b, :len(r)], cols[b, :len(r)] = r, c
            vals[b, :len(r)] = X[b // T, b % T][r, c]
        shp = (V, T, E)
        return cls(jnp.asarray(rows.reshape(shp), jnp.int32),
                   jnp.asarray(cols.reshape(shp), jnp.int32),
                   jnp.asarray(vals.reshape(shp), jnp.float32), N, p)

    def todense(self) -> jnp.ndarray:
        """The dense (V, T, N, p) float32 array (for small sizes)."""
        V, T, N, p = self.shape
        v = jnp.arange(V)[:, None, None]
        t = jnp.arange(T)[None, :, None]
        return jnp.zeros(self.shape, jnp.float32).at[
            v, t, self.rows, self.cols].add(self.vals)


class SparseZ(NamedTuple):
    """The label-signed augmented data Z = Y [X, 1] (mask-zeroed) of a
    sparse problem: ``x`` holds X's nonzeros times their row's
    s = y * mask, and ``s`` (V, T, N) is the bias column, dense."""
    x: SparseRows
    s: jnp.ndarray

    @property
    def shape(self):
        V, T, N, p = self.x.shape
        return (V, T, N, p + 1)


def require_dense(X, what: str) -> None:
    """Raise the ``ValueError`` of every path that takes dense X only."""
    if isinstance(X, SparseRows):
        raise ValueError(
            f"{what} takes a dense X; sparse rows (SparseRows) fit only "
            f"through DTSVM.fit / DSVM.fit on the 'vmap' backend with the "
            f"materialized f32 QP")


def compute_z(X: SparseRows, y, mask) -> SparseZ:
    """Z = Y [X, 1] (mask-zeroed) with X's part kept sparse."""
    s = y * mask
    sign = jnp.take_along_axis(s, X.rows, axis=-1)
    return SparseZ(SparseRows(X.rows, X.cols, X.vals * sign, X.N, X.p), s)


def _flat(Z: SparseZ):
    """Block-flattened (B*E,) rows and cols, offset into per-block
    segments of N and p, and the values."""
    V, T, N, D = Z.shape
    B = V * T
    E = Z.x.vals.shape[-1]
    blk = jnp.arange(B, dtype=jnp.int32)[:, None]
    rows = (Z.x.rows.reshape(B, E) + blk * N).reshape(-1)
    cols = (Z.x.cols.reshape(B, E) + blk * (D - 1)).reshape(-1)
    return rows, cols, Z.x.vals.reshape(-1)


def z_dot(Z: SparseZ, g: jnp.ndarray) -> jnp.ndarray:
    """Z g: (V, T, N) from g (V, T, p+1), over the nonzeros."""
    V, T, N, D = Z.shape
    rows, cols, vals = _flat(Z)
    feat = jax.ops.segment_sum(vals * g[..., : D - 1].reshape(-1)[cols],
                               rows, num_segments=V * T * N)
    return feat.reshape(V, T, N) + Z.s * g[..., D - 1:]


def zt_dot(Z: SparseZ, lam: jnp.ndarray) -> jnp.ndarray:
    """Z^T lam: (V, T, p+1) from lam (V, T, N), over the nonzeros."""
    V, T, N, D = Z.shape
    rows, cols, vals = _flat(Z)
    feat = jax.ops.segment_sum(vals * lam.reshape(-1)[rows], cols,
                               num_segments=V * T * (D - 1))
    bias = jnp.sum(Z.s * lam, axis=-1, keepdims=True)
    return jnp.concatenate([feat.reshape(V, T, D - 1), bias], -1)


def panel_width(p: int) -> int:
    """Columns a K-build step densifies: :data:`PANEL`, or p rounded up
    to the 128-lane width where that is narrower."""
    return min(PANEL, -(-p // 128) * 128)


def densified_bytes(shape) -> int:
    """float32 bytes of Z the panel build writes dense: every panel of
    every block's (N, panel) tile, the last panel's padding included."""
    V, T, N, p = shape
    w = panel_width(p)
    return 4 * V * T * N * w * (-(-p // w))


#: nonzeros scattered per step of a panel's densify loop
_CHUNK = 32768


def gram_and_lipschitz(Z: SparseZ, a: jnp.ndarray):
    """K = Z diag(a) Z^T (V, T, N, N) and its Gershgorin bound L (V, T),
    built panel by panel from the nonzeros (module docstring)."""
    return _gram_jit(Z, a, panel=panel_width(Z.x.p),
                     _pallas=kops._use_pallas())


@functools.partial(jax.jit, static_argnames=("panel", "_pallas"))
def _gram_jit(Z, a, *, panel, _pallas):
    with obs_spans.scope("invariant_build"), obs_spans.scope("sparse_gram"):
        return _gram(Z, a, panel=panel)


def _gram(Z: SparseZ, a, *, panel: int):
    V, T, N, D = Z.shape
    p, B = D - 1, V * T
    E = Z.x.vals.shape[-1]
    n_pan = -(-p // panel)
    chunk = min(_CHUNK, E)
    # each block's nonzeros in column order: a panel's are one range
    order = jnp.argsort(Z.x.cols.reshape(B, E), axis=-1, stable=True)
    pad = lambda x, fill: jnp.concatenate(
        [jnp.take_along_axis(x.reshape(B, E), order, -1),
         jnp.full((B, chunk), fill, x.dtype)], -1)
    rows, cols, vals = pad(Z.x.rows, 0), pad(Z.x.cols, p), pad(Z.x.vals, 0.0)
    edges = jnp.arange(n_pan + 1, dtype=jnp.int32) * panel
    off = jax.vmap(lambda c: jnp.searchsorted(c, edges))(
        cols[:, :E]).astype(jnp.int32)                        # (B, n_pan+1)
    ax = jnp.pad(a.reshape(B, D)[:, :p], ((0, 0), (0, n_pan * panel - p)))
    blk = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None],
                           (B, chunk))
    step = jnp.arange(chunk, dtype=jnp.int32)[None]

    def densify(k):
        start, end = off[:, k], off[:, k + 1]

        def body(i, tile):
            idx = start[:, None] + i * chunk + step
            ok = idx < end[:, None]
            r = jnp.where(ok, jnp.take_along_axis(rows, idx, -1), N)
            c = jnp.take_along_axis(cols, idx, -1) - k * panel
            v = jnp.take_along_axis(vals, idx, -1)
            return tile.at[blk, r, jnp.where(ok, c, 0)].add(v, mode="drop")

        n = jnp.max(-(-(end - start) // chunk))
        return jax.lax.fori_loop(0, n, body,
                                 jnp.zeros((B, N, panel), jnp.float32))

    def panel_step(k, K):
        tile = densify(k)
        ak = jax.lax.dynamic_slice_in_dim(ax, k * panel, panel, axis=1)

        def block(b, K):
            Kb = kops.weighted_gram(tile[b], ak[b])
            return jax.lax.dynamic_update_index_in_dim(K, K[b] + Kb, b, 0)

        return jax.lax.fori_loop(0, B, block, K)

    s = Z.s.reshape(B, N)
    bias = a.reshape(B, D)[:, p, None, None] * s[:, :, None] * s[:, None, :]
    K = jax.lax.fori_loop(0, n_pan, panel_step, bias)
    L = jnp.maximum(jnp.max(jnp.sum(jnp.abs(K), -1), -1), 1e-12)
    return K.reshape(V, T, N, N), L.reshape(V, T)


def dense_oracle_fit(prob, iters: int, qp_iters: int):
    """The plain reference of a sparse fit: the same problem with X
    densified (``SparseRows.todense``), stepped through the legacy
    single-iteration ``core.dtsvm_step`` (FISTA, ``core.qp``) from the
    zero state.  For sizes whose dense X fits."""
    from repro.core import dtsvm as core

    dense = prob._replace(X=prob.X.todense())
    st = core.init_state(dense)
    for _ in range(iters):
        st = core.dtsvm_step(st, dense, qp_iters=qp_iters)
    return st
