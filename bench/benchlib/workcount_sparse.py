"""The work any implementation of a sparse-feature DTSVM fit must do,
counted from the data (``rows``, ``cols``, ``vals`` (V, T, E), the
nonzeros of X per (node, task) block, and ``mask`` (V, T, N)):

- invariant build: every entry of K = Z diag(a) Z^T (the Gershgorin
  bound needs them all) sums, over the columns f of Z, the products of
  the rows that are nonzero in f; that is c_f^2 products per (v, t),
  2 c_f^2 operations, c_f the rows of the block with a nonzero in
  column f (the bias column's c being the block's real rows).  The
  count is the same however K is built.
- ADMM: each inner QP iteration applies K to the iterate at least once,
  and through the factors that is one read of Z's nonzeros: 8 bytes
  each (a float32 value and an int32 column index) plus 4 bytes a row
  for the bias column.

Counts are of nonzero values: stored zeros (padding) are not work.
"""
from __future__ import annotations

import numpy as np


def column_counts(rows, cols, vals, p: int) -> np.ndarray:
    """c (V, T, p): rows of each block with a nonzero in each column;
    repeated (row, col) entries count once."""
    rows, cols, vals = (np.asarray(x) for x in (rows, cols, vals))
    V, T, _ = vals.shape
    out = np.zeros((V, T, p), np.int64)
    for v in range(V):
        for t in range(T):
            nz = vals[v, t] != 0
            pairs = np.unique(rows[v, t][nz].astype(np.int64) * p
                              + cols[v, t][nz])
            out[v, t] = np.bincount(pairs % p, minlength=p)
    return out


def build_flops(rows, cols, vals, mask, p: int) -> float:
    """Operations of one fit's invariant build: 2 sum_{v,t} sum_f c_f^2,
    the bias column's c the block's real rows."""
    c = column_counts(rows, cols, vals, p).astype(np.float64)
    real = np.sum(np.asarray(mask, np.float64), axis=-1)
    return 2.0 * float(np.sum(c * c) + np.sum(real * real))


def admm_bytes(vals, mask, qp_iterations: int) -> float:
    """Bytes one fit's ADMM loop must read: per inner QP iteration 8 a
    nonzero of X plus 4 a row (the bias column)."""
    nnz = int(np.count_nonzero(np.asarray(vals)))
    n_rows = int(np.asarray(mask).size)
    return float(qp_iterations) * (8.0 * nnz + 4.0 * n_rows)
