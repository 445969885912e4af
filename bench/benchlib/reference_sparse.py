"""The plain reference of a sparse-feature DTSVM fit: the paper's
Proposition 1 (as ``benchlib.reference`` writes it out) on X given by
its nonzeros, per (node, task) block: ``rows``, ``cols``, ``vals``
(V, T, E), repeated (row, col) pairs adding.  It imports nothing of the
program, and never holds dense Z or K:

- Z = Y [X, 1] keeps X's nonzeros, each value times its row's
  s = y * mask; the bias column is s itself;
- Z^T v and Z g are gathers of v at the rows (g at the columns), times
  the values, summed by ``segment_sum`` into the columns (rows); the
  QP's matvec is K v = Z (a * (Z^T v));
- the Gershgorin bound L = max_i sum_j |K_ij| is summed a chunk of
  ``CHUNK`` columns of K at a time: the chunk's rows of Z, scattered
  into a dense (p+1) x chunk block, are gathered at every nonzero's
  column, times the nonzero's value and a, and summed into the rows.

Every product runs at ``precision``: ``"highest"`` (float32) or
``"high"`` (three bfloat16 passes, each operand split into a bfloat16
head and tail rounded on their bits as ``benchlib.reference._split``
rounds its head, partial products summed in float32), the control that
must come out not correct.
"""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np

from benchlib import reference

#: columns of K per step of the Gershgorin sum
CHUNK = 128
#: nonzeros scattered per step into a chunk's dense block of Z
SPAN = 16384


def _bf16(x):
    """x rounded to bfloat16 (to nearest, ties to even) on its bits, the
    result kept in float32.  No conversion is involved, so no compiler
    can drop the rounding under excess precision."""
    import jax.numpy as jnp
    from jax import lax

    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                    jnp.float32)


def _mul(x, y, precision: str):
    """x * y elementwise, in float32 or in three bfloat16 passes: each
    operand's bfloat16 head and the bfloat16 rounding of its tail, the
    three products of two bfloat16 values (exact in float32) summed as
    a matrix unit sums them.  The halves are rounded on their bits and
    stay float32 (``_bf16``): a bfloat16 value converted back to float32
    for an elementwise product is a round trip the TPU's compiler drops,
    which leaves the tail unrounded."""
    if precision == "highest":
        return x * y
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    xh, yh = _bf16(x), _bf16(y)
    xl, yl = _bf16(x - xh), _bf16(y - yh)
    return xh * yh + (xh * yl + xl * yh)


def _lipschitz(rows, vz, cols, s, a, *, N, p, precision):
    """max_i sum_j |K_ij| per block (B,), K = Z diag(a) Z^T from the
    block-flat (B, E) nonzeros of Z, s (B, N) and a (B, p+1)."""
    import jax
    import jax.numpy as jnp

    B, E = vz.shape
    c = min(CHUNK, N)
    n_chunks = -(-N // c)
    span = min(SPAN, E)
    order = jnp.argsort(rows, axis=-1, stable=True)
    pad = lambda x, fill: jnp.concatenate(
        [jnp.take_along_axis(x, order, -1), jnp.full((B, span), fill,
                                                     x.dtype)], -1)
    r_s, c_s, v_s = pad(rows, N), pad(cols, 0), pad(vz, 0.0)
    bounds = jnp.arange(n_chunks + 1, dtype=jnp.int32) * c
    start = jax.vmap(lambda r: jnp.searchsorted(r, bounds))(
        r_s[:, :E]).astype(jnp.int32)                     # (B, n_chunks+1)
    s_pad = jnp.pad(s, ((0, 0), (0, n_chunks * c - N)))
    va = vz * jnp.take_along_axis(a[:, :p], cols, -1)      # (Z a) values
    lane = jnp.arange(span, dtype=jnp.int32)

    def step(i, best):
        b, k = i // n_chunks, i % n_chunks
        lo, hi = start[b, k], start[b, k + 1]

        def scatter(j, blk):
            idx = lo + j * span + lane
            ok = idx < hi
            r = jnp.where(ok, r_s[b][idx] - k * c, c)
            return blk.at[c_s[b][idx], r].add(jnp.where(ok, v_s[b][idx], 0.0),
                                              mode="drop")

        zc = jax.lax.fori_loop(0, (hi - lo + span - 1) // span, scatter,
                               jnp.zeros((p, c), jnp.float32))
        sc = jax.lax.dynamic_slice_in_dim(s_pad[b], k * c, c)
        kc = jax.ops.segment_sum(_mul(va[b][:, None], zc[cols[b]],
                                      precision), rows[b], num_segments=N)
        kc = kc + _mul((a[b, p] * s[b])[:, None], sc[None], precision)
        return best.at[b].add(jnp.sum(jnp.abs(kc), -1))

    rs = jax.lax.fori_loop(0, B * n_chunks, step,
                           jnp.zeros((B, N), jnp.float32))
    return jnp.maximum(jnp.max(rs, -1), 1e-12)


def _dtsvm(rows, cols, vals, y, mask, adj, *, p, C, eps1, eps2, eta1, eta2,
           iters, qp_iters, precision):
    import jax
    import jax.numpy as jnp

    V, T, E = vals.shape
    N = y.shape[-1]
    D, B = p + 1, V * T
    adjf = adj.astype(jnp.float32)
    deg = jnp.sum(adjf, axis=1)
    ntp = jnp.full((V, T, 1), T - 1.0, jnp.float32)
    nbr = jnp.broadcast_to(deg[:, None, None], (V, T, 1))
    u = jnp.concatenate([
        jnp.broadcast_to(eps1 + 2 * eta1 * ntp + 2 * eta2 * nbr, (V, T, p)),
        2 * eta1 * ntp + 2 * eta2 * nbr,
        jnp.broadcast_to(eps2 + 2 * eta2 * nbr, (V, T, p)),
        2 * eta2 * nbr], axis=-1)
    u = jnp.maximum(u, 1e-6)
    a = 1.0 / u[..., :D] + 1.0 / u[..., D:]
    hi = (V * T) * C * mask
    s = y * mask
    rows_b, cols_b = rows.reshape(B, E), cols.reshape(B, E)
    vz = (vals * jnp.take_along_axis(s, rows, -1)).reshape(B, E)
    ri = (rows_b + N * jnp.arange(B)[:, None]).reshape(-1)
    ci = (cols_b + p * jnp.arange(B)[:, None]).reshape(-1)
    vz_f = vz.reshape(-1)
    step = (1.0 / _lipschitz(rows_b, vz, cols_b, s.reshape(B, N),
                             a.reshape(B, D), N=N, p=p,
                             precision=precision)).reshape(V, T, 1)

    def zt(v):                                   # Z^T v: (V,T,N) -> (V,T,D)
        feat = jax.ops.segment_sum(_mul(vz_f, v.reshape(-1)[ri], precision),
                                   ci, num_segments=B * p)
        bias = jnp.sum(_mul(s, v, precision), -1, keepdims=True)
        return jnp.concatenate([feat.reshape(V, T, p), bias], -1)

    def zg(g):                                   # Z g: (V,T,D) -> (V,T,N)
        feat = jax.ops.segment_sum(
            _mul(vz_f, g[..., :p].reshape(-1)[ci], precision), ri,
            num_segments=B * N)
        return feat.reshape(V, T, N) + _mul(s, g[..., p:], precision)

    def nbr_sum(r):
        return reference.contract("vu,utd->vtd", adjf, r, precision)

    def fista(q, lam0):
        lam = jnp.clip(lam0, 0.0, hi)

        def body(_, st):
            lam, yk, t = st
            lam_new = jnp.clip(yk + step * (q - zg(a * zt(yk))), 0.0, hi)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            return lam_new, lam_new + ((t - 1.0) / t_new) * (lam_new - lam), \
                t_new

        return jax.lax.fori_loop(0, qp_iters, body,
                                 (lam, lam, jnp.float32(1.0)))[0]

    def admm(_, st):
        r, alpha, beta, lam = st
        others = jnp.sum(r, axis=1, keepdims=True) - r
        task = (ntp * r + others).at[..., D:].set(0.0)
        f = (2.0 * jnp.concatenate([alpha, jnp.zeros_like(alpha)], -1)
             + 2.0 * beta - eta1 * task - eta2 * (nbr * r + nbr_sum(r)))
        g = f[..., :D] / u[..., :D] + f[..., D:] / u[..., D:]
        lam = fista(mask + zg(g), lam)
        zl = zt(lam)
        r_new = (jnp.concatenate([zl, zl], -1) - f) / u
        others = jnp.sum(r_new, axis=1, keepdims=True) - r_new
        alpha = alpha + 0.5 * eta1 * (ntp * r_new - others)[..., :D]
        beta = beta + 0.5 * eta2 * (nbr * r_new - nbr_sum(r_new))
        return r_new, alpha, beta, lam

    z = jnp.zeros
    s0 = (z((V, T, 2 * D), jnp.float32), z((V, T, D), jnp.float32),
          z((V, T, 2 * D), jnp.float32), z((V, T, N), jnp.float32))
    return jax.lax.fori_loop(0, iters, admm, s0)


@functools.lru_cache(maxsize=None)
def _dtsvm_jit(hp: tuple):
    import jax

    return jax.jit(functools.partial(_dtsvm, **dict(hp)))


def dtsvm_fit(cfg: dict, rows, cols, vals, y, mask, adj,
              precision: str = "highest") -> Dict[str, np.ndarray]:
    """The reference fit of a sparse configuration's problem: ``{"r",
    "alpha", "beta", "lam"}`` as float32 numpy arrays."""
    s = cfg["solver"]
    hp = (("p", int(cfg["p"])), ("C", float(s["C"])),
          ("eps1", float(s["eps1"])), ("eps2", float(s["eps2"])),
          ("eta1", float(s["eta1"])), ("eta2", float(s["eta2"])),
          ("iters", int(cfg["iters"])), ("qp_iters", int(cfg["qp_iters"])),
          ("precision", precision))
    out = _dtsvm_jit(hp)(rows, cols, vals, y, mask, adj)
    return {k: np.asarray(v) for k, v in zip(("r", "alpha", "beta", "lam"),
                                             out)}
