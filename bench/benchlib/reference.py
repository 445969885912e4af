"""The plain references: DTSVM (the paper's Proposition 1) and the served
decision values.  They import nothing of the program.

DTSVM, decision vector r = [w0 (p), b0, wt (p), bt] per (node, task):

    U_vt  = diag(eps1*M1 + eps2*M2 + 2*eta1*(T-1)*P0 + 2*eta2*|B_v|*I)
    a     = 1/U[:p+1] + 1/U[p+1:]             ([I,I] U^-1 [I,I]^T)
    Z     = y * [X, 1]                         (label-signed data)
    K     = Z diag(a) Z^T,  L = max_i sum_j |K_ij|   (Gershgorin)
    f     = 2 [alpha, 0] + 2 beta - eta1 P0 (T_v r + sum_{t'} r_t' - r)
            - eta2 (|B_v| r + sum_{u in B_v} r_u)                  eq. (11)
    q     = 1 + Z (f[:p+1]/U[:p+1] + f[p+1:]/U[p+1:])
    lam   = FISTA on max -1/2 l'Kl + q'l, 0 <= l <= V*T*C,
            step 1/L, warm-started from the previous lam            eq. (6)
    r     = ([Z'lam, Z'lam] - f) / U                                 eq. (7)
    alpha += eta1/2 ((T_v-1) r - (sum_{t'} r_t' - r))[:p+1]          eq. (8)
    beta  += eta2/2 (|B_v| r - sum_{u in B_v} r_u)                   eq. (9)

The QP's matvec is evaluated as K y = Z (a * (Z^T y)), which never holds
K: the reference fits on one chip beside sizes whose K does not.  The
Gershgorin bound streams K in row blocks and keeps only the row sums.

Every contraction runs at ``precision``: ``"highest"`` (float32, what the
configurations state) or ``"high"`` (three bfloat16 passes: each operand
split into a bfloat16 head and tail, the tail-by-tail product dropped),
the control that must come out not correct.  The split is written out,
so the control computes the same on any backend.  The head is rounded
on the operand's bits, not through a bfloat16 round trip: under
XLA's excess precision a compiler may skip such a round trip inside a
fusion (the TPU's does), which leaves the tail 0 and the control one
pass.  The passes take the bfloat16 halves themselves as operands.
"""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np


def _split(x):
    """bfloat16 head and tail of a float32 array: the head is ``x``
    rounded to bfloat16 (to nearest, ties to even) on its bits, the
    tail the rest, ``x - head`` (exact in float32), rounded to
    bfloat16."""
    import jax.numpy as jnp
    from jax import lax

    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def contract(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in float32 (``"highest"``) or in three
    bfloat16 passes (``"high"``), each accumulated in float32."""
    import jax.numpy as jnp
    from jax import lax

    ein = functools.partial(jnp.einsum, spec,
                            precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    if precision == "highest":
        return ein(a, b)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split(a)
    bh, bl = _split(b)
    return ein(ah, bh) + (ein(ah, bl) + ein(al, bh))


def _lipschitz(Z, a, chunk: int, precision: str):
    """max_i sum_j |K_ij| per (v, t), K built ``chunk`` rows at a time."""
    import jax
    import jax.numpy as jnp

    N = Z.shape[-2]
    chunk = min(chunk, N)
    Za = Z * a[..., None, :]
    n_blocks = -(-N // chunk)

    def body(i, best):
        start = jnp.minimum(i * chunk, N - chunk)      # last block overlaps
        rows = jax.lax.dynamic_slice_in_dim(Za, start, chunk, axis=-2)
        Kb = contract("vtmd,vtnd->vtmn", rows, Z, precision)
        return jnp.maximum(best, jnp.max(jnp.sum(jnp.abs(Kb), -1), -1))

    best = jax.lax.fori_loop(0, n_blocks, body,
                             jnp.zeros(Z.shape[:-2], jnp.float32))
    return jnp.maximum(best, 1e-12)


def _dtsvm(X, y, mask, adj, *, C, eps1, eps2, eta1, eta2, iters, qp_iters,
           chunk, precision):
    import jax
    import jax.numpy as jnp

    V, T, N, p = X.shape
    D = p + 1
    adjf = adj.astype(jnp.float32)
    deg = jnp.sum(adjf, axis=1)                                  # |B_v|
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
    Z = y[..., None] * jnp.concatenate(
        [X, jnp.ones((V, T, N, 1), jnp.float32)], -1) * mask[..., None]
    step = (1.0 / _lipschitz(Z, a, chunk, precision))[..., None]

    def nbr_sum(r):
        return contract("vu,utd->vtd", adjf, r, precision)

    def matvec(v):
        zt = contract("vtn,vtnd->vtd", v, Z, precision)
        return contract("vtnd,vtd->vtn", Z, a * zt, precision)

    def fista(q, lam0):
        lam = jnp.clip(lam0, 0.0, hi)

        def body(_, s):
            lam, yk, t = s
            lam_new = jnp.clip(yk + step * (q - matvec(yk)), 0.0, hi)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            return lam_new, lam_new + ((t - 1.0) / t_new) * (lam_new - lam), \
                t_new

        return jax.lax.fori_loop(0, qp_iters, body,
                                 (lam, lam, jnp.float32(1.0)))[0]

    def admm(_, s):
        r, alpha, beta, lam = s
        others = jnp.sum(r, axis=1, keepdims=True) - r
        task = (ntp * r + others).at[..., D:].set(0.0)
        f = (2.0 * jnp.concatenate([alpha, jnp.zeros_like(alpha)], -1)
             + 2.0 * beta - eta1 * task - eta2 * (nbr * r + nbr_sum(r)))
        g = f[..., :D] / u[..., :D] + f[..., D:] / u[..., D:]
        q = mask + contract("vtnd,vtd->vtn", Z, g, precision)
        lam = fista(q, lam)
        zl = contract("vtn,vtnd->vtd", lam, Z, precision)
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


def reference_chunk(cfg: dict) -> int:
    """Rows of K per Gershgorin block: about 2**27 elements a block."""
    B, N = cfg["V"] * cfg["T"], cfg["N"]
    return max(8, min(N, (1 << 27) // (B * N)))


def dtsvm_fit(cfg: dict, X, y, mask, adj, precision: str = "highest"
              ) -> Dict[str, np.ndarray]:
    """The reference fit of a configuration's problem: ``{"r", "alpha",
    "beta", "lam"}`` as float32 numpy arrays."""
    s = cfg["solver"]
    hp = (("C", float(s["C"])), ("eps1", float(s["eps1"])),
          ("eps2", float(s["eps2"])), ("eta1", float(s["eta1"])),
          ("eta2", float(s["eta2"])), ("iters", int(cfg["iters"])),
          ("qp_iters", int(cfg["qp_iters"])),
          ("chunk", reference_chunk(cfg)), ("precision", precision))
    out = _dtsvm_jit(hp)(X, y, mask, adj)
    return {k: np.asarray(v) for k, v in zip(("r", "alpha", "beta", "lam"),
                                             out)}


def serve_decisions(W, b, X, node, task, start, rows,
                    precision: str = "highest") -> np.ndarray:
    """Decision value of every served row, request by request: float64
    on the host at ``"highest"``; at ``"high"`` the three-pass bfloat16
    control on the default device."""
    req = np.repeat(np.arange(len(rows)), rows)
    Wr = np.asarray(W)[node[req], task[req]]               # (R, p)
    br = np.asarray(b)[node[req], task[req]]               # (R,)
    if precision == "highest":
        return (np.einsum("rp,rp->r", np.asarray(X, np.float64),
                          Wr.astype(np.float64)) + br.astype(np.float64))
    import jax.numpy as jnp

    g = contract("rp,rp->r", jnp.asarray(X), jnp.asarray(Wr), precision)
    return np.asarray(g + jnp.asarray(br), np.float64)


def serve_scale(W, X, node, task, rows, b) -> np.ndarray:
    """Per-row rounding scale of a decision value: sum_j |x_j w_j| + |b|."""
    req = np.repeat(np.arange(len(rows)), rows)
    Wr = np.asarray(W, np.float64)[node[req], task[req]]
    br = np.asarray(b, np.float64)[node[req], task[req]]
    return np.sum(np.abs(np.asarray(X, np.float64) * Wr), -1) + np.abs(br)
