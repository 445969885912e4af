"""Loop-invariant precomputation for the Prop.-1 ADMM iteration.

Every quantity here depends only on the *problem* (data, graph, masks,
hyper-parameters) — never on the ADMM state — so a fit() computes it
exactly once instead of once per iteration:

    Z    (V,T,N,p+1)   label-signed augmented data  (Y X~, mask-zeroed)
    a    (V,T,p+1)     [I,I] U^{-1} [I,I]^T diagonal
    K    (V,T,N,N)     dual Hessian  Z diag(a) Z^T  — the hot spot
    u    (V,T,2p+2)    diag(U_vt), eq. (10)
    ntp  (V,T)         coupling pair count  (T_v - 1) * couple * active
    nbr  (V,T)         active-neighbor count
    hi   (V,T,N)       QP box  box_scale * C * mask * active
    L    (V,T)         Gershgorin Lipschitz bound on K (the QP step size)

``compute_invariants`` is pure jnp (traceable inside jit / shard_map,
where each node computes only its own shard).  ``update_invariants`` is
the *incremental* host-side path behind the online Session: a change to
``active``/``couple`` recomputes counts/u/a/hi (cheap) and only the K
slices whose ``a`` row actually changed — untouched (v,t) reuse their
Gram block bit-for-bit.

Large-n scale path: the dense K build holds two K-sized buffers live at
once (the batched matmul output plus the |K| temporary of the
Gershgorin pass).  A ``PlanBudget`` caps that: ``gram_and_lipschitz``
streams K row-panel by row-panel (``kernels.ops.weighted_gram_rows``)
into a single preallocated buffer, folding the Gershgorin row sums into
the same pass — transient workspace ``chunk * N`` elements instead of a
second full K.  Streamed and dense builds are bitwise identical (each
K element reduces over the same D terms in the same order; row-sum /
max reductions are exact) — tests/test_scale.py asserts this, including
under ``REPRO_USE_PALLAS=1``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtsvm as core
from repro.core import qp as qp_lib
from repro.kernels import ops as kops
from repro.obs import spans as obs_spans


class PlanBudget(NamedTuple):
    """Memory budget for the invariant (Gram) build.

    Parameters
    ----------
    max_elems : int, optional
        Cap on the float32 elements of Gram workspace computed per
        streamed step.  The build streams K in row panels of
        ``chunk = max_elems // (batch * N)`` rows (rounded down to a
        multiple of 8, floor 8), so the transient footprint is one K
        buffer plus one ``batch * chunk * N`` panel — instead of the
        dense build's two full K-sized buffers.  A budget large enough
        to hold the whole build (``>= batch * N * N``) falls back to
        the dense path.
    tile : (int, int), optional
        Explicit ``(tile_m, tile_n)`` output tiling for the Pallas Gram
        kernel (aligned to the TPU (8, 128) layout grid — see
        ``kernels.gram.align_tile``).  Without ``max_elems``, ``tile_m``
        doubles as the streaming row-chunk size.  Tiling never changes
        results (bitwise) — it is a layout/memory knob only.

    Select per fit via ``SolverConfig(budget=PlanBudget(...))`` or pass
    directly to ``engine.compile_problem`` / ``engine.compile_sweep``.
    """
    max_elems: Optional[int] = None
    tile: Optional[Tuple[int, int]] = None

    def row_chunk(self, batch: int, n: int,
                  cols: Optional[int] = None) -> Optional[int]:
        """Rows of K streamed per step for a ``(batch, n, cols)`` build
        (``cols`` defaults to ``n`` — the square case) — or None when
        the budget doesn't bind (dense build)."""
        if self.max_elems is not None:
            per_row = max(int(batch) * int(cols if cols is not None
                                           else n), 1)
            chunk = max((int(self.max_elems) // per_row) // 8 * 8, 8)
        elif self.tile is not None:
            chunk = max(int(self.tile[0]) // 8 * 8, 8)
        else:
            return None
        return None if chunk >= n else chunk


class PlanInvariants(NamedTuple):
    ntp: jnp.ndarray      # (V, T)
    nbr: jnp.ndarray      # (V, T)
    u: jnp.ndarray        # (V, T, 2p+2)
    a: jnp.ndarray        # (V, T, p+1)
    Z: jnp.ndarray        # (V, T, N, p+1)
    K: Optional[jnp.ndarray]   # (V, T, N, N); None under the factored
    #                            operator (K is rank <= p+1 and the QP
    #                            matvec evaluates as Z (a (Z^T lam)) —
    #                            see engine.qp_engines.solve_factored_multi)
    hi: jnp.ndarray       # (V, T, N)
    L: jnp.ndarray        # (V, T)


def _masks_part(prob: core.DTSVMProblem,
                nbr_counts: Optional[jnp.ndarray] = None):
    """The active/couple-dependent pieces: counts, u, a, hi."""
    p = prob.X.shape[-1]
    ntp, nbr = core._counts(prob, nbr_counts)
    u = core._u_diag(prob, ntp, nbr)
    a = 1.0 / u[..., : p + 1] + 1.0 / u[..., p + 1:]
    hi = prob.box_scale * prob.C * prob.mask * prob.active[..., None]
    return ntp, nbr, u, a, hi


def streamed_gram_panel(Zm: jnp.ndarray, a: jnp.ndarray, Zn: jnp.ndarray,
                        chunk: int, tile=None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """K = Zm diag(a) Zn^T built ``chunk`` rows at a time, plus the
    per-row |K| sums (the Gershgorin ingredients) from the same pass.

    Zm: (..., M, D) row panel, Zn: (..., N, D), a: (..., D) ->
    ``(K (..., M, N), rowsums (..., M))``.  The row chunks write into
    one preallocated K via in-place loop carries, so the live set is K
    plus a single (batch, chunk, N) slab — the dense build's second
    K-sized |K| temporary never exists.  The loop runs as ONE jitted
    XLA while op (eager per-chunk dispatch would double-buffer the K
    carry and pay a K-sized copy per chunk).  A trailing chunk that
    would overrun clamps its start and recomputes a few rows —
    identical values rewritten, so the result stays bitwise equal to
    the dense build.  ``streamed_gram_panel(Z, a, Z, ...)`` is the
    square case; the sample-sharded backend streams its per-device row
    panel.
    """
    # the Pallas on/off decision is read at trace time inside
    # weighted_gram_rows — key the jit cache on it so an env flip
    # between calls cannot hit a stale entry
    return _streamed_gram_jit(Zm, a, Zn, chunk=int(chunk),
                              tile=None if tile is None else tuple(tile),
                              _pallas=kops._use_pallas())


@functools.partial(jax.jit, static_argnames=("chunk", "tile", "_pallas"))
def _streamed_gram_jit(Zm, a, Zn, *, chunk, tile, _pallas):
    with obs_spans.scope("invariant_build"):
        return _streamed_gram(Zm, a, Zn, chunk=chunk, tile=tile)


def _streamed_gram(Zm, a, Zn, *, chunk, tile):
    batch = Zm.shape[:-2]
    M, D = Zm.shape[-2:]
    N = Zn.shape[-2]
    Zmf = Zm.reshape((-1, M, D))
    Znf = Zn.reshape((-1, N, D))
    af = a.reshape((-1, D))
    B = Zmf.shape[0]
    chunk = min(chunk, M)
    nc = -(-M // chunk)
    K0 = jnp.zeros((B, M, N), jnp.float32)
    rs0 = jnp.zeros((B, M), jnp.float32)

    def body(i, carry):
        K, rs = carry
        b = i // nc
        start = jnp.minimum((i % nc) * chunk, M - chunk)
        zm = jax.lax.dynamic_slice(Zmf, (b, 0, 0), (1, M, D))[0]
        zn = jax.lax.dynamic_slice(Znf, (b, 0, 0), (1, N, D))[0]
        ab = jax.lax.dynamic_slice(af, (b, 0), (1, D))[0]
        zrows = jax.lax.dynamic_slice(zm, (start, 0), (chunk, D))
        Kc = kops.weighted_gram_rows(zrows, ab, zn, tile=tile)
        rc = jnp.sum(jnp.abs(Kc), axis=-1)
        K = jax.lax.dynamic_update_slice(K, Kc[None], (b, start, 0))
        rs = jax.lax.dynamic_update_slice(rs, rc[None], (b, start))
        return K, rs

    K, rs = jax.lax.fori_loop(0, B * nc, body, (K0, rs0))
    return K.reshape(batch + (M, N)), rs.reshape(batch + (M,))


@functools.partial(jax.jit, static_argnames=("chunk", "tile", "_pallas"))
def _streamed_rowsums_jit(Z, a, *, chunk, tile, _pallas):
    """Per-row |K| sums (the Gershgorin ingredients) computed chunk by
    chunk with the K panels DISCARDED — the factored operator's L pass.
    Each chunk runs the identical ``weighted_gram_rows`` + |.|-rowsum
    compute as ``_streamed_gram_jit``, so the resulting ``L`` is
    bitwise the streamed materialized build's at the same chunk."""
    with obs_spans.scope("invariant_build"):
        return _streamed_rowsums(Z, a, chunk=chunk, tile=tile)


def _streamed_rowsums(Z, a, *, chunk, tile):
    batch = Z.shape[:-2]
    N, D = Z.shape[-2:]
    Zf = Z.reshape((-1, N, D))
    af = a.reshape((-1, D))
    B = Zf.shape[0]
    chunk = min(chunk, N)
    nc = -(-N // chunk)
    rs0 = jnp.zeros((B, N), jnp.float32)

    def body(i, rs):
        b = i // nc
        start = jnp.minimum((i % nc) * chunk, N - chunk)
        zn = jax.lax.dynamic_slice(Zf, (b, 0, 0), (1, N, D))[0]
        ab = jax.lax.dynamic_slice(af, (b, 0), (1, D))[0]
        zrows = jax.lax.dynamic_slice(zn, (start, 0), (chunk, D))
        Kc = kops.weighted_gram_rows(zrows, ab, zn, tile=tile)
        rc = jnp.sum(jnp.abs(Kc), axis=-1)
        return jax.lax.dynamic_update_slice(rs, rc[None], (b, start))

    rs = jax.lax.fori_loop(0, B * nc, body, rs0)
    return rs.reshape(batch + (N,))


#: default row chunk of the K-less Lipschitz pass when no budget binds:
#: the transient panel is chunk*N elements — small against the O(N D)
#: factored working set, large enough to keep the per-chunk GEMM fat.
DEFAULT_LIPSCHITZ_CHUNK = 512


def streamed_lipschitz(Z: jnp.ndarray, a: jnp.ndarray,
                       budget: Optional[PlanBudget] = None) -> jnp.ndarray:
    """The Gershgorin bound L = max_i sum_j |K_ij| WITHOUT keeping K:
    row panels are computed, |.|-row-summed and discarded.  This is the
    factored operator's invariant build — its only K-sized quantity,
    streamed.  ``budget`` reuses the same ``row_chunk`` policy as the
    materialized streamed build (so factored and budgeted-materialized
    fits derive bitwise-identical L); without one the chunk defaults to
    :data:`DEFAULT_LIPSCHITZ_CHUNK`."""
    extra = (a.ndim - 1) - (Z.ndim - 2)
    if extra > 0:
        Z = jnp.broadcast_to(Z, a.shape[:-1] + Z.shape[-2:])
    batch = Z.shape[:-2]
    B = int(np.prod(batch, dtype=np.int64)) if batch else 1
    N = Z.shape[-2]
    chunk = budget.row_chunk(B, N) if budget is not None else None
    if chunk is None:
        chunk = min(DEFAULT_LIPSCHITZ_CHUNK, N)
    tile = None if budget is None else budget.tile
    rs = _streamed_rowsums_jit(Z, a, chunk=int(chunk),
                               tile=None if tile is None else tuple(tile),
                               _pallas=kops._use_pallas())
    return jnp.maximum(jnp.max(rs, axis=-1), 1e-12)


def gram_and_lipschitz(Z: jnp.ndarray, a: jnp.ndarray,
                       budget: Optional[PlanBudget] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The dual Hessian K = Z diag(a) Z^T and its Gershgorin bound L.

    Z: (..., N, D); ``a`` may carry extra leading batch dims (the
    sweep's shared-Z case) — Z broadcasts up.  Without a binding
    ``budget`` this is the dense pair (one batched ``weighted_gram``
    call, then ``gershgorin_lipschitz``); with one, K streams through
    bounded row panels (see ``_streamed_gram``).  Both paths are
    bitwise identical.
    """
    extra = (a.ndim - 1) - (Z.ndim - 2)
    if extra > 0:
        Z = jnp.broadcast_to(Z, a.shape[:-1] + Z.shape[-2:])
    if budget is not None:
        batch = Z.shape[:-2]
        B = int(np.prod(batch, dtype=np.int64)) if batch else 1
        chunk = budget.row_chunk(B, Z.shape[-2])
        if chunk is not None:
            K, rs = streamed_gram_panel(Z, a, Z, chunk, budget.tile)
            return K, jnp.maximum(jnp.max(rs, axis=-1), 1e-12)
    tile = None if budget is None else budget.tile
    with obs_spans.scope("invariant_build"):
        K = kops.weighted_gram(Z, a, tile=tile)
        return K, qp_lib.gershgorin_lipschitz(K)


def compute_z(prob: core.DTSVMProblem) -> jnp.ndarray:
    """The label-signed augmented data Z = Y [X, 1] (mask-zeroed).

    Z is the SHARED half of the invariant split: it depends only on the
    data (X, y, mask), never on hyper-parameters or membership masks, so
    a hyper-parameter sweep (``engine.sweep``) builds it once and shares
    it across every config; only ``_masks_part`` + the Gram re-weighting
    vary per config.
    """
    V, T, N, p = prob.X.shape
    Xa = jnp.concatenate([prob.X, jnp.ones((V, T, N, 1), jnp.float32)], -1)
    return prob.y[..., None] * Xa * prob.mask[..., None]


def compute_invariants(prob: core.DTSVMProblem, *,
                       nbr_counts: Optional[jnp.ndarray] = None,
                       Z: Optional[jnp.ndarray] = None,
                       budget: Optional[PlanBudget] = None,
                       materialize_k: bool = True
                       ) -> PlanInvariants:
    """All loop-invariants of Prop. 1, from scratch.  Pure jnp.

    ``Z`` may be passed in when the caller already holds it (the sweep
    compiler shares one Z across its whole config axis).  ``budget``
    streams the K build through bounded row panels (bitwise identical
    to the dense build — see ``gram_and_lipschitz``).
    ``materialize_k=False`` is the factored-operator build: K stays
    ``None`` and only the Gershgorin bound is computed, through
    discarded row panels (``streamed_lipschitz``) — the whole invariant
    set is O(N D) instead of O(N^2).
    """
    with obs_spans.span("invariant_build", budgeted=budget is not None,
                        materialize_k=materialize_k):
        ntp, nbr, u, a, hi = _masks_part(prob, nbr_counts)
        if Z is None:
            Z = compute_z(prob)
        if materialize_k:
            K, L = gram_and_lipschitz(Z, a, budget)
        else:
            K, L = None, streamed_lipschitz(Z, a, budget)
        return PlanInvariants(ntp=ntp, nbr=nbr, u=u, a=a, Z=Z, K=K, hi=hi,
                              L=L)


def update_invariants(prob: core.DTSVMProblem, inv: PlanInvariants, *,
                      active=None, couple=None,
                      budget: Optional[PlanBudget] = None
                      ) -> Tuple[core.DTSVMProblem, PlanInvariants, int]:
    """Incrementally re-plan after a membership change (host-side only).

    Returns ``(new_prob, new_inv, n_recomputed)`` where ``n_recomputed``
    is the number of (v,t) Gram slices that had to be rebuilt; the other
    ``V*T - n`` slices are reused unchanged (bit-for-bit — a Gram block
    depends only on Z, which membership events never touch, and its own
    ``a`` row).  ``budget`` streams the rebuilt slices through bounded
    row panels, so an online membership event at large n never
    materializes more Gram workspace than the original budgeted build.
    """
    new_prob = prob
    if active is not None:
        new_prob = new_prob._replace(
            active=jnp.asarray(active, jnp.float32))
    if couple is not None:
        new_prob = new_prob._replace(
            couple=jnp.asarray(couple, jnp.float32))
    ntp, nbr, u, a, hi = _masks_part(new_prob)
    changed = np.any(np.asarray(a) != np.asarray(inv.a), axis=-1)   # (V,T)
    n = int(changed.sum())
    if n == 0:
        K, L = inv.K, inv.L
    elif inv.K is None:                  # factored plan: L-only rebuild
        K = None
        if n == changed.size:
            L = streamed_lipschitz(inv.Z, a, budget)
        else:
            iv, it = np.nonzero(changed)
            L = inv.L.at[iv, it].set(
                streamed_lipschitz(inv.Z[iv, it], a[iv, it], budget))
    elif n == changed.size:
        K, L = gram_and_lipschitz(inv.Z, a, budget)
    else:
        iv, it = np.nonzero(changed)
        K_sub, L_sub = gram_and_lipschitz(inv.Z[iv, it], a[iv, it],
                                          budget)                   # (n,N,N)
        K = inv.K.at[iv, it].set(K_sub)
        L = inv.L.at[iv, it].set(L_sub)
    new_inv = PlanInvariants(ntp=ntp, nbr=nbr, u=u, a=a, Z=inv.Z, K=K,
                             hi=hi, L=L)
    return new_prob, new_inv, n
