"""Pure-jnp oracles for the Pallas kernels (and the CPU execution path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def weighted_gram(Z: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """K = Z diag(a) Z^T.  Z: (..., N, D), a: (..., D) -> (..., N, N).

    This is the dual Hessian of DTSVM's QP (6):
    K = (Y X~) [I,I] U^{-1} [I,I]^T (Y X~)^T with diagonal U.
    """
    # repro: noqa[raw-einsum-in-plan] — deliberate: this oracle DEFINES the Gram semantics the Pallas kernels must reproduce bitwise (interpret-vs-oracle tests)
    return jnp.einsum("...nd,...d,...md->...nm", Z, a.astype(Z.dtype), Z,
                      precision="highest")


def weighted_gram_rows(Zm: jnp.ndarray, a: jnp.ndarray,
                       Zn: jnp.ndarray) -> jnp.ndarray:
    """Rectangular weighted Gram block K = Zm diag(a) Zn^T.

    Zm: (..., M, D) row panel, Zn: (..., N, D), a: (..., D) ->
    (..., M, N).  ``weighted_gram_rows(Z, a, Z)`` IS ``weighted_gram``;
    a row-slice call computes the matching row panel of the full K with
    the identical per-element contraction (the streamed large-n build
    and the sample-sharded backend rely on this being bitwise — each
    K[i, j] reduces over the same D terms in the same order regardless
    of which panel it lands in).
    """
    # repro: noqa[raw-einsum-in-plan] — deliberate: identical per-element contraction as weighted_gram (the streamed/sharded builds rely on panel == full bitwise)
    return jnp.einsum("...nd,...d,...md->...nm", Zm, a.astype(Zm.dtype), Zn,
                      precision="highest")


def qp_pg_step(lam: jnp.ndarray, K: jnp.ndarray, q: jnp.ndarray,
               hi: jnp.ndarray, gamma) -> jnp.ndarray:
    """One projected-gradient ascent step of the box QP:

        lam <- clip(lam + gamma * (q - K lam), 0, hi)

    lam/q/hi: (..., N), K: (..., N, N).  ``gamma`` is a scalar or a
    per-problem array of step sizes over a PREFIX of the batch dims
    (the engine supplies 1/L per (v,t) sub-problem; a sweep may supply
    (S,) or (S,V,T) against an (S,V,T,N) lam) — leading-aligned.
    """
    gamma = jnp.asarray(gamma, lam.dtype)
    if gamma.ndim:
        gamma = gamma.reshape(gamma.shape + (1,) * (lam.ndim - gamma.ndim))
    # repro: noqa[raw-einsum-in-plan] — deliberate: the matvec oracle the fused Pallas QP step is tested bitwise against
    grad = q - jnp.einsum("...nm,...m->...n", K, lam, precision="highest")
    return jnp.clip(lam + gamma * grad, 0.0, hi)


def qp_pg_multi(lam0: jnp.ndarray, K: jnp.ndarray, q: jnp.ndarray,
                hi: jnp.ndarray, gamma, *, iters: int, Z=None,
                precision: str = "f32"):
    """The full PG solve: ``iters`` steps of :func:`qp_pg_step` from a
    box-projected warm start — the oracle of the fused multi-iteration
    kernel (``qp_step.qp_pg_multi_1d``).

    In f32 this is BY CONSTRUCTION bitwise identical to clipping the
    warm start and iterating ``qp_pg_step`` (it is exactly that code),
    which is the contract the ``pallas_fused_multi`` engine inherits.
    ``precision="bf16"`` mirrors the kernel's mixed mode: K is cast to
    bf16 once and each matvec contracts bf16 x bf16 into f32
    accumulators, while the iterate/step/projection stay f32.  With
    ``Z`` (..., N, D), the w-update contraction ``zl = Z^T lam`` of the
    final iterate is folded in and the return becomes ``(lam, zl)``.
    """
    if precision not in ("f32", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    lam = jnp.clip(lam0, 0.0, hi)
    if precision == "f32":
        body = lambda _, lam: qp_pg_step(lam, K, q, hi, gamma)
    else:
        K16 = K.astype(jnp.bfloat16)
        gamma_a = jnp.asarray(gamma, lam.dtype)
        if gamma_a.ndim:
            gamma_a = gamma_a.reshape(
                gamma_a.shape + (1,) * (lam.ndim - gamma_a.ndim))

        def body(_, lam):
            # repro: noqa[raw-einsum-in-plan] — deliberate: the bf16-tile matvec oracle (bf16 operands, f32 accumulation) the kernel's mixed mode is tested against
            Klam = jnp.einsum("...nm,...m->...n", K16,
                              lam.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
            return jnp.clip(lam + gamma_a * (q - Klam), 0.0, hi)

    lam = jax.lax.fori_loop(0, iters, body, lam)
    if Z is None:
        return lam
    # repro: noqa[raw-einsum-in-plan] — deliberate: the zl fold oracle; formula matches plan_step's einsum exactly so the oracle fold is bitwise the unfolded plan path
    zl = jnp.einsum("...n,...nd->...d", lam, Z, precision="highest")
    return lam, zl
