"""Pluggable QP engines for the dual sub-problem (6) of Prop. 1.

An engine solves the batched box QP

    maximize   -1/2 lam^T K lam + q^T lam,   0 <= lam <= hi

over arbitrary leading batch dims (K: (..., N, N), everything else
(..., N)) with a fixed iteration count and an optional precomputed
Lipschitz bound ``L`` (the Plan supplies the Gershgorin bound once per
fit instead of every solve):

    solve(K, q, hi, lam0=None, *, iters, L=None) -> lam

Built-ins:

- ``"fista"``        Nesterov-accelerated projected gradient — the
                     default, identical to the legacy `dtsvm_step` path.
- ``"pg"``           plain projected-gradient ascent.
- ``"pallas_fused"`` the fused matvec+step+projection Pallas kernel
                     (``repro.kernels.qp_step``) iterated via
                     ``kernels.ops.qp_pg_step`` — compiled on TPU,
                     interpret-mode under ``REPRO_USE_PALLAS=1`` on CPU,
                     jnp oracle otherwise.  Same fixed point as ``"pg"``.
- ``"pallas_fused_multi"`` the fused MULTI-iteration solve
                     (``kernels.ops.qp_pg_multi``): all ``iters``
                     projected-gradient iterations in one launch with
                     the duals VMEM-resident, K streamed tile-by-tile
                     per iteration.  Accepts ``precision="bf16"``
                     (mixed mode: bf16 K tiles, f32 iterates) and an
                     optional ``Z`` operand that folds the w-update
                     contraction ``zl = Z^T lam`` into the same pass
                     (the return becomes ``(lam, zl)``).  In f32 its
                     oracle path is clip + fori of the single step —
                     bitwise identical to ``"pallas_fused"``.

An engine's ``factored`` attribute is its low-rank form, which
consumes ``(Z, a)`` instead of ``K`` and evaluates each matvec as
``Z (a * (Z^T lam))`` in O(N D), K never materialized; it returns
``(lam, Z^T lam)``.  ``"fista"`` has one (``solve_fista_factored``),
and so has ``"pallas_fused_multi"`` (``solve_factored_multi``, the same
PG iteration).  ``compile_problem`` picks the operator from the shapes
(``engine.plan.resolve_qp_operator``) or takes
``SolverConfig(qp_operator=...)``.  Factored results match the
materialized path to float32 tolerance, not bitwise (the contraction
order differs by construction).

Register new engines with ``@qp_engines.register("name")``; select per
fit via ``SolverConfig(qp_solver="name")``.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import qp as qp_lib
from repro.kernels import ops as kops

_REGISTRY: Dict[str, Callable] = {}


def register(name: str):
    """Register a QP engine under ``name`` (decorator)."""
    def deco(fn: Callable) -> Callable:
        _REGISTRY[name] = fn
        return fn
    return deco


def get(name: str) -> Callable:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown QP engine {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def names():
    return sorted(_REGISTRY)


def _prep(K, q, hi, lam0, L):
    """Default the warm start and the Lipschitz bound."""
    if lam0 is None:
        lam0 = jnp.zeros_like(q)
    if L is None:
        L = qp_lib.gershgorin_lipschitz(K)
    return lam0, L


def _batched(recursion, matvec, ops, q, hi, lam0, L, iters):
    """``recursion`` (``core.qp._fista`` / ``_pg``) on every problem of
    the leading batch dims, K applied by ``matvec(*ops)`` of the
    problem's operands."""
    fn = lambda ob, qb, hb, l0, Lb: recursion(matvec(*ob), qb, hb, iters,
                                              l0, Lb)
    for _ in range(q.ndim - 1):
        fn = jax.vmap(fn)
    return fn(ops, q, hi, lam0, L)


def _k_matvec(gather):
    """``K -> (y -> K y)``; with ``gather``, a row panel K[rows, :] times
    the iterate's local rows joined over all rows (``plan.RowPanels``)."""
    return lambda K: lambda y: jnp.matmul(
        K, y if gather is None else gather(y), precision="highest")


def _factored(recursion, Z, a, q, hi, lam0, iters, L):
    """``recursion`` on K = Z diag(a) Z^T applied through its factors
    (``core.qp.factored_matvec``); returns ``(lam, zl)``, zl = Z^T lam."""
    if lam0 is None:
        lam0 = jnp.zeros_like(q)
    lam = _batched(recursion, qp_lib.factored_matvec, (Z, a), q, hi, lam0,
                   L, iters)
    # repro: noqa[raw-einsum-in-plan] — deliberate: plan_step's zl einsum, the same formula on the factored path
    zl = jnp.einsum("...n,...nd->...d", lam, Z, precision="highest")
    return lam, zl


@register("fista")
def solve_fista(K, q, hi, lam0=None, *, iters: int,
                L: Optional[jnp.ndarray] = None, gather=None):
    """``gather``: K holds row panels (``_k_matvec``)."""
    lam0, L = _prep(K, q, hi, lam0, L)
    return _batched(qp_lib._fista, _k_matvec(gather), (K,), q, hi, lam0, L,
                    iters)


def solve_fista_factored(Z, a, q, hi, lam0=None, *, iters: int, L):
    """:func:`solve_fista` on K = Z diag(a) Z^T applied through its
    factors: two reads of Z a step instead of one of K.  Returns
    ``(lam, zl)`` with ``zl = Z^T lam``, as :func:`solve_factored_multi`
    does."""
    return _factored(qp_lib._fista, Z, a, q, hi, lam0, iters, L)


#: the engine's factored form (``Z``, ``a`` in place of K), which
#: ``plan_step`` runs under ``qp_operator="factored"``
solve_fista.factored = solve_fista_factored


@register("pg")
def solve_pg(K, q, hi, lam0=None, *, iters: int,
             L: Optional[jnp.ndarray] = None, gather=None):
    """``gather``: as :func:`solve_fista`'s."""
    lam0, L = _prep(K, q, hi, lam0, L)
    return _batched(qp_lib._pg, _k_matvec(gather), (K,), q, hi, lam0, L,
                    iters)


@register("pallas_fused")
def solve_pallas_fused(K, q, hi, lam0=None, *, iters: int,
                       L: Optional[jnp.ndarray] = None):
    """Iterate the fused PG-step kernel: each step is one HBM round trip
    (matvec, gradient step and box projection fused — see
    ``repro.kernels.qp_step``)."""
    lam0, L = _prep(K, q, hi, lam0, L)
    gamma = 1.0 / L                                  # (...,) per problem
    lam = jnp.clip(lam0, 0.0, hi)

    def body(_, lam):
        return kops.qp_pg_step(lam, K, q, hi, gamma)

    return jax.lax.fori_loop(0, iters, body, lam)


@register("pallas_fused_multi")
def solve_pallas_fused_multi(K, q, hi, lam0=None, *, iters: int,
                             L: Optional[jnp.ndarray] = None,
                             precision: str = "f32", Z=None):
    """The fused multi-iteration solve: ONE launch runs every PG
    iteration with the duals VMEM-resident and K streamed tile-by-tile
    per iteration (``kernels.ops.qp_pg_multi``) — one HBM round trip
    per solve instead of per step.

    ``precision="bf16"`` streams bf16 K tiles against f32 iterates and
    accumulators.  With ``Z`` (..., N, D) the w-update contraction
    ``zl = Z^T lam`` of the final iterate folds into the same pass and
    the return becomes ``(lam, zl)``.  The f32 oracle path is clip +
    fori of ``ref.qp_pg_step`` — bitwise identical to
    :func:`solve_pallas_fused` by construction."""
    lam0, L = _prep(K, q, hi, lam0, L)
    gamma = 1.0 / L
    return kops.qp_pg_multi(lam0, K, q, hi, gamma, iters=iters, Z=Z,
                            precision=precision)


#: capability flags ``plan_step`` dispatches on: the engine understands
#: ``precision=`` and can fold the zl contraction via ``Z=``.
solve_pallas_fused_multi.supports_precision = True
solve_pallas_fused_multi.supports_fold = True


def solve_factored_multi(Z, a, q, hi, lam0=None, *, iters: int, L):
    """The low-rank PG solve: K = Z diag(a) Z^T is rank <= D << N, so
    each matvec evaluates as ``Z (a * (Z^T lam))`` in O(N D) — K is
    never materialized (``compile_problem`` skips the Gram build under
    ``qp_operator="factored"``; ``L`` is mandatory because there is no
    K to derive it from — the invariant build streams |K| row sums
    without keeping the panels).

    Returns ``(lam, zl)`` with ``zl = Z^T lam``.  NOT bitwise with the
    materialized path (the contraction reorders the reduction by
    construction); validated by the BENCH_fit risk deltas like the
    bf16 wire formats."""
    return _factored(qp_lib._pg, Z, a, q, hi, lam0, iters, L)


solve_pallas_fused_multi.factored = solve_factored_multi   # its PG form
