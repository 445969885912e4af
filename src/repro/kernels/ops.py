"""Dispatching wrappers for the Pallas kernels.

On a TPU the Pallas kernels always run compiled.  On the CPU they can
only run in ``interpret=True`` mode (the kernel body executes in
Python, validating the BlockSpec tiling and kernel semantics
bit-for-bit against ``ref.py``); because that is slow, the *default*
CPU execution path is the jnp oracle, and ``REPRO_USE_PALLAS=1`` forces
the interpreted kernels (the kernel-suite CI lane and the engine's
QP-equivalence tests do this).  ``REPRO_USE_PALLAS`` is the CPU tests'
switch only: on a TPU a value of ``0`` is refused, and the one way to
run the oracle there is the explicit :func:`reference_kernels` scope.

Both wrappers are live solve-path code, not just benchmarks:

- ``weighted_gram`` builds the dual Hessian K = Z diag(a) Z^T exactly
  once per fit, inside ``repro.engine.compile_problem``.
- ``qp_pg_step`` is the inner loop of the ``"pallas_fused"`` QP engine
  (``repro.engine.qp_engines``) — one fused matvec+step+projection per
  dual iteration, selected via ``SolverConfig(qp_solver="pallas_fused")``.
"""
from __future__ import annotations

import contextlib
import contextvars
import os

import jax
import jax.numpy as jnp

from repro.kernels import gram as gram_kernel
from repro.kernels import qp_step as qp_kernel
from repro.kernels import ref

_REFERENCE = contextvars.ContextVar("reference_kernels", default=False)


def _use_pallas() -> bool:
    """True when the wrappers run the Pallas kernels, False when they
    run the jnp oracle (``ref.py``).  Read at trace time."""
    if _REFERENCE.get():
        return False
    flag = os.environ.get("REPRO_USE_PALLAS", "auto")
    if jax.default_backend() == "tpu":
        if flag == "0":
            raise RuntimeError(
                "REPRO_USE_PALLAS=0 on a TPU would run the jnp oracle in "
                "place of the compiled kernels; it is the CPU tests' "
                "switch — use kernels.ops.reference_kernels() to run the "
                "oracle on purpose")
        return True
    return flag == "1"


@contextlib.contextmanager
def reference_kernels():
    """Run the jnp oracle (``ref.py``) in place of the Pallas kernels,
    on any backend, for the duration of the block — the plain reference
    a kernel run is checked against.  The scope holds in the calling
    thread only and is read at trace time, so a jitted function first
    traced inside the block keeps the oracle in its cache unless it
    keys the cache on the switch (the streamed Gram builds do)."""
    token = _REFERENCE.set(True)
    try:
        yield
    finally:
        _REFERENCE.reset(token)


def weighted_gram(Z: jnp.ndarray, a: jnp.ndarray, *,
                  tile=None) -> jnp.ndarray:
    """K = Z diag(a) Z^T over arbitrary leading batch dims.

    ``a`` may carry MORE leading dims than ``Z`` (the sweep engine's
    shared-Z case: one (V,T,N,D) data tensor re-weighted by an
    (S,V,T,D) stack of per-config diagonals) — Z is broadcast up to
    ``a``'s batch.  ``tile`` optionally selects an explicit
    ``(tile_m, tile_n)`` output tiling for the Pallas kernel (the
    ``PlanBudget.tile`` knob); tiled and square-kernel outputs are
    bitwise identical, so this is a layout choice, not a numeric one."""
    extra = (a.ndim - 1) - (Z.ndim - 2)
    if extra > 0:
        Z = jnp.broadcast_to(Z, a.shape[:-1] + Z.shape[-2:])
    if not _use_pallas():
        return ref.weighted_gram(Z, a)
    if tile is None:
        fn = gram_kernel.weighted_gram_2d
    else:
        tile = tuple(tile)
        fn = lambda z2, a1: gram_kernel.weighted_gram_tiled(
            z2, a1, z2, tile=tile)
    batch = Z.shape[:-2]
    if batch:
        flatZ = Z.reshape((-1,) + Z.shape[-2:])
        flata = a.reshape((-1,) + a.shape[-1:])
        out = jax.lax.map(lambda za: fn(*za), (flatZ, flata))
        return out.reshape(batch + out.shape[-2:])
    return fn(Z, a)


def weighted_gram_rows(Zm: jnp.ndarray, a: jnp.ndarray, Zn: jnp.ndarray, *,
                       tile=None) -> jnp.ndarray:
    """Rectangular Gram block K = Zm diag(a) Zn^T over leading batch dims.

    Zm: (..., M, D) row panel, Zn: (..., N, D), a: (..., D) ->
    (..., M, N).  One streamed chunk of the large-n invariant build
    (``engine.invariants`` under a ``PlanBudget``) and the per-device
    panel of the sample-sharded backend.  Row panels are bitwise
    identical to the matching rows of the dense ``weighted_gram`` on
    both the jnp and the interpret-mode Pallas path (tests/test_scale).
    ``tile``: ``(tile_m, tile_n)`` Pallas output tiling (default
    ``kernels.gram.DEFAULT_TILE``)."""
    if not _use_pallas():
        return ref.weighted_gram_rows(Zm, a, Zn)
    tile = gram_kernel.DEFAULT_TILE if tile is None else tuple(tile)
    fn = lambda zm, a1, zn: gram_kernel.weighted_gram_tiled(
        zm, a1, zn, tile=tile)
    batch = Zm.shape[:-2]
    if batch:
        flat = lambda x: x.reshape((-1,) + x.shape[len(batch):])
        out = jax.lax.map(lambda args: fn(*args),
                          (flat(Zm), flat(a), flat(Zn)))
        return out.reshape(batch + out.shape[-2:])
    return fn(Zm, a, Zn)


def qp_pg_step(lam, K, q, hi, gamma) -> jnp.ndarray:
    """Fused projected-gradient step over arbitrary leading batch dims.

    ``gamma`` may be a scalar or a per-problem step-size array over a
    PREFIX of the batch dims (1/L per (v,t) sub-problem, or per config
    in a sweep: an (S,) or (S,V,T) gamma against an (S,V,T,N) lam) —
    leading-aligned, then broadcast across the remaining batch dims."""
    if not _use_pallas():
        return ref.qp_pg_step(lam, K, q, hi, gamma)
    fn = qp_kernel.qp_pg_step_1d
    batch = lam.shape[:-1]
    gamma = _align_gamma(gamma, batch)
    if batch:
        flat = lambda x, nd: x.reshape((-1,) + x.shape[len(batch):])
        gamma_b = flat(jnp.broadcast_to(gamma, batch), 0)
        out = jax.lax.map(
            lambda args: fn(*args),
            (flat(lam, 1), flat(K, 2), flat(q, 1), flat(hi, 1), gamma_b))
        return out.reshape(batch + out.shape[-1:])
    return fn(lam, K, q, hi, gamma)


def _align_gamma(gamma, batch):
    """Normalize a step-size array for the 1-d kernels: leading-align a
    per-problem gamma against ``batch``, and in the UNBATCHED case
    squeeze a size-1 array (e.g. shape ``(1,)``) to 0-d — the 1-d
    kernels expect a scalar for their (1, 1) block, and a non-scalar
    gamma used to slip through when ``batch`` was empty."""
    gamma = jnp.asarray(gamma, jnp.float32)
    if not batch:
        return gamma.reshape(())            # raises if gamma.size != 1
    if gamma.ndim and gamma.ndim < len(batch):      # leading-align
        gamma = gamma.reshape(gamma.shape + (1,) * (len(batch) - gamma.ndim))
    return gamma


def qp_pg_multi(lam0, K, q, hi, gamma, *, iters: int, Z=None,
                precision: str = "f32"):
    """The fused multi-iteration PG solve over arbitrary leading batch
    dims: clip the warm start into the box, run ``iters`` fused
    matvec+step+projection iterations with the duals resident (VMEM on
    the kernel path), optionally folding the w-update contraction
    ``zl = Z^T lam`` of the final iterate into the same pass.

    Returns ``lam`` — or ``(lam, zl)`` when ``Z`` (..., N, D) is given.
    ``precision="bf16"`` selects the mixed mode (bf16 K tiles, f32
    iterates/accumulators) on both the kernel and the oracle path.  On
    a given dispatch path f32 is bitwise identical to iterating
    :func:`qp_pg_step` from a clipped warm start — exactly, by
    construction, on the oracle path; the interpret/compiled kernel is
    a separately compiled program and matches the iterated kernel to
    compiler-contraction (FMA) tolerance.  ``gamma`` follows the same
    leading-aligned convention as :func:`qp_pg_step`."""
    if not _use_pallas():
        return ref.qp_pg_multi(lam0, K, q, hi, gamma, iters=iters, Z=Z,
                               precision=precision)
    fn = lambda l0, K2, q1, h1, g0, z2: qp_kernel.qp_pg_multi_1d(
        l0, K2, q1, h1, g0, iters=iters, Z=z2, precision=precision)
    batch = lam0.shape[:-1]
    gamma = _align_gamma(gamma, batch)
    if not batch:
        return fn(lam0, K, q, hi, gamma, Z)
    flat = lambda x: x.reshape((-1,) + x.shape[len(batch):])
    gamma_b = flat(jnp.broadcast_to(gamma, batch))
    if Z is None:
        out = jax.lax.map(
            lambda args: fn(*args, None),
            (flat(lam0), flat(K), flat(q), flat(hi), gamma_b))
        return out.reshape(batch + out.shape[-1:])
    lam_f, zl_f = jax.lax.map(
        lambda args: fn(*args),
        (flat(lam0), flat(K), flat(q), flat(hi), gamma_b, flat(Z)))
    return (lam_f.reshape(batch + lam_f.shape[-1:]),
            zl_f.reshape(batch + zl_f.shape[-1:]))
