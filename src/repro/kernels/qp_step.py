"""Pallas TPU kernel: fused projected-gradient step of the box QP.

    lam <- clip(lam + gamma * (q - K lam), 0, hi)

One kernel performs the matvec K@lam (tiled over K's column blocks,
accumulated in a VMEM scratch buffer) and, on the last column step, applies
the gradient step + box projection in-register — lam never round-trips to
HBM between the matvec and the projection.  This is the inner loop of
DTSVM's dual solve (Prop. 1, eq. 6).

Vectors are carried as (1, N) row panels so the lane dimension is the
128-wide minor axis.  Grid: (N/BR, N/BC); the column index is the minor
(fastest) grid dimension, so each output row block accumulates over all of
its column blocks before finalizing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.launch import (LANE, LaunchSpec, default_interpret,
                                  next_multiple)

DEFAULT_BLOCK = 256


def qp_launch_spec(N: int, block: int = DEFAULT_BLOCK) -> LaunchSpec:
    """Geometry of one fused QP-step launch: K (N, N) in (bn, bn)
    tiles, the four vectors as (1, bn) row panels, the scalar gamma as
    a (1, 1) block, one (1, bn) VMEM accumulator.  The kernel below
    launches exactly this; ``repro.analysis.pallas_audit`` validates
    it statically."""
    bn = min(block, max(next_multiple(N, LANE), LANE))
    Np = next_multiple(N, bn)
    n = Np // bn
    return LaunchSpec(
        grid=(n, n),
        in_blocks=((bn, bn), (1, bn), (1, bn), (1, bn), (1, bn),
                   (1, 1)),
        padded_in=((Np, Np), (1, Np), (1, Np), (1, Np), (1, Np),
                   (1, 1)),
        out_block=(1, bn),
        out_shape=(1, Np),
        scratch=((1, bn),),
    )


def _qp_step_kernel(K_ref, lamc_ref, lamr_ref, q_ref, hi_ref, gamma_ref,
                    out_ref, acc_ref, *, n_col: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lam_c = lamc_ref[...]                   # (1, BC) column slice of lam
    Kb = K_ref[...]                         # (BR, BC)
    # (1, BC) x (BR, BC)^T -> (1, BR): y_r += sum_c K[r, c] lam[c]
    acc_ref[...] += jax.lax.dot_general(
        lam_c, Kb, (((1,), (1,)), ((), ())),
        precision="highest", preferred_element_type=jnp.float32)

    @pl.when(j == n_col - 1)
    def _finalize():
        lam_r = lamr_ref[...]               # (1, BR) row slice
        grad = q_ref[...] - acc_ref[...]
        stepped = lam_r + gamma_ref[0, 0] * grad
        out_ref[...] = jnp.clip(stepped, 0.0, hi_ref[...])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def qp_pg_step_1d(lam, K, q, hi, gamma, *, block: int = DEFAULT_BLOCK,
                  interpret=None):
    """One fused PG step for a single problem.  lam/q/hi: (N,), K: (N,N).

    Padding rows get hi=0, so their duals are projected back to 0 and they
    never contribute to the matvec (K padding is zero).  ``interpret``
    defaults to platform-derived (compiled on TPU, interpret elsewhere);
    pass it explicitly to pin a mode."""
    interpret = default_interpret(interpret)
    N = lam.shape[0]
    spec = qp_launch_spec(N, block)
    Np = spec.out_shape[1]
    pad = Np - N
    lam_p = jnp.pad(lam, (0, pad)).astype(jnp.float32)[None, :]
    q_p = jnp.pad(q, (0, pad)).astype(jnp.float32)[None, :]
    hi_p = jnp.pad(hi, (0, pad)).astype(jnp.float32)[None, :]
    K_p = jnp.pad(K, ((0, pad), (0, pad))).astype(jnp.float32)
    gamma_arr = jnp.asarray(gamma, jnp.float32).reshape(1, 1)

    n_col = spec.grid[1]
    out = pl.pallas_call(
        functools.partial(_qp_step_kernel, n_col=n_col),
        grid=spec.grid,
        in_specs=[
            pl.BlockSpec(spec.in_blocks[0], lambda i, j: (i, j)),  # K
            pl.BlockSpec(spec.in_blocks[1], lambda i, j: (0, j)),  # lam (col)
            pl.BlockSpec(spec.in_blocks[2], lambda i, j: (0, i)),  # lam (row)
            pl.BlockSpec(spec.in_blocks[3], lambda i, j: (0, i)),  # q
            pl.BlockSpec(spec.in_blocks[4], lambda i, j: (0, i)),  # hi
            pl.BlockSpec(spec.in_blocks[5], lambda i, j: (0, 0)),  # gamma
        ],
        out_specs=pl.BlockSpec(spec.out_block, lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct(spec.out_shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM(spec.scratch[0], jnp.float32)],
        interpret=interpret,
    )(K_p, lam_p, lam_p, q_p, hi_p, gamma_arr)
    return out[0, :N]


def qp_multi_launch_spec(N: int, iters: int, block: int = DEFAULT_BLOCK,
                         d: int = None) -> LaunchSpec:
    """Geometry of one fused multi-iteration QP solve: grid
    ``(iters, N/BN, N/BN)`` with K streamed in (bn, bn) tiles per
    iteration while lam0/q/hi live as full (1, Np) VMEM-resident rows;
    scratch holds the current iterate (1, Np) plus the (1, bn) matvec
    accumulator.  With ``d`` (the zl fold), Z joins as (bn, Dp) row
    panels and the (1, Dp) zl accumulator block is accounted under
    ``scratch`` (``LaunchSpec`` carries one primary out block — lam).
    ``repro.analysis.pallas_audit`` validates this statically."""
    bn = min(block, max(next_multiple(N, LANE), LANE))
    Np = next_multiple(N, bn)
    n = Np // bn
    in_blocks = [(bn, bn), (1, Np), (1, Np), (1, Np), (1, 1)]
    padded_in = [(Np, Np), (1, Np), (1, Np), (1, Np), (1, 1)]
    scratch = [(1, Np), (1, bn)]
    if d is not None:
        Dp = next_multiple(d, LANE)
        in_blocks.append((bn, Dp))
        padded_in.append((Np, Dp))
        scratch.append((1, Dp))             # the zl fold output block
    return LaunchSpec(
        grid=(iters, n, n),
        in_blocks=tuple(in_blocks),
        padded_in=tuple(padded_in),
        out_block=(1, Np),
        out_shape=(1, Np),
        scratch=tuple(scratch),
    )


def _matvec_precision(dtype):
    """f32 K tiles contract at full f32 precision (several MXU passes;
    the TPU's default is one bf16 pass).  bf16 tiles are exact in one
    pass, and Mosaic refuses an f32 contraction precision for them."""
    return "highest" if dtype == jnp.float32 else None


def _qp_multi_kernel(K_ref, lam0_ref, q_ref, hi_ref, gamma_ref, out_ref,
                     lam_ref, acc_ref, *, n_row: int, n_col: int,
                     iters: int, bn: int):
    """Multi-iteration PG solve: the whole inner loop in one launch.

    ``lam_ref`` (VMEM scratch) carries the current iterate across grid
    steps; ``out_ref`` doubles as the next-iterate buffer (two-buffer
    Jacobi), so every row block of iteration t reads the UNCHANGED
    iterate t-1 — the same Jacobi sweep the iterated single-step kernel
    computes (same bn, same per-row tile accumulation order).  The two
    are separately compiled XLA programs, so they agree to compiler
    contraction (FMA) tolerance, not bitwise — the bitwise contract
    lives on the oracle dispatch path (see ``ref.qp_pg_multi``).  K
    streams tile-by-tile each iteration; the duals never round-trip
    through HBM."""
    t, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((t == 0) & (i == 0) & (j == 0))
    def _warm_start():
        lam_ref[...] = jnp.clip(lam0_ref[...], 0.0, hi_ref[...])

    @pl.when(j == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lam_c = lam_ref[:, pl.ds(j * bn, bn)]   # (1, BC) column slice, iter t-1
    Kb = K_ref[...]                         # (BR, BC), f32 or bf16 tile
    # (1, BC) x (BR, BC)^T -> (1, BR): y_r += sum_c K[r, c] lam[c]
    acc_ref[...] += jax.lax.dot_general(
        lam_c.astype(Kb.dtype), Kb, (((1,), (1,)), ((), ())),
        precision=_matvec_precision(Kb.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(j == n_col - 1)
    def _row_update():
        lam_r = lam_ref[:, pl.ds(i * bn, bn)]
        grad = q_ref[:, pl.ds(i * bn, bn)] - acc_ref[...]
        stepped = lam_r + gamma_ref[0, 0] * grad
        out_ref[:, pl.ds(i * bn, bn)] = jnp.clip(
            stepped, 0.0, hi_ref[:, pl.ds(i * bn, bn)])

    @pl.when((j == n_col - 1) & (i == n_row - 1))
    def _next_iteration():
        lam_ref[...] = out_ref[...]


def _qp_multi_fold_kernel(K_ref, lam0_ref, q_ref, hi_ref, gamma_ref, Z_ref,
                          out_ref, zl_ref, lam_ref, acc_ref, *, n_row: int,
                          n_col: int, iters: int, bn: int):
    """The fold variant: identical iteration body, plus the per-task
    w-update contraction zl = Z^T lam accumulated in-register from the
    FINAL iterate's row blocks — the ADMM primal update's only
    dual-sized reduction rides the same launch instead of a separate
    HBM pass over lam."""
    t, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((t == 0) & (i == 0) & (j == 0))
    def _warm_start():
        lam_ref[...] = jnp.clip(lam0_ref[...], 0.0, hi_ref[...])
        zl_ref[...] = jnp.zeros_like(zl_ref)

    @pl.when(j == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lam_c = lam_ref[:, pl.ds(j * bn, bn)]
    Kb = K_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        lam_c.astype(Kb.dtype), Kb, (((1,), (1,)), ((), ())),
        precision=_matvec_precision(Kb.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(j == n_col - 1)
    def _row_update():
        lam_r = lam_ref[:, pl.ds(i * bn, bn)]
        grad = q_ref[:, pl.ds(i * bn, bn)] - acc_ref[...]
        stepped = lam_r + gamma_ref[0, 0] * grad
        new_row = jnp.clip(stepped, 0.0, hi_ref[:, pl.ds(i * bn, bn)])
        out_ref[:, pl.ds(i * bn, bn)] = new_row

        @pl.when(t == iters - 1)
        def _fold_zl():                     # (1, BR) x (BR, Dp) -> (1, Dp)
            zl_ref[...] += jax.lax.dot_general(
                new_row, Z_ref[...], (((1,), (0,)), ((), ())),
                precision="highest", preferred_element_type=jnp.float32)

    @pl.when((j == n_col - 1) & (i == n_row - 1))
    def _next_iteration():
        lam_ref[...] = out_ref[...]


@functools.partial(jax.jit, static_argnames=("iters", "block", "precision",
                                             "interpret"))
def qp_pg_multi_1d(lam0, K, q, hi, gamma, *, iters: int, Z=None,
                   block: int = DEFAULT_BLOCK, precision: str = "f32",
                   interpret=None):
    """The full fused PG solve for a single problem: ``iters`` projected
    gradient iterations in ONE launch.  lam0/q/hi: (N,), K: (N, N);
    optional Z: (N, D) folds the w-update contraction ``zl = Z^T lam``
    into the same launch and makes the return ``(lam, zl)``.

    The warm start is clipped into the box in-kernel, the iterate stays
    VMEM-resident across iterations, and K streams tile-by-tile per
    iteration — one HBM round trip per SOLVE, not per step.

    ``precision="bf16"`` streams bf16 K tiles against f32 iterates and
    accumulators (the MXU-native mixed mode; halves the dominant HBM
    traffic).  f32 mode performs the identical Jacobi arithmetic as
    iterating ``qp_pg_step_1d`` with the same ``block``; being a
    different compiled program, it matches to compiler-contraction
    (1-2 ulp) tolerance — the bitwise multi-vs-iterated contract is
    the ORACLE path's (``ref.qp_pg_multi`` is clip + fori of
    ``ref.qp_pg_step`` by construction).  ``interpret`` defaults to
    platform-derived."""
    if precision not in ("f32", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    interpret = default_interpret(interpret)
    N = lam0.shape[0]
    fold = Z is not None
    spec = qp_multi_launch_spec(N, iters, block,
                                d=Z.shape[1] if fold else None)
    Np = spec.out_shape[1]
    bn = spec.in_blocks[0][0]
    pad = Np - N
    lam_p = jnp.pad(lam0, (0, pad)).astype(jnp.float32)[None, :]
    q_p = jnp.pad(q, (0, pad)).astype(jnp.float32)[None, :]
    hi_p = jnp.pad(hi, (0, pad)).astype(jnp.float32)[None, :]
    K_p = jnp.pad(K, ((0, pad), (0, pad))).astype(jnp.float32)
    if precision == "bf16":
        K_p = K_p.astype(jnp.bfloat16)
    gamma_arr = jnp.asarray(gamma, jnp.float32).reshape(1, 1)

    _, n_row, n_col = spec.grid
    body = functools.partial(
        _qp_multi_fold_kernel if fold else _qp_multi_kernel,
        n_row=n_row, n_col=n_col, iters=iters, bn=bn)
    in_specs = [
        pl.BlockSpec(spec.in_blocks[0], lambda t, i, j: (i, j)),   # K
        pl.BlockSpec(spec.in_blocks[1], lambda t, i, j: (0, 0)),   # lam0
        pl.BlockSpec(spec.in_blocks[2], lambda t, i, j: (0, 0)),   # q
        pl.BlockSpec(spec.in_blocks[3], lambda t, i, j: (0, 0)),   # hi
        pl.BlockSpec(spec.in_blocks[4], lambda t, i, j: (0, 0)),   # gamma
    ]
    out_specs = pl.BlockSpec(spec.out_block, lambda t, i, j: (0, 0))
    out_shape = jax.ShapeDtypeStruct(spec.out_shape, jnp.float32)
    operands = [K_p, lam_p, q_p, hi_p, gamma_arr]
    if fold:
        D = Z.shape[1]
        Dp = spec.in_blocks[5][1]
        Z_p = jnp.pad(Z, ((0, pad), (0, Dp - D))).astype(jnp.float32)
        in_specs.append(
            pl.BlockSpec(spec.in_blocks[5], lambda t, i, j: (i, 0)))  # Z
        out_specs = [out_specs,
                     pl.BlockSpec((1, Dp), lambda t, i, j: (0, 0))]   # zl
        out_shape = [out_shape, jax.ShapeDtypeStruct((1, Dp), jnp.float32)]
        operands.append(Z_p)

    out = pl.pallas_call(
        body,
        grid=spec.grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(spec.scratch[0], jnp.float32),
                        pltpu.VMEM(spec.scratch[1], jnp.float32)],
        interpret=interpret,
    )(*operands)
    if fold:
        lam_out, zl_out = out
        return lam_out[0, :N], zl_out[0, :Z.shape[1]]
    return out[0, :N]


_next_multiple = next_multiple
