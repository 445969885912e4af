"""Pallas TPU kernel: weighted Gram matrix  K = Z diag(a) Z^T.

This is the dual Hessian of DTSVM's QP (6) — the only O(N^2 p) hot spot of
the paper's algorithm.  TPU adaptation (DESIGN.md §3): tile K into
(BN x BN) MXU-aligned output blocks; each grid step loads two (BN, D) row
panels of Z into VMEM, scales one by ``a`` (VPU) and contracts on the MXU.
The feature dimension D (= p+1, tiny for the paper's PCA-10 data) is padded
to the 128-lane width by the wrapper in ``ops.py``.

Grid: (N/BN, N/BN).  VMEM per step: 2*BN*D + BN*BN floats — with BN=256 and
D=128 that is ~0.5 MB, far under the ~16 MB v5e VMEM budget, so the block
size is MXU-bound, not VMEM-bound.

``weighted_gram_tiled`` is the large-n generalization: a RECTANGULAR
block K[m, n] = sum_d Zm[m,d] a[d] Zn[n,d] over an explicit
``(tile_m, tile_n)`` output grid.  It serves two callers:

- the streamed invariant build (``engine.invariants`` under a
  ``PlanBudget``), which computes K row-panel by row-panel so the build's
  transient workspace stays bounded instead of one giant batched matmul;
- the sample-sharded backend, where each device owns a row panel
  K[rows, :] of its node's Gram matrix.

Tile alignment follows the TPU layout constraints: ``tile_m`` rounds up
to the 8-row sublane, ``tile_n`` to the 128-lane width.  Each grid step
loads a (tile_m, D) and a (tile_n, D) panel and contracts the full
(padded) feature dim on the MXU, so the per-element contraction order is
independent of the tile choice — tiled outputs are bitwise identical to
the square-kernel path (asserted against interpret mode in
tests/test_scale.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.launch import (LANE, SUBLANE, LaunchSpec,
                                  default_interpret, next_multiple)

DEFAULT_BLOCK = 256
DEFAULT_TILE = (256, 256)


def gram_launch_spec(M: int, N: int, D: int, tile_m: int, tile_n: int
                     ) -> LaunchSpec:
    """Geometry of one (possibly rectangular) weighted-Gram launch:
    operands Zm (M, D), Zn (N, D), a (1, D) in ``(tile_m, tile_n)``
    output blocks with the feature dim padded to the lane width.  The
    kernels below launch exactly this; ``repro.analysis.pallas_audit``
    validates it statically."""
    Mp = next_multiple(M, tile_m)
    Np = next_multiple(N, tile_n)
    Dp = next_multiple(D, LANE)
    return LaunchSpec(
        grid=(Mp // tile_m, Np // tile_n),
        in_blocks=((tile_m, Dp), (tile_n, Dp), (1, Dp)),
        padded_in=((Mp, Dp), (Np, Dp), (1, Dp)),
        out_block=(tile_m, tile_n),
        out_shape=(Mp, Np),
    )


def _gram_kernel(zi_ref, zj_ref, a_ref, out_ref):
    zi = zi_ref[...]                       # (BN, D)
    zj = zj_ref[...]                       # (BN, D)
    a = a_ref[...]                         # (1, D)
    zia = zi * a                           # VPU elementwise scale
    out_ref[...] = jax.lax.dot_general(
        zia, zj, (((1,), (1,)), ((), ())),
        precision="highest", preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def weighted_gram_2d(Z: jnp.ndarray, a: jnp.ndarray, *,
                     block: int = DEFAULT_BLOCK,
                     interpret=None) -> jnp.ndarray:
    """K = Z diag(a) Z^T for a single problem.  Z: (N, D), a: (D,).

    ``interpret`` defaults to platform-derived (compiled on TPU,
    interpret elsewhere)."""
    interpret = default_interpret(interpret)
    N, D = Z.shape
    bn = min(block, max(_next_multiple(N, SUBLANE), SUBLANE))
    spec = gram_launch_spec(N, N, D, bn, bn)
    (Np, Dp) = spec.padded_in[0]
    Zp = jnp.pad(Z, ((0, Np - N), (0, Dp - D))).astype(jnp.float32)
    ap = jnp.pad(a, (0, Dp - D)).astype(jnp.float32)[None, :]   # (1, Dp)

    out = pl.pallas_call(
        _gram_kernel,
        grid=spec.grid,
        in_specs=[
            pl.BlockSpec(spec.in_blocks[0], lambda i, j: (i, 0)),
            pl.BlockSpec(spec.in_blocks[1], lambda i, j: (j, 0)),
            pl.BlockSpec(spec.in_blocks[2], lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec(spec.out_block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(spec.out_shape, jnp.float32),
        interpret=interpret,
    )(Zp, Zp, ap)
    return out[:N, :N]


def align_tile(tile, m: int, n: int):
    """Round a requested ``(tile_m, tile_n)`` to the TPU layout grid:
    tile_m up to a multiple of 8 (sublanes), tile_n up to a multiple of
    128 (lanes), each capped at the padded extent of its axis."""
    tm, tn = tile
    tm = min(_next_multiple(max(int(tm), 1), SUBLANE),
             _next_multiple(m, SUBLANE))
    tn = min(_next_multiple(max(int(tn), 1), LANE),
             _next_multiple(n, LANE))
    return tm, tn


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def weighted_gram_tiled(Zm: jnp.ndarray, a: jnp.ndarray,
                        Zn: jnp.ndarray, *,
                        tile=DEFAULT_TILE,
                        interpret=None) -> jnp.ndarray:
    """Rectangular weighted Gram block K = Zm diag(a) Zn^T, tiled.

    Zm: (M, D) row panel, Zn: (N, D) column panel, a: (D,) ->  (M, N),
    computed in ``(tile_m, tile_n)`` output blocks (aligned via
    ``align_tile``).  ``weighted_gram_tiled(Z, a, Z)`` is the square
    kernel; a row-panel call is one streamed chunk of the large-n build.
    ``interpret`` as in ``launch.default_interpret``.
    """
    interpret = default_interpret(interpret)
    M, D = Zm.shape
    N, _ = Zn.shape
    tm, tn = align_tile(tile, M, N)
    spec = gram_launch_spec(M, N, D, tm, tn)
    (Mp, Dp), (Np, _) = spec.padded_in[0], spec.padded_in[1]
    Zmp = jnp.pad(Zm, ((0, Mp - M), (0, Dp - D))).astype(jnp.float32)
    Znp = jnp.pad(Zn, ((0, Np - N), (0, Dp - D))).astype(jnp.float32)
    ap = jnp.pad(a, (0, Dp - D)).astype(jnp.float32)[None, :]    # (1, Dp)

    out = pl.pallas_call(
        _gram_kernel,
        grid=spec.grid,
        in_specs=[
            pl.BlockSpec(spec.in_blocks[0], lambda i, j: (i, 0)),
            pl.BlockSpec(spec.in_blocks[1], lambda i, j: (j, 0)),
            pl.BlockSpec(spec.in_blocks[2], lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec(spec.out_block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(spec.out_shape, jnp.float32),
        interpret=interpret,
    )(Zmp, Znp, ap)
    return out[:M, :N]


_next_multiple = next_multiple
