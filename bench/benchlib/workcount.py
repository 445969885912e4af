"""The work any implementation of a DTSVM fit must do, from its shapes.

Counts are of what the mathematics needs, not of what the program
happens to materialise, so that a program which drops a buffer cannot
push a roofline share past 100%:

- invariant build: the Gershgorin bound L = max_i sum_j |K_ij| needs
  every entry of K = Z diag(a) Z^T, 2 N^2 (p+1) operations per (v, t),
  however K is built or kept.  Its bytes (reading Z) are negligible
  beside that and are not counted.
- ADMM: each inner QP iteration applies K to the iterate at least once.
  Through the factors that is one read of Z, 4 N (p+1) bytes per
  (v, t) in float32, and 4 N (p+1) operations (Z^T y, then Z times it).
  N is far above (p+1), so no implementation reads less.

Each count is of the whole problem, or with ``chips`` of one chip's
share where the work is split evenly over that many chips (sample or
node sharding), so that one chip's share is held against one chip's
peak and its own device time.
"""
from __future__ import annotations


def _vtnd(cfg: dict):
    return cfg["V"] * cfg["T"], cfg["N"], cfg["p"] + 1


def build_flops(cfg: dict, chips: int = 1) -> float:
    """Operations of one fit's invariant build: 2 V T N^2 (p+1)."""
    B, N, D = _vtnd(cfg)
    return 2.0 * B * N * N * D / chips


def qp_iterations(cfg: dict) -> int:
    return int(cfg["iters"]) * int(cfg["qp_iters"])


def admm_bytes(cfg: dict, chips: int = 1) -> float:
    """Bytes one fit's ADMM loop must read: one float32 pass over Z per
    inner QP iteration, 4 V T N (p+1) each."""
    B, N, D = _vtnd(cfg)
    return 4.0 * B * N * D * qp_iterations(cfg) / chips


def admm_flops(cfg: dict, chips: int = 1) -> float:
    """Operations of one fit's ADMM loop: 4 V T N (p+1) per inner QP
    iteration."""
    B, N, D = _vtnd(cfg)
    return 4.0 * B * N * D * qp_iterations(cfg) / chips
