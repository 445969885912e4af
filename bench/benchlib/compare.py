"""The comparisons that decide ``correct``.

Each compared number has a limit (in the cell's ``workloads/<cell>.json``
under ``limits``); ``correct`` is true when every number is at or under
its limit.  ``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

from typing import Dict, Iterable, Mapping

import numpy as np

STATE_LEAVES = ("r", "alpha", "beta", "lam")


def leaf_rel_err(got, ref) -> float:
    """max|got - ref| / max|ref| of one leaf (1.0 where ref is all 0)."""
    g = np.asarray(got, np.float64)
    r = np.asarray(ref, np.float64)
    if g.shape != r.shape:
        return float("inf")
    if not np.isfinite(g).all():
        return float("inf")
    scale = float(np.max(np.abs(r))) if r.size else 0.0
    diff = float(np.max(np.abs(g - r))) if r.size else 0.0
    if scale == 0.0:
        return 0.0 if diff == 0.0 else float("inf")
    return diff / scale


def state_rel_err(got: Mapping[str, np.ndarray],
                  ref: Mapping[str, np.ndarray]) -> float:
    """Worst leaf of a fit's state (r, alpha, beta, lam) against the
    reference's, each as max abs gap over the reference's max abs."""
    return max(leaf_rel_err(got[k], ref[k]) for k in STATE_LEAVES)


def served_rel_gap(answers: np.ndarray, ref: np.ndarray,
                   scale: np.ndarray) -> float:
    """Widest gap of a served decision value from the reference's, over
    the value's rounding scale sum_j |x_j w_j| + |b|."""
    a = np.asarray(answers, np.float64)
    if a.shape != ref.shape or not np.isfinite(a).all():
        return float("inf")
    if not a.size:
        return 0.0
    return float(np.max(np.abs(a - ref) / np.maximum(scale, 1e-30)))


def judge(values: Mapping[str, float],
          limits: Mapping[str, float]) -> Dict[str, Dict[str, float]]:
    """``{name: {"value", "limit"}}`` for every limited number; a number
    the run did not produce reads as infinite."""
    out = {}
    for name, limit in limits.items():
        v = values.get(name, float("inf"))
        out[name] = {"value": float(v), "limit": float(limit)}
    return out


def all_within(checks: Mapping[str, Mapping[str, float]]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def check_lines(checks: Mapping[str, Mapping[str, float]]) -> Iterable[str]:
    for name, c in checks.items():
        ok = np.isfinite(c["value"]) and c["value"] <= c["limit"]
        yield (f"check {name} {c['value']!r} limit {c['limit']!r} "
               f"{'ok' if ok else 'FAIL'}")
