"""Device-side convergence telemetry: per-iteration ADMM diagnostics.

A :class:`Telemetry` spec threads through ``Plan.run`` / the async
fabric's scan / the sharded backends and collects one small pytree of
diagnostics per ADMM iteration, stacked by the scan that already runs
the fit.  Everything here is pure jnp on the *outputs* of the traced
step — the collector never reaches into kernel bodies (lint rule
``telemetry-read-in-kernel``) and never syncs to host inside the loop
(the streams materialize only after the scan, via :func:`materialize`),
so the two hard invariants hold:

- telemetry-on is **bitwise identical** to telemetry-off on every model
  output (the state carry is untouched; diagnostics are extra scan
  outputs), and
- telemetry adds **zero retraces** (the collector traces once inside
  the same scan body; tests/test_obs.py counts).

Stream catalog (all float32; ``iters`` is the scan length):

====================  ========  =========================================
stream                shape     meaning
====================  ========  =========================================
``primal_residual``   (iters,)  max consensus-constraint violation —
                                the larger of the task residual
                                (|w0b0 - task mean| over active tasks)
                                and the node residual (|r - neighbor
                                mean|), the quantity Prop. 1 drives to 0
``dual_residual``     (iters,)  max |r_k - r_{k-1}| over active entries
                                — the successive-iterate change standard
                                ADMM stopping rules pair with the primal
``disagreement``      (iters,T) per-task max over nodes of
                                ||c_v - c̄_t||_2 where c = w0+wt (the
                                working classifier) — the paper's
                                "nodes agree per task" claim as a curve
``qp_active_frac``    (iters,)  fraction of valid dual coordinates at a
                                box face (lam <= 0 or lam >= hi) after
                                the inner QP — saturation up, step
                                count's worth of progress down
====================  ========  =========================================

The async backend folds the fabric's per-round byte counts in as a
``bytes_round`` stream (from the same scan's outputs), the per-node
edge-staleness clock as ``staleness`` ((rounds, V): each node's oldest
incoming-edge silence, in rounds) and — under a node membership
(``repro.net.elastic``) — the live-node count as ``nodes_alive``;
``net.meter`` keeps the aggregate accounting.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: every stream ``collect_diagnostics`` knows how to compute, in the
#: order they are collected.
STREAMS: Tuple[str, ...] = ("primal_residual", "dual_residual",
                            "disagreement", "qp_active_frac")


class Telemetry:
    """An immutable telemetry spec: which streams to collect.

    Instances are plain host-side configuration — they carry no arrays,
    and two specs of the same streams are equal and hash alike, so a
    spec can key a jit cache (``engine.plan``'s cached scan) by value.
    The default collects every stream in :data:`STREAMS`.
    """

    def __init__(self, streams: Sequence[str] = STREAMS):
        unknown = sorted(set(streams) - set(STREAMS))
        if unknown:
            raise ValueError(f"unknown telemetry streams {unknown}; "
                             f"available: {list(STREAMS)}")
        self.streams: Tuple[str, ...] = tuple(
            s for s in STREAMS if s in set(streams))

    def collect(self, prob, hi, new_state, prev_state) -> Dict[str, jnp.ndarray]:
        """Per-iteration diagnostics for one step ``prev -> new``
        (delegates to :func:`collect_diagnostics`)."""
        return collect_diagnostics(prob, hi, new_state, prev_state,
                                   streams=self.streams)

    def __eq__(self, other):
        return (type(other) is type(self)
                and other.streams == self.streams)

    def __hash__(self):
        return hash((type(self), self.streams))

    def __repr__(self):
        return f"Telemetry(streams={list(self.streams)})"


def collect_diagnostics(prob, hi, new_state, prev_state, *,
                        streams: Sequence[str] = STREAMS
                        ) -> Dict[str, jnp.ndarray]:
    """One iteration's diagnostics from the step's inputs/outputs.

    Pure jnp, traced inside the fit's own scan; every contraction is in
    the mul+reduce form (the batching-stable idiom the engine pins), and
    nothing forces a host sync.  ``prob`` is the ``DTSVMProblem``,
    ``hi`` the (V, T, N) QP box ceiling (``PlanInvariants.hi``),
    ``new_state``/``prev_state`` the post-/pre-step ``DTSVMState``.
    Returns ``{stream: f32 array}`` for the requested streams.
    """
    out: Dict[str, jnp.ndarray] = {}
    r = new_state.r
    p = prob.X.shape[-1]
    act = prob.active[..., None]                       # (V, T, 1)
    r_act = r * act
    want = set(streams)

    if "primal_residual" in want:
        # task residual: shared-block deviation from the task mean,
        # active tasks only (the r-layout's [w0, b0] head)
        w0b0 = r[..., : p + 1] * act
        n_act = jnp.maximum(jnp.sum(act, axis=1, keepdims=True), 1.0)
        mean_t = jnp.sum(w0b0, axis=1, keepdims=True) / n_act
        task_res = jnp.max(jnp.abs((w0b0 - mean_t) * act))
        # node residual: deviation from the active-neighbor mean
        A = prob.adj.astype(jnp.float32)               # (V, V)
        deg_raw = jnp.sum(A[:, :, None] * prob.active[None, :, :], axis=1)
        deg = jnp.maximum(deg_raw, 1.0)[..., None]     # (V, T, 1)
        nbr_mean = jnp.sum(A[:, :, None, None] * r_act[None], axis=1) / deg
        has_nbr = (deg_raw[..., None] > 0).astype(jnp.float32)
        node_res = jnp.max(jnp.abs((r - nbr_mean) * act) * has_nbr)
        out["primal_residual"] = jnp.maximum(task_res, node_res)

    if "dual_residual" in want:
        out["dual_residual"] = jnp.max(
            jnp.abs(new_state.r - prev_state.r) * act)

    if "disagreement" in want:
        # working classifier c = (w0+wt, b0+bt); per-task active mean
        c = (r[..., : p + 1] + r[..., p + 1:]) * act   # (V, T, p+1)
        cnt = jnp.maximum(jnp.sum(prob.active, axis=0), 1.0)     # (T,)
        cbar = jnp.sum(c, axis=0) / cnt[:, None]                 # (T, p+1)
        diff = (c - cbar[None]) * act
        norms = jnp.sqrt(jnp.sum(diff * diff, axis=-1))          # (V, T)
        out["disagreement"] = jnp.max(norms, axis=0)             # (T,)

    if "qp_active_frac" in want:
        lam = new_state.lam
        at_face = ((lam <= 0.0) | (lam >= hi)).astype(jnp.float32)
        valid = prob.mask
        out["qp_active_frac"] = (jnp.sum(at_face * valid)
                                 / jnp.maximum(jnp.sum(valid), 1.0))
    return out


def collect_shard_diagnostics(prob, hi_rows, new_state, prev_state,
                              streams: Sequence[str], axis: str
                              ) -> Dict[str, jnp.ndarray]:
    """The sample-sharded variant of :func:`collect_diagnostics`.

    Inside the sample-shard backend the consensus leaves (``r``, the
    masks' (V, T) reductions, ``adj``) are replicated while ``lam`` /
    ``mask`` / ``hi`` live on row panels — so the state streams compute
    exactly as in the dense collector, and the box-face fraction sums
    per-shard partials and combines with one ``lax.psum`` over ``axis``
    (the result is replicated, matching the backend's out_specs).
    """
    state_streams = tuple(s for s in streams if s != "qp_active_frac")
    out = collect_diagnostics(prob, hi_rows, new_state, prev_state,
                              streams=state_streams)
    if "qp_active_frac" in set(streams):
        lam = new_state.lam
        at_face = ((lam <= 0.0) | (lam >= hi_rows)).astype(jnp.float32)
        num = jax.lax.psum(jnp.sum(at_face * prob.mask), axis)
        den = jax.lax.psum(jnp.sum(prob.mask), axis)
        out["qp_active_frac"] = num / jnp.maximum(den, 1.0)
    return out


def materialize(streams: Dict[str, jnp.ndarray]) -> Dict[str, np.ndarray]:
    """Bring stacked device streams to host as float32 numpy — the one
    sanctioned sync point, AFTER the scan that produced them."""
    return {k: np.asarray(v, np.float32) for k, v in streams.items()}


def concat_streams(old: Optional[Dict[str, np.ndarray]],
                   new: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Append one run's materialized streams to an accumulated set
    (stream-wise ``np.concatenate`` over the iteration axis; ``old`` may
    be None).  Streams absent from either side pass through unchanged —
    an async stage contributes ``bytes_round``, a vmap stage does not."""
    if old is None:
        return dict(new)
    out = dict(old)
    for k, v in new.items():
        out[k] = (np.concatenate([old[k], v], axis=0)
                  if k in old else np.asarray(v))
    return out


def summarize(streams: Dict[str, np.ndarray]) -> Dict[str, dict]:
    """Per-stream scalar summary (for the metrics registry / CLI): the
    iteration count plus first/last/min/max of the per-iteration scalar
    (multi-dim streams reduce with max over their trailing axes)."""
    out = {}
    for k, v in streams.items():
        v = np.asarray(v, np.float32)
        flat = v.reshape(v.shape[0], -1).max(axis=1) if v.ndim > 1 else v
        out[k] = {
            "iters": int(flat.shape[0]),
            "first": float(flat[0]) if flat.size else None,
            "last": float(flat[-1]) if flat.size else None,
            "min": float(flat.min()) if flat.size else None,
            "max": float(flat.max()) if flat.size else None,
        }
    return out
