"""Durable ``OnlineSession``s: snapshot, restore, and a step-indexed store.

A snapshot is a plain pytree (see ``repro.store.schema`` for the
version stamp) serialized on the msgpack substrate of
``repro.checkpoint`` — every array round-trips as raw bytes, so the
restored session CONTINUES BITWISE where the saved one stopped, across
every backend (vmap / shard_map / sample_shard / async with live
mailboxes) and both dense and budgeted plans (tests/test_store.py).

What is stored, and what is rebuilt:

- stored   — the problem data (X, y, mask, adj), the config
  (``SolverConfig.to_dict``), the membership masks, the node-churn
  event list (``repro.net.elastic``), the ADMM state, the iteration
  counter, the recorded history blocks, the fabric state (mailboxes,
  delay rings, credit, counters, staleness clocks, error-feedback
  residuals, round) and per-round byte series of async sessions, and
  the compiled plan's content FINGERPRINT.
- rebuilt  — the plan's invariants (the K Gram blocks dominate a
  snapshot's would-be size) via a fresh ``compile_problem`` on restore;
  the engine's established invariant — a fresh build is bitwise equal
  to any incrementally re-planned one — makes this lossless, and the
  stored fingerprint is asserted against the rebuild so a drifted
  environment fails loudly instead of continuing subtly wrong.
  ``plan_stats`` counters restart on restore (bookkeeping, not state).

``SessionStore`` puts snapshots on the existing ``ckpt_<step>.msgpack``
/ ``LATEST`` index (step = the session's iteration counter), which
brings along retention (``keep_last``) and corrupt-head fallback from
``repro.checkpoint`` for free.
"""
from __future__ import annotations

import os
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.api.session import OnlineSession
from repro.api.solvers import SolverConfig, effective_backend
from repro.core import dtsvm as core
from repro.engine import plan as engine_plan
from repro.net import elastic as elastic_lib
from repro.net import fabric as fabric_lib
from repro.net import meter as meter_lib
from repro.net.policies import NetConfig
from repro.obs import spans as obs_spans
from repro.store import schema


def snapshot_session(sess: OnlineSession) -> dict:
    """The session as a plain, versioned pytree (see module docstring
    for the stored/rebuilt split).  Serialize it with
    ``repro.checkpoint.save`` or hand it to a ``SessionStore``."""
    with obs_spans.span("store_snapshot", iteration=int(sess.iteration)):
        return _snapshot_session(sess)


def _snapshot_session(sess: OnlineSession) -> dict:
    state = None
    if sess.state is not None:
        state = {"r": sess.state.r, "alpha": sess.state.alpha,
                 "beta": sess.state.beta, "lam": sess.state.lam}
    plan = None
    if sess._plan is not None:
        plan = {"fingerprint": sess._plan.fingerprint(),
                "active": np.asarray(sess._plan.prob.active),
                "couple": np.asarray(sess._plan.prob.couple)}
    net = None
    if sess._net_state is not None:
        net = {"fabric_state": fabric_lib.snapshot_state(sess._net_state),
               "mode": sess._net_fabric.mode,
               "series": np.asarray(sess._net_series, np.float32)}
    test = None
    if sess._test is not None:
        test = {"X": sess._test[0], "y": sess._test[1]}
    obs = None
    if sess.telemetry_ is not None:
        obs = {"telemetry": {k: np.asarray(v, np.float32)
                             for k, v in sess.telemetry_.items()}}
    return schema.stamp("online_session", {
        "config": sess.config.to_dict(),
        "data": {"X": sess._X, "y": sess._y, "mask": sess._mask,
                 "adj": sess._adj},
        "active": sess._active,
        "couple": sess._couple,
        "masks_dirty": bool(sess._masks_dirty),
        "jit": bool(sess._jit),
        "test": test,
        "state": state,
        "iteration": int(sess.iteration),
        "history": [np.asarray(h) for h in sess.history],
        "plan": plan,
        "net": net,
        "obs": obs,
        # v3: node-churn events (repro.net.elastic) — the absolute-round
        # list IS the membership state; restore replays it, so the
        # staleness/EF arrays in the fabric state line up with it
        "membership": (None if not sess._node_events
                       else [e.to_dict() for e in sess._node_events]),
    })


def _problem_for(sess: OnlineSession, active, couple) -> core.DTSVMProblem:
    """The session's problem under EXPLICIT masks — the snapshot's plan
    may predate pending membership events (``masks_dirty``), so the
    rebuild must use the masks the plan was compiled with, not the
    session's current ones."""
    cfg = sess.config
    return core.make_problem(
        sess._X, sess._y, sess._mask, sess._adj, C=cfg.C, eps1=cfg.eps1,
        eps2=cfg.eps2, eta1=cfg.eta1, eta2=cfg.eta2,
        box_scale=cfg.box_scale, active=np.asarray(active),
        couple=np.asarray(couple))


def restore_session(tree: Any, *, check_fingerprint: bool = True
                    ) -> OnlineSession:
    """Rebuild a live ``OnlineSession`` from a snapshot pytree.

    Runs schema migrations first (``repro.store.schema.migrate``), then
    recompiles the plan and asserts its content fingerprint against the
    stored one (``check_fingerprint=False`` skips the assert — the
    escape hatch for intentionally changed environments).  Async
    sessions come back with their fabric rebuilt from the config and
    their mailboxes/delay rings/counters restored bitwise, so the
    message stream — including the round-keyed drop stream — continues
    exactly where it stopped.
    """
    with obs_spans.span("store_restore"):
        return _restore_session(tree, check_fingerprint=check_fingerprint)


def _restore_session(tree: Any, *, check_fingerprint: bool
                     ) -> OnlineSession:
    tree = schema.migrate(tree)
    if tree.get("kind") != "online_session":
        raise schema.SchemaError(
            f"expected an 'online_session' snapshot, got kind="
            f"{tree.get('kind')!r}")
    cfg = SolverConfig.from_dict(tree["config"])
    d = tree["data"]
    sess = OnlineSession(
        d["X"], d["y"], mask=d["mask"], adj=d["adj"], config=cfg,
        active=np.asarray(tree["active"]),
        couple=np.asarray(tree["couple"]), jit=bool(tree["jit"]))
    if tree["test"] is not None:
        # dtype pinned: a bare jnp.asarray would silently downcast
        # 64-bit snapshot leaves under x32 (the PR-6 bug class)
        sess._test = (jnp.asarray(tree["test"]["X"], jnp.float32),
                      jnp.asarray(tree["test"]["y"], jnp.float32))
    if tree["state"] is not None:
        st = tree["state"]
        sess.state = core.DTSVMState(
            r=jnp.asarray(st["r"], jnp.float32),
            alpha=jnp.asarray(st["alpha"], jnp.float32),
            beta=jnp.asarray(st["beta"], jnp.float32),
            lam=jnp.asarray(st["lam"], jnp.float32))
    sess.iteration = int(tree["iteration"])
    sess.history = [np.asarray(h) for h in tree["history"]]
    sess._masks_dirty = bool(tree["masks_dirty"])
    mem = tree.get("membership")
    if mem is not None:
        sess._node_events = [elastic_lib.MembershipEvent.from_dict(e)
                             for e in mem]

    pl = tree["plan"]
    if pl is not None:
        plan = engine_plan.compile_problem(
            _problem_for(sess, pl["active"], pl["couple"]), cfg,
            backend=effective_backend(cfg))
        if check_fingerprint and plan.fingerprint() != pl["fingerprint"]:
            raise schema.SchemaError(
                "rebuilt plan fingerprint does not match the snapshot — "
                "the environment produces different invariants than the "
                "one that saved this session (jax/hardware drift?); "
                "restore_session(..., check_fingerprint=False) to "
                "continue anyway")
        sess._plan = plan

    net = tree["net"]
    if net is not None:
        netcfg = cfg.net if cfg.net is not None else NetConfig()
        prob = (sess._plan.prob if sess._plan is not None
                else sess.problem())
        fab = fabric_lib.build_fabric(
            prob, netcfg, force_mailbox=(net["mode"] == "mailbox"))
        sess._net_fabric = fab
        sess._net_state = fabric_lib.restore_state(net["fabric_state"])
        sess._net_series = [np.float32(b) for b in
                            np.asarray(net["series"])]
        sess.net_report_ = meter_lib.report(
            fab, sess._net_state, rounds=sess.iteration,
            bytes_per_round=np.asarray(sess._net_series))

    obs = tree.get("obs")
    if obs is not None:
        # np.asarray with pinned dtype, not jnp: telemetry streams are
        # host-side diagnostics, and x32 must not rewrite them
        sess.telemetry_ = {k: np.asarray(v, np.float32)
                           for k, v in obs["telemetry"].items()}
    return sess


def save_session(path: str, sess: OnlineSession) -> None:
    """One session snapshot at an explicit path (atomic write)."""
    checkpoint.save(path, snapshot_session(sess))


def load_session(path: str, *, check_fingerprint: bool = True
                 ) -> OnlineSession:
    """Inverse of ``save_session`` (``CheckpointError`` on a bad file,
    ``SchemaError`` on an unmigratable one)."""
    return restore_session(checkpoint.load(path),
                           check_fingerprint=check_fingerprint)


class SessionStore:
    """A step-indexed directory of session snapshots with retention.

    Snapshots land on the ``repro.checkpoint`` index
    (``ckpt_<iteration>.msgpack`` + ``LATEST``), so ``keep_last``
    pruning, atomic writes, and corrupt-head fallback all apply::

        store = SessionStore(dir, keep_last=3)
        store.save(sess)                # after every stage
        sess = store.load()             # newest readable snapshot
    """

    def __init__(self, root: str, *, keep_last: Optional[int] = None):
        self.root = os.fspath(root)
        self.keep_last = keep_last

    def save(self, sess: OnlineSession) -> str:
        """Snapshot ``sess`` as step ``sess.iteration``; returns the
        written path (older steps pruned per ``keep_last``)."""
        return checkpoint.save_step(self.root, sess.iteration,
                                    snapshot_session(sess),
                                    keep_last=self.keep_last)

    def load(self, *, fallback: bool = True,
             check_fingerprint: bool = True) -> Optional[OnlineSession]:
        """The newest readable snapshot as a live session (None when the
        store is empty).  ``fallback`` walks back past corrupt heads —
        see ``repro.checkpoint.restore_latest``."""
        step, tree = checkpoint.restore_latest(self.root, fallback=fallback)
        if step is None:
            return None
        return restore_session(tree, check_fingerprint=check_fingerprint)

    def steps(self):
        """Sorted iteration numbers with a snapshot on disk."""
        return checkpoint.available_steps(self.root)
