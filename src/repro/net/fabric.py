"""The fabric: per-edge links + per-node mailboxes between ADMM rounds.

Each node publishes ONE message bundle per round — its masked decision
vectors ``r * active`` (one (2p+2)-vector per active task) — and each
directed edge applies its ``LinkPolicy`` in flight: token-bucket
bandwidth gating at the sender, i.i.d. in-transit drops, a fixed delay
in rounds, and a wire-format quantization.  Receivers keep the LAST
value delivered per (neighbor, task) in a mailbox; the consensus
neighbor sums of Prop. 1 read the mailbox, never the live neighbor
state — that single change is what makes the iteration asynchronous.

Two execution modes, chosen statically at build time:

- ``buffer``  — the identity fast path: when every link is a perfect
  synchronous float32 wire AND link availability never varies, every
  receiver holds byte-identical copies, so the fabric keeps ONE shared
  (V, T, D) buffer of last-published values and reduces with the SAME
  dense-adjacency einsum as the synchronous vmap backend.  This is what
  makes the lossless/zero-delay configuration bitwise identical to
  ``backend="vmap"`` (asserted in tests/test_net.py) rather than merely
  close.
- ``mailbox`` — the general path: per-receiver (V, V, T, D) mailboxes, a
  circular published-payload ring for delays, per-edge send decisions
  (availability x activation x bandwidth x drop) under a counter-based
  PRNG (``fold_in(key, round)`` — reproducible and independent of how a
  run is split across calls).

All state lives in an explicit ``FabricState`` pytree threaded through
``lax.scan`` (``repro.net.async_admm``); the ``Fabric`` object itself is
static configuration.  Counters accumulate in units of per-task wire
vectors, so per-edge bytes are exactly ``msgs_sent * bytes_m``;
``repro.net.meter`` turns them into reports.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.net import policies as pol


class FabricState(NamedTuple):
    """Everything that evolves round to round.  In buffer mode the
    delay/credit machinery is inert (zero delay, infinite bandwidth)
    but kept in the pytree so both modes scan the same structure."""
    mailbox: jnp.ndarray         # (V,T,D) buffer mode | (V,V,T,D) mailbox
    pub_hist: jnp.ndarray        # (L, V, T, D) published-payload ring
    ok_hist: jnp.ndarray         # (L, V, V) bool send-success ring
    tc_hist: jnp.ndarray         # (L, V) task-vectors per send, per ring slot
    credit: jnp.ndarray          # (V, V) token-bucket credit [v, u]
    round: jnp.ndarray           # () int32 absolute round counter
    msgs_sent: jnp.ndarray       # (V, V) f32 task-vectors charged [v, u]
    msgs_delivered: jnp.ndarray  # (V, V) f32 task-vectors delivered
    warmfill_msgs: jnp.ndarray   # () f32 bootstrap deliveries
    silence: jnp.ndarray         # (V, V) int32 rounds since last delivery
    ef_resid: jnp.ndarray        # (V,V,T,D) error-feedback residuals, or
    #                              (1,1,1,1) zeros when EF is off (static
    #                              per fabric config, so the scan
    #                              structure never changes shape)


class Fabric:
    """Static link-layer configuration over one consensus graph.

    Edge matrices are indexed ``[v, u]`` = (receiver, sender), matching
    the dense-adjacency reduce ``einsum("vu,utd->vtd", adj, x)``.
    """

    def __init__(self, adj, dim: int, net: pol.NetConfig, *,
                 force_mailbox: bool = False):
        adj = np.asarray(adj, bool)
        V = adj.shape[0]
        self.V, self.D = V, int(dim)
        self.net = net
        self.adj = jnp.asarray(adj)
        self.adjf = jnp.asarray(adj, jnp.float32)
        self.mode = ("buffer" if net.is_identity and not force_mailbox
                     else "mailbox")

        delay = np.zeros((V, V), np.int32)
        drop = np.zeros((V, V), np.float32)
        qcode = np.zeros((V, V), np.int32)
        bw = np.full((V, V), np.inf, np.float32)
        bpm = np.zeros((V, V), np.float32)
        for v in range(V):
            for u in range(V):
                if not adj[v, u]:
                    continue
                p = net.edge_policy(u, v)          # directed link u -> v
                delay[v, u] = p.delay
                drop[v, u] = p.drop
                qcode[v, u] = pol.QUANT_CODES[p.quant]
                if p.bandwidth is not None:
                    bw[v, u] = p.bandwidth
                bpm[v, u] = pol.bytes_per_message(p.quant, self.D)
        self.delay_m = jnp.asarray(delay)
        self.drop_m = jnp.asarray(drop)
        self.qcode_m = jnp.asarray(qcode)
        self.bw_m = jnp.asarray(bw)
        self.bytes_m = jnp.asarray(bpm * adj)
        self.hist_len = int(delay.max()) + 1
        self.key = jax.random.PRNGKey(net.seed)
        self._codes = sorted({int(c) for c in np.unique(qcode[adj])}
                             - {0}) if adj.any() else []
        self._vv = np.indices((V, V))              # static gather helpers
        self.stale_limit = net.stale_limit
        # error feedback compensates the SENDER-side quantizer, so the
        # compressed values are per-edge at publish time — incompatible
        # with the per-sender delay ring, which stores one raw payload
        # per sender and quantizes at delivery
        self.error_feedback = bool(net.error_feedback)
        if self.error_feedback and self.hist_len > 1:
            raise ValueError(
                "error_feedback requires zero-delay links (the residual "
                "compensates the sender's quantizer at publish time; a "
                "delay ring would re-quantize the raw payload at "
                "delivery) — set delay=0 or error_feedback=False")

    # ------------------------------------------------------------------
    # state construction
    # ------------------------------------------------------------------
    def init_state(self, payload0: jnp.ndarray,
                   round0: int = 0) -> FabricState:
        """Fresh fabric state for payloads shaped like ``payload0``
        (V, T, D).  When the NetConfig says ``warm_fill``, mailboxes
        bootstrap from ``payload0`` (the senders' initial decision
        variables — one out-of-band metered exchange); otherwise they
        start at zero and neighbors look silent until first delivery.
        """
        payload0 = jnp.asarray(payload0, jnp.float32)
        V, D = self.V, self.D
        T = payload0.shape[1]
        zero_box = (jnp.zeros((V, T, D), jnp.float32)
                    if self.mode == "buffer"
                    else jnp.zeros((V, V, T, D), jnp.float32))
        ef_shape = ((V, V, T, D) if self.error_feedback
                    and self.mode == "mailbox" else (1, 1, 1, 1))
        st = FabricState(
            mailbox=zero_box,
            pub_hist=jnp.zeros((self.hist_len, V, T, D), jnp.float32),
            ok_hist=jnp.zeros((self.hist_len, V, V), bool),
            tc_hist=jnp.zeros((self.hist_len, V), jnp.float32),
            credit=jnp.where(jnp.isinf(self.bw_m), self.bw_m,
                             jnp.maximum(self.bw_m, self.bytes_m)),
            round=jnp.asarray(round0, jnp.int32),
            msgs_sent=jnp.zeros((V, V), jnp.float32),
            msgs_delivered=jnp.zeros((V, V), jnp.float32),
            warmfill_msgs=jnp.asarray(0.0, jnp.float32),
            silence=jnp.zeros((V, V), jnp.int32),
            ef_resid=jnp.zeros(ef_shape, jnp.float32),
        )
        if self.net.warm_fill:
            st = self.warm_fill(st, payload0)
        return st

    def warm_fill(self, st: FabricState, payload: jnp.ndarray,
                  task_mask: Optional[jnp.ndarray] = None) -> FabricState:
        """Deliver ``payload`` (V, T, D) into mailboxes out of band — the
        bootstrap at session start, and the Fig.-7 refresh on membership
        events.  ``task_mask`` (V, T) marks the entries whose membership
        changed; the refresh republishes every changed task NETWORK-WIDE
        (column granularity): an entering task's mailboxes fill from its
        neighbors' current variables, a leaving task's contributions
        zero out everywhere (the payload is already ``r * active``).
        None refreshes everything.  Deliveries are quantized per edge
        like any other message and counted in ``warmfill_msgs``
        (units: task-vectors).
        """
        payload = jnp.asarray(payload, jnp.float32)
        T = payload.shape[1]
        if task_mask is None:
            tcols = jnp.ones((T,), bool)
        else:
            tcols = jnp.max(jnp.asarray(task_mask, jnp.float32), axis=0) > 0
        n = jnp.sum(self.adjf) * jnp.sum(tcols)
        if self.mode == "buffer":
            box = jnp.where(tcols[None, :, None], payload, st.mailbox)
            return st._replace(mailbox=box,
                               warmfill_msgs=st.warmfill_msgs + n)
        vals = self._per_edge_quant(
            jnp.broadcast_to(payload[None], (self.V,) + payload.shape))
        sel = self.adj[:, :, None, None] & tcols[None, None, :, None]
        box = jnp.where(sel, vals, st.mailbox)
        # an out-of-band delivery crossed every consensus edge — the
        # bounded-staleness clock restarts (values-invisible when no
        # stale_limit is set)
        silence = jnp.where(self.adj, 0, st.silence)
        return st._replace(mailbox=box, silence=silence,
                           warmfill_msgs=st.warmfill_msgs + n)

    def apply_membership(self, st: FabricState, gc: jnp.ndarray,
                         fill: jnp.ndarray, payload: jnp.ndarray
                         ) -> FabricState:
        """Node-level membership maintenance on a mailbox fabric.

        ``gc`` (V,) bool marks nodes leaving GRACEFULLY this round:
        their contributions are garbage-collected — every receiver's
        mailbox column from that sender zeroes out and any in-flight
        ring entries are cancelled.  (A *crash* performs no GC: the
        stale values linger until the bounded-staleness policy ages
        them out — that asymmetry is the whole difference between the
        two failure modes.)

        ``fill`` (V,) bool marks nodes (re)joining this round: every
        consensus edge incident to such a node warm-fills from
        ``payload`` (V, T, D) — the rejoiner's mailboxes from its
        neighbors' current variables AND the neighbors' mailboxes from
        the rejoiner's — quantized per edge like any other message,
        metered in ``warmfill_msgs`` (units: task-vectors, T per
        touched edge), with the staleness clock reset on those edges.

        Traceable with static shapes (the masks are data, never
        structure): an all-false round is a value-level no-op, so the
        async scan applies this every round without re-tracing.
        """
        if self.mode == "buffer":
            raise ValueError("membership events need a mailbox-mode "
                             "fabric; build it with force_mailbox=True")
        gc = jnp.asarray(gc, bool)
        fill = jnp.asarray(fill, bool)
        payload = jnp.asarray(payload, jnp.float32)
        T = payload.shape[1]
        # -- GC: zero the leaver's columns + cancel in-flight sends ----
        box = jnp.where(gc[None, :, None, None], 0.0, st.mailbox)
        ok_hist = st.ok_hist & ~gc[None, None, :]
        ef_resid = st.ef_resid
        if self.error_feedback:
            # the leaver's quantizer state dies with its link
            ef_resid = jnp.where(gc[None, :, None, None], 0.0, ef_resid)
        # -- warm-fill: both directions of every edge touching a joiner
        touched = self.adj & (fill[:, None] | fill[None, :])
        vals = self._per_edge_quant(
            jnp.broadcast_to(payload[None], (self.V,) + payload.shape))
        box = jnp.where(touched[:, :, None, None], vals, box)
        silence = jnp.where(touched, 0, st.silence)
        n = jnp.sum(touched.astype(jnp.float32)) * T
        return st._replace(mailbox=box, ok_hist=ok_hist,
                           ef_resid=ef_resid, silence=silence,
                           warmfill_msgs=st.warmfill_msgs + n)

    # ------------------------------------------------------------------
    # the per-round exchange
    # ------------------------------------------------------------------
    def _per_edge_quant(self, vals: jnp.ndarray) -> jnp.ndarray:
        """Apply each edge's wire format to gathered values (V,V,T,D) —
        only the formats actually present on some edge are computed."""
        out = vals
        for code in self._codes:
            sel = (self.qcode_m == code)[:, :, None, None]
            out = jnp.where(sel, pol.apply_quant(vals, code), out)
        return out

    def exchange(self, st: FabricState, payload: jnp.ndarray,
                 act: jnp.ndarray, links: Optional[jnp.ndarray],
                 task_counts: Optional[jnp.ndarray] = None
                 ) -> Tuple[FabricState, jnp.ndarray]:
        """Publish every active node's ``payload`` rows through the links.

        ``act`` (V,) gates senders (a node that did not compute this
        round sends nothing); ``links`` (V, V) bool is this round's
        availability (None = the full consensus graph);
        ``task_counts`` (V,) is each sender's number of live task
        vectors — zero rows of the bundle are not transmitted, so bytes
        scale with it (default: the full task axis).  Returns the
        updated state and this round's charged bytes (scalar f32).
        Traceable; called once per round inside the async scan.
        """
        T = payload.shape[1]
        if task_counts is None:
            # T is a static shape int; jnp.full casts it exactly —
            # float() here would bake a host-computed literal into the
            # trace (host-sync-in-hot-path)
            task_counts = jnp.full((self.V,), T, jnp.float32)
        nvec = task_counts[None, :]                # per edge [v, u]: u's
        sending = act > 0                          # (V,) senders
        if self.mode == "buffer":
            box = jnp.where(sending[:, None, None], payload, st.mailbox)
            edges = (self.adj & sending[None, :]).astype(jnp.float32)
            sent = edges * nvec
            bytes_now = jnp.sum(self.bytes_m * sent)
            return st._replace(
                mailbox=box,
                round=st.round + 1,
                msgs_sent=st.msgs_sent + sent,
                msgs_delivered=st.msgs_delivered + sent,
            ), bytes_now

        V, L = self.V, self.hist_len
        k = st.round
        slot = jnp.mod(k, L)
        pub_hist = jax.lax.dynamic_update_index_in_dim(
            st.pub_hist, payload, slot, axis=0)

        avail = self.adj if links is None else (links & self.adj)
        live = avail & sending[None, :]            # sender u computed
        credit = jnp.where(
            jnp.isinf(self.bw_m), self.bw_m,
            jnp.minimum(st.credit + self.bw_m,
                        jnp.maximum(self.bw_m, self.bytes_m * nvec)))
        cost = self.bytes_m * nvec                 # this round's bundle
        can_pay = credit >= cost
        attempt = live & can_pay                   # bytes are charged here
        credit = credit - jnp.where(attempt, cost, 0.0)
        keep = jax.random.uniform(
            jax.random.fold_in(self.key, k), (V, V)) >= self.drop_m
        sent_ok = attempt & keep                   # survives transit
        ok_hist = jax.lax.dynamic_update_index_in_dim(
            st.ok_hist, sent_ok, slot, axis=0)
        tc_hist = jax.lax.dynamic_update_index_in_dim(
            st.tc_hist, task_counts, slot, axis=0)

        # delivery: edge (u -> v) with delay d receives the payload
        # published at round k - d, iff that round's send succeeded —
        # charged at the SEND round's task count (membership may have
        # changed while the message sat in the ring)
        slots = jnp.mod(k - self.delay_m, L)                    # (V, V)
        vv, uu = self._vv
        delivered = ok_hist[slots, vv, uu] & (k >= self.delay_m)
        raw = pub_hist[slots, uu]                               # (V,V,T,D)
        ef_resid = st.ef_resid
        if self.error_feedback:
            # residual-compensated quantization: send Q(x + e), then
            # e <- (x + e) - Q(x + e).  The residual advances wherever
            # the sender produced a message (``attempt``) — transit loss
            # is invisible to the sender, so a dropped message's error
            # still feeds the next send.  Wire bytes are UNCHANGED.
            inp = raw + ef_resid
            vals = self._per_edge_quant(inp)
            ef_resid = jnp.where(attempt[:, :, None, None],
                                 inp - vals, ef_resid)
        else:
            vals = self._per_edge_quant(raw)
        box = jnp.where(delivered[:, :, None, None], vals, st.mailbox)
        # bounded-staleness clock: per-edge rounds since last delivery
        # (values-invisible unless a stale_limit gates the reduce)
        silence = jnp.where(self.adj,
                            jnp.where(delivered, 0, st.silence + 1),
                            st.silence)

        bytes_now = jnp.sum(jnp.where(attempt, cost, 0.0))
        return st._replace(
            mailbox=box,
            pub_hist=pub_hist,
            ok_hist=ok_hist,
            tc_hist=tc_hist,
            credit=credit,
            round=k + 1,
            msgs_sent=st.msgs_sent + attempt.astype(jnp.float32) * nvec,
            msgs_delivered=(st.msgs_delivered
                            + delivered.astype(jnp.float32)
                            * tc_hist[slots, uu]),
            silence=silence,
            ef_resid=ef_resid,
        ), bytes_now

    # ------------------------------------------------------------------
    # the consensus reduce
    # ------------------------------------------------------------------
    def reduce(self, st: FabricState) -> jnp.ndarray:
        """Per-node sum of mailbox values over consensus neighbors.

        Buffer mode is the EXACT expression of the synchronous backend
        (``core.dtsvm._default_nbr_reduce``) over the shared buffer —
        the keystone of the bitwise-identity guarantee.

        With a ``stale_limit`` K (mailbox mode), a neighbor whose edge
        has been silent for MORE than K consecutive rounds is dropped
        from the sum — the bounded-staleness straggler policy: its last
        value is too old to average in, so the receiver proceeds
        without it until the edge delivers again.
        """
        if self.mode == "buffer":
            # repro: noqa[raw-einsum-in-plan] — deliberate: must be the EXACT expression of core._default_nbr_reduce (the bitwise-identity keystone); tests pin async == sync
            return jnp.einsum("vu,utd->vtd", self.adjf, st.mailbox,
                              precision="highest")
        if self.stale_limit is not None:
            fresh = (st.silence <= self.stale_limit).astype(jnp.float32)
            return jnp.sum((self.adjf * fresh)[:, :, None, None]
                           * st.mailbox, axis=1)
        return jnp.sum(self.adjf[:, :, None, None] * st.mailbox, axis=1)


def build_fabric(prob, net: pol.NetConfig, *,
                 force_mailbox: bool = False) -> Fabric:
    """A Fabric over a DTSVMProblem's consensus graph and vector size."""
    p = prob.X.shape[-1]
    return Fabric(prob.adj, 2 * p + 2, net, force_mailbox=force_mailbox)


# ---------------------------------------------------------------------------
# durability (repro.store)
# ---------------------------------------------------------------------------
def snapshot_state(st: FabricState) -> dict:
    """One FabricState as a name-keyed pytree of arrays — the schema
    form the durable session layer serializes.  Field names (not tuple
    positions) key the snapshot, so a reordered/extended FabricState in
    a later schema version stays migratable."""
    return dict(st._asdict())


def restore_state(tree) -> FabricState:
    """Rebuild a FabricState from ``snapshot_state``'s name-keyed form.

    The array bytes round-trip untouched (``repro.checkpoint`` encodes
    raw buffers), so mailboxes, delay rings, token-bucket credit and
    the drop-stream round counter continue bitwise — the keystone of
    the save → restore → continue guarantee for async sessions.
    Missing or unknown fields raise (a schema mismatch should fail
    loudly, not zero-fill a mailbox).
    """
    want = set(FabricState._fields)
    got = set(tree)
    if got != want:
        raise ValueError(
            f"fabric snapshot fields {sorted(got)} do not match "
            f"FabricState{sorted(want)}; run a schema migration "
            f"(repro.store.schema) before restoring")
    # dtypes pinned per field — a bare jnp.asarray would silently
    # downcast 64-bit leaves under x32 (the PR-6 bug class), and the
    # round counter / ok ring / staleness clock must come back as
    # int32 / bool / int32 even from a widened decode
    dtypes = {"round": jnp.int32, "ok_hist": jnp.bool_,
              "silence": jnp.int32}
    kw = {k: jnp.asarray(v, dtypes.get(k, jnp.float32))
          for k, v in tree.items()}
    return FabricState(**kw)
