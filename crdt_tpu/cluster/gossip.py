"""Gossip scheduler — who syncs with whom, when.

The sync protocol (PR 2) answers *how* two replicas reconcile; the
telemetry layer (PR 3) answers *how far apart* every peer pair is.
This module closes the loop: a scheduler that each round ranks the
roster by the per-peer staleness/divergence the convergence tracker
already keeps (``sync.peer.<peer>.staleness_s`` — the gauges ROADMAP
said a gossip scheduler should pick peers off), dials the most-needy
``fanout`` peers, and runs their sessions concurrently over hardened
transports.

Scheduling policy (:meth:`GossipScheduler.rank_peers`):

1. never-synced peers first (infinite staleness),
2. then by seconds since the last converged sync with that peer,
3. ties broken toward the peer that diverged most last time
   (:meth:`~crdt_tpu.obs.convergence.ConvergenceTracker.urgency`);
4. dead peers join the candidate set only every ``probe_dead_every``
   rounds — the probe that re-admits a flapping peer without letting a
   truly dead one eat a dial every round.

Per-endpoint session locks: the scheduler holds one lock per peer id
and skips (never queues behind) a peer whose previous session is still
running, so two rounds can never interleave frames on one endpoint —
the lock-step protocol cannot multiplex.  The node itself serializes
initiated-vs-accepted sessions the same way (:class:`ClusterNode`).

Every round lands in the flight recorder (kind ``cluster.round`` with
the per-peer outcomes) and the ``cluster.{rounds,sessions.*}``
counters; round wall time is the ``cluster.round`` span histogram.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from typing import Callable, Dict, List, Optional

from ..error import PeerUnavailableError, SyncProtocolError, TransportError
from ..obs import convergence as obs_convergence
from ..obs import events as obs_events
from ..obs import metrics as obs_metrics
from ..sync.session import SyncReport, SyncSession
from ..utils import tracing
from . import faults as faults_mod
from . import membership as membership_mod
from .transport import Transport

#: a dialer: PeerInfo -> connected Transport (raises
#: PeerUnavailableError when the peer cannot be reached)
Dialer = Callable[[membership_mod.PeerInfo], Transport]


def hello_dial(transport: Transport, node_id: str) -> None:
    """Initiator half of the one-frame identity handshake: ship our
    node id so the acceptor can label its gauges/session events with
    WHO dialed (the sync protocol itself is peer-anonymous)."""
    transport.send(node_id.encode("utf-8"))


def hello_accept(transport: Transport,
                 timeout: Optional[float] = None) -> str:
    """Acceptor half: the dialer's node id, decoded defensively (a
    garbage hello still yields a usable label — the session's own frame
    validation is what rejects a broken peer)."""
    raw = transport.recv(timeout)
    return raw.decode("utf-8", errors="replace")[:64] or "peer"


class ClusterNode:
    """One replica's identity + fleet batch, with session serialization.

    The node owns the batch; every session (initiated via
    :meth:`sync_with` or accepted via :meth:`accept`) runs under the
    node's busy lock so two sessions never read-modify-write the batch
    concurrently, and the converged batch replaces the old one under a
    separate state lock.  A session that cannot start within
    ``busy_timeout_s`` fails with :class:`~crdt_tpu.error.
    PeerUnavailableError` — bounded, so two nodes dialing each other
    simultaneously degrade to one retried session, not a deadlock.

    **Live writes** enter through :meth:`submit_ops` — the op-based
    write front-end (:mod:`crdt_tpu.oplog`): any thread may submit an
    op batch (or a decoded op frame) at any time.  An idle node folds
    the ops immediately (one jitted scatter); a node mid-session queues
    them in its op log and folds them the moment the session releases
    the busy lock — a write can never be lost to a concurrent
    anti-entropy round, because the fold always happens on the batch
    the session produced.  Sessions advertise the oplog capability in
    their hello and piggyback pending op batches to the peer at session
    close (exactly the fleet-snapshot discipline), so a mid-session
    write reaches the peer in the SAME session instead of waiting a
    round; re-delivery through later state sync is idempotent — the
    CmRDT contract.
    """

    def __init__(self, node_id: str, batch, universe, *,
                 full_state_threshold: float = 0.5,
                 busy_timeout_s: float = 10.0,
                 observatory=None,
                 oplog=None,
                 capacity_tracker=None,
                 gc=None,
                 digest_tree: bool = False,
                 durability=None,
                 applier=None,
                 lag_tracker=None,
                 stability_tracker=None,
                 heat_tracker=None):
        from ..obs import heat as obs_heat
        from ..obs import latency as obs_latency
        from ..obs import stability as obs_stability
        from ..serve.query import ViewCache

        self.node_id = node_id
        self.universe = universe
        self.full_state_threshold = full_state_threshold
        self.busy_timeout_s = busy_timeout_s
        #: the node's :class:`crdt_tpu.obs.latency.LagTracker` — always
        #: on (host-side deques, bounded): every ingested write is
        #: stamped at :meth:`submit_ops`, every session ships/receives
        #: the lag sidecar, and every op-log fold re-checks visibility.
        #: Private per node by default so in-process fleets keep their
        #: (origin, observer) pairs apart; pass one to share or bound
        #: differently.
        self.lag_tracker = lag_tracker if lag_tracker is not None \
            else obs_latency.LagTracker()
        #: the node's :class:`crdt_tpu.obs.stability.StabilityTracker`
        #: — the convergence observatory: every session this node runs
        #: feeds its divergence aging and frontier planes, the gossip
        #: scheduler recomputes the frontier + runs the lattice auditor
        #: per round, and checkpoints persist the frontier clocks.
        #: Private per node by default (like the lag tracker) so
        #: in-process fleets keep their observers apart; pass the one a
        #: durable recovery restored (after ``.restore(frontier)``) to
        #: resume instead of regrowing from zero.
        self.stability = stability_tracker if stability_tracker \
            is not None else obs_stability.StabilityTracker()
        #: the node's :class:`crdt_tpu.obs.heat.HeatTracker` — the
        #: placement plane: serve gathers record read heat, the op
        #: drain records write heat, sync sessions record repair heat,
        #: and the gossip scheduler publishes the EWMA/top-k gauge
        #: surface per round.  Private per node by default (same
        #: discipline as the lag/stability observers).
        self.heat = heat_tracker if heat_tracker is not None \
            else obs_heat.HeatTracker()
        #: a :class:`crdt_tpu.durable.Durability`; when set, every
        #: ingested op batch is WAL-appended BEFORE the in-memory fold
        #: (a write acknowledged to the caller survives kill -9), and
        #: the gossip scheduler runs :meth:`checkpoint` at round end on
        #: the manager's cadence — same busy-lock discipline as GC:
        #: never concurrent with a session, skipped when one runs
        self.durability = durability
        #: advertise the digest-tree capability (sync protocol v3) in
        #: every session this node runs: peers that also advertise it
        #: replace the flat O(N) digest exchange with the subtree
        #: descent; mixed fleets fall back per session, loudly
        #: (``sync.tree.fallback.*``)
        self.digest_tree = bool(digest_tree)
        #: a :class:`crdt_tpu.obs.capacity.CapacityTracker` this node's
        #: occupancy samples feed (None = the process-global one); the
        #: gossip scheduler samples once per round
        self.capacity_tracker = capacity_tracker
        #: a :class:`crdt_tpu.gc.GcEngine`; when set, the gossip
        #: scheduler runs :meth:`collect_garbage` at round end on the
        #: engine's cadence — compaction between sessions, never
        #: concurrently with one (the busy lock serializes them)
        self.gc = gc
        #: a :class:`crdt_tpu.obs.fleet.FleetObservatory`; every session
        #: this node runs advertises it in the hello and piggybacks a
        #: merged-snapshot exchange once the session converged, so
        #: telemetry slices spread through the fleet on the gossip the
        #: fleet already does
        self.observatory = observatory
        #: the write front-end's staging log (:class:`crdt_tpu.oplog.
        #: OpLog`); pass one to bound/observe it, or leave None — the
        #: first :meth:`submit_ops` creates a default
        self._oplog = oplog
        #: the op fold's causal-gap applier; pass the one
        #: :func:`crdt_tpu.durable.recover` returns when rebuilding a
        #: crashed node — it carries the ops still parked at snapshot
        #: time, which exist nowhere else until their gaps close
        self._applier = applier
        self._lock = threading.Lock()   # guards batch + last_report
        self._busy = threading.Lock()   # serializes whole sessions
        self._mint = threading.Lock()   # serializes dot minting
        # serializes (WAL append, log append) pairs against the
        # checkpoint's wal_seq capture: with the pair atomic w.r.t. the
        # capture, every frame below the captured sequence is in the
        # in-memory log by drain time — the replay-bound invariant
        # (crdt_tpu/durable/manager.py module docstring)
        self._ingest = threading.Lock()
        self._batch = batch
        self._last_report: Optional[SyncReport] = None
        self._last_gc_report = None
        # the read front-end (crdt_tpu/serve): built lazily on the
        # first serve_reads call so write-only nodes pay nothing
        self._serve_loop = None
        #: the row view the serve path reads the current ORSWOT
        #: snapshot through (:class:`crdt_tpu.serve.query.ViewCache`,
        #: empty until the first read).  Every path that replaces the
        #: batch releases it before its fold allocates the next
        #: snapshot: the old batch, the new one and the view do not fit
        #: one chip together at the ★ size (6.2 + 6.2 + 5.76 GB); the
        #: next read rebuilds it from the new snapshot
        self.serve_views = ViewCache()

    @property
    def batch(self):
        with self._lock:
            return self._batch

    @property
    def last_report(self) -> Optional[SyncReport]:
        """The most recent converged session's report — carries the
        hello-negotiated ``trace_id`` the demo/walkthrough prints."""
        with self._lock:
            return self._last_report

    def digest(self):
        """The canonical (name-salted) digest vector of the current
        fleet (numpy u64[N]) — the convergence oracle the tests and the
        example compare across nodes."""
        import numpy as np

        from ..sync import digest as digest_mod

        return np.asarray(
            digest_mod.digest_of(self.batch, self.universe), dtype="u8")

    # -- the op-based write front-end ---------------------------------------

    def _ensure_oplog(self):
        from ..oplog import OpApplier, OpLog

        # benign create race: submit_ops callers may race here, but the
        # assignment is idempotent (a second OpLog replacing an empty
        # first drops nothing because append happens after this returns
        # the FINAL instance read below)
        if self._oplog is None:
            self._oplog = OpLog(self.universe)
        if self._applier is None:
            self._applier = OpApplier(self.universe)
        return self._oplog

    def submit_ops(self, ops) -> int:
        """Ingest live user writes: ``ops`` is an
        :class:`~crdt_tpu.oplog.OpBatch` or an encoded op frame
        (:func:`crdt_tpu.oplog.wire.encode_ops_frame` bytes).  Returns
        how many ops are still pending (0 = folded immediately).

        Never blocks on a running session: ops queue in the op log and
        fold when the session ends.  Raises
        :class:`~crdt_tpu.error.OpLogOverflowError` when the log fills
        faster than sessions drain it (backpressure, not silent drop).
        """
        from ..oplog.records import OpBatch
        from ..oplog.wire import decode_ops_frame

        if isinstance(ops, (bytes, bytearray, memoryview)):
            ops = decode_ops_frame(
                bytes(ops), num_actors=self.universe.config.num_actors)
        if not isinstance(ops, OpBatch):
            raise TypeError(
                f"submit_ops wants an OpBatch or an encoded op frame, "
                f"got {type(ops).__name__}"
            )
        log = self._ensure_oplog()
        if self.durability is not None and len(ops):
            # write-AHEAD: the ops hit fsync'd disk before the
            # in-memory log, inside the ingest critical section the
            # checkpoint's wal_seq capture synchronizes with.  Ingest
            # is at-least-once — a crash (or a log-overflow raise)
            # after the WAL append may replay ops the caller saw
            # rejected, which batched apply dedups (CmRDT idempotence)
            with self._ingest:
                self.durability.wal_append(ops)
                log.append(ops)
        else:
            log.append(ops)
        # write-to-visible lag starts HERE: stamp the batch's dot
        # frontier with this node's monotonic clock (bounded per-actor
        # table; the stamps ride the next session's lag sidecar)
        self.lag_tracker.record_ingest_batch(ops)
        if self._busy.acquire(blocking=False):
            try:
                self._drain_ops_locked()
            finally:
                self._busy.release()
        pending = len(log)
        obs_metrics.registry().gauge_set("oplog.pending", pending)
        return pending

    def write_clock(self):
        """The node's WRITE view of the fleet clock (numpy ``[N, A]``):
        the current batch clock joined with the dot of every op still
        queued in the log or parked in the applier.  THE safe base for
        ``derive_add_ctx`` against a live node — deriving from the raw
        batch clock while earlier writes are still queued (the node was
        mid-session) would re-mint their counters, and a reused dot
        violates the one-shot dot contract (`error.rs:9-13`)."""
        import numpy as np

        from ..oplog.records import OP_ADD, OP_DEC, OP_INC

        with self._lock:
            batch = self._batch
        clock = np.array(np.asarray(batch.clock), dtype=np.uint64)
        pending = []
        if self._oplog is not None:
            pending.append(self._oplog.pending())
        if self._applier is not None and len(self._applier.parked):
            pending.append(self._applier.parked)
        for ops in pending:
            dotted = np.isin(ops.kind, np.asarray(
                [OP_ADD, OP_INC, OP_DEC], np.uint8))
            if dotted.any():
                np.maximum.at(
                    clock, (ops.obj[dotted], ops.actor[dotted]),
                    ops.counter[dotted])
        return clock

    def submit_writes(self, obj, member, *, actor) -> int:
        """Mint-and-submit in one step: derive fresh dots for these
        adds against :meth:`write_clock` and :meth:`submit_ops` them —
        atomically against other minters, so two writer threads can
        never derive the same dot.  ``actor`` is the writer's dense
        actor index (scalar or per-write array).  Returns the pending
        count like :meth:`submit_ops`."""
        import numpy as np

        from ..oplog.records import derive_add_ctx

        obj = np.asarray(obj, np.int64)
        actor = np.broadcast_to(np.asarray(actor, np.int32), obj.shape)
        self._ensure_oplog()
        with self._mint:
            ops, _ = derive_add_ctx(self.write_clock(), obj, actor,
                                    member=member)
            return self.submit_ops(ops)

    def write_vv(self) -> "np.ndarray":
        """The writer's ACK version vector (``uint64[A]``): the
        pointwise max of :meth:`write_clock` over objects.  This is
        the floor a client hands a read-your-writes request
        (:mod:`crdt_tpu.serve.consistency`) — once a node's visible
        clock covers it, every write acknowledged before the call is
        in the serving snapshot."""
        import numpy as np

        return np.asarray(self.write_clock(), np.uint64).max(axis=0)

    def read_token(self):
        """The node's current monotonic-reads token (the visible
        version vector) — what a fresh client starts a monotonic
        session with."""
        from ..serve.loop import visible_vv

        return visible_vv(self.batch)

    def try_drain(self) -> bool:
        """One NON-BLOCKING op-drain attempt: fold pending ops if the
        busy lock is free, else return False immediately (the same
        acquire discipline :meth:`submit_ops` uses).  The serve loop's
        consistency park calls this so a read-your-writes read waiting
        on its own write nudges visibility instead of spinning on a
        clock that nothing advances."""
        if not self._busy.acquire(blocking=False):
            return False
        try:
            self._drain_ops_locked()
        finally:
            self._busy.release()
        return True

    def serve_reads(self, request):
        """Answer one batched read request
        (:class:`crdt_tpu.serve.ReadRequest`) under its
        session-consistency mode — reads run OUTSIDE the busy lock
        against a consistent batch snapshot, so gossip, writes, and
        reads coexist.  Raises :class:`~crdt_tpu.error.
        ConsistencyUnavailableError` on a terminal admission
        rejection.  Returns the :class:`crdt_tpu.serve.ResultFrame`."""
        if self._serve_loop is None:
            from ..serve.loop import ServeLoop

            self._serve_loop = ServeLoop(self)
        return self._serve_loop.serve(request)

    def _drain_ops_locked(self) -> None:
        """Fold every queued op batch into the fleet — caller holds
        ``_busy`` (either a fresh acquire in :meth:`submit_ops` or the
        tail of :meth:`_run_session`, so the fold always sees the batch
        a concurrent session produced, never a snapshot it replaced)."""
        log = self._oplog
        if log is None:
            return
        parked = self._applier is not None and len(self._applier.parked)
        if len(log) == 0 and not parked:
            return
        # an empty drain still re-checks the applier's parked ops: the
        # session that just ended may have synced in exactly the
        # predecessor dots a parked add was waiting for
        ops = log.drain()
        # mid-fold kill -9 shape: the drained ops exist only in this
        # frame's locals (and, on a durable node, in the WAL — which is
        # why recovery replays them).  The node-scoped name lets a
        # multi-node in-process soak kill ONE replica deterministically
        faults_mod.crash_point("oplog.fold")
        faults_mod.crash_point(f"oplog.fold.{self.node_id}")
        with self._lock:
            batch = self._batch
        if len(ops):
            # write heat: every drained op row, before the fold (the
            # attribution is per submitted row — duplicates the fold
            # drops still landed on this node's ingest path)
            clock = getattr(batch, "clock", None)
            if clock is not None:
                self.heat.record_writes(ops.obj, int(clock.shape[0]))
        self.serve_views.release()
        batch, report = self._applier.apply_ops(batch, ops)
        with self._lock:
            self._batch = batch
        obs_events.record(
            "oplog.drain", node=self.node_id, ops=report.ops,
            applied=report.applied, duplicates=report.duplicates,
            parked=report.still_parked,
        )
        if report.applied:
            # the fold advanced visibility: peer writes parked in the
            # lag tracker (sidecar entries whose dots arrived via the
            # op piggyback rather than state sync) are measurable now
            import numpy as np

            clock = getattr(batch, "clock", None)
            if clock is not None:
                self.lag_tracker.observe_visibility(
                    np.asarray(clock).max(axis=0))

    def _op_outbox(self) -> bytes:
        """Session piggyback source: everything queued while the
        session ran (shipped as a COPY — the local drain still folds
        it; the peer's re-receipt through state sync is idempotent)."""
        from ..oplog.wire import encode_ops_frame

        return encode_ops_frame(self._oplog.pending())

    def _op_sink(self, frame: bytes) -> None:
        """Session piggyback sink: peer ops queue like any other write
        and fold at the session-tail drain — WAL'd first (the frame
        bytes verbatim: the wire codec IS the WAL codec) when the node
        is durable, so a peer write this node acknowledged by folding
        survives its own kill -9 without waiting for the peer's next
        round."""
        from ..oplog.wire import decode_ops_frame

        frame = bytes(frame)
        ops = decode_ops_frame(
            frame, num_actors=self.universe.config.num_actors)
        log = self._ensure_oplog()
        if self.durability is not None and len(ops):
            with self._ingest:
                self.durability.wal_append(frame)
                log.append(ops)
        else:
            log.append(ops)

    def _run_session(self, peer_label: str, transport: Transport
                     ) -> SyncReport:
        if not self._busy.acquire(timeout=self.busy_timeout_s):
            raise PeerUnavailableError(
                f"node {self.node_id}: busy with another session for "
                f">{self.busy_timeout_s:.1f}s, refusing session with "
                f"{peer_label}"
            )
        faults_mod.crash_point("cluster.session")
        faults_mod.crash_point(f"cluster.session.{self.node_id}")
        try:
            op_hooks = {}
            if self._oplog is not None:
                self._ensure_oplog()
                op_hooks = {"op_outbox": self._op_outbox,
                            "op_sink": self._op_sink}
            session = SyncSession(
                self.batch, self.universe, peer=peer_label,
                full_state_threshold=self.full_state_threshold,
                observatory=self.observatory,
                digest_tree=self.digest_tree,
                lag_tracker=self.lag_tracker,
                stability=self.stability,
                heat=self.heat,
                **op_hooks,
            )
            self.serve_views.release()
            report = session.sync(transport)
            with self._lock:
                self._batch = session.batch
                self._last_report = report
            return report
        finally:
            try:
                # fold writes queued while the session ran — BEFORE the
                # busy release, so the next session's snapshot sees them
                self._drain_ops_locked()
            finally:
                self._busy.release()

    @property
    def last_gc_report(self):
        """The most recent collection pass's
        :class:`~crdt_tpu.gc.GcReport` (None until GC has run)."""
        with self._lock:
            return self._last_gc_report

    def collect_garbage(self, peers=None):
        """Run one causal-GC pass on this node's batch + op buffers
        (:meth:`crdt_tpu.gc.GcEngine.collect`).  Returns the
        :class:`~crdt_tpu.gc.GcReport`, or None when no engine is
        configured or a sync session currently holds the busy lock —
        compaction never runs concurrently with a session on the same
        node (it retries next round instead of queueing).  ``peers``
        is the roster the fleet watermark must account for."""
        if self.gc is None:
            return None
        if not self._busy.acquire(blocking=False):
            return None
        try:
            with self._lock:
                batch = self._batch
            self.serve_views.release()
            batch, report = self.gc.collect(
                batch, universe=self.universe, oplog=self._oplog,
                applier=self._applier, peers=peers)
            with self._lock:
                self._batch = batch
                self._last_gc_report = report
            return report
        finally:
            self._busy.release()

    @property
    def last_snapshot(self):
        """The most recent checkpoint's
        :class:`~crdt_tpu.durable.Snapshot` (None until one ran)."""
        return self.durability.last_snapshot \
            if self.durability is not None else None

    def checkpoint(self):
        """Run one durability checkpoint on this node: capture the WAL
        replay bound under the ingest lock, fold pending ops, then
        snapshot the planes + parked ops + version vector + GC
        watermark (:meth:`crdt_tpu.durable.Durability.checkpoint`).

        Returns the :class:`~crdt_tpu.durable.Snapshot`, or None when
        no durability manager is configured or a sync session holds
        the busy lock — a checkpoint never runs concurrently with a
        session on the same node (it retries next round instead of
        queueing), the same non-blocking discipline as
        :meth:`collect_garbage`."""
        if self.durability is None:
            return None
        if not self._busy.acquire(blocking=False):
            return None
        try:
            # capture BEFORE the drain: every WAL frame below this
            # sequence has completed its log append (the ingest lock
            # makes the pair atomic), so the drain folds it into the
            # snapshot; frames at or above it replay on recovery —
            # possibly redundantly, which batched apply dedups
            with self._ingest:
                wal_seq = self.durability.wal.head_seq
            self._drain_ops_locked()
            with self._lock:
                batch = self._batch
                gc_report = self._last_gc_report
            parked = None
            if self._applier is not None and len(self._applier.parked):
                parked = self._applier.parked
            watermark = None
            if gc_report is not None and gc_report.watermark is not None:
                watermark = gc_report.watermark.clock
            # the stability frontier rides the snapshot so a kill -9
            # rejoin restores it as a monotone floor — the same
            # discipline as the GC watermark above
            frontier = self.stability.frontier_clock() \
                if self.stability is not None else None
            faults_mod.crash_point(f"durable.checkpoint.{self.node_id}")
            return self.durability.checkpoint(
                batch, self.universe, wal_seq=wal_seq,
                watermark=watermark, parked=parked, frontier=frontier,
                node_id=self.node_id)
        finally:
            self._busy.release()

    def observe_stability(self, peers=None):
        """Refresh this node's stability plane: recompute + publish the
        fleet frontier against ``peers`` (the full roster incl. DEAD
        peers — quarantine, not membership state, decides exclusion,
        exactly the GC watermark rule) and run the sampled lattice
        auditor on its cadence.  Reads an immutable batch snapshot, so
        it never needs the busy lock.  Returns the
        :class:`~crdt_tpu.obs.stability.FrontierReport` (None for
        clockless batch types)."""
        if self.stability is None:
            return None
        with self._lock:
            batch = self._batch
        try:
            report = self.stability.frontier(batch, peers=peers)
        except TypeError:
            return None  # no clock plane for this batch type
        self.stability.maybe_audit(batch, self.universe, peers=peers)
        return report

    def sample_capacity(self) -> list:
        """Sample this node's dense planes + op buffers into the
        ``crdt_tpu_capacity_*`` gauges (one jitted reduction + a small
        host fetch per plane family — cheap enough for every round).
        The gossip scheduler calls this once per round; call it
        directly for scheduler-less deployments.  Returns the
        occupancies sampled (batch types without dense planes are
        skipped, never an error)."""
        from ..obs import capacity as obs_capacity

        trk = self.capacity_tracker if self.capacity_tracker is not None \
            else obs_capacity.capacity_tracker()
        occs = []
        try:
            occs.append(trk.sample(self.batch))
        except TypeError:
            pass  # no occupancy kernel for this batch type
        if self._oplog is not None:
            occs.append(trk.sample_oplog(self._oplog))
        if self._applier is not None:
            occs.append(trk.sample_gap_buffer(self._applier))
        # the device-memory gauges ride the same cadence: what the
        # device actually holds next to the plane bytes by construction
        trk.sample_device_memory()
        return occs

    def sync_with(self, peer_id: str, transport: Transport) -> SyncReport:
        """Run the initiator leg of one session against ``peer_id``."""
        return self._run_session(peer_id, transport)

    def accept(self, transport: Transport, peer_id: str = "peer"
               ) -> SyncReport:
        """Run the acceptor leg of a session a peer dialed into us.
        The protocol is symmetric, so this is the same state machine —
        the split exists for listeners' readability and telemetry."""
        return self._run_session(peer_id, transport)

    def sync_shard_subset(self, peer: "ClusterNode", layout):
        """Repair ONLY the diverged shards of a mesh-sharded fleet
        against an in-process peer replica: per-shard root compare,
        then the digest-tree descent scoped to each diverged shard's
        leaf range (:func:`crdt_tpu.mesh.sync.shard_subset_sync`),
        pulling exactly those shards' diverged rows from ``peer``'s
        batch.  ``layout`` is the fleet's shard→leaf-range map
        (:class:`~crdt_tpu.mesh.state.MeshLayout`).

        Both busy locks are taken (initiator first, timeout-bounded —
        a cross-pair would raise :class:`PeerUnavailableError` rather
        than deadlock, the session discipline), so neither side's
        batch moves mid-repair.  Repaired rows feed this node's heat
        tracker exactly like a flat session's deltas.  Returns the
        :class:`~crdt_tpu.mesh.sync.ShardSyncStats`."""
        from ..mesh import sync as mesh_sync

        if not self._busy.acquire(timeout=self.busy_timeout_s):
            raise PeerUnavailableError(
                f"node {self.node_id}: busy with another session for "
                f">{self.busy_timeout_s:.1f}s, refusing shard-subset "
                f"sync with {peer.node_id}"
            )
        try:
            if not peer._busy.acquire(timeout=peer.busy_timeout_s):
                raise PeerUnavailableError(
                    f"peer {peer.node_id}: busy with another session "
                    f"for >{peer.busy_timeout_s:.1f}s, refusing "
                    f"shard-subset sync from {self.node_id}"
                )
            try:
                with self._lock:
                    mine = self._batch
                with peer._lock:
                    theirs = peer._batch
                self.serve_views.release()
                merged, stats = mesh_sync.shard_subset_sync(
                    mine, theirs, layout, self.universe,
                    applier=self._applier)
                with self._lock:
                    self._batch = merged
                if stats.objects and self.heat is not None:
                    self.heat.record_repair(stats.object_ids, layout.n)
                return stats
            finally:
                peer._busy.release()
        finally:
            self._busy.release()


@dataclasses.dataclass
class RoundReport:
    """One gossip round's outcome, per peer id."""

    round_no: int
    ranked: List[str] = dataclasses.field(default_factory=list)
    ok: List[str] = dataclasses.field(default_factory=list)
    failed: Dict[str, str] = dataclasses.field(default_factory=dict)
    skipped_busy: List[str] = dataclasses.field(default_factory=list)

    @property
    def attempted(self) -> int:
        return len(self.ok) + len(self.failed)


class GossipScheduler:
    """Staleness-driven peer selection + concurrent session fan-out.

    ``dialer`` turns a :class:`~crdt_tpu.cluster.membership.PeerInfo`
    into a connected :class:`~crdt_tpu.cluster.transport.Transport`
    (typically ``ResilientTransport(TcpTransport(...))`` — the dialer
    owns transport policy, the scheduler owns peer policy).  ``fanout``
    bounds concurrent sessions per round; ``seed`` drives the interval
    jitter so a fleet of schedulers doesn't phase-lock.

    Drive it deterministically with :meth:`run_round` (what the tests
    and the example's sweep loop do) or as a background thread via
    :meth:`start`/:meth:`stop`.
    """

    def __init__(self, node: ClusterNode,
                 membership: membership_mod.Membership,
                 dialer: Dialer, *,
                 fanout: int = 2,
                 interval_s: float = 1.0,
                 probe_dead_every: int = 4,
                 session_timeout_s: float = 120.0,
                 seed: int = 0,
                 tracker: Optional[obs_convergence.ConvergenceTracker]
                 = None):
        if fanout < 1:
            raise ValueError(f"fanout {fanout} < 1")
        self.node = node
        self.membership = membership
        self.dialer = dialer
        self.fanout = fanout
        self.interval_s = interval_s
        self.probe_dead_every = max(1, probe_dead_every)
        self.session_timeout_s = session_timeout_s
        self._tracker = tracker or obs_convergence.tracker()
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._peer_locks: Dict[str, threading.Lock] = {}
        self._round_no = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- peer selection ------------------------------------------------------

    def _endpoint_lock(self, peer_id: str) -> threading.Lock:
        with self._lock:
            lk = self._peer_locks.get(peer_id)
            if lk is None:
                lk = self._peer_locks[peer_id] = threading.Lock()
            return lk

    def rank_peers(self, round_no: int = 0
                   ) -> List[membership_mod.PeerInfo]:
        """The candidate roster for one round, most-in-need first.
        Alive and suspect peers always qualify; dead peers only on
        probe rounds (every ``probe_dead_every``-th)."""
        states = [membership_mod.ALIVE, membership_mod.SUSPECT]
        if round_no % self.probe_dead_every == 0:
            states.append(membership_mod.DEAD)
        candidates = self.membership.peers(*states)
        return sorted(
            candidates,
            key=lambda p: self._tracker.urgency(p.peer_id),
            reverse=True,
        )

    # -- one round -----------------------------------------------------------

    def _session_leg(self, peer: membership_mod.PeerInfo,
                     lock: threading.Lock, report: RoundReport,
                     results_lock: threading.Lock) -> None:
        try:
            try:
                transport = self.dialer(peer)
                try:
                    self.node.sync_with(peer.peer_id, transport)
                finally:
                    transport.close()
            except (SyncProtocolError, TransportError) as e:
                tracing.count("cluster.sessions.failed")
                self.membership.record_failure(peer.peer_id)
                obs_events.record("cluster.session", peer=peer.peer_id,
                                  outcome="failed",
                                  error=f"{type(e).__name__}: {e}"[:200])
                with results_lock:
                    report.failed[peer.peer_id] = type(e).__name__
            else:
                tracing.count("cluster.sessions.ok")
                self.membership.record_success(peer.peer_id)
                obs_events.record("cluster.session", peer=peer.peer_id,
                                  outcome="ok")
                with results_lock:
                    report.ok.append(peer.peer_id)
        finally:
            lock.release()

    def run_round(self) -> RoundReport:
        """Rank, pick ``fanout`` peers, run their sessions concurrently,
        record the outcomes.  Synchronous: returns when every session
        leg finished (or the round's join deadline passed)."""
        with self._lock:
            self._round_no += 1
            round_no = self._round_no
        tracing.count("cluster.rounds")
        report = RoundReport(round_no=round_no)
        results_lock = threading.Lock()
        round_t0 = time.monotonic()
        with tracing.span("cluster.round"):
            ranked = self.rank_peers(round_no)
            report.ranked = [p.peer_id for p in ranked]
            legs: List[threading.Thread] = []
            for peer in ranked:
                if len(legs) >= self.fanout:
                    break
                lk = self._endpoint_lock(peer.peer_id)
                if not lk.acquire(blocking=False):
                    tracing.count("cluster.sessions.skipped_busy")
                    report.skipped_busy.append(peer.peer_id)
                    continue
                t = threading.Thread(
                    target=self._session_leg,
                    args=(peer, lk, report, results_lock),
                    name=f"gossip-{self.node.node_id}-{peer.peer_id}",
                    daemon=True,
                )
                legs.append(t)
                t.start()
            deadline = time.monotonic() + self.session_timeout_s
            for t in legs:
                t.join(timeout=max(deadline - time.monotonic(), 0.0))
        obs_events.record(
            "cluster.round", node=self.node.node_id, round=round_no,
            ok=list(report.ok), failed=dict(report.failed),
            skipped_busy=list(report.skipped_busy),
        )
        self._publish_round_health(report)
        # the convergence SLO: a round "meets" it when every attempted
        # session succeeded AND the round finished within the lag
        # tracker's budget — published as sync.slo.converged_frac over
        # a bounded window of recent rounds
        self.node.lag_tracker.observe_round(
            converged=not report.failed,
            wall_s=time.monotonic() - round_t0)
        # capacity sample per round: the sessions above may have merged
        # in peer members (plane growth) or drained queued ops, so the
        # occupancy gauges / growth ETAs refresh on the post-round state
        self.node.sample_capacity()
        # heat plane per round: refresh the EWMA *_per_s windows, the
        # top-k hot-object gauges, and the fitted Zipf exponent from
        # whatever the serve/drain/repair paths attributed this round
        self.node.heat.publish()
        # stability plane per round: the frontier recomputes against
        # the FULL roster (incl. DEAD peers — quarantine, not the
        # membership state, decides when a silent peer stops pinning
        # it) and the sampled lattice auditor re-checks merge
        # idempotence + frontier soundness on the post-round state
        roster = [
            p.peer_id for p in self.membership.peers(
                membership_mod.ALIVE, membership_mod.SUSPECT,
                membership_mod.DEAD)
        ]
        self.node.observe_stability(peers=roster)
        # causal GC between sessions: the engine decides cadence (every
        # Nth round, or early on a capacity-watermark trigger); the
        # roster includes DEAD peers — the watermark's quarantine, not
        # the membership state, decides when a silent peer stops
        # freezing the fleet's memory
        if self.node.gc is not None and self.node.gc.due(round_no):
            if self.node.collect_garbage(peers=roster) is not None:
                # a shrink/settle changed the planes: refresh the
                # occupancy gauges on the post-GC state (and re-seed
                # the EWMA on a capacity change)
                self.node.sample_capacity()
        # durability checkpoint at round end, AFTER GC: the snapshot
        # then captures the settled/re-packed planes and the freshest
        # watermark clock.  Non-blocking like GC — a session racing in
        # just defers the checkpoint one round (the WAL already holds
        # every write, so deferral risks nothing)
        if self.node.durability is not None \
                and self.node.durability.due(round_no):
            self.node.checkpoint()
        return report

    def _publish_round_health(self, report: RoundReport) -> None:
        """Mirror the round's outcome + the tracker's divergence view
        into the ``cluster.gossip.*`` gauges, so one scrape of any node
        answers "is the fleet converging": peers attempted / failed /
        skipped-busy this round, the max per-peer divergence the digest
        exchanges last saw, and a rounds-to-converge ETA (peers still
        diverged over the per-round fanout — 0 once every known peer's
        last digest exchange was clean)."""
        conv = self._tracker.snapshot()
        # outstanding divergence only: a converged session resolved
        # what its digest exchange found (the per-peer gauge keeps the
        # found value — this view answers "what is still diverged NOW")
        divergences = [
            0 if st.get("divergence_resolved", True)
            else st.get("divergence", 0)
            for st in conv.values()
        ]
        diverged_peers = sum(1 for d in divergences if d > 0)
        eta = -(-diverged_peers // self.fanout) if diverged_peers else 0
        reg = obs_metrics.registry()
        reg.gauge_set("cluster.gossip.attempted", report.attempted)
        reg.gauge_set("cluster.gossip.ok", len(report.ok))
        reg.gauge_set("cluster.gossip.failed", len(report.failed))
        reg.gauge_set("cluster.gossip.skipped_busy",
                      len(report.skipped_busy))
        reg.gauge_set("cluster.gossip.fleet_divergence_max",
                      max(divergences, default=0))
        reg.gauge_set("cluster.gossip.eta_rounds", eta)

    # -- the background loop -------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            self.run_round()
            # jittered inter-round sleep so a fleet of schedulers
            # doesn't phase-lock into synchronized dial storms
            pause = self.interval_s * (0.5 + self._rng.random())
            self._stop.wait(timeout=pause)

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name=f"gossip-{self.node.node_id}",
            daemon=True,
        )
        self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        self._thread = None
