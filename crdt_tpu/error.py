"""CRDT error codes.

Mirrors the reference error enum (`/root/reference/src/error.rs:8-18`):
``ConflictingMarker``, ``MergeConflict``, ``NestedOpFailed``.  The reference
returns ``Result<T, Error>`` from the Funky (fallible) traits
(`/root/reference/src/traits.rs:53-75`); in Python the idiomatic equivalent
is raising — the funky merge/apply/update entry points raise these.

Batched TPU kernels cannot raise per-element; they surface a conflict bitmap
instead (see ``crdt_tpu.ops.lww_ops``), which the host converts into a
:class:`ConflictingMarker` for scalar-path error parity (SURVEY.md §7.3).
"""

from __future__ import annotations


class CrdtError(Exception):
    """Base class for all CRDT errors."""


class ConflictingMarker(CrdtError):
    """A conflicting change witnessed by a marker/dot that already exists.

    Reference: `error.rs:9-13` — "Dot's are used exactly once for the
    lifetime of a CRDT".
    """

    def __str__(self) -> str:
        base = "Dot's are used exactly once for the lifetime of a CRDT"
        # keep the reference's Display string (error.rs:9-13) but don't
        # swallow caller detail (e.g. which register conflicted in a join)
        return f"{base}: {self.args[0]}" if self.args else base


class MergeConflict(CrdtError):
    """A generic error for any unmergable conflict (`error.rs:14-15`)."""

    def __str__(self) -> str:
        return "There was a conflict while merging"


class CapacityOverflowError(CrdtError, ValueError):
    """A batched join outgrew its padded slot capacity.

    No reference counterpart — capacities are the TPU build's static-shape
    concession (SURVEY.md §7.3).  Carries which axis overflowed so elastic
    recovery (``crdt_tpu.parallel.JoinExecutor``) grows only that axis.
    Subclasses ``ValueError`` for backward compatibility with callers that
    catch the old error type.
    """

    def __init__(self, message: str, member: bool = True, deferred: bool = True):
        super().__init__(message)
        self.member = member
        self.deferred = deferred


def raise_for_overflow(overflow, context: str) -> None:
    """Reduce an ORSWOT overflow bitmap (``bool[..., 2]``, member/deferred
    flags in the last axis) and raise :class:`CapacityOverflowError` naming
    the overflowed axes.  One host sync; no-op when nothing overflowed.

    Multi-process arrays (a ``jax.distributed`` mesh spanning hosts) are
    checked shard-locally: each process inspects the shards it can
    address — an overflow raises on the process whose partition
    overflowed, which is also the process that must regrow."""
    import numpy as np

    shards = getattr(overflow, "addressable_shards", None)
    if shards is not None and not getattr(overflow, "is_fully_addressable", True):
        flat = np.concatenate(
            [np.asarray(s.data).reshape(-1, 2) for s in shards]
        ) if shards else np.zeros((0, 2), bool)
        flags = flat.any(axis=0)
    else:
        flags = np.asarray(overflow).reshape(-1, 2).any(axis=0)
    m_over, d_over = bool(flags[0]), bool(flags[1])
    if not (m_over or d_over):
        return
    axes = "/".join(
        name
        for name, hit in (("member_capacity", m_over), ("deferred_capacity", d_over))
        if hit
    )
    raise CapacityOverflowError(
        f"Orswot capacity overflow in {context}: raise {axes}",
        member=m_over,
        deferred=d_over,
    )


class WireFormatError(CrdtError, ValueError):
    """A wire blob violated the binary grammar or the static capacities
    of the receiving fleet (actor outside the identity registry, more
    members than ``member_capacity``, ...).

    No reference counterpart — the reference's serde is infallible by
    construction (serde derive); the TPU build's native bulk parsers
    triage per-blob status codes instead, and hard statuses surface as
    this.  Subclasses ``ValueError`` so existing callers (and tests)
    that catch the old error type keep working; the wire error-contract
    lint (``crdt_tpu.analysis.wire``) requires every decode path to
    raise a :class:`CrdtError` subclass, which this satisfies.
    """


class OpLogOverflowError(CrdtError):
    """A bounded op-log structure ran out of room: the append-only
    columnar log (:class:`crdt_tpu.oplog.OpLog`) hit its capacity, or
    the causal-gap parking buffer (:class:`crdt_tpu.oplog.OpApplier`)
    filled with ops whose causal predecessors never arrived.

    No reference counterpart — the reference applies one op at a time
    and delegates delivery (`traits.rs:15-41`); bounding the batched
    front-end is this build's backpressure story.  Deliberately NOT a
    ``ValueError``: a full log means the caller must drain (apply) or
    shed load, not that the op itself was malformed.
    """


class DurabilityError(CrdtError):
    """The durable-replica layer (:mod:`crdt_tpu.durable`) could not
    produce or restore persistent state: every retained snapshot
    generation rejected, a WAL directory in an impossible shape, a
    restored batch failing its digest-root self-check.

    No reference counterpart — the reference's checkpoint story ends at
    ``to_binary``/``from_binary`` (`lib.rs:62-83`); surviving kill -9
    is this build's addition.  Deliberately NOT a ``ValueError``: an
    unrecoverable store means the operator must intervene (restore a
    backup, rejoin as a fresh replica), not that one payload was
    malformed — that is :class:`CheckpointFormatError`.
    """


class CheckpointFormatError(DurabilityError, ValueError):
    """One checkpoint/snapshot payload violated its binary format:
    torn/truncated container, CRC mismatch, version skew, or a restored
    batch whose digest-tree root disagrees with the one recorded at
    save time.

    Raised by the checkpoint loader (:mod:`crdt_tpu.utils.checkpoint`)
    and the snapshot store (:mod:`crdt_tpu.durable.snapshot`); recovery
    treats it as "this generation is bad, fall back to the previous
    one" — loudly (``durable.snapshot.rejected.*``), never silently.
    Subclasses ``ValueError`` because ``load_bytes`` doubles as the
    state-replication receive path, whose historical contract was
    ValueError-on-corruption; existing callers keep working while the
    wire error-contract lint sees a :class:`CrdtError`.
    """


class NestedOpFailed(CrdtError):
    """We failed to apply a nested op to a nested CRDT (`error.rs:16-17`)."""

    def __str__(self) -> str:
        return "We failed to apply a nested op to a nested CRDT"


class SyncProtocolError(CrdtError):
    """An anti-entropy sync frame or session violated the protocol.

    No reference counterpart — the reference ships no transport
    (`lib.rs:62-83`); this covers the sync layer built above the wire
    codec (:mod:`crdt_tpu.sync`): version mismatches, truncated or
    CRC-failing frames, fleet-size disagreements, and sessions that
    fail to converge after the full-state retry.  Deliberately NOT a
    ``ValueError``: a malformed peer frame is an I/O-boundary fault to
    catch and drop, not a local programming error.
    """


class TransportError(CrdtError):
    """A transport leg (send/recv/connect) failed below the sync
    protocol: the frames were fine, moving them was not.

    The split from :class:`SyncProtocolError` is deliberate — a
    protocol error means the PEER misbehaved (drop the peer), a
    transport error means the NETWORK misbehaved (retry with backoff).
    The gossip scheduler (:mod:`crdt_tpu.cluster.gossip`) treats both
    as a failed session but only transport errors feed the
    alive→suspect→dead health thresholds.
    """


class SyncTimeoutError(TransportError):
    """A transport leg blew its deadline: the peer (or the path to it)
    went quiet mid-session.  Raised by :class:`crdt_tpu.cluster.
    transport.ResilientTransport` when a receive deadline elapses or a
    send exhausts its per-frame retransmit window — always bounded, the
    lock-step session never spins forever on a dead peer."""


class PeerUnavailableError(TransportError):
    """The peer cannot be reached at all: dial refused, link closed, or
    the transport's retry budget ran dry.  Distinct from
    :class:`SyncTimeoutError` (mid-session silence) so membership can
    treat "never answered" and "stopped answering" with different
    thresholds if it wants to; both count as failures today."""


class TransportClosedError(TransportError):
    """The underlying byte channel closed (peer hung up, injected
    disconnect).  Raised by the raw transports; the resilient wrapper
    converts persistent closure into :class:`PeerUnavailableError`
    after its retry budget."""


class TransportFrameError(TransportError):
    """A transport-level envelope (the resilient wrapper's ARQ framing,
    not a sync-protocol frame) was malformed — truncated header, CRC
    mismatch, unknown kind.  The receiver treats it exactly like frame
    loss (drop it; the sender's retransmit covers it), so this rarely
    escapes the transport."""


class MeshContractError(CrdtError, TypeError):
    """A kernel was dispatched onto a device mesh its declared
    :class:`~crdt_tpu.analysis.kernels.ShardContract` forbids: a
    ``host_only`` or ``replicated`` kernel asked to run sharded, a
    mesh size outside the contract's verified ladder, or a kernel with
    no contract row at all.

    No reference counterpart — the reference has no device mesh; this
    is the runtime half of shardcheck's static guarantee
    (:mod:`crdt_tpu.analysis.shard_rules`): the mesh layer consults the
    SAME manifest the static checker proves, so "it shardchecks" and
    "it dispatches" can never drift apart silently.  Subclasses
    ``TypeError`` because the caller passed a kernel of the wrong
    *kind* for the mesh — a programming error at the dispatch site,
    not a data fault.
    """

    def __init__(self, message: str, *, kernel: str = "",
                 sclass: str = ""):
        super().__init__(message)
        self.kernel = kernel
        self.sclass = sclass


class ConsistencyUnavailableError(CrdtError):
    """A session-consistency admission could not be satisfied: a
    read-your-writes / monotonic read parked past its deadline without
    the node's visible clock covering the request's floor, or a
    frontier-stable read arrived at a node with no stability frontier
    yet (:mod:`crdt_tpu.serve.consistency`).  Typed so a client can
    distinguish "retry / downgrade the mode" from a protocol fault —
    the serve loop rejects loudly rather than silently serving a
    weaker read."""

    def __init__(self, message: str, *, mode: str = "",
                 reason: str = ""):
        super().__init__(message)
        self.mode = mode
        self.reason = reason
