"""Delta frames: versioned wire envelopes + diverged-row gather/apply.

The sync protocol moves three frame kinds between peers — digest
vectors, delta payloads (object ids + their wire blobs), and full-state
payloads.  Every frame leads with a 1-byte protocol version so
mixed-version peers fail loudly (:class:`crdt_tpu.error.
SyncProtocolError`) instead of misparsing, and carries a CRC32 of its
payload so truncation/tampering is a clean rejection, not a crash in
the blob parser.

Frame layout (all little-endian)::

    version(1) | type(1) | crc32(4) | payload_len(8) | payload

The gather side encodes only diverged rows — through the native
indexed encoder (``orswot_encode_wire_rows``, ABI v10) when it applies,
so the fleet planes are never copied just to serialize 1% of them.  The
apply side parses delta blobs into REUSED staging planes
(``engine.orswot_ingest_wire(..., out=)`` — the same warm-buffer path
that fixed the e2e ingest collapse, docs/GUIDE.md) and scatter-merges the
rows into the local fleet.
"""

from __future__ import annotations

import struct
import zlib
from typing import NamedTuple

import numpy as np

from ..error import SyncProtocolError

#: bumped whenever the protocol grows; peers negotiate DOWN to the
#: lower of the two in the hello exchange, and versions outside
#: ``COMPAT_VERSIONS`` fail loudly at the first frame, never misparse.
#: v2: sessions open with a HELLO frame (trace-ID negotiation + fleet
#: observability capability flag) and may close with a FLEET frame.
#: v3: hello carries ``ver`` + a ``digest_tree`` capability; tree-mode
#: sessions replace the flat digest exchange with a root comparison +
#: subtree descent (FRAME_TREE).  The envelope grammar is unchanged
#: since v2, so v2 and v3 interoperate: hello frames always ship at
#: ``BASELINE_VERSION`` (they precede negotiation), every later frame
#: at the negotiated version, and a v2 peer never sees a TREE frame
#: because the capability defaults off for hellos without the key.
#: v4: hello carries a ``window`` advertisement (the transport's ARQ
#: in-flight window); sessions whose negotiated version AND window
#: both allow it stream — diverged rows ship as pipelined DELTA_CHUNK
#: frames and tree descents go speculative (TREE/spec subframes cover
#: whole levels ahead of the lock-step answer).  Same discipline as
#: v3: a v2/v3 peer never sees a CHUNK or spec frame because the
#: window key defaults to 0 (stop-and-wait) for hellos without it.
PROTOCOL_VERSION = 4

#: the version hello frames ship at, and the version assumed for a
#: peer whose hello predates the ``ver`` key
BASELINE_VERSION = 2

#: envelope versions this build parses (the grammar is shared; frame
#: TYPES gate on the hello-negotiated version instead)
COMPAT_VERSIONS = frozenset({2, 3, 4})

FRAME_DIGEST = 0x01
FRAME_DELTA = 0x02
FRAME_FULL = 0x03
FRAME_HELLO = 0x04
FRAME_FLEET = 0x05
FRAME_OPS = 0x06
FRAME_TREE = 0x07
FRAME_LAG = 0x08
FRAME_DELTA_CHUNK = 0x09

_FRAME_NAMES = {FRAME_DIGEST: "digest", FRAME_DELTA: "delta",
                FRAME_FULL: "full", FRAME_HELLO: "hello",
                FRAME_FLEET: "fleet", FRAME_OPS: "ops",
                FRAME_TREE: "tree", FRAME_LAG: "lag",
                FRAME_DELTA_CHUNK: "delta_chunk"}
_HEADER = struct.Struct("<BBIQ")


def _frame(ftype: int, payload: bytes, version: int | None = None) -> bytes:
    return _HEADER.pack(
        PROTOCOL_VERSION if version is None else version,
        ftype, zlib.crc32(payload), len(payload)
    ) + payload


def _reject(reason: str, message: str) -> "SyncProtocolError":
    """A :class:`SyncProtocolError` carrying flight-recorder evidence:
    every rejected frame leaves a ``sync.protocol_error`` event and a
    ``sync.frame.rejected.<reason>`` counter before the raise, so a
    misbehaving peer is visible on ``/events`` even when the caller
    catches and drops the error (the I/O-boundary discipline
    :class:`SyncProtocolError` documents)."""
    from ..obs import events as obs_events
    from ..utils import tracing

    tracing.count(f"sync.frame.rejected.{reason}")
    obs_events.record("sync.protocol_error", reason=reason,
                      error=message[:200])
    return SyncProtocolError(message)


def decode_frame(frame: bytes) -> tuple[int, bytes]:
    """``(frame_type, payload)`` of a validated frame.  Raises
    :class:`SyncProtocolError` on a version mismatch, unknown frame
    type, truncated/overlong frame, or CRC mismatch — the caller never
    sees a payload that could misparse downstream."""
    from ..utils import tracing

    if len(frame) < _HEADER.size:
        raise _reject(
            "truncated",
            f"truncated sync frame: {len(frame)} bytes < "
            f"{_HEADER.size}-byte header"
        )
    version, ftype, crc, plen = _HEADER.unpack_from(frame)
    if version not in COMPAT_VERSIONS:
        raise _reject(
            "version_mismatch",
            f"sync protocol version mismatch: peer sent v{version}, "
            f"this build speaks v{PROTOCOL_VERSION} "
            f"(compatible: {sorted(COMPAT_VERSIONS)})"
        )
    if ftype not in _FRAME_NAMES:
        raise _reject("unknown_type", f"unknown sync frame type {ftype:#04x}")
    payload = frame[_HEADER.size:]
    if len(payload) != plen:
        raise _reject(
            "length_mismatch",
            f"sync frame length mismatch: header says {plen} payload "
            f"bytes, frame carries {len(payload)}"
        )
    if zlib.crc32(payload) != crc:
        raise _reject(
            "crc_mismatch",
            f"sync {_FRAME_NAMES[ftype]} frame CRC mismatch "
            "(tampered or corrupted in transit)"
        )
    tracing.count(f"sync.frame.{_FRAME_NAMES[ftype]}.decoded")
    return ftype, payload


# ---- hello frames ----------------------------------------------------------


class HelloInfo(NamedTuple):
    """One peer's decoded hello: trace proposal, node label, the
    capability flags, and the protocol version it speaks (``ver``
    absent = a v2 peer — both sides then run the v2 flat protocol).
    ``window`` is the peer's advertised ARQ in-flight window (absent or
    0 = a stop-and-wait peer; sessions stream only when both sides
    advertise >= 2 at v4+)."""

    trace: str
    node: str
    fleet_obs: bool
    oplog: bool
    ver: int
    digest_tree: bool
    lag: bool = False
    window: int = 0


def encode_hello_frame(trace: str, node: str, fleet_obs: bool,
                       oplog: bool = False, digest_tree: bool = False,
                       lag: bool = False, window: int = 0,
                       ver: int = PROTOCOL_VERSION) -> bytes:
    """A HELLO frame — the session-opening handshake: this side's
    trace-ID proposal (both peers adopt the lexicographic min, so the
    two halves of one session share ONE fleet-unique ID), its node
    label, the protocol version it speaks, four capability flags —
    piggybacked fleet-observability snapshots, piggybacked op batches,
    digest-tree descent, and the write-to-visible lag sidecar (each
    only happens when BOTH peers advertise it, which keeps the
    lock-step protocol symmetric; an older peer simply never sees the
    key) — and the transport's ARQ window advertisement (v4: both
    peers clamp to the minimum; 0 means stop-and-wait and disables
    streaming for the session).  The hello itself ships at
    ``BASELINE_VERSION`` — it precedes the negotiation every later
    frame's version byte follows."""
    import json

    payload = json.dumps(
        {"trace": str(trace), "node": str(node),
         "fleet_obs": bool(fleet_obs), "oplog": bool(oplog),
         "ver": int(ver), "digest_tree": bool(digest_tree),
         "lag": bool(lag), "window": int(window)},
        sort_keys=True, separators=(",", ":"),
    ).encode("utf-8")
    return _frame(FRAME_HELLO, payload, version=BASELINE_VERSION)


def decode_hello_payload(payload: bytes) -> HelloInfo:
    """The :class:`HelloInfo` of a HELLO payload.  Labels are bounded
    defensively — a garbage hello must yield a rejection, not an
    unbounded event field.  A hello without the ``oplog`` /
    ``digest_tree`` / ``lag`` / ``ver`` / ``window`` keys (an older
    peer) reads as "no capability, v2, stop-and-wait", so mixed fleets
    degrade to flat state-only lock-step sessions instead of
    rejecting."""
    import json

    try:
        doc = json.loads(payload.decode("utf-8"))
        trace = str(doc["trace"])[:128]
        node = str(doc.get("node", "peer"))[:64]
        fleet_obs = bool(doc.get("fleet_obs", False))
        oplog = bool(doc.get("oplog", False))
        ver = int(doc.get("ver", BASELINE_VERSION))
        digest_tree = bool(doc.get("digest_tree", False))
        lag = bool(doc.get("lag", False))
        window = max(0, int(doc.get("window", 0)))
    except (UnicodeDecodeError, ValueError, KeyError, TypeError) as e:
        raise SyncProtocolError(f"malformed hello payload: {e}") from None
    if not trace:
        raise SyncProtocolError("hello payload carries an empty trace ID")
    return HelloInfo(trace, node, fleet_obs, oplog, ver, digest_tree, lag,
                     window)


def encode_fleet_frame(snapshot_frame: bytes,
                       version: int | None = None) -> bytes:
    """A FLEET frame: one fleet-observatory snapshot frame
    (:func:`crdt_tpu.obs.fleet.encode_snapshot` — itself versioned and
    CRC-guarded) nested in the sync envelope, so the piggyback ride
    gets the same loud-rejection treatment as every other sync leg."""
    return _frame(FRAME_FLEET, bytes(snapshot_frame), version=version)


def decode_fleet_payload(payload: bytes) -> bytes:
    """The nested fleet-snapshot frame from a FLEET payload (validated
    by the fleet codec's own decode, not here)."""
    return bytes(payload)


def encode_ops_sync_frame(ops_frame: bytes,
                          version: int | None = None) -> bytes:
    """An OPS frame: one op-batch frame
    (:func:`crdt_tpu.oplog.wire.encode_ops_frame` — itself versioned
    and CRC-guarded) nested in the sync envelope, exactly the FLEET
    piggyback discipline: converged sessions may close with an op
    exchange when both hellos advertised the capability, so live
    writes submitted mid-session reach the peer in the same session
    instead of waiting a gossip round."""
    return _frame(FRAME_OPS, bytes(ops_frame), version=version)


def decode_ops_sync_payload(payload: bytes) -> bytes:
    """The nested op-batch frame from an OPS payload (validated by the
    oplog codec's own decode, not here)."""
    return bytes(payload)


def encode_lag_frame(entries, proc_tag: str,
                     version: int | None = None) -> bytes:
    """A LAG frame — the write-to-visible sidecar: this origin's
    bounded ingest-stamp table as ``(actor, counter, mono_ns)``
    triples, plus the origin's monotonic clock-domain tag (monotonic
    stamps are only comparable within one process; the receiver drops
    foreign-domain entries loudly instead of publishing a lie).  Rides
    a converged session only when BOTH hellos advertised the ``lag``
    capability — the 23 B/op op-frame wire format is untouched."""
    proc = str(proc_tag).encode("utf-8")[:255]
    parts = [struct.pack("<B", len(proc)), proc,
             struct.pack("<I", len(entries))]
    for actor, counter, mono_ns in entries:
        parts.append(struct.pack("<HQq", int(actor), int(counter),
                                 int(mono_ns)))
    return _frame(FRAME_LAG, b"".join(parts), version=version)


def decode_lag_payload(payload: bytes) -> tuple[str, list]:
    """``(origin_proc_tag, [(actor, counter, mono_ns), ...])`` from a
    LAG payload."""
    try:
        (plen,) = struct.unpack_from("<B", payload, 0)
        off = 1
        proc = payload[off:off + plen].decode("utf-8")
        if len(payload[off:off + plen]) != plen:
            raise ValueError("proc tag truncated")
        off += plen
        (n,) = struct.unpack_from("<I", payload, off)
        off += 4
        entry = struct.Struct("<HQq")
        if off + n * entry.size != len(payload):
            raise ValueError(
                f"expected {n} entries, payload holds "
                f"{(len(payload) - off) // entry.size}"
            )
        entries = [entry.unpack_from(payload, off + i * entry.size)
                   for i in range(n)]
    except (struct.error, ValueError, UnicodeDecodeError) as e:
        raise SyncProtocolError(f"malformed lag payload: {e}") from None
    return proc, entries


# ---- digest frames ---------------------------------------------------------


def encode_digest_frame(digests: np.ndarray,
                        version_vec: np.ndarray | None = None,
                        version: int | None = None) -> bytes:
    """A DIGEST frame: the per-object u64 digest vector plus the
    (possibly empty) per-fleet version-vector summary."""
    d = np.ascontiguousarray(digests, dtype="<u8")
    vv = np.ascontiguousarray(
        version_vec if version_vec is not None else np.zeros(0), dtype="<u8"
    ).reshape(-1)
    payload = (
        struct.pack("<Q", d.shape[0]) + d.tobytes()
        + struct.pack("<I", vv.shape[0]) + vv.tobytes()
    )
    return _frame(FRAME_DIGEST, payload, version=version)


def decode_digest_payload(payload: bytes) -> tuple[np.ndarray, np.ndarray]:
    """``(digests u64[n], version_vector u64[v])`` from a DIGEST
    payload."""
    try:
        (n,) = struct.unpack_from("<Q", payload, 0)
        off = 8
        d = np.frombuffer(payload, dtype="<u8", count=n, offset=off)
        off += 8 * n
        (v,) = struct.unpack_from("<I", payload, off)
        off += 4
        vv = np.frombuffer(payload, dtype="<u8", count=v, offset=off)
        if off + 8 * v != len(payload):
            raise ValueError("trailing bytes")
    except (struct.error, ValueError) as e:
        raise SyncProtocolError(f"malformed digest payload: {e}") from None
    return d.astype(np.uint64), vv.astype(np.uint64)


# ---- digest-tree frames (protocol v3, capability-gated) --------------------

TREE_SUB_ROOT = 0x01
TREE_SUB_LEVEL = 0x02
TREE_SUB_SPEC = 0x03


def tree_subframe_kind(payload: bytes) -> int:
    """The subframe tag of a TREE payload (ROOT/LEVEL/SPEC) — the
    dispatch byte a streaming receiver looks at before picking a
    decoder."""
    if not payload:
        raise SyncProtocolError("empty tree payload")
    return payload[0]


def encode_tree_root_frame(tree, version_vec: np.ndarray | None = None,
                           version: int | None = None) -> bytes:
    """A TREE/root frame: fan-out k, fleet size, the u64 root, the top
    children level (u32 wire lanes — the first descent comparison rides
    along, so a dense-divergence cutover costs exactly one root frame),
    and the per-fleet version vector the flat digest frame would have
    carried (the GC watermark feeds off every exchange, tree or flat).
    """
    from .tree import wire_lanes

    children = (tree.levels[-2] if tree.num_levels >= 2
                else np.zeros(0, dtype=np.uint64))
    cw = wire_lanes(children)
    vv = np.ascontiguousarray(
        version_vec if version_vec is not None else np.zeros(0), dtype="<u8"
    ).reshape(-1)
    payload = (
        struct.pack("<BBQQQI", TREE_SUB_ROOT, tree.k, tree.n,
                    tree.num_levels, tree.root & 0xFFFFFFFFFFFFFFFF,
                    cw.shape[0])
        + cw.tobytes()
        + struct.pack("<I", vv.shape[0]) + vv.tobytes()
    )
    return _frame(FRAME_TREE, payload, version=version)


def decode_tree_root_payload(payload: bytes
                             ) -> tuple[int, int, int, int, np.ndarray,
                                        np.ndarray]:
    """``(k, n, levels, root, children u32[c], version_vector u64[v])``
    from a TREE/root payload."""
    try:
        sub, k, n, levels, root, c = struct.unpack_from("<BBQQQI", payload, 0)
        if sub != TREE_SUB_ROOT:
            raise ValueError(f"expected a tree ROOT subframe, got {sub}")
        off = struct.calcsize("<BBQQQI")
        children = np.frombuffer(payload, dtype="<u4", count=c, offset=off)
        off += 4 * c
        (v,) = struct.unpack_from("<I", payload, off)
        off += 4
        vv = np.frombuffer(payload, dtype="<u8", count=v, offset=off)
        if off + 8 * v != len(payload):
            raise ValueError("trailing bytes")
    except (struct.error, ValueError) as e:
        raise SyncProtocolError(
            f"malformed tree root payload: {e}") from None
    return (int(k), int(n), int(levels), int(root),
            children.astype(np.uint32), vv.astype(np.uint64))


def _encode_tree_sublevel(sub: int, level: int, parents: np.ndarray,
                          lanes: np.ndarray,
                          version: int | None = None) -> bytes:
    from .tree import TREE_K, wire_lanes

    parents = np.ascontiguousarray(parents, dtype="<u8")
    lw = wire_lanes(lanes)
    if lw.shape[0] != parents.shape[0] * TREE_K:
        raise ValueError(
            f"tree level frame: {parents.shape[0]} parents need "
            f"{parents.shape[0] * TREE_K} child lanes, got {lw.shape[0]}"
        )
    payload = (
        struct.pack("<BBI", sub, level, parents.shape[0])
        + parents.tobytes() + lw.tobytes()
    )
    return _frame(FRAME_TREE, payload, version=version)


def _decode_tree_sublevel(sub: int, kind: str, payload: bytes
                          ) -> tuple[int, np.ndarray, np.ndarray]:
    from .tree import TREE_K

    try:
        got, level, p = struct.unpack_from("<BBI", payload, 0)
        if got != sub:
            raise ValueError(f"expected a tree {kind} subframe, got {got}")
        off = struct.calcsize("<BBI")
        parents = np.frombuffer(payload, dtype="<u8", count=p, offset=off)
        off += 8 * p
        lanes = np.frombuffer(payload, dtype="<u4", count=p * TREE_K,
                              offset=off)
        if off + 4 * p * TREE_K != len(payload):
            raise ValueError("trailing bytes")
    except (struct.error, ValueError) as e:
        raise SyncProtocolError(
            f"malformed tree {kind.lower()} payload: {e}") from None
    return int(level), parents.astype(np.int64), lanes.astype(np.uint32)


def encode_tree_level_frame(level: int, parents: np.ndarray,
                            lanes: np.ndarray,
                            version: int | None = None) -> bytes:
    """A TREE/level frame: one descent step — the diverged parent node
    ids (level ``level + 1``; both peers computed the same set, they
    travel for lock-step validation) and the u32 wire lanes of their k
    children each, parent-major."""
    return _encode_tree_sublevel(TREE_SUB_LEVEL, level, parents, lanes,
                                 version)


def decode_tree_level_payload(payload: bytes
                              ) -> tuple[int, np.ndarray, np.ndarray]:
    """``(level, parents int64[p], lanes u32[p*k])`` from a TREE/level
    payload."""
    return _decode_tree_sublevel(TREE_SUB_LEVEL, "LEVEL", payload)


def encode_tree_spec_frame(level: int, parents: np.ndarray,
                           lanes: np.ndarray,
                           version: int | None = None) -> bytes:
    """A TREE/spec frame — one SPECULATIVE descent level (v4 streaming
    sessions): the full k-ary expansion under the top diverged
    children, shipped before the peer's answer to the previous level
    so the whole descent completes in ~1 extra RTT.  Same wire grammar
    as a LEVEL frame; the tag tells the receiver these parents are the
    sender's GUESS (a pure function of the shared root exchange, so
    both peers ship identical expansions) — the receiver reads the
    blocks its true diverged set needs (``sync.tree.speculate.hit``)
    and discards the rest (``.miss``), bounded by the dense-cutover
    byte budget."""
    return _encode_tree_sublevel(TREE_SUB_SPEC, level, parents, lanes,
                                 version)


def decode_tree_spec_payload(payload: bytes
                             ) -> tuple[int, np.ndarray, np.ndarray]:
    """``(level, parents int64[p], lanes u32[p*k])`` from a TREE/spec
    payload."""
    return _decode_tree_sublevel(TREE_SUB_SPEC, "SPEC", payload)


# ---- delta / full-state frames ---------------------------------------------


def _pack_blobs(blobs) -> bytes:
    parts = []
    for b in blobs:
        parts.append(struct.pack("<I", len(b)))
        parts.append(b)
    return b"".join(parts)


def _unpack_blobs(payload: bytes, off: int, count: int) -> list[bytes]:
    out = []
    view = memoryview(payload)
    for _ in range(count):
        if off + 4 > len(payload):
            raise SyncProtocolError(
                "malformed sync payload: blob length field truncated"
            )
        (ln,) = struct.unpack_from("<I", payload, off)
        off += 4
        if off + ln > len(payload):
            raise SyncProtocolError(
                f"malformed sync payload: blob of {ln} bytes overruns frame"
            )
        out.append(bytes(view[off:off + ln]))
        off += ln
    if off != len(payload):
        raise SyncProtocolError(
            f"malformed sync payload: {len(payload) - off} trailing bytes"
        )
    return out


def encode_delta_frame(fleet_n: int, ids: np.ndarray, blobs,
                       version: int | None = None) -> bytes:
    """A DELTA frame: the diverged object ids and their wire blobs, in
    id order.  ``fleet_n`` rides along so a peer with a different fleet
    size rejects cleanly."""
    ids = np.ascontiguousarray(ids, dtype="<u8")
    if ids.shape[0] != len(blobs):
        raise ValueError(
            f"delta frame: {ids.shape[0]} ids vs {len(blobs)} blobs"
        )
    payload = (
        struct.pack("<QQ", fleet_n, ids.shape[0]) + ids.tobytes()
        + _pack_blobs(blobs)
    )
    return _frame(FRAME_DELTA, payload, version=version)


def decode_delta_payload(payload: bytes) -> tuple[int, np.ndarray, list[bytes]]:
    """``(fleet_n, ids int64[k], blobs)`` from a DELTA payload."""
    try:
        fleet_n, k = struct.unpack_from("<QQ", payload, 0)
        ids = np.frombuffer(payload, dtype="<u8", count=k, offset=16)
    except (struct.error, ValueError) as e:
        raise SyncProtocolError(f"malformed delta payload: {e}") from None
    blobs = _unpack_blobs(payload, 16 + 8 * k, k)
    return int(fleet_n), ids.astype(np.int64), blobs


#: rows per streamed DELTA_CHUNK frame.  Fixed (not adaptive) on
#: purpose: the apply side's warm staging planes are sized to the
#: largest chunk seen (power-of-two rows), so a fixed chunk size means
#: ONE buffer rung for the life of an endpoint — the wireloop
#: staging-pool discipline applied to the sync path.  256 rows at the
#: default config is a few hundred KB of blobs: big enough to amortize
#: the frame header, small enough that apply overlaps the wire.
DELTA_CHUNK_ROWS = 256


def encode_delta_chunk_frame(fleet_n: int, chunk_idx: int, chunk_count: int,
                             ids: np.ndarray, blobs,
                             version: int | None = None) -> bytes:
    """A DELTA_CHUNK frame (v4 streaming sessions): one fixed-size
    slice of the diverged rows, shipped while earlier chunks are still
    unacked so encode/apply overlap the wire.  ``chunk_idx`` /
    ``chunk_count`` pin the stream's shape — the ARQ delivers in
    order, so a receiver seeing idx != expected is a protocol error,
    not a reordering."""
    ids = np.ascontiguousarray(ids, dtype="<u8")
    if ids.shape[0] != len(blobs):
        raise ValueError(
            f"delta chunk frame: {ids.shape[0]} ids vs {len(blobs)} blobs"
        )
    payload = (
        struct.pack("<QIIQ", fleet_n, chunk_idx, chunk_count, ids.shape[0])
        + ids.tobytes() + _pack_blobs(blobs)
    )
    return _frame(FRAME_DELTA_CHUNK, payload, version=version)


def decode_delta_chunk_payload(payload: bytes
                               ) -> tuple[int, int, int, np.ndarray,
                                          list[bytes]]:
    """``(fleet_n, chunk_idx, chunk_count, ids int64[k], blobs)`` from
    a DELTA_CHUNK payload."""
    try:
        fleet_n, idx, total, k = struct.unpack_from("<QIIQ", payload, 0)
        off = struct.calcsize("<QIIQ")
        ids = np.frombuffer(payload, dtype="<u8", count=k, offset=off)
    except (struct.error, ValueError) as e:
        raise SyncProtocolError(
            f"malformed delta chunk payload: {e}") from None
    blobs = _unpack_blobs(payload, off + 8 * k, k)
    return int(fleet_n), int(idx), int(total), ids.astype(np.int64), blobs


def encode_full_frame(blobs, version: int | None = None) -> bytes:
    """A FULL frame: every object's wire blob, in object order — the
    fallback when divergence is wide or digests disagree after a delta
    pass."""
    payload = struct.pack("<Q", len(blobs)) + _pack_blobs(blobs)
    return _frame(FRAME_FULL, payload, version=version)


def decode_full_payload(payload: bytes) -> list[bytes]:
    try:
        (n,) = struct.unpack_from("<Q", payload, 0)
    except struct.error as e:
        raise SyncProtocolError(f"malformed full-state payload: {e}") from None
    return _unpack_blobs(payload, 8, n)


# ---- diverged-row gather ---------------------------------------------------


def diverged_indices(mine: np.ndarray, theirs: np.ndarray) -> np.ndarray:
    """Ascending object indices where the two digest vectors disagree.
    Both peers compute the SAME set from the exchanged vectors, which is
    what keeps the lock-step protocol deadlock-free."""
    mine = np.asarray(mine, dtype=np.uint64)
    theirs = np.asarray(theirs, dtype=np.uint64)
    if mine.shape != theirs.shape:
        raise SyncProtocolError(
            f"digest vector shape mismatch: {mine.shape} vs {theirs.shape} "
            "(peers must sync equal-sized fleets)"
        )
    return np.nonzero(mine != theirs)[0].astype(np.int64)


def _tree_gather(batch, ids: np.ndarray):
    """``batch[ids]`` across every plane — batches are flax pytrees, so
    one tree_map covers all types."""
    import jax

    return jax.tree_util.tree_map(lambda p: p[ids], batch)


def gather_blobs(batch, ids: np.ndarray, universe) -> list[bytes]:
    """Wire blobs of the fleet rows named by ``ids``, byte-identical to
    ``batch.to_wire(universe)`` restricted to those rows.

    OrswotBatch with an identity universe takes the native indexed
    encoder (ABI v10) — no gather copy of the planes; everything else
    (other types, non-identity universes, pre-v10 engines, the u64
    zigzag guard) gathers the rows and uses the type's own ``to_wire``.
    """
    from ..batch.orswot_batch import OrswotBatch
    from ..batch.wirebulk import (
        counters_overflow_zigzag, probe_engine, record_wire, slice_blobs,
    )
    from ..config import counter_dtype

    ids = np.ascontiguousarray(ids, dtype=np.int64)
    if ids.size == 0:
        return []
    if isinstance(batch, OrswotBatch):
        engine = probe_engine(
            universe, "orswot_encode_wire_rows", counter_dtype(universe.config)
        )
        if engine is not None:
            planes = tuple(
                np.asarray(x)
                for x in (batch.clock, batch.ids, batch.dots,
                          batch.d_ids, batch.d_clocks)
            )
            if not counters_overflow_zigzag(
                (planes[0], planes[2], planes[4])
            ):
                buf, offsets = engine.orswot_encode_wire_rows(*planes, ids)
                record_wire("orswot", "to_wire", native=ids.size)
                return slice_blobs(buf, offsets)
    return _tree_gather(batch, ids).to_wire(universe)


# ---- delta apply -----------------------------------------------------------


def _next_pow2(c: int) -> int:
    return 1 if c <= 0 else 1 << (c - 1).bit_length()


class OrswotDeltaApplier:
    """Scatter-merge delta rows into an ORSWOT fleet through warm
    buffers.

    One instance owns two reusable plane sets sized to the largest delta
    seen (power-of-two rows): a parse staging set handed to
    ``engine.orswot_ingest_wire(..., out=)`` — the allocation-churn fix
    the pipelined wire loop is built on — and a merge output set for the
    native row merge.  A session applies one delta per sync, but a
    long-lived endpoint syncing every round reuses the same buffers
    forever.

    Falls back to the jnp path (``from_wire`` + batch merge +
    ``.at[ids].set``) when the native engine is unavailable or the
    universe's keys are neither identity ints nor ``str`` / ``bytes``
    names; results are identical either way (the parity tests pin
    this)."""

    def __init__(self, universe):
        self.universe = universe
        self._cap = 0
        self._staging = None
        self._merge_out = None

    def _plane_set(self, n: int) -> tuple:
        from ..config import counter_dtype

        cfg = self.universe.config
        dt = counter_dtype(cfg)
        a, m, d = cfg.num_actors, cfg.member_capacity, cfg.deferred_capacity
        return (
            np.zeros((n, a), dtype=dt),
            np.full((n, m), -1, dtype=np.int32),
            np.zeros((n, m, a), dtype=dt),
            np.full((n, d), -1, dtype=np.int32),
            np.zeros((n, d, a), dtype=dt),
        )

    def _buffers(self, k: int) -> tuple[tuple, tuple]:
        cap = _next_pow2(k)
        if cap > self._cap:
            self._cap = cap
            self._staging = self._plane_set(cap)
            self._merge_out = self._plane_set(cap)
        # leading-axis slices of C-contiguous planes stay C-contiguous,
        # so the exact-(k, ...) shape contract of out= holds
        return (
            tuple(p[:k] for p in self._staging),
            tuple(p[:k] for p in self._merge_out),
        )

    def apply(self, batch, ids: np.ndarray, blobs) -> "object":
        """``batch`` with ``merge(local_row, peer_row)`` applied at every
        ``ids`` row; peer rows decoded from ``blobs``.  Raises
        :class:`crdt_tpu.error.CapacityOverflowError` when a row union
        outgrows the padded capacities (the caller regrows and retries,
        as any merge path)."""
        import jax.numpy as jnp

        from ..batch.orswot_batch import OrswotBatch
        from ..batch.wirebulk import (
            named_engine, orswot_planes_from_wire, probe_engine,
        )
        from ..config import counter_dtype
        from ..error import raise_for_overflow

        ids = np.ascontiguousarray(ids, dtype=np.int64)
        k = len(blobs)
        if k != ids.shape[0]:
            raise SyncProtocolError(
                f"delta apply: {ids.shape[0]} ids vs {k} blobs"
            )
        if k == 0:
            return batch
        n = batch.clock.shape[0]
        if ids.min() < 0 or ids.max() >= n:
            raise SyncProtocolError(
                f"delta apply: object id outside fleet [0, {n})"
            )
        cfg = self.universe.config
        dt = counter_dtype(cfg)
        if self.universe.is_identity:
            engine = probe_engine(self.universe, "orswot_merge", dt)
        else:
            engine = named_engine(self.universe, "orswot_merge", dt)[0]
        if engine is not None and (
            batch.member_capacity != cfg.member_capacity
            or batch.deferred_capacity != cfg.deferred_capacity
        ):
            # the warm staging/merge-out buffers (and the native row
            # codec) are shaped by the CONFIG capacities; a batch that
            # regrew above — or was GC-repacked to a different rung —
            # must take the shape-polymorphic jnp route (the merge
            # kernel handles asymmetric slot widths, out= does not)
            engine = None
        if engine is not None:
            staging, merge_out = self._buffers(k)
            peer = orswot_planes_from_wire(blobs, self.universe, out=staging)
            if peer is not None:
                local = tuple(
                    np.ascontiguousarray(np.asarray(p)[ids])
                    for p in (batch.clock, batch.ids, batch.dots,
                              batch.d_ids, batch.d_clocks)
                )
                res = engine.orswot_merge(*local, *peer, out=merge_out)
                raise_for_overflow(res[5], "delta apply")
                host = [
                    np.array(np.asarray(p))
                    for p in (batch.clock, batch.ids, batch.dots,
                              batch.d_ids, batch.d_clocks)
                ]
                for dst, src in zip(host, res[:5]):
                    dst[ids] = src
                return OrswotBatch(*(jnp.asarray(h) for h in host))
        # jnp route: parse (Python codec if need be), merge the gathered
        # rows on device, scatter back
        sub_peer = OrswotBatch.from_wire(blobs, self.universe)
        sub_local = _tree_gather(batch, ids)
        merged = sub_local.merge(sub_peer)
        return OrswotBatch(
            clock=batch.clock.at[ids].set(merged.clock),
            ids=batch.ids.at[ids].set(merged.ids),
            dots=batch.dots.at[ids].set(merged.dots),
            d_ids=batch.d_ids.at[ids].set(merged.d_ids),
            d_clocks=batch.d_clocks.at[ids].set(merged.d_clocks),
        )


def apply_delta_rows(batch, ids: np.ndarray, blobs, universe,
                     applier: OrswotDeltaApplier | None = None):
    """Generic scatter-merge for any fleet batch type: decode the peer's
    delta rows, merge them with the gathered local rows, scatter the
    result back.  ORSWOT fleets route through ``applier`` (or a
    transient one) for the warm-buffer native path."""
    import jax

    from ..batch.orswot_batch import OrswotBatch

    ids = np.ascontiguousarray(ids, dtype=np.int64)
    if ids.size == 0:
        return batch
    if isinstance(batch, OrswotBatch):
        if applier is None:
            applier = OrswotDeltaApplier(universe)
        return applier.apply(batch, ids, blobs)
    sub_peer = type(batch).from_wire(blobs, universe)
    merged = _tree_gather(batch, ids).merge(sub_peer)
    return jax.tree_util.tree_map(
        lambda p, s: p.at[ids].set(s), batch, merged
    )
