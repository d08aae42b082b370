"""Op frames: the versioned wire envelope for columnar op batches.

Follows the envelope discipline of :mod:`crdt_tpu.sync.delta` exactly —
a 1-byte protocol version leads every frame so mixed-version peers fail
loudly, a CRC32 of the payload turns truncation/tampering into a clean
rejection, and every rejection leaves a counter
(``oplog.frames.rejected.<reason>``) and a flight-recorder event before
the raise.  Frame faults speak :class:`~crdt_tpu.error.
SyncProtocolError` (the envelope lied) or :class:`~crdt_tpu.error.
WireFormatError` (the payload violated the op grammar) — never a bare
``ValueError`` (the wire error-contract lint enforces this).

Frame layout (all little-endian)::

    version(1) | type(1) | crc32(4) | payload_len(8) | payload

Payload layout (columnar, B ops)::

    B(4) | A(2)
    | kind    u8 [B]
    | obj     u64[B]
    | actor   u16[B]
    | counter u64[B]
    | member  i32[B]
    | R(4) | row u32[R] | ractor u16[R] | rcounter u64[R]

The tail triples are the SPARSE remove clocks: ``Op::Rm`` ships a full
witnessing clock (`orswot.rs:80-83`) while ``Op::Add`` ships only its
dot (`orswot.rs:66-79`) — so the wire cost of an add is the 23-byte
fixed row, a few dozen bytes against the wire codec's per-object state
cost (the whole point of the op path).
"""

from __future__ import annotations

import struct
import zlib

import numpy as np

from ..error import SyncProtocolError, WireFormatError
from ..utils import tracing
from .records import OP_KINDS, OP_RM, OpBatch

#: bumped whenever the op-frame grammar changes; mixed-version peers
#: must fail loudly at the first frame, never misparse.
OPLOG_PROTOCOL_VERSION = 1

#: frame type byte — disjoint from the sync (0x01-0x05) and fleet
#: (0x21) codecs so a frame routed to the wrong decoder rejects on
#: type, not CRC luck
FRAME_OPS = 0x31

_HEADER = struct.Struct("<BBIQ")
_FIXED = struct.Struct("<IH")


def _reject(reason: str, message: str, hard: bool = False):
    """Reject a frame with flight-recorder evidence (the
    :func:`crdt_tpu.sync.delta._reject` discipline): counter + event,
    then the typed error — ``hard`` grammar violations speak
    :class:`WireFormatError`, envelope faults :class:`SyncProtocolError`."""
    from ..obs import events as obs_events

    tracing.count(f"oplog.frames.rejected.{reason}")
    obs_events.record("oplog.protocol_error", reason=reason,
                      error=message[:200])
    return (WireFormatError if hard else SyncProtocolError)(message)


def encode_ops_frame(ops: OpBatch) -> bytes:
    """One op frame for ``ops`` (B may be 0 — the session piggyback
    ships empty frames to keep the lock-step exchange symmetric)."""
    b = len(ops)
    a = 0 if ops.rm_clocks is None else ops.rm_clocks.shape[1]
    parts = [
        _FIXED.pack(b, a),
        np.ascontiguousarray(ops.kind, dtype="<u1").tobytes(),
        np.ascontiguousarray(ops.obj, dtype="<u8").tobytes(),
        np.ascontiguousarray(ops.actor, dtype="<u2").tobytes(),
        np.ascontiguousarray(ops.counter, dtype="<u8").tobytes(),
        np.ascontiguousarray(ops.member, dtype="<i4").tobytes(),
    ]
    if ops.rm_clocks is not None:
        rows, actors = np.nonzero(ops.rm_clocks)
        vals = ops.rm_clocks[rows, actors]
    else:
        rows = actors = vals = np.zeros(0, np.int64)
    parts.append(struct.pack("<I", rows.shape[0]))
    parts.append(np.ascontiguousarray(rows, dtype="<u4").tobytes())
    parts.append(np.ascontiguousarray(actors, dtype="<u2").tobytes())
    parts.append(np.ascontiguousarray(vals, dtype="<u8").tobytes())
    payload = b"".join(parts)
    frame = _HEADER.pack(
        OPLOG_PROTOCOL_VERSION, FRAME_OPS, zlib.crc32(payload),
        len(payload),
    ) + payload
    tracing.count("wire.oplog.encode.ops", b)
    tracing.count("wire.oplog.encode.bytes", len(frame))
    return frame


def _take(payload: memoryview, off: int, nbytes: int, what: str):
    if off + nbytes > len(payload):
        raise _reject(
            "truncated_column",
            f"op payload truncated inside {what}: needs {nbytes} bytes "
            f"at offset {off}, frame has {len(payload) - off}",
            hard=True,
        )
    return payload[off:off + nbytes], off + nbytes


def decode_ops_frame(frame: bytes, *, num_actors: int | None = None
                     ) -> OpBatch:
    """The validated :class:`OpBatch` of an op frame.  Raises
    :class:`SyncProtocolError` on an envelope fault (version / type /
    length / CRC) and :class:`WireFormatError` on a payload grammar
    violation (unknown kind, clock triple out of range, truncated
    column) — the caller never sees a batch that could misfold.
    ``num_actors`` additionally bounds the actor column against the
    receiving universe (an actor outside the dense axis cannot be
    scattered)."""
    frame = bytes(frame)
    if len(frame) < _HEADER.size:
        raise _reject(
            "truncated",
            f"truncated op frame: {len(frame)} bytes < "
            f"{_HEADER.size}-byte header",
        )
    version, ftype, crc, plen = _HEADER.unpack_from(frame)
    if version != OPLOG_PROTOCOL_VERSION:
        raise _reject(
            "version_mismatch",
            f"op-frame protocol version mismatch: peer sent v{version}, "
            f"this build speaks v{OPLOG_PROTOCOL_VERSION}",
        )
    if ftype != FRAME_OPS:
        raise _reject("unknown_type",
                      f"unknown op frame type {ftype:#04x}")
    payload = memoryview(frame)[_HEADER.size:]
    if len(payload) != plen:
        raise _reject(
            "length_mismatch",
            f"op frame length mismatch: header says {plen} payload "
            f"bytes, frame carries {len(payload)}",
        )
    if zlib.crc32(payload) != crc:
        raise _reject(
            "crc_mismatch",
            "op frame CRC mismatch (tampered or corrupted in transit)",
        )

    head, off = _take(payload, 0, _FIXED.size, "the column header")
    b, a = _FIXED.unpack(bytes(head))
    cols = {}
    for name, dt, width in (
        ("kind", "<u1", 1), ("obj", "<u8", 8), ("actor", "<u2", 2),
        ("counter", "<u8", 8), ("member", "<i4", 4),
    ):
        raw, off = _take(payload, off, b * width, f"the {name} column")
        cols[name] = np.frombuffer(raw, dtype=dt)
    raw, off = _take(payload, off, 4, "the clock-triple count")
    (r,) = struct.unpack("<I", bytes(raw))
    raw, off = _take(payload, off, 4 * r, "the clock rows")
    rows = np.frombuffer(raw, dtype="<u4").astype(np.int64)
    raw, off = _take(payload, off, 2 * r, "the clock actors")
    ractors = np.frombuffer(raw, dtype="<u2").astype(np.int64)
    raw, off = _take(payload, off, 8 * r, "the clock counters")
    rvals = np.frombuffer(raw, dtype="<u8")
    if off != len(payload):
        raise _reject(
            "trailing_bytes",
            f"op payload carries {len(payload) - off} trailing bytes",
            hard=True,
        )

    kind = cols["kind"]
    if b and not np.isin(kind, np.asarray(OP_KINDS, np.uint8)).all():
        bad = int(kind[~np.isin(kind, np.asarray(OP_KINDS, np.uint8))][0])
        raise _reject("bad_kind", f"op frame carries unknown kind {bad}",
                      hard=True)
    actor = cols["actor"].astype(np.int32)
    if num_actors is not None and b and int(actor.max()) >= num_actors:
        raise _reject(
            "actor_range",
            f"op actor {int(actor.max())} outside the receiving "
            f"universe's dense axis [0, {num_actors})",
            hard=True,
        )
    rm_clocks = None
    if r:
        if a == 0:
            raise _reject(
                "clock_width",
                "op frame carries clock triples but a zero actor width",
                hard=True,
            )
        if int(rows.max()) >= b:
            raise _reject(
                "clock_row_range",
                f"clock triple names op row {int(rows.max())} of a "
                f"{b}-op frame", hard=True,
            )
        if not np.isin(rows, np.nonzero(kind == OP_RM)[0]).all():
            raise _reject(
                "clock_on_non_rm",
                "clock triple attached to a non-remove op (Op::Add "
                "ships only its dot, orswot.rs:66-79)", hard=True,
            )
        if int(ractors.max()) >= a or (
                num_actors is not None and int(ractors.max()) >= num_actors):
            raise _reject(
                "clock_actor_range",
                f"clock triple actor {int(ractors.max())} outside "
                f"width {a}", hard=True,
            )
        rm_clocks = np.zeros((b, a), np.uint64)
        np.maximum.at(rm_clocks, (rows, ractors), rvals)
    try:
        ops = OpBatch(
            kind=kind, obj=cols["obj"].astype(np.int64), actor=actor,
            counter=cols["counter"], member=cols["member"],
            rm_clocks=rm_clocks,
        )
    except ValueError as e:
        raise _reject("bad_columns", f"malformed op columns: {e}",
                      hard=True) from None
    tracing.count("oplog.frames.decoded")
    tracing.count("wire.oplog.decode.ops", b)
    tracing.count("wire.oplog.decode.bytes", len(frame))
    return ops


def frame_op_count(frame: bytes) -> int:
    """The op count of a frame WITHOUT a full decode — the ``B`` field
    of the column header (telemetry peek for a frame this process just
    encoded; received frames go through :func:`decode_ops_frame`)."""
    frame = bytes(frame)
    if len(frame) < _HEADER.size + _FIXED.size:
        return 0
    return _FIXED.unpack_from(frame, _HEADER.size)[0]


def frame_bytes_per_op(ops: OpBatch) -> float:
    """Wire bytes per op for ``ops`` (header amortized) — the number
    to compare against the per-object delta-sync cost."""
    if len(ops) == 0:
        return float(_HEADER.size + _FIXED.size + 4)
    return len(encode_ops_frame(ops)) / len(ops)
