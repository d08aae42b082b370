"""Serve frames: the versioned wire envelope for read batches.

Follows the envelope discipline of :mod:`crdt_tpu.sync.delta` exactly —
a 1-byte protocol version leads every frame so mixed-version peers fail
loudly, a CRC32 of the payload turns truncation/tampering into a clean
rejection, and every rejection leaves a counter
(``serve.frames.rejected.<reason>``) and a flight-recorder event before
the raise.  Frame faults speak :class:`~crdt_tpu.error.
SyncProtocolError` (the envelope lied) or :class:`~crdt_tpu.error.
WireFormatError` (the payload violated the read grammar) — never a bare
``ValueError`` (the wire error-contract lint enforces this).

Frame layout (all little-endian)::

    version(1) | type(1) | crc32(4) | payload_len(8) | payload

Read-request payload (columnar, B rows)::

    B(4) | W(2) | mode(1)
    | obj    u64[B] | kind u8[B] | member i32[B]
    | require u64[W]

Result-frame payload::

    B(4) | W(2) | T(2)
    | obj    u64[B] | kind u8[B] | member i32[B]
    | status u8 [B] | val  u64[B]
    | add_clock u64[B*W] | rm_clock u64[B*W]
    | token u64[T]

``W`` is the clock-row width (0 for clockless kinds); ``T`` the token
width.  Per-kind extras (ORSWOT member rows, MV slot values) never
ride the wire — they are local bridges back into the scalar API.
"""

from __future__ import annotations

import struct
import zlib

import numpy as np

from ..error import SyncProtocolError, WireFormatError
from ..utils import tracing
from .consistency import CODE_MODES, MODE_CODES
from .query import NO_MEMBER, READ_KINDS, STATUSES, ReadRequest, ResultFrame

#: bumped whenever the serve-frame grammar changes; mixed-version peers
#: must fail loudly at the first frame, never misparse.
SERVE_PROTOCOL_VERSION = 1

#: frame type bytes — disjoint from the sync (0x01-0x09), fleet (0x21)
#: and oplog (0x31) codecs so a frame routed to the wrong decoder
#: rejects on type, not CRC luck
FRAME_READ = 0x41
FRAME_RESULT = 0x42

_HEADER = struct.Struct("<BBIQ")
_REQ_FIXED = struct.Struct("<IHB")
_RES_FIXED = struct.Struct("<IHH")


def _reject(reason: str, message: str, hard: bool = False):
    """Reject a frame with flight-recorder evidence (the
    :func:`crdt_tpu.sync.delta._reject` discipline): counter + event,
    then the typed error — ``hard`` grammar violations speak
    :class:`WireFormatError`, envelope faults :class:`SyncProtocolError`."""
    from ..obs import events as obs_events

    tracing.count(f"serve.frames.rejected.{reason}")
    obs_events.record("serve.protocol_error", reason=reason,
                      error=message[:200])
    return (WireFormatError if hard else SyncProtocolError)(message)


def _take(payload: memoryview, off: int, nbytes: int, what: str):
    if off + nbytes > len(payload):
        raise _reject(
            "truncated_column",
            f"serve payload truncated inside {what}: needs {nbytes} "
            f"bytes at offset {off}, frame has {len(payload) - off}",
            hard=True,
        )
    return payload[off:off + nbytes], off + nbytes


def _envelope(ftype: int, payload: bytes) -> bytes:
    return _HEADER.pack(
        SERVE_PROTOCOL_VERSION, ftype, zlib.crc32(payload), len(payload),
    ) + payload


def _open(frame: bytes, want_type: int, what: str) -> memoryview:
    frame = bytes(frame)
    if len(frame) < _HEADER.size:
        raise _reject(
            "truncated",
            f"truncated {what} frame: {len(frame)} bytes < "
            f"{_HEADER.size}-byte header",
        )
    version, ftype, crc, plen = _HEADER.unpack_from(frame)
    if version != SERVE_PROTOCOL_VERSION:
        raise _reject(
            "version_mismatch",
            f"serve protocol version mismatch: peer sent v{version}, "
            f"this build speaks v{SERVE_PROTOCOL_VERSION}",
        )
    if ftype != want_type:
        raise _reject("unknown_type",
                      f"unexpected serve frame type {ftype:#04x} "
                      f"(wanted {want_type:#04x})")
    payload = memoryview(frame)[_HEADER.size:]
    if len(payload) != plen:
        raise _reject(
            "length_mismatch",
            f"serve frame length mismatch: header says {plen} payload "
            f"bytes, frame carries {len(payload)}",
        )
    if zlib.crc32(payload) != crc:
        raise _reject(
            "crc_mismatch",
            f"serve {what} frame CRC mismatch (tampered or corrupted "
            "in transit)",
        )
    return payload


def encode_read_request(req: ReadRequest) -> bytes:
    """One read-request frame (B may be 0 — a pure token refresh)."""
    b = len(req)
    require = np.zeros(0, np.uint64) if req.require is None \
        else np.asarray(req.require, np.uint64).reshape(-1)
    payload = b"".join([
        _REQ_FIXED.pack(b, require.size, MODE_CODES[req.mode]),
        np.ascontiguousarray(req.obj, dtype="<u8").tobytes(),
        np.ascontiguousarray(req.kind, dtype="<u1").tobytes(),
        np.ascontiguousarray(req.member, dtype="<i4").tobytes(),
        np.ascontiguousarray(require, dtype="<u8").tobytes(),
    ])
    frame = _envelope(FRAME_READ, payload)
    tracing.count("wire.serve.encode.ops", b)
    tracing.count("wire.serve.encode.bytes", len(frame))
    return frame


def decode_read_request(frame: bytes, *, num_objects: int | None = None
                        ) -> ReadRequest:
    """The validated :class:`ReadRequest` of a read frame.
    ``num_objects`` additionally bounds the object column against the
    serving fleet (an object outside the dense axis cannot be
    gathered)."""
    with tracing.span("serve.leg.decode"):
        payload = _open(frame, FRAME_READ, "read-request")
        head, off = _take(payload, 0, _REQ_FIXED.size, "the request header")
        b, w, mode_code = _REQ_FIXED.unpack(bytes(head))
        if mode_code not in CODE_MODES:
            raise _reject("bad_mode",
                          f"read frame carries unknown consistency mode "
                          f"code {mode_code}", hard=True)
        raw, off = _take(payload, off, b * 8, "the object column")
        obj = np.frombuffer(raw, dtype="<u8").astype(np.int64)
        raw, off = _take(payload, off, b, "the kind column")
        kind = np.frombuffer(raw, dtype="<u1")
        raw, off = _take(payload, off, b * 4, "the member column")
        member = np.frombuffer(raw, dtype="<i4").astype(np.int32)
        raw, off = _take(payload, off, w * 8, "the require clock")
        require = np.frombuffer(raw, dtype="<u8").astype(np.uint64)
        if off != len(payload):
            raise _reject(
                "trailing_bytes",
                f"read payload carries {len(payload) - off} trailing bytes",
                hard=True,
            )
        known = np.isin(kind, np.asarray(READ_KINDS, np.uint8))
        if b and not known.all():
            bad = int(kind[~known][0])
            raise _reject("bad_kind",
                          f"read frame carries unknown kind {bad}", hard=True)
        if b and int(member.min()) < NO_MEMBER:
            raise _reject("bad_member",
                          f"read frame member {int(member.min())} below the "
                          f"NO_MEMBER sentinel {NO_MEMBER}", hard=True)
        if b and num_objects is not None and int(obj.max()) >= num_objects:
            raise _reject(
                "object_range",
                f"read object {int(obj.max())} outside the serving fleet's "
                f"dense axis [0, {num_objects})", hard=True,
            )
        req = ReadRequest(obj=obj, kind=kind.copy(), member=member,
                          mode=CODE_MODES[mode_code],
                          require=require if w else None)
        tracing.count("serve.frames.decoded")
        tracing.count("wire.serve.decode.ops", b)
        tracing.count("wire.serve.decode.bytes", len(bytes(frame)))
        return req


def encode_result_frame(res: ResultFrame) -> bytes:
    """One result frame for a gathered batch."""
    with tracing.span("serve.leg.encode"):
        b = len(res)
        w = int(res.add_clock.shape[1]) if res.add_clock.ndim == 2 else 0
        token = np.asarray(res.token, np.uint64).reshape(-1)
        payload = b"".join([
            _RES_FIXED.pack(b, w, token.size),
            np.ascontiguousarray(res.obj, dtype="<u8").tobytes(),
            np.ascontiguousarray(res.kind, dtype="<u1").tobytes(),
            np.ascontiguousarray(res.member, dtype="<i4").tobytes(),
            np.ascontiguousarray(res.status, dtype="<u1").tobytes(),
            np.ascontiguousarray(res.val, dtype="<u8").tobytes(),
            np.ascontiguousarray(res.add_clock, dtype="<u8").tobytes(),
            np.ascontiguousarray(res.rm_clock, dtype="<u8").tobytes(),
            np.ascontiguousarray(token, dtype="<u8").tobytes(),
        ])
        frame = _envelope(FRAME_RESULT, payload)
        tracing.count("wire.serve.encode.ops", b)
        tracing.count("wire.serve.encode.bytes", len(frame))
        return frame


def decode_result_frame(frame: bytes) -> ResultFrame:
    """The validated :class:`ResultFrame` of a result frame — what a
    client derives its next ``AddCtx``/``RmCtx`` (and monotonic token)
    from."""
    payload = _open(frame, FRAME_RESULT, "result")
    head, off = _take(payload, 0, _RES_FIXED.size, "the result header")
    b, w, t = _RES_FIXED.unpack(bytes(head))
    raw, off = _take(payload, off, b * 8, "the object column")
    obj = np.frombuffer(raw, dtype="<u8").astype(np.int64)
    raw, off = _take(payload, off, b, "the kind column")
    kind = np.frombuffer(raw, dtype="<u1")
    raw, off = _take(payload, off, b * 4, "the member column")
    member = np.frombuffer(raw, dtype="<i4").astype(np.int32)
    raw, off = _take(payload, off, b, "the status column")
    status = np.frombuffer(raw, dtype="<u1")
    raw, off = _take(payload, off, b * 8, "the value column")
    val = np.frombuffer(raw, dtype="<u8").astype(np.uint64)
    raw, off = _take(payload, off, b * w * 8, "the add-clock rows")
    add = np.frombuffer(raw, dtype="<u8").astype(np.uint64).reshape(b, w)
    raw, off = _take(payload, off, b * w * 8, "the rm-clock rows")
    rm = np.frombuffer(raw, dtype="<u8").astype(np.uint64).reshape(b, w)
    raw, off = _take(payload, off, t * 8, "the token")
    token = np.frombuffer(raw, dtype="<u8").astype(np.uint64)
    if off != len(payload):
        raise _reject(
            "trailing_bytes",
            f"result payload carries {len(payload) - off} trailing bytes",
            hard=True,
        )
    if b and not np.isin(kind, np.asarray(READ_KINDS, np.uint8)).all():
        bad = int(kind[~np.isin(kind, np.asarray(READ_KINDS, np.uint8))][0])
        raise _reject("bad_kind",
                      f"result frame carries unknown kind {bad}", hard=True)
    if b and not np.isin(status, np.asarray(STATUSES, np.uint8)).all():
        bad = int(status[
            ~np.isin(status, np.asarray(STATUSES, np.uint8))][0])
        raise _reject("bad_status",
                      f"result frame carries unknown status {bad}",
                      hard=True)
    res = ResultFrame(obj=obj, kind=kind.copy(), member=member,
                      status=status.copy(), val=val,
                      add_clock=add, rm_clock=rm, token=token)
    tracing.count("serve.frames.decoded")
    tracing.count("wire.serve.decode.ops", b)
    tracing.count("wire.serve.decode.bytes", len(bytes(frame)))
    return res
