"""The plain reference: ORSWOT semantics written out from the published
crate (rust-crdt ``orswot.rs`` and ``vclock.rs``), with nothing taken
from the program under test.

A state is ``(clock, entries, deferred)``: ``clock`` maps actor to
counter, ``entries`` maps member to its dot clock, and ``deferred`` maps
a remove's clock (a sorted tuple of ``(actor, counter)``) to the set of
members it removes.  Functions never mutate their inputs.

Besides the merge and the two fold orders the store documents, this
module decodes what the program hands its users (wire blobs, result
frames) and computes the per-object digest from its published
definition, all without the program's code.
"""

from __future__ import annotations

import struct
import zlib

import numpy as np

# -- clocks -------------------------------------------------------------------


def _le(a: dict, b: dict) -> bool:
    """``a <= b`` in the clock partial order."""
    return all(b.get(k, 0) >= v for k, v in a.items())


def _minus(a: dict, b: dict) -> dict:
    """The dots of ``a`` that ``b`` has not seen."""
    return {k: v for k, v in a.items() if v > b.get(k, 0)}


def _join(a: dict, b: dict) -> dict:
    out = dict(a)
    for k, v in b.items():
        if v > out.get(k, 0):
            out[k] = v
    return out


def _key(clock: dict) -> tuple:
    return tuple(sorted(clock.items()))


# -- the merge ---------------------------------------------------------------


def _remove(clock, entries, deferred, member, rm: dict) -> None:
    """``apply_rm``: buffer a remove the set clock has not reached, and
    take its dots off the member either way."""
    if not _le(rm, clock):
        deferred.setdefault(_key(rm), set()).add(member)
    if member in entries:
        left = _minus(entries.pop(member), rm)
        if left:
            entries[member] = left


def merge(x, y, replay: bool = True):
    """``x.merge(y)``: the ORSWOT dot algebra, then the deferred removes
    of both sides replayed against the joined clock.  ``replay=False``
    is the control: buffered removes are carried but never applied."""
    xc, xe, xd = x
    yc, ye, yd = y
    entries = {}
    for m, c in xe.items():
        if m not in ye:
            # absent on the other side: dropped if the other has seen
            # every dot (it removed the member), kept whole otherwise
            if not _le(c, yc):
                entries[m] = dict(c)
            continue
        o = ye[m]
        common = {k: v for k, v in c.items() if o.get(k) == v}
        mine = _minus({k: v for k, v in c.items() if k not in common}, yc)
        theirs = _minus({k: v for k, v in o.items() if k not in common}, xc)
        kept = _join(_join(common, mine), theirs)
        if kept:
            entries[m] = kept
    for m, c in ye.items():
        if m not in xe:
            novel = _minus(c, xc)
            if novel:
                entries[m] = novel
    deferred = {k: set(v) for k, v in xd.items()}
    for k, v in yd.items():
        deferred.setdefault(k, set()).update(v)
    clock = _join(xc, yc)
    if not replay:
        return clock, entries, deferred
    pending = deferred
    deferred = {}
    for k, members in pending.items():
        for m in members:
            _remove(clock, entries, deferred, m, dict(k))
    return clock, entries, deferred


def fold(states: list, shape: str, replay: bool = True):
    """Join ``states`` to fixpoint in a documented order, then merge the
    result with itself once (the plunger).  ``shape`` ``"tree"``:
    neighbours pairwise per level, an odd state carried to the next;
    ``"left"``: left to right."""
    if shape == "left":
        acc = states[0]
        for s in states[1:]:
            acc = merge(acc, s, replay)
    elif shape == "tree":
        level = list(states)
        while len(level) > 1:
            nxt = [merge(level[i], level[i + 1], replay)
                   for i in range(0, len(level) - 1, 2)]
            if len(level) % 2:
                nxt.append(level[-1])
            level = nxt
        acc = level[0]
    else:
        raise ValueError(f"unknown fold shape {shape!r}")
    return merge(acc, acc, replay)


def canonical(state):
    """A hashable form of a state: equal states, equal forms."""
    clock, entries, deferred = state
    return (frozenset(clock.items()),
            frozenset((m, frozenset(c.items())) for m, c in entries.items()),
            frozenset((k, m) for k, ms in deferred.items() for m in ms))


class Malformed(ValueError):
    """A dense row or blob that no state encodes (a member twice, a dot
    on an empty slot)."""


def row_state(clock, ids, dots, d_ids, d_clocks):
    """The state of one dense object row (``-1`` marks an empty slot)."""
    st_clock = {int(k): int(clock[k]) for k in np.flatnonzero(clock)}
    entries = {}
    for s in range(ids.shape[0]):
        m = int(ids[s])
        if m == -1:
            if np.any(dots[s]):
                raise Malformed(f"dots on empty slot {s}")
            continue
        if m in entries:
            raise Malformed(f"member {m} twice")
        c = {int(k): int(dots[s, k]) for k in np.flatnonzero(dots[s])}
        if not c:
            raise Malformed(f"member {m} with an empty clock")
        entries[m] = c
    deferred = {}
    for s in range(d_ids.shape[0]):
        m = int(d_ids[s])
        if m == -1:
            continue
        c = {int(k): int(d_clocks[s, k]) for k in np.flatnonzero(d_clocks[s])}
        deferred.setdefault(_key(c), set()).add(m)
    return st_clock, entries, deferred


def count_wrong(got: list, want: list) -> int:
    """How many states of ``got`` differ from ``want`` (a ``Malformed``
    entry counts as wrong)."""
    wrong = 0
    for g, w in zip(got, want, strict=True):
        if isinstance(g, Malformed) or canonical(g) != canonical(w):
            wrong += 1
    return wrong


def dense_states(planes, rows) -> list:
    """``row_state`` of each of ``rows``, a malformed row kept as its
    error."""
    out = []
    for i in rows:
        try:
            out.append(row_state(*(p[i] for p in planes)))
        except Malformed as e:
            out.append(e)
    return out


# -- reads -------------------------------------------------------------------


def read(state, member: int, width: int):
    """``value()`` (``member < 0``: the member count) or
    ``contains(member)`` (1 or 0) as ``(val, add_clock, rm_clock)``, the
    clocks as dense ``u64[width]`` rows: add is the set clock; rm is the
    set clock for ``value()`` and the member's dots for ``contains``."""
    clock, entries, _ = state
    add = np.zeros(width, np.uint64)
    for k, v in clock.items():
        add[k] = v
    if member < 0:
        return len(entries), add, add.copy()
    rm = np.zeros(width, np.uint64)
    for k, v in entries.get(member, {}).items():
        rm[k] = v
    return int(member in entries), add, rm


_RESULT_HEADER = struct.Struct("<BBIQ")
_RESULT_FIXED = struct.Struct("<IHH")


def decode_result_frame(frame: bytes) -> dict:
    """The columns of one serve result frame (little-endian header
    ``version u8 | type u8 | crc32 u32 | length u64``, then ``B u32 |
    W u16 | T u16``, then ``obj u64[B] | kind u8[B] | member i32[B] |
    status u8[B] | val u64[B] | add u64[B*W] | rm u64[B*W] |
    token u64[T]``).  Raises ``Malformed`` on a bad envelope."""
    frame = bytes(frame)
    _, _, crc, plen = _RESULT_HEADER.unpack_from(frame)
    payload = frame[_RESULT_HEADER.size:]
    if len(payload) != plen or zlib.crc32(payload) != crc:
        raise Malformed("result frame envelope")
    b, w, t = _RESULT_FIXED.unpack_from(payload)
    off = _RESULT_FIXED.size
    cols = {}
    for name, dt, count in (("obj", "<u8", b), ("kind", "<u1", b),
                            ("member", "<i4", b), ("status", "<u1", b),
                            ("val", "<u8", b), ("add", "<u8", b * w),
                            ("rm", "<u8", b * w), ("token", "<u8", t)):
        size = np.dtype(dt).itemsize * count
        cols[name] = np.frombuffer(payload[off:off + size], dt)
        off += size
    if off != len(payload):
        raise Malformed("result frame length")
    cols["add"] = cols["add"].reshape(b, w)
    cols["rm"] = cols["rm"].reshape(b, w)
    return cols


# -- wire blobs ----------------------------------------------------------------

_T_INT, _T_TUPLE, _T_VCLOCK, _T_ORSWOT = 0x03, 0x08, 0x20, 0x26


class _Reader:
    def __init__(self, data: bytes):
        self.data, self.pos = data, 0

    def byte(self) -> int:
        if self.pos >= len(self.data):
            raise Malformed("truncated blob")
        self.pos += 1
        return self.data[self.pos - 1]

    def uvarint(self) -> int:
        out = shift = 0
        while True:
            b = self.byte()
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out
            shift += 7

    def value(self):
        tag = self.byte()
        if tag == _T_INT:
            z = self.uvarint()
            return (z >> 1) ^ -(z & 1)
        if tag == _T_TUPLE:
            return tuple(self.value() for _ in range(self.uvarint()))
        if tag == _T_VCLOCK:
            return self.clock_body()
        raise Malformed(f"unexpected tag {tag:#x}")

    def clock_body(self) -> dict:
        out = {}
        for _ in range(self.uvarint()):
            k = self.value()
            out[k] = self.value()
        return out


def decode_orswot_blob(blob: bytes):
    """The state of one ORSWOT wire blob: tag ``0x26``, the set clock
    (a count, then ``actor, counter`` pairs), the entries (a count, then
    ``member, 0x20 clock`` pairs), the deferred removes (a count, then
    a clock key as a tuple of ``(actor, counter)`` tuples, a member
    count and the members).  Integers are tag ``0x03`` with a zigzag
    varint."""
    r = _Reader(bytes(blob))
    if r.byte() != _T_ORSWOT:
        raise Malformed("not an ORSWOT blob")
    clock = r.clock_body()
    entries = {}
    for _ in range(r.uvarint()):
        m = r.value()
        c = r.value()
        if not isinstance(c, dict) or m in entries:
            raise Malformed("bad entry")
        entries[m] = c
    deferred = {}
    for _ in range(r.uvarint()):
        key = r.value()
        members = {r.value() for _ in range(r.uvarint())}
        deferred.setdefault(_key(dict(key)), set()).update(members)
    if r.pos != len(r.data):
        raise Malformed("trailing bytes")
    return clock, entries, deferred


# -- the per-object digest -----------------------------------------------------

_M64 = (1 << 64) - 1
_T_CLOCK = 0x9E3779B97F4A7C15
_T_ENTRY = 0xC2B2AE3D27D4EB4F
_T_DOT = 0x165667B19E3779F9
_T_DREF = 0x27D4EB2F165667C5
_T_DCLK = 0x85EBCA77C2B2AE63
_K1 = 0xFF51AFD7ED558CCD
_K2 = 0xC4CEB9FE1A85EC53
_T_ASALT = 0x6C62272E07BB0142
_T_MSALT = 0x27220A95FE7D4D7C


def _mix(x: int) -> int:
    """The SplitMix64 finalizer."""
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def _lane(value: int, key: int, tag: int) -> int:
    return _mix(value ^ _mix((key + tag) & _M64))


def digest(state) -> int:
    """The published u64 digest of an integer-named ORSWOT state: the
    XOR of one lane per set-clock dot, per live member, per member dot,
    per deferred ``(member, clock)`` remove and per dot of its clock.
    Actor ``a`` is keyed ``mix(a + T_ASALT) * K1``, member ``m``
    ``mix(m + T_MSALT) * K2`` (mod 2**64)."""
    clock, entries, deferred = state

    def akey(a):
        return (_mix((a + _T_ASALT) & _M64) * _K1) & _M64

    def mkey(m):
        return (_mix((m + _T_MSALT) & _M64) * _K2) & _M64

    out = 0
    for a, c in clock.items():
        out ^= _lane(c, akey(a), _T_CLOCK)
    for m, dots in entries.items():
        mk = mkey(m)
        out ^= _mix((mk + _T_ENTRY) & _M64)
        for a, c in dots.items():
            out ^= _lane(c, (mk + akey(a)) & _M64, _T_DOT)
    for k, members in deferred.items():
        for m in members:
            dk = mkey(m)
            out ^= _mix((dk + _T_DREF) & _M64)
            for a, c in k:
                out ^= _lane(c, (dk + akey(a)) & _M64, _T_DCLK)
    return out
