"""The names of the named store (``orswot-named-1chip``), and a plain
decoder of its wire blobs written from the wire format, with nothing
taken from the program under test.

Actors are 8-byte binary vnode ids drawn from the seed: the actor of a
Riak data-type update is the coordinating vnode's id, and ``ring_size``
64 gives 64 vnodes.  Members are YCSB key names: ``CoreWorkload.
buildKeyName`` with hashed inserts, ``"user"`` followed by the decimal
of ``Utils.fnvhash64(n)`` over the generator's member number ``n``.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import Malformed, _key

#: YCSB ``Utils`` FNV-1a 64 constants
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
_M64 = (1 << 64) - 1
#: the host stream of the actor names
_ACTORS = 20


def fnv_hash64(n: int) -> int:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 low-first octets of
    a Java long, then ``Math.abs`` of the signed result (which leaves
    ``Long.MIN_VALUE`` negative)."""
    h = FNV_OFFSET_BASIS_64
    for _ in range(8):
        h ^= n & 0xFF
        n >>= 8
        h = (h * FNV_PRIME_64) & _M64
    signed = h - (1 << 64) if h >> 63 else h
    return signed if signed == -(1 << 63) else abs(signed)


def member_name(n: int) -> str:
    """``CoreWorkload.buildKeyName(n)`` with hashed inserts and the
    default zero padding of 1: 5 to 23 characters."""
    return f"user{fnv_hash64(n)}"


def member_names(ns: np.ndarray) -> list[str]:
    """``member_name`` of each member number in ``ns``, in bulk."""
    v = np.asarray(ns, dtype=np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        v >>= np.uint64(8)
        h *= np.uint64(FNV_PRIME_64)
    signed = h.view(np.int64)
    out = np.where(signed == np.iinfo(np.int64).min, signed, np.abs(signed))
    return [f"user{x}" for x in out.tolist()]


def actor_name(seed: int, a: int) -> bytes:
    """Actor ``a``'s 8-byte vnode id under ``seed``."""
    return np.random.default_rng([int(seed), _ACTORS, int(a)]).bytes(8)


# -- the wire blobs ----------------------------------------------------------

_T_INT, _T_STR, _T_BYTES, _T_TUPLE = 0x03, 0x05, 0x06, 0x08
_T_VCLOCK, _T_ORSWOT = 0x20, 0x26


class _Reader:
    def __init__(self, data: bytes):
        self.data, self.pos = data, 0

    def byte(self) -> int:
        if self.pos >= len(self.data):
            raise Malformed("truncated blob")
        self.pos += 1
        return self.data[self.pos - 1]

    def expect(self, tag: int) -> None:
        if self.byte() != tag:
            raise Malformed(f"expected tag {tag:#x}")

    def uvarint(self) -> int:
        out = shift = 0
        while True:
            b = self.byte()
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out
            shift += 7

    def int_(self) -> int:
        self.expect(_T_INT)
        z = self.uvarint()
        return (z >> 1) ^ -(z & 1)

    def name(self):
        """A str or bytes key as ``(value, its encoded bytes)``."""
        start = self.pos
        tag = self.byte()
        if tag not in (_T_STR, _T_BYTES):
            raise Malformed(f"key tag {tag:#x} is not a name")
        n = self.uvarint()
        raw = self.data[self.pos:self.pos + n]
        if len(raw) != n:
            raise Malformed("truncated name")
        self.pos += n
        try:
            value = raw.decode("utf-8") if tag == _T_STR else raw
        except UnicodeDecodeError as e:
            raise Malformed(f"name not UTF-8: {e}") from None
        return value, self.data[start:self.pos]

    def clock_body(self) -> dict:
        """A clock's pairs, in strictly ascending encoded-key order."""
        out, last = {}, None
        for _ in range(self.uvarint()):
            actor, enc = self.name()
            if last is not None and enc <= last:
                raise Malformed("clock pairs out of canonical order")
            last = enc
            out[actor] = self.int_()
        return out


def decode_named_blob(blob: bytes):
    """The state of one named ORSWOT blob, keyed by names: tag ``0x26``;
    the set clock (a count, then ``actor, counter`` pairs); the entries
    (a count, then ``member, 0x20 clock`` pairs); the deferred removes (a
    count, then a clock key as a tuple of ``(actor, counter)`` tuples, a
    member count and the members).  Actors and members are str (tag
    ``0x05``) or bytes (``0x06``), a length varint and the bytes;
    counters are ints (``0x03``, a zigzag varint).  Refuses a blob whose
    clock pairs or entries are not in ascending encoded-byte order."""
    r = _Reader(bytes(blob))
    r.expect(_T_ORSWOT)
    clock = r.clock_body()
    entries, last = {}, None
    for _ in range(r.uvarint()):
        member, enc = r.name()
        if last is not None and enc <= last:
            raise Malformed("entries out of canonical order")
        last = enc
        r.expect(_T_VCLOCK)
        entries[member] = r.clock_body()
    deferred = {}
    for _ in range(r.uvarint()):
        r.expect(_T_TUPLE)
        key = {}
        for _ in range(r.uvarint()):
            r.expect(_T_TUPLE)
            if r.uvarint() != 2:
                raise Malformed("clock-key pair is not a 2-tuple")
            actor, _ = r.name()
            key[actor] = r.int_()
        members = {r.name()[0] for _ in range(r.uvarint())}
        deferred.setdefault(tuple(sorted(key.items(), key=repr)),
                            set()).update(members)
    if r.pos != len(r.data):
        raise Malformed("trailing bytes")
    return clock, entries, deferred


def to_numbers(state, actor_of: dict, member_of: dict):
    """A named state with each name replaced by its number (the
    reference's integer form); an unknown name is ``Malformed``."""
    def a(x):
        if x not in actor_of:
            raise Malformed(f"unknown actor {x!r}")
        return actor_of[x]

    def m(x):
        if x not in member_of:
            raise Malformed(f"unknown member {x!r}")
        return member_of[x]

    clock, entries, deferred = state
    out_deferred = {}
    for key, members in deferred.items():
        out_deferred.setdefault(_key({a(k): c for k, c in key}), set()) \
            .update(m(x) for x in members)
    return ({a(k): c for k, c in clock.items()},
            {m(x): {a(k): c for k, c in d.items()}
             for x, d in entries.items()},
            out_deferred)
