"""Bulk wire-format ingest (`OrswotBatch.from_wire` + the native parallel
decoder `crdt_tpu/native/wire_ingest.cpp`).

Contract under test: ``from_wire(blobs, uni)`` is semantically identical
to ``from_scalar([from_binary(b) for b in blobs], uni)`` for every input
— the native fast path (identity universe, integer keys) never changes
what a blob means, only how fast it lands; anything outside the
integer-keyed grammar falls back to the Python decoder per blob.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from crdt_tpu import Orswot, from_binary, to_binary
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.config import CrdtConfig
from crdt_tpu.utils.interning import Universe


def _identity_uni(**kw):
    base = dict(num_actors=8, member_capacity=8, deferred_capacity=4)
    base.update(kw)
    return Universe.identity(CrdtConfig(**base))


def _random_states(rng, n, n_actors=8, deferred_frac=0.3):
    states = []
    for _ in range(n):
        s = Orswot()
        for j in range(int(rng.randint(1, 5))):
            member = int(rng.randint(0, 40))
            actor = int(rng.randint(0, n_actors))
            s.apply(s.add(member, s.value().derive_add_ctx(actor)))
        if rng.rand() < deferred_frac and s.entries:
            # causally-future remove: buffers in the deferred table
            member = next(iter(s.entries))
            ctx = s.contains(member).derive_rm_ctx()
            ctx.clock.witness(int(rng.randint(0, n_actors)),
                              int(rng.randint(100, 200)))
            s.apply(s.remove(member, ctx))
        states.append(s)
    return states


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_from_wire_matches_python_decode(counter_bits):
    rng = np.random.RandomState(41)
    uni = _identity_uni(counter_bits=counter_bits)
    states = _random_states(rng, 64)
    blobs = [to_binary(s) for s in states]

    # via_device=False: the host route preserves wire slot order, which
    # is what makes exact-plane comparison against from_scalar possible
    # (the device route canonicalizes slots to ascending id — covered by
    # test_from_wire_via_device_route_matches_host_route)
    got = OrswotBatch.from_wire(blobs, uni, via_device=False)
    want = OrswotBatch.from_scalar([from_binary(b) for b in blobs], uni)

    # set clock / member tables are deterministic (wire order == decode
    # order); deferred row ORDER may differ (python sets vs wire order),
    # so compare those semantically below
    np.testing.assert_array_equal(np.asarray(got.clock), np.asarray(want.clock))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.dots), np.asarray(want.dots))
    assert got.to_scalar(uni) == states  # full state incl. deferred


def test_from_wire_deferred_resolves_like_scalar():
    """Wire-ingested deferred rows must REPLAY identically: merge a state
    that covers the buffered clock and compare against the scalar path."""
    uni = _identity_uni()
    a = Orswot()
    a.apply(a.add(7, a.value().derive_add_ctx(1)))
    ctx = a.contains(7).derive_rm_ctx()
    ctx.clock.witness(2, 50)  # future dot: defers
    a.apply(a.remove(7, ctx))
    b = Orswot()
    for c in range(50):
        b.apply(b.add(9, b.value().derive_add_ctx(2)))

    batch_a = OrswotBatch.from_wire([to_binary(a)], uni)
    batch_b = OrswotBatch.from_wire([to_binary(b)], uni)
    merged = batch_a.merge(batch_b).merge(
        OrswotBatch.from_scalar([Orswot()], uni)
    )

    oracle = a.clone()
    oracle.merge(b)
    oracle.merge(Orswot())
    assert merged.to_scalar(uni)[0].value().val == oracle.value().val


def test_from_wire_fallback_non_int_members():
    """A string-keyed blob is outside the fast-path grammar; with an
    identity universe the Python fallback must raise exactly as
    from_scalar would (identity registries hold ints only)."""
    uni = _identity_uni()
    s = Orswot()
    s.apply(s.add("name", s.value().derive_add_ctx(0)))
    with pytest.raises(ValueError, match="identity registry"):
        OrswotBatch.from_wire([to_binary(s)], uni)

    # with a standard universe the same blobs take the Python path whole
    std = Universe(CrdtConfig(num_actors=8, member_capacity=8,
                              deferred_capacity=4))
    batch = OrswotBatch.from_wire([to_binary(s)], std)
    assert batch.to_scalar(std)[0] == s


def test_from_wire_mixed_fallback_rows():
    """Int-keyed and non-conforming blobs in ONE batch: fast rows parse
    natively, flagged rows patch through the Python decoder.  A u64
    counter >= 2^63 zigzags past the native varint's u64 range (status 1,
    deterministic) while the Python path handles it fine — so this
    actually drives the row-patching scatter, not just the fast path."""
    from crdt_tpu.scalar.vclock import VClock

    rng = np.random.RandomState(43)
    uni = _identity_uni(counter_bits=64)
    states = _random_states(rng, 12)
    big = Orswot()
    big.clock = VClock({3: 2**63 + 5})
    big.entries[17] = VClock({3: 2**63 + 5})
    states[3] = big
    states[9] = Orswot()  # empty state: trivially conformant
    blobs = [to_binary(s) for s in states]
    got = OrswotBatch.from_wire(blobs, uni)
    assert got.to_scalar(uni) == states
    # the patched row really carries the big counter
    assert int(np.asarray(got.clock)[3, 3]) == 2**63 + 5


def test_from_wire_counter_overflow_matches_python_path():
    """u32 build + a counter in [2^32, 2^64): the native parser must NOT
    silently wrap — it flags the blob and the Python fallback raises the
    same OverflowError the pure-Python path raises (causal counters must
    never regress silently)."""
    from crdt_tpu.scalar.vclock import VClock

    uni = _identity_uni(counter_bits=32)
    s = Orswot()
    s.clock = VClock({1: 2**32 + 7})
    blob = to_binary(s)
    with pytest.raises(OverflowError):
        OrswotBatch.from_wire([blob], uni)
    with pytest.raises(OverflowError):
        OrswotBatch.from_scalar([from_binary(blob)], uni)


def test_from_wire_max_int32_member_both_paths():
    """Member id 2^31 - 1 is a valid int32 id on BOTH paths (the identity
    registry bound and the native decoder's check must agree)."""
    uni = _identity_uni()
    s = Orswot()
    s.apply(s.add((1 << 31) - 1, s.value().derive_add_ctx(0)))
    blob = to_binary(s)
    fast = OrswotBatch.from_wire([blob], uni)
    slow = OrswotBatch.from_scalar([from_binary(blob)], uni)
    np.testing.assert_array_equal(np.asarray(fast.ids), np.asarray(slow.ids))
    assert fast.to_scalar(uni) == [s]


def test_from_wire_overflow_raises():
    uni = _identity_uni(member_capacity=2)
    s = Orswot()
    for member in (1, 2, 3):
        s.apply(s.add(member, s.value().derive_add_ctx(0)))
    with pytest.raises(ValueError, match="member_capacity"):
        OrswotBatch.from_wire([to_binary(s)], uni)


def test_from_wire_actor_out_of_range_raises():
    uni = _identity_uni(num_actors=2)
    s = Orswot()
    s.apply(s.add(1, s.value().derive_add_ctx(5)))  # actor 5 >= 2
    with pytest.raises(ValueError, match="actor"):
        OrswotBatch.from_wire([to_binary(s)], uni)


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_to_wire_matches_python_encode(counter_bits):
    """Bulk egress parity: to_wire must be BYTE-identical to to_binary of
    the per-object scalars — including the codec's deterministic
    orderings (encoded-bytes pair sort, repr-sorted clock keys)."""
    rng = np.random.RandomState(53)
    uni = _identity_uni(counter_bits=counter_bits)
    states = _random_states(rng, 48)
    batch = OrswotBatch.from_scalar(states, uni)
    got = batch.to_wire(uni)
    want = [to_binary(s) for s in batch.to_scalar(uni)]
    assert got == want


def test_to_wire_ordering_edge_cases():
    """The three orderings diverge exactly where this state puts them:
    members {100, 8192} sort 8192-first under encoded-bytes order
    (varint [0x80,0x80,0x01] < [0xC8,0x01]) though 100 < 8192 numerically;
    deferred clock keys sort pairs by repr, so actors {2, 10} order
    10-first ("10" < "2")."""
    from crdt_tpu.scalar.vclock import VClock

    uni = _identity_uni(num_actors=16, member_capacity=8,
                        deferred_capacity=4)
    s = Orswot()
    for member in (100, 8192, 63, 64):
        s.apply(s.add(member, s.value().derive_add_ctx(2)))
    # deferred remove witnessed by a clock over actors {2, 10}
    ctx = s.contains(100).derive_rm_ctx()
    ctx.clock.witness(10, 500)
    ctx.clock.witness(2, 400)
    s.apply(s.remove(100, ctx))
    # second member buffered under the SAME clock (grouping leg)
    ctx2 = s.contains(8192).derive_rm_ctx()
    ctx2.clock = VClock({2: 400, 10: 500})
    s.apply(s.remove(8192, ctx2))

    batch = OrswotBatch.from_scalar([s], uni)
    got = batch.to_wire(uni)
    want = [to_binary(x) for x in batch.to_scalar(uni)]
    assert got == want
    # and the round trip re-ingests to the same state
    assert OrswotBatch.from_wire(got, uni).to_scalar(uni) == batch.to_scalar(uni)


def test_to_wire_u64_high_counter_falls_back():
    """u64 counters >= 2^63 exceed the native encoder's zigzag range; the
    Python path must take over with identical bytes."""
    from crdt_tpu.scalar.vclock import VClock

    uni = _identity_uni(counter_bits=64)
    s = Orswot()
    s.clock = VClock({1: 2**63 + 9})
    s.entries[5] = VClock({1: 2**63 + 9})
    batch = OrswotBatch.from_scalar([s], uni)
    got = batch.to_wire(uni)
    assert got == [to_binary(x) for x in batch.to_scalar(uni)]
    assert from_binary(got[0]).clock.dots[1] == 2**63 + 9


def test_from_wire_via_device_route_matches_host_route():
    """``via_device=True`` routes the parsed state through COO columns +
    the device-side expand (dense planes never cross the host link on a
    real accelerator); the result must be semantically identical to the
    host route — member slots canonicalize to ascending-id order."""
    rng = np.random.RandomState(61)
    uni = _identity_uni()
    states = _random_states(rng, 24)
    blobs = [to_binary(s) for s in states]
    host = OrswotBatch.from_wire(blobs, uni, via_device=False)
    dev = OrswotBatch.from_wire(blobs, uni, via_device=True)
    assert dev.to_scalar(uni) == host.to_scalar(uni) == states
    # and the wire bytes agree too (to_binary is canonical)
    assert dev.to_wire(uni) == host.to_wire(uni)


def test_wire_roundtrip_fuzz():
    """from_wire(to_wire(batch)) is the identity on scalar states across
    random deferred-bearing fleets, both widths."""
    rng = np.random.RandomState(59)
    for bits in (32, 64):
        uni = _identity_uni(counter_bits=bits)
        states = _random_states(rng, 40)
        batch = OrswotBatch.from_scalar(states, uni)
        blobs = batch.to_wire(uni)
        back = OrswotBatch.from_wire(blobs, uni)
        assert back.to_scalar(uni) == batch.to_scalar(uni)


@given(
    seed=st.integers(0, 999),
    pos=st.integers(0, 4096),
    byte=st.integers(0, 255),
    mode=st.sampled_from(["flip", "insert", "delete", "truncate"]),
)
def test_wire_parser_total_on_mutated_blobs(seed, pos, byte, mode):
    """The C parser consumes UNTRUSTED replication bytes: any mutation of
    a valid blob must either ingest to exactly what the documented
    contract produces — ``from_scalar([from_binary(blob)])``, i.e. the
    Python decode THROUGH the dense engine (which canonicalizes
    adversarial-only structures like duplicate-actor clock keys the same
    last-wins way) — or surface as the codec's contract exceptions.
    Never crash, never silently diverge from the Python pipeline."""
    rng = np.random.RandomState(seed)
    uni = _identity_uni()
    s = _random_states(rng, 1)[0]
    data = bytearray(to_binary(s))
    if mode == "insert":
        # pos == len(data) appends TRAILING garbage — the framing case
        # (parser must demand consumed == blob length, not stop early)
        pos %= len(data) + 1
        data.insert(pos, byte)
    else:
        pos %= max(1, len(data))
        if mode == "flip":
            data[pos] = byte
        elif mode == "delete":
            del data[pos]
        else:
            data = data[:pos]
    blob = bytes(data)

    try:
        want = OrswotBatch.from_scalar(
            [from_binary(blob)], uni
        ).to_scalar(uni)
    except Exception:
        want = None  # the python pipeline rejects it; from_wire must too
    try:
        got = OrswotBatch.from_wire([blob], uni, via_device=False)
    except (ValueError, OverflowError, TypeError):
        # BOTH directions must agree: from_wire's non-fast-path blobs go
        # through the python pipeline itself, and its hard errors
        # (capacity/actor range, malformed decoded types) are the same
        # checks from_scalar makes — so a clean rejection here implies
        # the python pipeline rejected the blob too
        assert want is None, (
            "from_wire rejected a blob the python pipeline accepts"
        )
        return
    # ingest succeeded: the python pipeline must agree on the state
    assert want is not None, (
        "from_wire accepted a blob the python pipeline rejects"
    )
    assert got.to_scalar(uni) == want


# -- MVReg / LWWReg wire legs -------------------------------------------------


def _random_mvregs(rng, n, n_actors=8):
    from crdt_tpu.scalar.mvreg import MVReg

    regs = []
    for _ in range(n):
        reg = MVReg()
        for actor in rng.choice(n_actors, size=int(rng.randint(1, 4)),
                                replace=False):
            ctx = reg.read().derive_add_ctx(int(actor))
            reg.apply(reg.set(int(rng.randint(0, 1000)), ctx))
        regs.append(reg)
    return regs


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_mvreg_wire_roundtrip_and_parity(counter_bits):
    """MVReg leg of the bulk wire path: ingest matches the Python
    pipeline, egress is byte-identical to to_binary, round trip is the
    identity on scalars."""
    from crdt_tpu.batch import MVRegBatch

    rng = np.random.RandomState(67)
    uni = _identity_uni(counter_bits=counter_bits)
    regs = _random_mvregs(rng, 40)
    blobs = [to_binary(r) for r in regs]

    got = MVRegBatch.from_wire(blobs, uni)
    want = MVRegBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.clocks), np.asarray(want.clocks))
    np.testing.assert_array_equal(np.asarray(got.vals), np.asarray(want.vals))

    out = got.to_wire(uni)
    assert out == [to_binary(r) for r in got.to_scalar(uni)]
    back = MVRegBatch.from_wire(out, uni)
    assert back.to_scalar(uni) == got.to_scalar(uni)


def test_mvreg_wire_fallbacks():
    from crdt_tpu.batch import MVRegBatch
    from crdt_tpu.scalar.mvreg import MVReg

    uni = _identity_uni(mv_capacity=2)
    # overflow: 3 concurrent values > mv_capacity 2 → same error as
    # from_scalar
    regs = []
    for actor in range(3):
        r = MVReg()
        r.apply(r.set(actor, r.read().derive_add_ctx(actor)))
        regs.append(r)
    merged = regs[0]
    merged.merge(regs[1])
    merged.merge(regs[2])
    with pytest.raises(ValueError, match="mv_capacity"):
        MVRegBatch.from_wire([to_binary(merged)], uni)

    # non-int payload: python fallback raises the identity-registry error
    s = MVReg()
    s.apply(s.set("text", s.read().derive_add_ctx(0)))
    with pytest.raises(ValueError, match="identity registry"):
        MVRegBatch.from_wire([to_binary(s)], uni)


def test_mvreg_wire_mixed_patch_path():
    """A u64 counter >= 2^63 is outside the native zigzag (status 1) but
    fine for the Python decoder — drives the row-patch splice alongside
    natively-parsed rows."""
    from crdt_tpu.batch import MVRegBatch
    from crdt_tpu.scalar.mvreg import MVReg
    from crdt_tpu.scalar.vclock import VClock

    rng = np.random.RandomState(73)
    uni = _identity_uni(counter_bits=64)
    regs = _random_mvregs(rng, 10)
    big = MVReg([(VClock({2: 2**63 + 3}), 42)])
    regs[4] = big
    blobs = [to_binary(r) for r in regs]
    got = MVRegBatch.from_wire(blobs, uni)
    want = MVRegBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.clocks), np.asarray(want.clocks))
    np.testing.assert_array_equal(np.asarray(got.vals), np.asarray(want.vals))
    assert int(np.asarray(got.clocks)[4, 0, 2]) == 2**63 + 3


def test_lww_wire_roundtrip_and_parity():
    """LWW leg: both directions byte/plane-faithful, incl. the mixed
    patch path (a marker >= 2^63 is outside the native zigzag range and
    routes through the Python decoder per blob)."""
    from crdt_tpu.batch import LWWRegBatch
    from crdt_tpu.scalar.lwwreg import LWWReg

    rng = np.random.RandomState(71)
    uni = _identity_uni()
    regs = [
        LWWReg(int(rng.randint(0, 1000)), int(rng.randint(1, 10**9)))
        for _ in range(50)
    ]
    regs[7] = LWWReg(5, 2**63 + 11)  # native flags it; python patches it
    blobs = [to_binary(r) for r in regs]

    got = LWWRegBatch.from_wire(blobs, uni)
    want = LWWRegBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.vals), np.asarray(want.vals))
    np.testing.assert_array_equal(
        np.asarray(got.markers), np.asarray(want.markers)
    )
    assert int(np.asarray(got.markers)[7]) == 2**63 + 11

    # egress: the big marker forces the whole-batch Python path; bytes
    # still identical.  Without it, the native path must agree too.
    assert got.to_wire(uni) == blobs
    small = LWWRegBatch.from_scalar(regs[:7], uni)
    assert small.to_wire(uni) == blobs[:7]


def test_gset_wire_roundtrip_and_parity():
    """GSet leg: bitmap ingest/egress, sorted-items byte parity, overflow
    and non-int fallbacks."""
    from crdt_tpu.batch import GSetBatch
    from crdt_tpu.scalar.gset import GSet

    rng = np.random.RandomState(79)
    uni = _identity_uni()
    U = 64
    sets = []
    for _ in range(30):
        s = GSet()
        for _ in range(int(rng.randint(0, 6))):
            s.insert(int(rng.randint(0, U)))
        sets.append(s)
    blobs = [to_binary(s) for s in sets]

    got = GSetBatch.from_wire(blobs, uni, U)
    want = GSetBatch.from_scalar([from_binary(b) for b in blobs], uni, U)
    np.testing.assert_array_equal(np.asarray(got.bits), np.asarray(want.bits))
    out = got.to_wire(uni)
    assert out == [to_binary(s) for s in got.to_scalar(uni)] == blobs

    # member beyond the bitmap: same error as from_scalar
    big = GSet({U + 5})
    with pytest.raises(ValueError, match="universe overflow"):
        GSetBatch.from_wire([to_binary(big)], uni, U)
    # non-int member: python fallback raises the identity-registry error
    s = GSet({"txt"})
    with pytest.raises(ValueError, match="identity registry"):
        GSetBatch.from_wire([to_binary(s)], uni, U)


def test_identity_universe_checkpoint_roundtrip():
    """Identity universes survive checkpoint save/load as identity (a
    value-list restore would rebuild a dict registry whose lookups fail
    for never-interned ids)."""
    from crdt_tpu.utils.checkpoint import load_bytes, save_bytes

    rng = np.random.RandomState(47)
    uni = _identity_uni()
    states = _random_states(rng, 8)
    batch = OrswotBatch.from_wire([to_binary(s) for s in states], uni)
    loaded, uni2 = load_bytes(save_bytes(batch, uni))
    assert uni2.is_identity
    assert loaded.to_scalar(uni2) == states


# ---------------------------------------------------------------------------
# clock-shaped legs: VClock / GCounter / PNCounter
# ---------------------------------------------------------------------------


def _random_vclock(rng, n_actors=8, hi=100):
    from crdt_tpu.scalar.vclock import VClock

    vc = VClock()
    for a in range(n_actors):
        if rng.rand() < 0.5:
            vc.dots[a] = int(rng.randint(1, hi))
    return vc


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_vclock_wire_roundtrip_and_parity(counter_bits):
    """Causality-kernel leg of the bulk wire path (tag 0x20)."""
    from crdt_tpu.batch.vclock_batch import VClockBatch

    rng = np.random.RandomState(91)
    uni = _identity_uni(counter_bits=counter_bits)
    clocks = [_random_vclock(rng) for _ in range(40)]
    blobs = [to_binary(c) for c in clocks]

    got = VClockBatch.from_wire(blobs, uni)
    want = VClockBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.clocks), np.asarray(want.clocks))

    assert got.to_wire(uni) == blobs  # byte-identical egress
    assert VClockBatch.from_wire(got.to_wire(uni), uni).to_scalar(uni) == clocks


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_gcounter_wire_roundtrip_and_parity(counter_bits):
    """GCounter leg (tag 0x22 — a GCounter IS a VClock, gcounter.rs:26-28)."""
    from crdt_tpu.batch.gcounter_batch import GCounterBatch
    from crdt_tpu.scalar.gcounter import GCounter

    rng = np.random.RandomState(92)
    uni = _identity_uni(counter_bits=counter_bits)
    states = [GCounter(_random_vclock(rng)) for _ in range(40)]
    blobs = [to_binary(s) for s in states]

    got = GCounterBatch.from_wire(blobs, uni)
    want = GCounterBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.clocks), np.asarray(want.clocks))

    assert got.to_wire(uni) == blobs
    # values survive the loop (the counter's actual API surface)
    assert [g.value() for g in GCounterBatch.from_wire(blobs, uni).to_scalar(uni)] == [
        s.value() for s in states
    ]


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_pncounter_wire_roundtrip_and_parity(counter_bits):
    """PNCounter leg (tag 0x23 — two clock bodies, P then N)."""
    from crdt_tpu.batch.pncounter_batch import PNCounterBatch
    from crdt_tpu.scalar.gcounter import GCounter
    from crdt_tpu.scalar.pncounter import PNCounter

    rng = np.random.RandomState(93)
    uni = _identity_uni(counter_bits=counter_bits)
    states = [
        PNCounter(GCounter(_random_vclock(rng)), GCounter(_random_vclock(rng)))
        for _ in range(40)
    ]
    blobs = [to_binary(s) for s in states]

    got = PNCounterBatch.from_wire(blobs, uni)
    want = PNCounterBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.planes), np.asarray(want.planes))

    assert got.to_wire(uni) == blobs
    assert [p.value() for p in PNCounterBatch.from_wire(blobs, uni).to_scalar(uni)] == [
        s.value() for s in states
    ]


def test_clockish_wire_empty_and_zero_rows():
    """Empty batches and all-zero clocks round-trip (0-pair bodies)."""
    from crdt_tpu.scalar.vclock import VClock
    from crdt_tpu.batch.vclock_batch import VClockBatch

    uni = _identity_uni()
    assert VClockBatch.from_wire([], uni).clocks.shape == (0, 8)
    assert VClockBatch.zeros(0, uni).to_wire(uni) == []

    blobs = [to_binary(VClock()), to_binary(VClock({3: 7}))]
    got = VClockBatch.from_wire(blobs, uni)
    assert got.to_wire(uni) == blobs


def test_clockish_wire_mixed_patch_path():
    """u64 counters >= 2^63 are outside the native zigzag (status 1) but
    fine for Python — drives the row-patch splice next to fast rows, and
    the egress guard routes the whole batch through the Python encoder."""
    from crdt_tpu.scalar.vclock import VClock
    from crdt_tpu.batch.vclock_batch import VClockBatch

    rng = np.random.RandomState(94)
    uni = _identity_uni(counter_bits=64)
    clocks = [_random_vclock(rng) for _ in range(10)]
    clocks[3] = VClock({1: 2**63 + 11})
    blobs = [to_binary(c) for c in clocks]
    got = VClockBatch.from_wire(blobs, uni)
    want = VClockBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.clocks), np.asarray(want.clocks))
    assert int(np.asarray(got.clocks)[3, 1]) == 2**63 + 11
    assert got.to_wire(uni) == blobs  # python-path egress, still byte-equal


def test_clockish_wire_actor_out_of_range_raises():
    from crdt_tpu.scalar.vclock import VClock
    from crdt_tpu.batch.vclock_batch import VClockBatch
    from crdt_tpu.batch.pncounter_batch import PNCounterBatch
    from crdt_tpu.scalar.gcounter import GCounter
    from crdt_tpu.scalar.pncounter import PNCounter

    uni = _identity_uni()
    with pytest.raises(ValueError, match="identity registry"):
        VClockBatch.from_wire([to_binary(VClock({100: 1}))], uni)
    bad = PNCounter(GCounter(VClock({0: 1})), GCounter(VClock({100: 1})))
    with pytest.raises(ValueError, match="identity registry"):
        PNCounterBatch.from_wire([to_binary(bad)], uni)


def test_clockish_wire_duplicate_actor_canonicalizes_last_wins():
    """Adversarial blob with a repeated actor key (to_binary never emits
    one): the C scatter and the Python dict decode both keep the LAST
    pair — the through-pipeline contract, like the ORSWOT leg's fuzz."""
    import io

    from crdt_tpu.batch.vclock_batch import VClockBatch

    def uv(v):
        out = bytearray()
        while True:
            b = v & 0x7F
            v >>= 7
            if v:
                out.append(b | 0x80)
            else:
                out.append(b)
                return bytes(out)

    def pair(actor, counter):
        return b"\x03" + uv(actor << 1) + b"\x03" + uv(counter << 1)

    blob = b"\x20" + uv(2) + pair(1, 5) + pair(1, 9)
    uni = _identity_uni()
    got = VClockBatch.from_wire([blob], uni)
    assert int(np.asarray(got.clocks)[0, 1]) == 9
    # the Python pipeline agrees (dict insertion: last wins)
    want = VClockBatch.from_scalar([from_binary(blob)], uni)
    np.testing.assert_array_equal(np.asarray(got.clocks), np.asarray(want.clocks))


def test_clockish_wire_non_identity_universe_falls_back():
    """Interning universes take the Python path end-to-end; results and
    bytes match the scalar pipeline exactly."""
    from crdt_tpu.scalar.vclock import VClock
    from crdt_tpu.batch.gcounter_batch import GCounterBatch
    from crdt_tpu.scalar.gcounter import GCounter

    cfg = CrdtConfig(num_actors=4)
    uni = Universe(cfg)
    states = [GCounter(VClock({"a": 3, "b": 1})), GCounter(VClock({"c": 9}))]
    blobs = [to_binary(s) for s in states]
    got = GCounterBatch.from_wire(blobs, uni)
    assert got.to_scalar(uni) == states
    assert got.to_wire(uni) == blobs


def test_pncounter_wire_mixed_patch_path():
    """PNCounter rides the shared planes_from_wire/planes_to_wire flow;
    drive its status-1 splice (u64 counter >= 2^63 in the N plane) and
    the egress guard through the public methods."""
    from crdt_tpu.batch.pncounter_batch import PNCounterBatch
    from crdt_tpu.scalar.gcounter import GCounter
    from crdt_tpu.scalar.pncounter import PNCounter
    from crdt_tpu.scalar.vclock import VClock

    rng = np.random.RandomState(95)
    uni = _identity_uni(counter_bits=64)
    states = [
        PNCounter(GCounter(_random_vclock(rng)), GCounter(_random_vclock(rng)))
        for _ in range(8)
    ]
    states[5] = PNCounter(GCounter(VClock({0: 4})),
                          GCounter(VClock({3: 2**63 + 7})))
    blobs = [to_binary(s) for s in states]
    got = PNCounterBatch.from_wire(blobs, uni)
    want = PNCounterBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.planes), np.asarray(want.planes))
    assert int(np.asarray(got.planes)[5, 1, 3]) == 2**63 + 7
    assert got.to_wire(uni) == blobs  # python-path egress, byte-equal


# ---------------------------------------------------------------------------
# Map<K, MVReg> leg
# ---------------------------------------------------------------------------


def _random_map_mvregs(rng, n, n_actors=8, deferred_frac=0.3):
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg

    maps = []
    for i in range(n):
        m = Map(MVReg)
        for _ in range(int(rng.randint(0, 4))):
            key = int(rng.randint(0, 30))
            actor = int(rng.randint(0, n_actors))
            ctx = m.get(key).derive_add_ctx(actor)
            val = int(rng.randint(0, 100))
            m.apply(m.update(key, ctx, lambda v, c, _v=val: v.set(_v, c)))
        if rng.rand() < deferred_frac and m.entries:
            key = next(iter(m.entries))
            ctx = m.get(key).derive_rm_ctx()
            ctx.clock.witness(int(rng.randint(0, n_actors)),
                              int(rng.randint(100, 200)))
            m.apply(m.rm(key, ctx))
        maps.append(m)
    return maps


def _map_uni(counter_bits=64):
    return Universe.identity(CrdtConfig(
        num_actors=8, key_capacity=4, deferred_capacity=4, mv_capacity=2,
        counter_bits=counter_bits,
    ))


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_map_mvreg_wire_roundtrip_and_parity(counter_bits):
    """Map<K, MVReg> leg: ingest matches the Python pipeline plane-for-
    plane (wire order == decode order), egress is byte-identical to
    to_binary, round trip is the identity on scalars incl. deferred."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import MVRegKernel

    rng = np.random.RandomState(101)
    uni = _map_uni(counter_bits)
    vk = MVRegKernel.from_config(uni.config)
    maps = _random_map_mvregs(rng, 30)
    blobs = [to_binary(m) for m in maps]

    got = MapBatch.from_wire(blobs, uni, vk)
    want = MapBatch.from_scalar([from_binary(b) for b in blobs], uni, vk)
    np.testing.assert_array_equal(np.asarray(got.clock), np.asarray(want.clock))
    np.testing.assert_array_equal(np.asarray(got.keys), np.asarray(want.keys))
    np.testing.assert_array_equal(
        np.asarray(got.entry_clocks), np.asarray(want.entry_clocks))
    np.testing.assert_array_equal(np.asarray(got.vals[0]), np.asarray(want.vals[0]))
    np.testing.assert_array_equal(np.asarray(got.vals[1]), np.asarray(want.vals[1]))
    assert got.to_scalar(uni) == maps  # full state incl. deferred

    out = got.to_wire(uni)
    assert out == blobs  # byte-identical egress
    assert MapBatch.from_wire(out, uni, vk).to_scalar(uni) == maps


def test_map_wire_non_mvreg_kernel_falls_back():
    """Map<K, Orswot> has no native codec — the Python path serves both
    directions with identical results (and bytes)."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import OrswotKernel
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.orswot import Orswot

    uni = _map_uni()
    vk = OrswotKernel.from_config(uni.config)
    m = Map(Orswot)
    ctx = m.get(3).derive_add_ctx(1)
    m.apply(m.update(3, ctx, lambda v, c: v.add(7, c)))
    blobs = [to_binary(m)]
    got = MapBatch.from_wire(blobs, uni, vk)
    assert got.to_scalar(uni) == [m]
    assert got.to_wire(uni) == blobs


def test_map_wire_overflow_and_actor_errors():
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import MVRegKernel
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg

    uni = _map_uni()
    vk = MVRegKernel.from_config(uni.config)
    # key overflow: 5 keys > key_capacity 4 — same error class as
    # from_scalar
    m = Map(MVReg)
    for key in range(5):
        ctx = m.get(key).derive_add_ctx(0)
        m.apply(m.update(key, ctx, lambda v, c: v.set(1, c)))
    with pytest.raises(ValueError, match="key_capacity"):
        MapBatch.from_wire([to_binary(m)], uni, vk)
    # actor out of the identity range
    m2 = Map(MVReg)
    ctx = m2.get(1).derive_add_ctx(100)
    m2.apply(m2.update(1, ctx, lambda v, c: v.set(1, c)))
    with pytest.raises(ValueError, match="identity registry"):
        MapBatch.from_wire([to_binary(m2)], uni, vk)


def test_map_wire_mixed_patch_path():
    """A u64 counter >= 2^63 is outside the native zigzag (status 1) but
    fine for the Python big-int decoder — drives the row-patch splice
    alongside natively-parsed maps, and the egress guard routes the
    whole batch through the Python encoder."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import MVRegKernel
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg

    rng = np.random.RandomState(103)
    uni = _map_uni(counter_bits=64)
    vk = MVRegKernel.from_config(uni.config)
    maps = _random_map_mvregs(rng, 8)
    big = Map(MVReg)
    ctx = big.get(2).derive_add_ctx(1)
    big.apply(big.update(2, ctx, lambda v, c: v.set(5, c)))
    big.clock.witness(3, 2**63 + 17)  # only the Python decoder lands this
    maps[4] = big
    blobs = [to_binary(m) for m in maps]
    got = MapBatch.from_wire(blobs, uni, vk)
    want = MapBatch.from_scalar([from_binary(b) for b in blobs], uni, vk)
    np.testing.assert_array_equal(np.asarray(got.clock), np.asarray(want.clock))
    np.testing.assert_array_equal(np.asarray(got.vals[0]), np.asarray(want.vals[0]))
    assert int(np.asarray(got.clock)[4, 3]) == 2**63 + 17
    assert got.to_wire(uni) == blobs  # python-path egress, byte-equal


def test_map_to_scalar_val_type_is_serializable():
    """to_scalar must hand back Maps whose val_type survives to_binary —
    the registered class (or MapOf for nesting), not the kernel's bound
    factory (which _encode_val_type rejects)."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import MapKernel, MVRegKernel
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg

    uni = _map_uni()
    vk = MVRegKernel.from_config(uni.config)
    m = Map(MVReg)
    ctx = m.get(1).derive_add_ctx(0)
    m.apply(m.update(1, ctx, lambda v, c: v.set(9, c)))
    got = MapBatch.from_scalar([m], uni, vk).to_scalar(uni)
    assert from_binary(to_binary(got[0])) == m  # round-trips
    # nested kernel maps to MapOf(MVReg)
    nested = MapKernel.from_config(uni.config, vk)
    t = nested.scalar_val_type()
    from crdt_tpu.utils.serde import MapOf
    assert isinstance(t, MapOf) and t.inner is MVReg


def test_map_wire_deferred_and_value_overflow_errors():
    """Status 3 (deferred rows > deferred_capacity) and status 5 (value
    antichain > mv_capacity) raise the same error class as from_scalar."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import MVRegKernel
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg

    uni = _map_uni()  # deferred_capacity=4, mv_capacity=2
    vk = MVRegKernel.from_config(uni.config)

    # 5 deferred rows > capacity 4
    m = Map(MVReg)
    for key in range(5):
        ctx = m.get(key).derive_rm_ctx()
        ctx.clock.witness(key % 8, 100 + key)  # future: buffers
        m.apply(m.rm(key, ctx))
    with pytest.raises(ValueError, match="deferred_capacity"):
        MapBatch.from_wire([to_binary(m)], uni, vk)

    # a 3-wide antichain > mv_capacity 2
    regs = []
    for actor in range(3):
        r = Map(MVReg)
        ctx = r.get(1).derive_add_ctx(actor)
        r.apply(r.update(1, ctx, lambda v, c, _a=actor: v.set(_a, c)))
        regs.append(r)
    merged = regs[0]
    merged.merge(regs[1])
    merged.merge(regs[2])
    with pytest.raises(ValueError, match="mv_capacity"):
        MapBatch.from_wire([to_binary(merged)], uni, vk)


def test_map_wire_duplicate_key_blob_falls_back():
    """An adversarial blob repeating an entry key (to_binary never emits
    one) must NOT fast-parse into two live slots — non-canonical key
    order falls back to the Python decoder, whose dict dedupes; the
    contract `from_wire == from_scalar(from_binary)` holds."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import MVRegKernel
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg

    uni = _map_uni()
    vk = MVRegKernel.from_config(uni.config)
    uv, iv = _uv_bytes, _iv_bytes  # module-level blob-forging helpers

    clock_body = uv(1) + iv(1) + iv(1)          # {actor 1: 1}
    mvreg = b"\x25" + uv(1) + clock_body + iv(3)  # one (clock, val=3) pair
    entry = iv(7) + clock_body + mvreg           # key 7
    forged = (b"\x27" + b"\x50" + uv(5) + b"MVReg"
              + clock_body + uv(2) + entry + entry + uv(0))
    got = MapBatch.from_wire([forged], uni, vk)
    want = MapBatch.from_scalar([from_binary(forged)], uni, vk)
    np.testing.assert_array_equal(np.asarray(got.keys), np.asarray(want.keys))
    assert (np.asarray(got.keys)[0] != -1).sum() == 1  # deduped, one slot


@given(
    seed=st.integers(0, 999),
    pos=st.integers(0, 4096),
    byte=st.integers(0, 255),
    mode=st.sampled_from(["flip", "insert", "delete", "truncate"]),
    leg=st.sampled_from(["vclock", "pncounter", "map", "map_orswot", "map_map"]),
)
def test_new_leg_parsers_total_on_mutated_blobs(seed, pos, byte, mode, leg):
    """Mutation-fuzz totality for the round-4 parsers (clockish /
    PNCounter / Map<K, MVReg>) — same contract as the ORSWOT fuzz: any
    mutation of a valid blob either ingests to exactly what the Python
    pipeline produces through the dense engine, or raises the codec's
    contract exceptions.  Never crash, never silently diverge."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.pncounter_batch import PNCounterBatch
    from crdt_tpu.batch.vclock_batch import VClockBatch
    from crdt_tpu.batch.val_kernels import MVRegKernel
    from crdt_tpu.scalar.gcounter import GCounter
    from crdt_tpu.scalar.pncounter import PNCounter

    rng = np.random.RandomState(seed)
    if leg == "map":
        uni = _map_uni()
        vk = MVRegKernel.from_config(uni.config)
        state = _random_map_mvregs(rng, 1)[0]
        ingest = lambda blob: MapBatch.from_wire([blob], uni, vk)
        pipeline = lambda blob: MapBatch.from_scalar(
            [from_binary(blob)], uni, vk)
    elif leg == "map_orswot":
        from crdt_tpu.batch.val_kernels import OrswotKernel

        uni = _map_uni()
        vk = OrswotKernel.from_config(uni.config)
        state = _random_map_orswots(rng, 1)[0]
        ingest = lambda blob: MapBatch.from_wire([blob], uni, vk)
        pipeline = lambda blob: MapBatch.from_scalar(
            [from_binary(blob)], uni, vk)
    elif leg == "map_map":
        uni = _map_uni()
        vk = _nested_kernel(uni)
        state = _random_nested_maps(rng, 1)[0]
        ingest = lambda blob: MapBatch.from_wire([blob], uni, vk)
        pipeline = lambda blob: MapBatch.from_scalar(
            [from_binary(blob)], uni, vk)
    elif leg == "pncounter":
        uni = _identity_uni()
        state = PNCounter(GCounter(_random_vclock(rng)),
                          GCounter(_random_vclock(rng)))
        ingest = lambda blob: PNCounterBatch.from_wire([blob], uni)
        pipeline = lambda blob: PNCounterBatch.from_scalar(
            [from_binary(blob)], uni)
    else:
        uni = _identity_uni()
        state = _random_vclock(rng)
        ingest = lambda blob: VClockBatch.from_wire([blob], uni)
        pipeline = lambda blob: VClockBatch.from_scalar(
            [from_binary(blob)], uni)

    data = bytearray(to_binary(state))
    if mode == "insert":
        pos %= len(data) + 1
        data.insert(pos, byte)
    else:
        pos %= max(1, len(data))
        if mode == "flip":
            data[pos] = byte
        elif mode == "delete":
            del data[pos]
        else:
            data = data[:pos]
    blob = bytes(data)

    try:
        want = pipeline(blob).to_scalar(uni)
    except Exception:
        want = None
    try:
        got = ingest(blob)
    except (ValueError, OverflowError, TypeError, AttributeError):
        # the python pipeline must reject it too (from_wire's fallback IS
        # the python pipeline, and its hard errors are the same checks)
        assert want is None, (
            f"{leg} from_wire rejected a blob the python pipeline accepts"
        )
        return
    assert want is not None, (
        f"{leg} from_wire accepted a blob the python pipeline rejects"
    )
    assert got.to_scalar(uni) == want


def _random_map_orswots(rng, n, n_actors=8):
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.orswot import Orswot

    maps = []
    for i in range(n):
        m = Map(Orswot)
        for _ in range(int(rng.randint(0, 4))):
            key = int(rng.randint(0, 30))
            actor = int(rng.randint(0, n_actors))
            ctx = m.get(key).derive_add_ctx(actor)
            member = int(rng.randint(0, 40))
            m.apply(m.update(key, ctx, lambda v, c, _m=member: v.add(_m, c)))
        if rng.rand() < 0.3 and m.entries:
            key = next(iter(m.entries))
            ctx = m.get(key).derive_rm_ctx()
            ctx.clock.witness(int(rng.randint(0, n_actors)),
                              int(rng.randint(100, 200)))
            m.apply(m.rm(key, ctx))
        maps.append(m)
    return maps


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_map_orswot_wire_roundtrip_and_parity(counter_bits):
    """Map<K, Orswot> leg — the reset-remove-over-sets composition."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import OrswotKernel

    rng = np.random.RandomState(107)
    uni = _map_uni(counter_bits)
    vk = OrswotKernel.from_config(uni.config)
    maps = _random_map_orswots(rng, 30)
    blobs = [to_binary(m) for m in maps]

    got = MapBatch.from_wire(blobs, uni, vk)
    want = MapBatch.from_scalar([from_binary(b) for b in blobs], uni, vk)
    np.testing.assert_array_equal(np.asarray(got.clock), np.asarray(want.clock))
    np.testing.assert_array_equal(np.asarray(got.keys), np.asarray(want.keys))
    np.testing.assert_array_equal(
        np.asarray(got.entry_clocks), np.asarray(want.entry_clocks))
    # value member tables are wire-order deterministic
    np.testing.assert_array_equal(np.asarray(got.vals[1]), np.asarray(want.vals[1]))
    np.testing.assert_array_equal(np.asarray(got.vals[2]), np.asarray(want.vals[2]))
    assert got.to_scalar(uni) == maps  # full state incl. nested deferred

    out = got.to_wire(uni)
    assert out == blobs
    assert MapBatch.from_wire(out, uni, vk).to_scalar(uni) == maps


def test_map_orswot_wire_value_overflow_raises():
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.batch.val_kernels import OrswotKernel
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.orswot import Orswot

    uni = Universe.identity(CrdtConfig(
        num_actors=8, key_capacity=4, deferred_capacity=4, member_capacity=2))
    vk = OrswotKernel.from_config(uni.config)
    m = Map(Orswot)
    for member in (1, 2, 3):  # 3 members > value member_capacity 2
        ctx = m.get(0).derive_add_ctx(0)
        m.apply(m.update(0, ctx, lambda v, c, _m=member: v.add(_m, c)))
    with pytest.raises(ValueError, match="member_capacity"):
        MapBatch.from_wire([to_binary(m)], uni, vk)


def _uv_bytes(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _iv_bytes(v):  # 0x03 + zigzag varint (non-negative)
    return b"\x03" + _uv_bytes(v << 1)


def _orswot_blob_with_deferred(groups):
    """Hand-built ORSWOT blob: set clock {a0: 5}, one member 3 with the
    same entry clock, then a deferred section given as a list of
    ``(clock_pairs, members)`` groups IN THE GIVEN ORDER (so tests can
    craft non-canonical layouts to_binary would never emit)."""
    clock_body = _uv_bytes(1) + _iv_bytes(0) + _iv_bytes(5)  # {actor 0: 5}
    entry = _iv_bytes(3) + b"\x20" + clock_body
    out = b"\x26" + clock_body + _uv_bytes(1) + entry
    out += _uv_bytes(len(groups))
    for pairs, members in groups:
        out += b"\x08" + _uv_bytes(len(pairs))
        for actor, counter in pairs:
            out += b"\x08" + _uv_bytes(2) + _iv_bytes(actor) + _iv_bytes(counter)
        out += _uv_bytes(len(members))
        for m in members:
            out += _iv_bytes(m)
    return out


@pytest.mark.parametrize(
    "groups",
    [
        # duplicate clock-key groups (to_binary merges them into one)
        [([(0, 9)], [3]), ([(0, 9)], [4])],
        # members out of encoded-bytes order within a group
        [([(0, 9)], [4, 3])],
        # duplicate member within a group (set() would dedupe)
        [([(0, 9)], [3, 3])],
        # groups out of encoded clock-key-bytes order
        [([(1, 9)], [3]), ([(0, 9)], [4])],
    ],
    ids=["dup-group", "member-order", "dup-member", "group-order"],
)
def test_from_wire_non_canonical_deferred_falls_back(groups):
    """Adversarial deferred sections to_binary never emits (duplicate
    groups/members, unordered groups/members) must not fast-parse into
    extra dense rows: the parser's canonical-order checks route them to
    the Python decoder, which dedupes via dict/set — the documented
    ``from_wire == from_scalar(from_binary)`` contract."""
    uni = _identity_uni()
    blob = _orswot_blob_with_deferred(groups)
    got = OrswotBatch.from_wire([blob], uni)
    want = OrswotBatch.from_scalar([from_binary(blob)], uni)
    for name in ("clock", "ids", "dots", "d_ids", "d_clocks"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(want, name)),
            err_msg=name,
        )


def test_from_wire_canonical_deferred_still_fast_parses():
    """The canonical layout (ascending groups, ascending members) must
    keep fast-parsing — guard the guard against over-rejection."""
    uni = _identity_uni()
    blob = _orswot_blob_with_deferred(
        [([(0, 9)], [3, 4]), ([(1, 9)], [5])]
    )
    got = OrswotBatch.from_wire([blob], uni)
    want = OrswotBatch.from_scalar([from_binary(blob)], uni)
    for name in ("clock", "ids", "dots", "d_ids", "d_clocks"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(want, name)),
            err_msg=name,
        )
    assert (np.asarray(got.d_ids)[0] != -1).sum() == 3


def _random_nested_maps(rng, n, n_actors=8, deferred_frac=0.3):
    """Random ``Map<int, Map<int, MVReg>>`` states — the reference's
    canonical nesting (`/root/reference/test/map.rs:8`) — with deferred
    removes planted at BOTH map levels."""
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg
    from crdt_tpu.utils.serde import MapOf

    maps = []
    for i in range(n):
        m = Map(MapOf(MVReg))
        for _ in range(int(rng.randint(0, 4))):
            key = int(rng.randint(0, 30))
            ikey = int(rng.randint(0, 30))
            actor = int(rng.randint(0, n_actors))
            val = int(rng.randint(0, 100))
            ctx = m.get(key).derive_add_ctx(actor)
            m.apply(m.update(
                key, ctx,
                lambda v, c, _ik=ikey, _v=val: v.update(
                    _ik, c, lambda reg, c2: reg.set(_v, c2)
                ),
            ))
        if rng.rand() < deferred_frac and m.entries:
            # outer-level causally-future remove
            key = next(iter(m.entries))
            ctx = m.get(key).derive_rm_ctx()
            ctx.clock.witness(int(rng.randint(0, n_actors)),
                              int(rng.randint(100, 200)))
            m.apply(m.rm(key, ctx))
        if rng.rand() < deferred_frac and m.entries:
            # inner-level causally-future remove inside one value map
            key = next(iter(m.entries))
            inner = m.entries[key].val
            if inner.entries:
                ikey = next(iter(inner.entries))
                ctx = m.get(key).derive_add_ctx(int(rng.randint(0, n_actors)))
                ictx = inner.get(ikey).derive_rm_ctx()
                ictx.clock.witness(int(rng.randint(0, n_actors)),
                                   int(rng.randint(100, 200)))
                from crdt_tpu.scalar.map import Rm as MapRm, Up as MapUp
                m.apply(MapUp(dot=ctx.dot, key=key,
                              op=MapRm(clock=ictx.clock, key=ikey)))
        maps.append(m)
    return maps


def _nested_kernel(uni):
    from crdt_tpu.batch.val_kernels import MapKernel, MVRegKernel

    return MapKernel.from_config(uni.config, MVRegKernel.from_config(uni.config))


@pytest.mark.parametrize("counter_bits", [32, 64])
def test_map_map_mvreg_wire_roundtrip_and_parity(counter_bits):
    """Nested Map<K, Map<K2, MVReg>> leg: ingest matches the Python
    pipeline plane-for-plane, egress is byte-identical to to_binary,
    round trip is the identity on scalars incl. deferred at both
    levels."""
    from crdt_tpu.batch.map_batch import MapBatch

    rng = np.random.RandomState(211)
    uni = _map_uni(counter_bits)
    vk = _nested_kernel(uni)
    maps = _random_nested_maps(rng, 30)
    blobs = [to_binary(m) for m in maps]

    got = MapBatch.from_wire(blobs, uni, vk)
    want = MapBatch.from_scalar([from_binary(b) for b in blobs], uni, vk)
    import jax

    for g, w in zip(
        jax.tree_util.tree_leaves(got.state), jax.tree_util.tree_leaves(want.state)
    ):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert got.to_scalar(uni) == maps  # full state incl. deferred

    out = got.to_wire(uni)
    assert out == blobs  # byte-identical egress
    assert MapBatch.from_wire(out, uni, vk).to_scalar(uni) == maps


def test_map_map_mvreg_wire_inner_overflow_raises():
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg
    from crdt_tpu.utils.serde import MapOf

    uni = _map_uni()
    vk = _nested_kernel(uni)
    m = Map(MapOf(MVReg))
    # 5 inner keys under one outer key > key_capacity 4
    for ikey in range(5):
        ctx = m.get(1).derive_add_ctx(0)
        m.apply(m.update(
            1, ctx,
            lambda v, c, _ik=ikey: v.update(_ik, c, lambda r, c2: r.set(7, c2)),
        ))
    with pytest.raises(ValueError, match="inner map"):
        MapBatch.from_wire([to_binary(m)], uni, vk)


def test_map_map_mvreg_wire_mixed_patch_path():
    """Blobs outside the native varint range (a u64 counter >= 2^63
    zigzags past the parser's u64) splice through the per-blob Python
    fallback while fast rows parse natively."""
    from crdt_tpu.batch.map_batch import MapBatch
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg
    from crdt_tpu.scalar.vclock import VClock
    from crdt_tpu.utils.serde import MapOf

    rng = np.random.RandomState(212)
    uni = _map_uni(64)
    vk = _nested_kernel(uni)
    maps = _random_nested_maps(rng, 6)
    big = Map(MapOf(MVReg))
    big.clock = VClock({3: 2**63 + 5})
    maps = maps[:3] + [big] + maps[3:]
    blobs = [to_binary(m) for m in maps]
    got = MapBatch.from_wire(blobs, uni, vk)
    assert got.to_scalar(uni) == maps
    assert int(np.asarray(got.clock)[3, 3]) == 2**63 + 5
