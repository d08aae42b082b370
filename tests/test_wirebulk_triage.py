"""Wire-path triage plumbing: the shared ``wirebulk`` flows (per-blob
patch splice, hard-status raise, u64 zigzag egress guard), the
native-vs-fallback counters they feed, and ``native_fraction``.
"""

import numpy as np
import pytest

from crdt_tpu import from_binary, to_binary
from crdt_tpu.batch import GCounterBatch, PNCounterBatch, VClockBatch
from crdt_tpu.batch.wirebulk import (
    counters_overflow_zigzag,
    probe_engine,
    record_wire,
)
from crdt_tpu.config import CrdtConfig
from crdt_tpu.scalar.gcounter import GCounter
from crdt_tpu.scalar.vclock import VClock
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe


def _identity_uni(**kw):
    base = dict(num_actors=8, member_capacity=8, deferred_capacity=4)
    base.update(kw)
    return Universe.identity(CrdtConfig(**base))


_HAVE_ENGINE = probe_engine(
    _identity_uni(counter_bits=32), "clockish_ingest_wire", np.uint32
) is not None


# ---- planes_from_wire triage ------------------------------------------------


@pytest.mark.skipif(not _HAVE_ENGINE, reason="native engine unavailable")
def test_planes_from_wire_patch_splice_status1():
    """A u64 counter >= 2^63 zigzags past the native varint (status 1)
    but decodes fine in Python — its row must arrive via the per-blob
    patch splice, bit-equal to the full Python decode, with the mixed
    native/fallback counts recorded."""
    uni = _identity_uni(counter_bits=64)
    clocks = []
    for i in range(8):
        c = VClock()
        c.witness(i % 4, i + 1)
        clocks.append(c)
    big = VClock()
    big.witness(2, 1 << 63)
    clocks[5] = big
    blobs = [to_binary(c) for c in clocks]

    before = tracing.counters()
    got = VClockBatch.from_wire(blobs, uni)
    deltas = tracing.counters_since(before)
    want = VClockBatch.from_scalar([from_binary(b) for b in blobs], uni)
    np.testing.assert_array_equal(np.asarray(got.clocks),
                                  np.asarray(want.clocks))
    assert int(np.asarray(got.clocks)[5, 2]) == 1 << 63
    assert deltas["wire.vclock.from_wire.native"] == 7
    assert deltas["wire.vclock.from_wire.fallback"] == 1
    assert deltas["wire.vclock.from_wire.fallback_reason.grammar"] == 1
    assert tracing.native_fraction(
        deltas, "wire.vclock.from_wire"
    ) == pytest.approx(7 / 8)


@pytest.mark.skipif(not _HAVE_ENGINE, reason="native engine unavailable")
def test_planes_from_wire_hard_status_raises():
    """An actor at/past num_actors is a hard status (4): the identity
    registry cannot represent it, so the batch ingest must raise with
    the caller's blob index — not fall back, not truncate."""
    uni = _identity_uni(num_actors=4, counter_bits=32)
    good = GCounter()
    good.apply(good.inc(1))
    bad = GCounter()
    bad.apply(bad.inc(7))  # actor 7 >= num_actors 4
    blobs = [to_binary(good), to_binary(bad)]
    with pytest.raises(ValueError, match="object 1.*identity registry"):
        GCounterBatch.from_wire(blobs, uni)


# ---- counters_overflow_zigzag ----------------------------------------------


def test_counters_overflow_zigzag_u64():
    below = np.full((2, 3), (1 << 63) - 1, dtype=np.uint64)
    at = below.copy()
    at[1, 2] = 1 << 63
    assert not counters_overflow_zigzag((below,))
    assert counters_overflow_zigzag((below, at))


def test_counters_overflow_zigzag_skips_u32_and_empty():
    u32_max = np.full((4,), 0xFFFFFFFF, dtype=np.uint32)
    assert not counters_overflow_zigzag((u32_max,))
    assert not counters_overflow_zigzag((np.zeros((0,), dtype=np.uint64),))


@pytest.mark.skipif(not _HAVE_ENGINE, reason="native engine unavailable")
def test_egress_zigzag_guard_takes_python_path_and_counts():
    """u64 counters >= 2^63 force the Python encoder (the C emitter's
    zigzag would overflow) — output must still be byte-identical to
    to_binary, and the fallback reason recorded."""
    uni = _identity_uni(counter_bits=64)
    c = VClock()
    c.witness(1, 1 << 63)
    batch = VClockBatch.from_scalar([c], uni)
    before = tracing.counters()
    blobs = batch.to_wire(uni)
    deltas = tracing.counters_since(before)
    assert blobs == [to_binary(c)]
    assert deltas["wire.vclock.to_wire.fallback"] == 1
    assert deltas["wire.vclock.to_wire.fallback_reason.overflow_zigzag"] == 1
    assert tracing.native_fraction(deltas, "wire.vclock.to_wire") == 0.0


@pytest.mark.skipif(not _HAVE_ENGINE, reason="native engine unavailable")
def test_pncounter_wire_counters_native():
    uni = _identity_uni(counter_bits=32)
    from crdt_tpu.scalar.pncounter import PNCounter

    s = PNCounter()
    s.apply(s.inc(2))
    before = tracing.counters()
    batch = PNCounterBatch.from_wire([to_binary(s)], uni)
    batch.to_wire(uni)
    deltas = tracing.counters_since(before)
    assert deltas["wire.pncounter.from_wire.native"] == 1
    assert deltas["wire.pncounter.to_wire.native"] == 1


# ---- tracing counter API ----------------------------------------------------


def test_tracing_counters_thread_safe_and_reset():
    t = tracing.Tracer(enabled=False)
    t.count("x", 3)
    t.count("x")
    t.count("zero", 0)  # dropped — absent from the snapshot
    assert t.counters() == {"x": 4}
    assert "x" in t.report()
    t.reset()
    assert t.counters() == {}


def test_native_fraction_none_when_no_traffic():
    assert tracing.native_fraction({}, "wire.orswot.from_wire") is None
    assert tracing.native_fraction(
        {"wire.orswot.from_wire.native": 10}, "wire.orswot.from_wire"
    ) == 1.0


def test_record_wire_shapes_counter_names():
    before = tracing.counters()
    record_wire("testleg", "from_wire", native=5, fallback=2, reason="grammar")
    deltas = tracing.counters_since(before)
    assert deltas == {
        "wire.testleg.from_wire.native": 5,
        "wire.testleg.from_wire.fallback": 2,
        "wire.testleg.from_wire.fallback_reason.grammar": 2,
    }
