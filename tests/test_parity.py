"""Scalar ↔ batch parity — the engine-split contract (SURVEY.md §7.0).

For every type: generate random scalar states from op sequences (the same
generators as the reference property tests), pack them into SoA batches,
merge on device (jit), unpack, and require **bit-identical** state vs the
scalar merge — clocks, entries, and deferred buffers, not just ``value()``.

These run on the CPU backend (conftest forces ``JAX_PLATFORMS=cpu``); the
same kernels run unchanged on TPU.
"""

import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from crdt_tpu import Dot, GCounter, LWWReg, MVReg, Orswot, PNCounter, RmCtx, VClock
from crdt_tpu.batch import (
    GCounterBatch,
    LWWRegBatch,
    MVRegBatch,
    OrswotBatch,
    PNCounterBatch,
    VClockBatch,
)
from crdt_tpu.config import CrdtConfig
from crdt_tpu.scalar.orswot import Add, Rm
from crdt_tpu.utils.interning import Universe


def small_universe(**kw):
    defaults = dict(num_actors=8, member_capacity=24, deferred_capacity=16, mv_capacity=12)
    defaults.update(kw)
    return Universe(CrdtConfig(**defaults))


# -- strategies -------------------------------------------------------------

actors = st.integers(0, 7)
counters = st.integers(0, 9)

vclocks = st.lists(st.tuples(actors, counters), max_size=10).map(VClock.from_iter)


@st.composite
def orswots(draw):
    """Random Orswot built from an op sequence (mirrors `test/orswot.rs:14-34`)."""
    s = Orswot()
    for actor, member, choice, counter in draw(
        st.lists(st.tuples(actors, st.integers(0, 9), st.integers(0, 3), st.integers(1, 9)), max_size=12)
    ):
        if choice % 2 == 0:
            s.apply(Add(dot=Dot(actor, counter), member=member))
        else:
            s.apply(Rm(clock=Dot(actor, counter).to_vclock(), member=member))
    return s


@st.composite
def mvregs(draw):
    r = MVReg()
    for val, actor in draw(st.lists(st.tuples(st.integers(0, 20), actors), max_size=6)):
        r.apply(r.set(val, r.read().derive_add_ctx(actor)))
    return r


# -- helpers ----------------------------------------------------------------


def scalar_merge(a, b):
    out = a.clone()
    out.merge(b)
    return out


# -- VClock / counters ------------------------------------------------------


@given(st.lists(st.tuples(vclocks, vclocks), min_size=4, max_size=4))
def test_vclock_merge_parity(pairs):
    uni = small_universe()
    lhs = [a for a, _ in pairs]
    rhs = [b for _, b in pairs]
    expected = [scalar_merge(a, b) for a, b in pairs]

    ba = VClockBatch.from_scalar(lhs, uni)
    bb = VClockBatch.from_scalar(rhs, uni)
    got = ba.merge(bb).to_scalar(uni)
    assert got == expected

    # partial-order predicates agree too
    import numpy as np

    leq = np.asarray(ba.leq(bb))
    conc = np.asarray(ba.concurrent(bb))
    for i, (a, b) in enumerate(pairs):
        assert bool(leq[i]) == (a <= b)
        assert bool(conc[i]) == a.concurrent(b)


@given(st.lists(st.tuples(vclocks, vclocks), min_size=4, max_size=4))
def test_gcounter_merge_parity(pairs):
    uni = small_universe()
    lhs = [GCounter(a.clone()) for a, _ in pairs]
    rhs = [GCounter(b.clone()) for _, b in pairs]
    expected = [scalar_merge(a, b) for a, b in zip(lhs, rhs)]

    got = (
        GCounterBatch.from_scalar(lhs, uni)
        .merge(GCounterBatch.from_scalar(rhs, uni))
        .to_scalar(uni)
    )
    assert [g.value() for g in got] == [e.value() for e in expected]
    assert [g.inner for g in got] == [e.inner for e in expected]


@given(st.lists(st.tuples(vclocks, vclocks, vclocks, vclocks), min_size=4, max_size=4))
def test_pncounter_merge_parity(quads):
    from crdt_tpu.scalar.gcounter import GCounter as G

    uni = small_universe()
    lhs = [PNCounter(G(p.clone()), G(n.clone())) for p, n, _, _ in quads]
    rhs = [PNCounter(G(p.clone()), G(n.clone())) for _, _, p, n in quads]
    expected = [scalar_merge(a, b) for a, b in zip(lhs, rhs)]

    batch = PNCounterBatch.from_scalar(lhs, uni).merge(PNCounterBatch.from_scalar(rhs, uni))
    got = batch.to_scalar(uni)
    assert [g.value() for g in got] == [e.value() for e in expected]
    import numpy as np

    assert list(np.asarray(batch.value())) == [e.value() for e in expected]


# -- LWWReg -----------------------------------------------------------------


@given(
    st.lists(
        st.tuples(st.integers(0, 30), st.integers(0, 9), st.integers(0, 30), st.integers(0, 9)),
        min_size=6,
        max_size=6,
    )
)
def test_lwwreg_merge_parity(prims):
    from crdt_tpu.error import ConflictingMarker

    uni = small_universe()
    lhs = [LWWReg(val=v1, marker=m1) for v1, m1, _, _ in prims]
    rhs = [LWWReg(val=v2, marker=m2) for _, _, v2, m2 in prims]

    expected, conflicts = [], []
    for a, b in zip(lhs, rhs):
        out = a.clone()
        try:
            out.merge(b)
            conflicts.append(False)
        except ConflictingMarker:
            conflicts.append(True)
        expected.append(out)

    ba = LWWRegBatch.from_scalar(lhs, uni)
    bb = LWWRegBatch.from_scalar(rhs, uni)
    merged, bitmap = ba.merge_with_conflicts(bb)
    import numpy as np

    assert list(np.asarray(bitmap)) == conflicts
    got = merged.to_scalar(uni)
    for g, e, c in zip(got, expected, conflicts):
        if not c:
            assert g == e

    if any(conflicts):
        try:
            ba.merge(bb)
            assert False, "expected ConflictingMarker"
        except ConflictingMarker:
            pass


# -- MVReg ------------------------------------------------------------------


@given(st.lists(st.tuples(mvregs(), mvregs()), min_size=3, max_size=3))
@settings(max_examples=50)
def test_mvreg_merge_parity(pairs):
    uni = small_universe()
    lhs = [a for a, _ in pairs]
    rhs = [b for _, b in pairs]
    expected = [scalar_merge(a, b) for a, b in pairs]

    got = (
        MVRegBatch.from_scalar(lhs, uni)
        .merge(MVRegBatch.from_scalar(rhs, uni))
        .to_scalar(uni)
    )
    for g, e in zip(got, expected):
        assert g == e  # MVReg __eq__ is set-equality over (clock, val)


@given(mvregs(), st.integers(0, 20), actors)
@settings(max_examples=50)
def test_mvreg_apply_put_parity(reg, val, actor):
    uni = small_universe()
    ctx = reg.read().derive_add_ctx(actor)
    op = reg.set(val, ctx)

    expected = reg.clone()
    expected.apply(op)

    batch = MVRegBatch.from_scalar([reg], uni)
    op_clock = VClockBatch.from_scalar([op.clock], uni).clocks
    op_val = jnp.asarray([uni.member_id(op.val)])
    got = batch.apply_put(op_clock, op_val).to_scalar(uni)[0]
    assert got == expected


# -- Orswot -----------------------------------------------------------------


@given(st.lists(st.tuples(orswots(), orswots()), min_size=3, max_size=3))
@settings(max_examples=60)
def test_orswot_merge_parity(pairs):
    uni = small_universe()
    lhs = [a for a, _ in pairs]
    rhs = [b for _, b in pairs]
    expected = [scalar_merge(a, b) for a, b in pairs]

    got = (
        OrswotBatch.from_scalar(lhs, uni)
        .merge(OrswotBatch.from_scalar(rhs, uni))
        .to_scalar(uni)
    )
    for g, e in zip(got, expected):
        assert g == e, f"\nbatch:  {g!r}\nscalar: {e!r}"


@given(orswots(), actors, st.integers(0, 9))
@settings(max_examples=60)
def test_orswot_apply_add_parity(s, actor, member):
    uni = small_universe()
    ctx = s.value().derive_add_ctx(actor)
    op = s.add(member, ctx)

    expected = s.clone()
    expected.apply(op)

    batch = OrswotBatch.from_scalar([s], uni)
    got = batch.apply_add(
        jnp.asarray([uni.actor_idx(op.dot.actor)]),
        jnp.asarray([op.dot.counter]),
        jnp.asarray([uni.member_id(op.member)]),
    ).to_scalar(uni)[0]
    assert got == expected, f"\nbatch:  {got!r}\nscalar: {expected!r}"


@given(orswots(), st.integers(0, 9), vclocks)
@settings(max_examples=60)
def test_orswot_apply_remove_parity(s, member, rm_clock)    :
    uni = small_universe()
    op = s.remove(member, RmCtx(clock=rm_clock))

    expected = s.clone()
    expected.apply(op)

    batch = OrswotBatch.from_scalar([s], uni)
    got = batch.apply_remove(
        VClockBatch.from_scalar([op.clock], uni).clocks,
        jnp.asarray([uni.member_id(op.member)]),
    ).to_scalar(uni)[0]
    assert got == expected, f"\nbatch:  {got!r}\nscalar: {expected!r}"


def test_orswot_regressions_on_batch():
    """The riak_dt regression scenarios, replayed through the batch engine:
    pack → merge → unpack at each merge point (`test/orswot.rs:193-230`)."""
    uni = small_universe()

    def bmerge(a, b):
        return (
            OrswotBatch.from_scalar([a], uni)
            .merge(OrswotBatch.from_scalar([b], uni))
            .to_scalar(uni)[0]
        )

    # test_no_dots_left
    a, b = Orswot(), Orswot()
    a.apply(a.add(0, a.value().derive_add_ctx(1)))
    b.apply(b.add(0, b.value().derive_add_ctx(2)))
    c = a.clone()
    a.apply(a.remove(0, a.contains(0).derive_rm_ctx()))
    a = bmerge(a, b)
    assert a.value().val == {0}
    b.apply(b.remove(0, b.contains(0).derive_rm_ctx()))
    b = bmerge(b, c)
    assert b.value().val == {0}
    b = bmerge(b, a)
    b = bmerge(b, c)
    assert b.value().val == set()


# -- GSet -------------------------------------------------------------------


@given(
    st.lists(st.sets(st.integers(0, 15)), min_size=4, max_size=4),
    st.lists(st.sets(st.integers(0, 15)), min_size=4, max_size=4),
)
def test_gset_merge_parity(xs, ys):
    from crdt_tpu import GSet
    from crdt_tpu.batch import GSetBatch

    uni = small_universe()
    lhs = [GSet(x) for x in xs]
    rhs = [GSet(y) for y in ys]
    expected = [scalar_merge(a, b) for a, b in zip(lhs, rhs)]

    cap = 16
    got = (
        GSetBatch.from_scalar(lhs, uni, cap)
        .merge(GSetBatch.from_scalar(rhs, uni, cap))
        .to_scalar(uni)
    )
    assert got == expected


def test_gset_rejects_out_of_capacity_ids():
    import pytest

    from crdt_tpu.batch import GSetBatch

    b = GSetBatch.zeros(2, 4)
    with pytest.raises(ValueError):
        b.insert(jnp.asarray([4, 0]))
    with pytest.raises(ValueError):
        b.contains(jnp.asarray([9, 0]))


def test_orswot_join_fleet_parity():
    """OrswotBatch.join_fleet (tree reduction) value()-parity vs the
    scalar engine's merge-all loop (`test/orswot.rs:45-62`), including
    deferred removes flushed by the plunger."""
    import numpy as np

    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.scalar.orswot import Orswot
    from crdt_tpu.utils.interning import Universe

    rng = np.random.RandomState(11)
    uni = Universe(CrdtConfig(num_actors=6, member_capacity=16, deferred_capacity=8))
    n, r = 9, 5
    fleets = []
    for _ in range(r):
        row = []
        for _ in range(n):
            s = Orswot()
            for _ in range(int(rng.randint(0, 6))):
                actor, member = int(rng.randint(0, 6)), int(rng.randint(0, 10))
                ctx = s.value().derive_add_ctx(actor)
                s.apply(s.add(member, ctx))
            if rng.rand() < 0.4 and s.entries:
                member = next(iter(s.entries))
                ctx = s.contains(member).derive_rm_ctx()
                ctx.clock.witness(int(rng.randint(0, 6)), int(rng.randint(50, 60)))
                s.apply(s.remove(member, ctx))  # causally-future: defers
            row.append(s)
        fleets.append(row)

    joined = OrswotBatch.join_fleet(
        [OrswotBatch.from_scalar(row, uni) for row in fleets]
    )
    got_sets = joined.value_sets(uni)

    expected = []
    for i in range(n):
        merged = Orswot()
        for row in fleets:
            merged.merge(row[i].clone())
        merged.merge(Orswot())  # plunger
        expected.append(merged.value().val)
    assert got_sets == expected


def test_counter_bits_32_parity():
    """counter_bits=32 — the TPU-native width (no 64-bit emulation) —
    must produce identical value() results through Orswot, MVReg and
    nested Map batch paths, with every counter plane actually uint32."""
    import numpy as np

    from crdt_tpu.batch import MapBatch, OrswotBatch
    from crdt_tpu.batch.val_kernels import MVRegKernel
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.scalar.map import Map
    from crdt_tpu.scalar.mvreg import MVReg
    from crdt_tpu.scalar.orswot import Orswot
    from crdt_tpu.utils.interning import Universe
    from crdt_tpu.utils.testdata import random_mvreg_map

    rng = np.random.RandomState(21)
    cfg32 = CrdtConfig(num_actors=8, member_capacity=12, deferred_capacity=4,
                       mv_capacity=6, key_capacity=8, counter_bits=32)
    uni = Universe(cfg32)

    # Orswot
    rows_a, rows_b = [], []
    for _ in range(12):
        x, y = Orswot(), Orswot()
        for j in range(int(rng.randint(1, 5))):
            x.apply(x.add(int(rng.randint(0, 9)), x.value().derive_add_ctx(j % 8)))
            y.apply(y.add(int(rng.randint(0, 9)), y.value().derive_add_ctx((j + 3) % 8)))
        rows_a.append(x)
        rows_b.append(y)
    ba = OrswotBatch.from_scalar(rows_a, uni)
    assert ba.clock.dtype == jnp.uint32 and ba.dots.dtype == jnp.uint32
    got = ba.merge(OrswotBatch.from_scalar(rows_b, uni)).value_sets(uni)
    for i in range(12):
        want = rows_a[i].clone()
        want.merge(rows_b[i])
        assert got[i] == want.value().val, i

    # nested Map<int, MVReg> through the value-kernel protocol
    maps_a = [random_mvreg_map(rng) for _ in range(6)]
    maps_b = [random_mvreg_map(rng) for _ in range(6)]
    kern = MVRegKernel.from_config(cfg32)
    assert kern.counter_bits == 32
    ma = MapBatch.from_scalar(maps_a, uni, kern)
    assert ma.clock.dtype == jnp.uint32
    merged = ma.merge(MapBatch.from_scalar(maps_b, uni, kern))
    back = merged.to_scalar(uni)
    for i in range(6):
        want = maps_a[i].clone()
        want.merge(maps_b[i])
        assert back[i] == want, i


def test_counter_bits_32_parity_fused_kernel():
    """u32-vs-u64 parity through the FUSED merge path (VERDICT r3 item 4):
    the same logical fleet packed at counter_bits=32 and joined through
    the pallas kernel (interpret emulation on the CPU test backend) must
    produce the same value() sets as the u64 pack joined through the rank
    reference — the product-default (u32, fused) and parity-oracle (u64,
    rank) configurations agree end-to-end, deferred removes included."""
    import numpy as np

    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.scalar.orswot import Orswot
    from crdt_tpu.utils.interning import Universe

    rng = np.random.RandomState(37)
    base = dict(num_actors=8, member_capacity=12, deferred_capacity=4)
    uni32 = Universe(CrdtConfig(counter_bits=32, merge_impl="pallas", **base))
    uni64 = Universe(CrdtConfig(counter_bits=64, merge_impl="rank", **base))

    fleets = []
    for _ in range(4):
        row = []
        for _ in range(10):
            s = Orswot()
            for j in range(int(rng.randint(1, 5))):
                s.apply(s.add(int(rng.randint(0, 9)),
                              s.value().derive_add_ctx(j % 8)))
            if rng.rand() < 0.4 and s.entries:
                member = next(iter(s.entries))
                ctx = s.contains(member).derive_rm_ctx()
                ctx.clock.witness(int(rng.randint(0, 8)),
                                  int(rng.randint(50, 60)))
                s.apply(s.remove(member, ctx))  # causally-future: defers
            row.append(s)
        fleets.append(row)

    j32 = OrswotBatch.join_fleet(
        [OrswotBatch.from_scalar(row, uni32) for row in fleets],
        impl=uni32.config.merge_impl,
    )
    assert j32.clock.dtype == jnp.uint32
    j64 = OrswotBatch.join_fleet(
        [OrswotBatch.from_scalar(row, uni64) for row in fleets],
        impl=uni64.config.merge_impl,
    )
    assert j64.clock.dtype == jnp.uint64
    assert j32.value_sets(uni32) == j64.value_sets(uni64)
    # counters themselves agree (no narrowing happened at these counts)
    np.testing.assert_array_equal(
        np.asarray(j32.clock, dtype=np.uint64), np.asarray(j64.clock)
    )


def test_lww_markers_stay_64bit_under_counter_bits_32():
    """Markers are timestamps (u64, `lwwreg.rs:16-24`), not op counters:
    counter_bits=32 must not narrow them."""
    from crdt_tpu.batch import LWWRegBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.scalar.lwwreg import LWWReg
    from crdt_tpu.utils.interning import Universe

    uni = Universe(CrdtConfig(num_actors=4, counter_bits=32))
    epoch_micros = 1_785_375_612_441_000  # > 2**32
    regs = [LWWReg("v", epoch_micros)]
    batch = LWWRegBatch.from_scalar(regs, uni)
    assert batch.markers.dtype == jnp.uint64
    assert batch.to_scalar(uni)[0].marker == epoch_micros


# -- Causal::truncate --------------------------------------------------------


@given(st.lists(st.tuples(orswots(), vclocks), min_size=1, max_size=4))
@settings(max_examples=60)
def test_orswot_truncate_parity(pairs):
    """`orswot.rs:159-172` on the batch engine: bit-identical state vs the
    scalar truncate, per object."""
    uni = small_universe()
    states = [s for s, _ in pairs]
    clocks = [c for _, c in pairs]

    expected = []
    for s, c in pairs:
        e = s.clone()
        e.truncate(c)
        expected.append(e)

    batch = OrswotBatch.from_scalar(states, uni)
    got = batch.truncate(
        VClockBatch.from_scalar(clocks, uni).clocks
    ).to_scalar(uni)
    assert got == expected, f"\nbatch:  {got!r}\nscalar: {expected!r}"


@given(st.lists(st.tuples(mvregs(), vclocks), min_size=1, max_size=4))
@settings(max_examples=60)
def test_mvreg_truncate_parity(pairs):
    """`mvreg.rs:100-113` on the batch engine."""
    uni = small_universe()
    expected = []
    for r, c in pairs:
        e = r.clone()
        e.truncate(c)
        expected.append(e)

    batch = MVRegBatch.from_scalar([r for r, _ in pairs], uni)
    got = batch.truncate(
        VClockBatch.from_scalar([c for _, c in pairs], uni).clocks
    ).to_scalar(uni)
    assert got == expected, f"\nbatch:  {got!r}\nscalar: {expected!r}"


def test_truncate_empty_clock_is_identity():
    """Truncating by the empty clock must be a no-op (`vclock.rs:103-120`
    GLB with nothing removes nothing)."""
    uni = small_universe()
    s = Orswot()
    s.apply(s.add("m", s.value().derive_add_ctx(1)))
    batch = OrswotBatch.from_scalar([s], uni)
    got = batch.truncate(jnp.zeros_like(batch.clock)).to_scalar(uni)[0]
    assert got == s


# -- BASELINE.md configs 1-5 at their published widths ----------------------


def _baseline_vclocks(rng, n, a):
    return [VClock({int(x): int(rng.randint(1, 1000))
                    for x in rng.choice(a, size=rng.randint(0, a + 1),
                                        replace=False)})
            for _ in range(n)]


def _baseline_orswot(rng, a, m):
    """Causal adds and removes over ``m`` members from ``a`` actors, and
    now and then one causally-future remove that defers."""
    s = Orswot()
    for _ in range(int(rng.randint(0, 10))):
        actor, member = int(rng.randint(0, a)), int(rng.randint(0, m))
        if rng.rand() < 0.75 or member not in s.entries:
            s.apply(s.add(member, s.value().derive_add_ctx(actor)))
        else:
            s.apply(s.remove(member, s.contains(member).derive_rm_ctx()))
    if rng.rand() < 0.3 and s.entries:
        member = next(iter(s.entries))
        ctx = s.contains(member).derive_rm_ctx()
        ctx.clock.witness(int(rng.randint(0, a)), int(rng.randint(2000, 3000)))
        s.apply(s.remove(member, ctx))
    return s


def _baseline_pair(config, rng, n):
    """``(batch_type, universe, lhs, rhs)`` for one BASELINE.md config."""
    if config == "gcounter":  # config 1: 2 replicas, 4 actors
        uni = Universe(CrdtConfig(num_actors=4, member_capacity=8,
                                  deferred_capacity=4))
        a, b = GCounter(), GCounter()
        for actor in ("A", "B", "A", "C"):
            a.apply(a.inc(actor))
        for actor in ("B", "D"):
            b.apply(b.inc(actor))
        return GCounterBatch, uni, [a], [b]
    if config == "vclock":  # config 2: 1k clocks x 64 actors
        uni = Universe(CrdtConfig(num_actors=64))
        return (VClockBatch, uni, _baseline_vclocks(rng, n, 64),
                _baseline_vclocks(rng, n, 64))
    if config == "pncounter":  # config 3: 1M replicas x 32 actors
        from crdt_tpu.scalar.gcounter import GCounter as G

        uni = Universe(CrdtConfig(num_actors=32))

        def side():
            return [PNCounter(G(p), G(q)) for p, q in
                    zip(_baseline_vclocks(rng, n, 32),
                        _baseline_vclocks(rng, n, 32))]

        return PNCounterBatch, uni, side(), side()
    if config == "orswot":  # config 4: 100k sets x 16 actors, pairwise
        uni = Universe(CrdtConfig(num_actors=16, member_capacity=8,
                                  deferred_capacity=4))
        return (OrswotBatch, uni,
                [_baseline_orswot(rng, 16, 8) for _ in range(n)],
                [_baseline_orswot(rng, 16, 8) for _ in range(n)])
    assert config == "lwwreg"  # config 5: 10M registers, u64 markers
    uni = Universe(CrdtConfig(num_actors=8))
    epoch_ms = 1_700_000_000_000
    # even markers left, odd right: no equal-marker conflict
    marks = epoch_ms + 2 * rng.randint(0, 1 << 20, size=(2, n))
    return (LWWRegBatch, uni,
            [LWWReg(val=int(rng.randint(0, 1 << 40)), marker=int(m))
             for m in marks[0]],
            [LWWReg(val=int(rng.randint(0, 1 << 40)), marker=int(m) + 1)
             for m in marks[1]])


@pytest.mark.parametrize(
    "config", ["gcounter", "vclock", "pncounter", "orswot", "lwwreg"])
def test_baseline_config_merge_parity(config):
    """Each BASELINE.md config at its published width, the object count
    cut to test size: the batch merge equals the scalar merge object by
    object (config 1 also pins its value, 2+1+1+1)."""
    import numpy as np

    rng = np.random.RandomState(5)
    cls, uni, lhs, rhs = _baseline_pair(config, rng, 64)
    expected = [scalar_merge(a, b) for a, b in zip(lhs, rhs)]
    got = cls.from_scalar(lhs, uni).merge(cls.from_scalar(rhs, uni)) \
        .to_scalar(uni)
    assert len(got) == len(expected)
    for i, (g, e) in enumerate(zip(got, expected)):
        assert g == e, f"{config} object {i}:\nbatch:  {g!r}\nscalar: {e!r}"
    if config == "gcounter":
        assert got[0].value() == 5
