"""Native (C++) engine parity tests.

The three engines — scalar Python, JAX/XLA batch, native C++ — implement the
same dense-array contracts; here every native kernel is compared
byte-for-byte against the JAX kernels (which the rest of the suite pins to
the scalar reference semantics), over randomized states, both counter
dtypes, and the op paths.
"""

import numpy as np
import pytest

from crdt_tpu.native import available

pytestmark = pytest.mark.skipif(
    not available(), reason="native toolchain unavailable (g++/make)"
)


@pytest.fixture(scope="module")
def engines():
    import jax.numpy as jnp

    from crdt_tpu.native import engine
    from crdt_tpu.ops import clock_ops, lww_ops, mvreg_ops, orswot_ops

    return engine, clock_ops, lww_ops, mvreg_ops, orswot_ops, jnp


DTYPES = [np.uint32, np.uint64]


def rand_clocks(rng, shape, dtype, p_zero=0.4):
    x = rng.randint(0, 50, size=shape).astype(dtype)
    return np.where(rng.rand(*shape) < p_zero, np.zeros_like(x), x)


@pytest.mark.parametrize("dtype", DTYPES)
def test_vclock_ops_parity(engines, dtype):
    engine, clock_ops, *_ = engines
    rng = np.random.RandomState(0)
    a = rand_clocks(rng, (64, 16), dtype)
    b = rand_clocks(rng, (64, 16), dtype)
    for native_fn, jax_fn in [
        (engine.vclock_merge, clock_ops.merge),
        (engine.vclock_intersection, clock_ops.intersection),
        (engine.vclock_subtract, clock_ops.subtract),
        (engine.vclock_truncate, clock_ops.truncate),
    ]:
        np.testing.assert_array_equal(
            native_fn(a, b), np.asarray(jax_fn(a, b)).astype(dtype)
        )
    leq, geq = engine.vclock_compare(a, b)
    np.testing.assert_array_equal(leq, np.asarray(clock_ops.leq(a, b)))
    np.testing.assert_array_equal(geq, np.asarray(clock_ops.dominates_or_eq(a, b)))


@pytest.mark.parametrize("dtype", DTYPES)
def test_lww_merge_parity(engines, dtype):
    engine, _, lww_ops, *_ = engines
    rng = np.random.RandomState(1)
    n = 1000
    va = rng.randint(0, 5, size=n).astype(np.int64)
    vb = rng.randint(0, 5, size=n).astype(np.int64)
    ma = rng.randint(0, 10, size=n).astype(dtype)  # small range forces ties
    mb = rng.randint(0, 10, size=n).astype(dtype)
    val, marker, conflict = engine.lww_merge(va, ma, vb, mb)
    jval, jmarker, jconflict = lww_ops.merge(va, ma, vb, mb)
    np.testing.assert_array_equal(val, np.asarray(jval))
    np.testing.assert_array_equal(marker, np.asarray(jmarker).astype(dtype))
    np.testing.assert_array_equal(conflict, np.asarray(jconflict))
    assert conflict.any(), "test vector should include real conflicts"


@pytest.mark.parametrize("dtype", DTYPES)
def test_mvreg_merge_parity(engines, dtype):
    engine, _, _, mvreg_ops, _, jnp = engines
    rng = np.random.RandomState(2)
    n, k, a = 200, 4, 6
    ca = rand_clocks(rng, (n, k, a), dtype, p_zero=0.6)
    cb = rand_clocks(rng, (n, k, a), dtype, p_zero=0.6)
    # make some slots exact duplicates across sides (the dedup path)
    dup = rng.rand(n) < 0.3
    cb[dup, 0] = ca[dup, 0]
    va = rng.randint(1, 100, size=(n, k)).astype(np.int64)
    vb = rng.randint(1, 100, size=(n, k)).astype(np.int64)
    vb[dup, 0] = va[dup, 0]
    # zero the payload of dead slots (the JAX kernel masks them to 0)
    va = np.where((ca != 0).any(-1), va, 0)
    vb = np.where((cb != 0).any(-1), vb, 0)

    k_cap = 2 * k  # no truncation: compare full survivor sets
    clocks, vals, overflow = engine.mvreg_merge(ca, va, cb, vb, k_cap=k_cap)
    jc, jv, keep = mvreg_ops.merge(ca, va, cb, vb)
    jc, jv, joverflow = mvreg_ops.compact(jc, jv, keep, k_cap)
    np.testing.assert_array_equal(clocks, np.asarray(jc).astype(dtype))
    np.testing.assert_array_equal(vals, np.asarray(jv))
    np.testing.assert_array_equal(overflow, np.asarray(joverflow))
    assert not overflow.any()


def random_orswot_pair(rng, n, a, m, d, dtype):
    from crdt_tpu.utils.testdata import random_orswot_arrays

    lhs = random_orswot_arrays(rng, n, a, m, d, dtype=dtype)
    rhs = random_orswot_arrays(rng, n, a, m, d, dtype=dtype)
    return lhs, rhs


@pytest.mark.parametrize("dtype", DTYPES)
def test_orswot_merge_parity(engines, dtype):
    engine, *_, orswot_ops, jnp = engines
    rng = np.random.RandomState(3)
    n, a, m, d = 128, 8, 6, 3
    lhs, rhs = random_orswot_pair(rng, n, a, m, d, dtype)
    # output capacity 2m so nothing truncates and slot order is fully checked
    got = engine.orswot_merge(*lhs, *rhs, m_cap=2 * m, d_cap=2 * d)
    exp = orswot_ops.merge(*[jnp.asarray(x) for x in lhs],
                           *[jnp.asarray(x) for x in rhs], 2 * m, 2 * d)
    names = ["clock", "ids", "dots", "d_ids", "d_clocks", "overflow"]
    for g, e, name in zip(got, exp, names):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(e), err_msg=f"orswot merge field {name}"
        )


@pytest.mark.parametrize("dtype", DTYPES)
def test_orswot_merge_with_deferred_parity(engines, dtype):
    """Deferred rows exercise dedup, replay, and the still-ahead filter."""
    engine, *_, orswot_ops, jnp = engines
    rng = np.random.RandomState(4)
    n, a, m, d = 64, 6, 5, 3
    lhs, rhs = random_orswot_pair(rng, n, a, m, d, dtype)
    lhs, rhs = list(lhs), list(rhs)

    # inject deferred removes: some targeting existing members with clocks
    # ahead of the set clock, some duplicated on both sides
    for side in (lhs, rhs):
        ids, d_ids, d_clocks = side[1], side[3], side[4]
        d_ids[:, 0] = ids[:, 0]  # remove the first member...
        d_clocks[:, 0, :] = rand_clocks(rng, (n, a), dtype, p_zero=0.3) + 1
    # duplicate row 0 of lhs into rhs for half the objects (dedup path)
    half = rng.rand(n) < 0.5
    rhs[3][half, 1] = lhs[3][half, 0]
    rhs[4][half, 1] = lhs[4][half, 0]

    got = engine.orswot_merge(*lhs, *rhs, m_cap=2 * m, d_cap=2 * d)
    exp = orswot_ops.merge(*[jnp.asarray(x) for x in lhs],
                           *[jnp.asarray(x) for x in rhs], 2 * m, 2 * d)
    for g, e, name in zip(got, exp, ["clock", "ids", "dots", "d_ids", "d_clocks", "overflow"]):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(e), err_msg=f"field {name}"
        )


@pytest.mark.parametrize("dtype", DTYPES)
def test_orswot_apply_add_parity(engines, dtype):
    engine, *_, orswot_ops, jnp = engines
    rng = np.random.RandomState(5)
    n, a, m, d = 100, 6, 5, 2
    (state, _) = random_orswot_pair(rng, n, a, m, d, dtype)
    actor = rng.randint(0, a, size=n).astype(np.int32)
    # mix of novel counters (apply) and stale ones (dedup no-op)
    counter = rng.randint(1, 150, size=n).astype(dtype)
    member = rng.randint(0, 1 << 20, size=n).astype(np.int32)

    got = engine.orswot_apply_add(*state, actor, counter, member)
    exp = orswot_ops.apply_add(*[jnp.asarray(x) for x in state],
                               jnp.asarray(actor), jnp.asarray(counter),
                               jnp.asarray(member))
    for g, e, name in zip(got, exp, ["clock", "ids", "dots", "d_ids", "d_clocks", "overflow"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e), err_msg=name)


@pytest.mark.parametrize("dtype", DTYPES)
def test_orswot_apply_remove_parity(engines, dtype):
    engine, *_, orswot_ops, jnp = engines
    rng = np.random.RandomState(6)
    n, a, m, d = 100, 6, 5, 3
    (state, _) = random_orswot_pair(rng, n, a, m, d, dtype)
    # remove an existing member for half the objects, a random id otherwise
    member = np.where(
        rng.rand(n) < 0.5, state[1][:, 0], rng.randint(0, 1 << 20, size=n)
    ).astype(np.int32)
    # rm clocks: mix of covered (apply now) and ahead (defer)
    rm_clock = rand_clocks(rng, (n, a), dtype, p_zero=0.5)
    ahead = rng.rand(n) < 0.4
    rm_clock[ahead] += 200

    got = engine.orswot_apply_remove(*state, rm_clock, member)
    exp = orswot_ops.apply_remove(*[jnp.asarray(x) for x in state],
                                  jnp.asarray(rm_clock), jnp.asarray(member))
    for g, e, name in zip(got, exp, ["clock", "ids", "dots", "d_ids", "d_clocks", "overflow"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e), err_msg=name)


@pytest.mark.parametrize("dtype", DTYPES)
def test_orswot_merge_overflow_flag(engines, dtype):
    """Truncation must be flagged, never silent."""
    engine, *_, orswot_ops, jnp = engines
    rng = np.random.RandomState(7)
    n, a, m, d = 16, 4, 4, 2
    lhs, rhs = random_orswot_pair(rng, n, a, m, d, dtype)
    got = engine.orswot_merge(*lhs, *rhs, m_cap=1, d_cap=d)
    exp = orswot_ops.merge(*[jnp.asarray(x) for x in lhs],
                           *[jnp.asarray(x) for x in rhs], 1, d)
    np.testing.assert_array_equal(np.asarray(got[5]), np.asarray(exp[5]))
    assert np.asarray(got[5]).any()


def test_shape_validation_rejects_mismatches(engines):
    """The C kernels do raw pointer arithmetic; the wrappers must reject
    inconsistent shapes instead of reading out of bounds."""
    engine, *_ = engines
    rng = np.random.RandomState(9)
    n, a, m, d = 8, 4, 3, 2
    lhs, _ = random_orswot_pair(rng, n, a, m, d, np.uint64)
    short, _ = random_orswot_pair(rng, n // 2, a, m, d, np.uint64)
    with pytest.raises(ValueError, match="side shapes differ"):
        engine.orswot_merge(*lhs, *short)
    with pytest.raises(ValueError, match="inconsistent ORSWOT state"):
        engine.orswot_merge(*lhs[:2], lhs[2][:, :1], *lhs[3:], *lhs)
    with pytest.raises(ValueError, match="actor_idx"):
        engine.orswot_apply_add(
            *lhs, np.zeros(n // 2, np.int32), np.ones(n, np.uint64),
            np.zeros(n, np.int32),
        )
    with pytest.raises(ValueError, match="out of range"):
        engine.orswot_apply_add(
            *lhs, np.full(n, a, np.int32), np.ones(n, np.uint64),
            np.zeros(n, np.int32),
        )
    with pytest.raises(ValueError, match="rm_clock"):
        engine.orswot_apply_remove(
            *lhs, np.zeros((n, a + 1), np.uint64), np.zeros(n, np.int32)
        )
    with pytest.raises(ValueError, match="shape mismatch"):
        engine.lww_merge(
            np.zeros(4, np.int64), np.zeros(4, np.uint64),
            np.zeros(5, np.int64), np.zeros(5, np.uint64),
        )


def test_lww_merge_preserves_lead_shape(engines):
    engine, _, lww_ops, *_ = engines
    rng = np.random.RandomState(10)
    shape = (16, 8)
    va = rng.randint(0, 3, size=shape).astype(np.int64)
    vb = rng.randint(0, 3, size=shape).astype(np.int64)
    ma = rng.randint(0, 5, size=shape).astype(np.uint64)
    mb = rng.randint(0, 5, size=shape).astype(np.uint64)
    val, marker, conflict = engine.lww_merge(va, ma, vb, mb)
    assert val.shape == marker.shape == conflict.shape == shape
    jval, jmarker, jconflict = lww_ops.merge(va, ma, vb, mb)
    np.testing.assert_array_equal(conflict, np.asarray(jconflict))


def test_native_fold_matches_scalar_orswot():
    """End-to-end: native N-way left-fold join == scalar engine join."""
    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.native import engine
    from crdt_tpu.scalar.orswot import Orswot
    from crdt_tpu.scalar.vclock import Dot
    from crdt_tpu.utils.interning import Universe

    rng = np.random.RandomState(8)
    uni = Universe(CrdtConfig(num_actors=6, member_capacity=12, deferred_capacity=6))
    n_rep, n_obj = 5, 4
    fleet = []
    for r in range(n_rep):
        row = []
        for i in range(n_obj):
            s = Orswot()
            for _ in range(rng.randint(0, 6)):
                actor = int(rng.randint(0, 6))
                counter = int(rng.randint(1, 5))
                member = int(rng.randint(0, 6))
                if rng.rand() < 0.7:
                    s.apply(
                        __import__("crdt_tpu.scalar.orswot", fromlist=["Add"]).Add(
                            dot=Dot(actor, counter), member=member
                        )
                    )
                else:
                    s.apply_remove(member, Dot(actor, counter).to_vclock())
            row.append(s)
        fleet.append(row)

    batches = [OrswotBatch.from_scalar(row, uni) for row in fleet]
    arrays = [
        tuple(np.asarray(x) for x in (b.clock, b.ids, b.dots, b.d_ids, b.d_clocks))
        for b in batches
    ]
    acc = arrays[0]
    for nxt in arrays[1:]:
        out = engine.orswot_merge(*acc, *nxt)
        assert not out[5].any()
        acc = out[:5]
    # defer plunger
    zero = tuple(
        np.asarray(x)
        for x in (
            np.zeros_like(acc[0]), np.full_like(acc[1], -1), np.zeros_like(acc[2]),
            np.full_like(acc[3], -1), np.zeros_like(acc[4]),
        )
    )
    acc = engine.orswot_merge(*acc, *zero)[:5]

    import jax.numpy as jnp

    merged_batch = OrswotBatch(
        clock=jnp.asarray(acc[0]), ids=jnp.asarray(acc[1]), dots=jnp.asarray(acc[2]),
        d_ids=jnp.asarray(acc[3]), d_clocks=jnp.asarray(acc[4]),
    )
    got = merged_batch.to_scalar(uni)

    expected = []
    for i in range(n_obj):
        merged = Orswot()
        for row in fleet:
            merged.merge(row[i])
        merged.merge(Orswot())
        expected.append(merged)
    assert got == expected


# -- Map<K, MVReg> merge (map.rs:192-269) ------------------------------------


def _random_map_batch_arrays(seed, n_obj, uni):
    """Random op-built Map<int, MVReg> fleet packed to dense arrays, plus
    the scalar states (for building the batch on both engines)."""
    import random as pyrandom

    from crdt_tpu import Dot, Map, MVReg, VClock
    from crdt_tpu.batch import MapBatch, MVRegKernel
    from crdt_tpu.scalar.map import Rm as MapRm, Up
    from crdt_tpu.scalar.mvreg import Put

    rng = pyrandom.Random(seed)
    states = []
    for _ in range(n_obj):
        m = Map(MVReg)
        for _ in range(rng.randrange(0, 10)):
            actor = rng.randrange(0, 6)
            counter = rng.randrange(1, 6)
            key = rng.randrange(0, 5)
            clock = VClock.from_iter([(actor, counter)])
            if rng.random() < 0.3:
                m.apply(MapRm(clock=clock, key=key))
            else:
                m.apply(Up(dot=Dot(actor, counter), key=key,
                           op=Put(clock=clock, val=rng.randrange(0, 9))))
        states.append(m)
    vk = MVRegKernel.from_config(uni.config)
    batch = MapBatch.from_scalar(states, uni, vk)
    mv_clocks, mv_vals = batch.vals
    return (
        np.asarray(batch.clock), np.asarray(batch.keys),
        np.asarray(batch.entry_clocks), np.asarray(mv_clocks),
        np.asarray(mv_vals), np.asarray(batch.d_keys),
        np.asarray(batch.d_clocks),
    ), batch


def test_map_mvreg_merge_parity(engines):
    """Native Map<K, MVReg> merge == jnp map_ops.merge, byte-for-byte —
    the composition path (`map.rs:192-269`) through the C++ oracle."""
    engine = engines[0]
    import jax.numpy as jnp

    from crdt_tpu.batch.val_kernels import MVRegKernel
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.ops import map_ops
    from crdt_tpu.utils.interning import Universe

    uni = Universe(CrdtConfig(
        num_actors=6, member_capacity=8, deferred_capacity=6,
        mv_capacity=8, key_capacity=8,
    ))
    vk = MVRegKernel.from_config(uni.config)
    n_obj = 32
    A, batch_a = _random_map_batch_arrays(101, n_obj, uni)
    B, batch_b = _random_map_batch_arrays(202, n_obj, uni)

    k_cap = A[1].shape[-1]
    d_cap = A[5].shape[-1]
    got_state, got_over = engine.map_mvreg_merge(A, B, k_cap, d_cap)

    state_a = (batch_a.clock, batch_a.keys, batch_a.entry_clocks,
               batch_a.vals, batch_a.d_keys, batch_a.d_clocks)
    state_b = (batch_b.clock, batch_b.keys, batch_b.entry_clocks,
               batch_b.vals, batch_b.d_keys, batch_b.d_clocks)
    want_state, want_over = map_ops.merge(state_a, state_b, vk, k_cap, d_cap)
    w_clock, w_keys, w_e, (w_mvc, w_mvv), w_dk, w_dc = want_state

    np.testing.assert_array_equal(got_state[0], np.asarray(w_clock))
    np.testing.assert_array_equal(got_state[1], np.asarray(w_keys))
    np.testing.assert_array_equal(got_state[2], np.asarray(w_e))
    np.testing.assert_array_equal(got_state[3], np.asarray(w_mvc))
    np.testing.assert_array_equal(got_state[4], np.asarray(w_mvv))
    np.testing.assert_array_equal(got_state[5], np.asarray(w_dk))
    np.testing.assert_array_equal(got_state[6], np.asarray(w_dc))
    np.testing.assert_array_equal(got_over, np.asarray(want_over))


def test_map_mvreg_merge_deferred_parity(engines):
    """Causally-future Map removes buffer and replay identically in the
    C++ and jnp engines (`map.rs:256-267`)."""
    engine = engines[0]

    from crdt_tpu import Dot, Map, MVReg, VClock
    from crdt_tpu.batch import MapBatch, MVRegKernel
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.ops import map_ops
    from crdt_tpu.scalar.map import Rm as MapRm, Up
    from crdt_tpu.scalar.mvreg import Put
    from crdt_tpu.utils.interning import Universe

    uni = Universe(CrdtConfig(
        num_actors=6, member_capacity=8, deferred_capacity=6,
        mv_capacity=8, key_capacity=8,
    ))
    vk = MVRegKernel.from_config(uni.config)

    writer = Map(MVReg)
    clock = VClock.from_iter([(0, 3)])
    writer.apply(Up(dot=Dot(0, 3), key=1, op=Put(clock=clock, val=7)))

    remover = Map(MVReg)
    remover.apply(MapRm(clock=VClock.from_iter([(0, 3)]), key=1))  # future
    assert remover.deferred

    ba = MapBatch.from_scalar([writer], uni, vk)
    bb = MapBatch.from_scalar([remover], uni, vk)

    def arrays(b):
        mvc, mvv = b.vals
        return (np.asarray(b.clock), np.asarray(b.keys),
                np.asarray(b.entry_clocks), np.asarray(mvc), np.asarray(mvv),
                np.asarray(b.d_keys), np.asarray(b.d_clocks))

    got_state, got_over = engine.map_mvreg_merge(arrays(ba), arrays(bb))
    want_state, want_over = map_ops.merge(
        (ba.clock, ba.keys, ba.entry_clocks, ba.vals, ba.d_keys, ba.d_clocks),
        (bb.clock, bb.keys, bb.entry_clocks, bb.vals, bb.d_keys, bb.d_clocks),
        vk, ba.keys.shape[-1], ba.d_keys.shape[-1],
    )
    w_clock, w_keys, w_e, (w_mvc, w_mvv), w_dk, w_dc = want_state
    for got, want in zip(
        got_state,
        (w_clock, w_keys, w_e, w_mvc, w_mvv, w_dk, w_dc),
    ):
        np.testing.assert_array_equal(got, np.asarray(want))
    # the asymmetric discard (`map.rs:256-260`): the remover's buffered row
    # is already covered by the writer's clock, so it is dropped WITHOUT
    # effect — the key survives and the deferred buffer drains
    assert np.any(got_state[1] != -1), "covered deferred row must not remove"
    assert np.all(got_state[5] == -1), "covered deferred row must drain"
    np.testing.assert_array_equal(got_over, np.asarray(want_over))


# -- Map<K, Orswot> merge (map.rs:192-269 over orswot.rs:89-156) --------------


def _random_map_orswot_states(seed, n_obj, uni):
    """Random op-built Map<int, Orswot> fleet + its dense MapBatch — the
    hardest composition path (nested member tables, nested deferred rows,
    reset-remove truncates through the nested set)."""
    import random as pyrandom

    from crdt_tpu import Dot, Map, Orswot
    from crdt_tpu.batch import MapBatch, OrswotKernel
    from crdt_tpu.scalar.map import Rm as MapRm, Up
    from crdt_tpu.scalar.orswot import Add as OrswotAdd, Rm as OrswotRm

    rng = pyrandom.Random(seed)
    states = []
    for _ in range(n_obj):
        m = Map(Orswot)
        for _ in range(rng.randrange(0, 12)):
            actor = rng.randrange(0, 6)
            counter = rng.randrange(1, 6)
            key = rng.randrange(0, 5)
            member = rng.randrange(0, 9)
            dot = Dot(actor, counter)
            p = rng.random()
            if p < 0.2:
                m.apply(MapRm(clock=dot.to_vclock(), key=key))
            elif p < 0.4:
                m.apply(Up(dot=dot, key=key,
                           op=OrswotRm(clock=dot.to_vclock(), member=member)))
            else:
                m.apply(Up(dot=dot, key=key, op=OrswotAdd(dot=dot, member=member)))
        states.append(m)
    vk = OrswotKernel.from_config(uni.config)
    batch = MapBatch.from_scalar(states, uni, vk)
    state = (batch.clock, batch.keys, batch.entry_clocks, batch.vals,
             batch.d_keys, batch.d_clocks)
    import jax

    arrays = jax.tree_util.tree_map(np.asarray, state)
    return arrays, state, states, vk


def _map_orswot_uni():
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.utils.interning import Universe

    return Universe(CrdtConfig(
        num_actors=6, member_capacity=8, deferred_capacity=6, key_capacity=8,
    ))


def test_map_orswot_merge_parity(engines):
    """Native Map<K, Orswot> merge == jnp map_ops.merge under OrswotKernel,
    byte-for-byte including nested member-slot order and truncate holes."""
    engine = engines[0]

    from crdt_tpu.ops import map_ops

    uni = _map_orswot_uni()
    A, state_a, _, vk = _random_map_orswot_states(303, 32, uni)
    B, state_b, _, _ = _random_map_orswot_states(404, 32, uni)

    k_cap = A[1].shape[-1]
    d_cap = A[4].shape[-1]
    got_state, got_over = engine.map_orswot_merge(A, B, k_cap, d_cap)
    want_state, want_over = map_ops.merge(state_a, state_b, vk, k_cap, d_cap)

    import jax

    got_flat = jax.tree_util.tree_leaves(got_state)
    want_flat = jax.tree_util.tree_leaves(want_state)
    assert len(got_flat) == len(want_flat) == 10
    for g, w in zip(got_flat, want_flat):
        np.testing.assert_array_equal(g, np.asarray(w))
    np.testing.assert_array_equal(got_over, np.asarray(want_over))


def test_map_orswot_three_engine_agreement():
    """C++ N-way fold == scalar Python N-way merge (value semantics), with
    the JAX engine pinned byte-for-byte in the parity test above — all
    three engines meet on the hardest composition path."""
    import jax.numpy as jnp

    from crdt_tpu.batch import MapBatch
    from crdt_tpu.native import engine

    uni = _map_orswot_uni()
    rows = [_random_map_orswot_states(500 + i, 8, uni) for i in range(4)]

    acc_arrays = rows[0][0]
    for arrays, *_ in rows[1:]:
        acc_arrays, over = engine.map_orswot_merge(acc_arrays, arrays)
        assert not over.any()

    import jax

    from crdt_tpu.batch import MapKernel

    mk = MapKernel.from_config(uni.config, rows[0][3])
    merged = MapBatch.from_state(
        jax.tree_util.tree_map(jnp.asarray, acc_arrays), mk
    )
    got = merged.to_scalar(uni)

    expected = []
    for i in range(8):
        m = rows[0][2][i].clone()
        for _, _, states, _ in rows[1:]:
            m.merge(states[i])
        expected.append(m)
    assert got == expected


# -- Map<K, Map<K2, MVReg>> merge (map.rs:192-269 recursing at :229) ----------


def _random_nested_map_states(seed, n_obj, uni):
    """Random op-built Map<int, Map<int, MVReg>> fleet (`test/map.rs:8`
    shape) + its dense MapBatch under a nested MapKernel."""
    import random as pyrandom

    from crdt_tpu import Dot, Map, MVReg
    from crdt_tpu.batch import MapBatch, MapKernel, MVRegKernel
    from crdt_tpu.scalar.map import Rm as MapRm, Up
    from crdt_tpu.scalar.mvreg import Put
    from crdt_tpu.scalar.vclock import VClock

    rng = pyrandom.Random(seed)
    states = []
    for _ in range(n_obj):
        m = Map(lambda: Map(MVReg))
        for _ in range(rng.randrange(0, 12)):
            actor = rng.randrange(0, 6)
            counter = rng.randrange(1, 6)
            key = rng.randrange(0, 4)
            ikey = rng.randrange(0, 4)
            dot = Dot(actor, counter)
            clock = VClock.from_iter([(actor, counter)])
            p = rng.random()
            if p < 0.2:
                m.apply(MapRm(clock=clock, key=key))
            elif p < 0.4:
                m.apply(Up(dot=dot, key=key, op=MapRm(clock=clock, key=ikey)))
            else:
                m.apply(Up(dot=dot, key=key,
                           op=Up(dot=dot, key=ikey,
                                 op=Put(clock=clock,
                                        val=rng.randrange(0, 9)))))
        states.append(m)
    inner = MapKernel.from_config(uni.config, MVRegKernel.from_config(uni.config))
    batch = MapBatch.from_scalar(states, uni, inner)
    state = (batch.clock, batch.keys, batch.entry_clocks, batch.vals,
             batch.d_keys, batch.d_clocks)
    import jax

    arrays = jax.tree_util.tree_map(np.asarray, state)
    return arrays, state, states, inner


def _nested_map_uni():
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.utils.interning import Universe

    return Universe(CrdtConfig(
        num_actors=6, mv_capacity=6, deferred_capacity=5, key_capacity=6,
    ))


def test_map_map_mvreg_merge_parity(engines):
    """Native nested-map merge == jnp map_ops.merge under a nested
    MapKernel, byte-for-byte — all three engines now cover Map-in-Map."""
    engine = engines[0]

    from crdt_tpu.ops import map_ops

    uni = _nested_map_uni()
    A, state_a, _, vk = _random_nested_map_states(606, 24, uni)
    B, state_b, _, _ = _random_nested_map_states(707, 24, uni)

    k_cap = A[1].shape[-1]
    d_cap = A[4].shape[-1]
    got_state, got_over = engine.map_map_mvreg_merge(A, B, k_cap, d_cap)
    want_state, want_over = map_ops.merge(state_a, state_b, vk, k_cap, d_cap)

    import jax

    got_flat = jax.tree_util.tree_leaves(got_state)
    want_flat = jax.tree_util.tree_leaves(want_state)
    assert len(got_flat) == len(want_flat) == 12
    for g, w in zip(got_flat, want_flat):
        np.testing.assert_array_equal(g, np.asarray(w))
    np.testing.assert_array_equal(got_over, np.asarray(want_over))


def test_map_map_mvreg_three_engine_agreement():
    """C++ N-way nested-map fold == scalar Python N-way merge, with the JAX
    engine pinned byte-for-byte above — three engines on the deepest
    composition shape the reference tests (`test/map.rs:8`)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.batch import MapBatch, MapKernel
    from crdt_tpu.native import engine

    uni = _nested_map_uni()
    rows = [_random_nested_map_states(800 + i, 6, uni) for i in range(4)]

    acc_arrays = rows[0][0]
    for arrays, *_ in rows[1:]:
        acc_arrays, over = engine.map_map_mvreg_merge(acc_arrays, arrays)
        assert not over.any()

    mk = MapKernel.from_config(uni.config, rows[0][3])
    merged = MapBatch.from_state(
        jax.tree_util.tree_map(jnp.asarray, acc_arrays), mk
    )
    got = merged.to_scalar(uni)

    expected = []
    for i in range(6):
        m = rows[0][2][i].clone()
        for _, _, states, _ in rows[1:]:
            m.merge(states[i])
        expected.append(m)
    assert got == expected


@pytest.mark.parametrize("dtype", DTYPES)
def test_orswot_fold_parity(engines, dtype):
    """The native sequential R-way fold + defer plunger (the wire loop's
    CPU fold) must be bit-identical to
    the jnp fold on anti-entropy-shaped fleets with deferred rows."""
    import jax

    engine, *_, orswot_ops, jnp = engines
    from crdt_tpu.utils.testdata import anti_entropy_fleets

    rng = np.random.RandomState(11)
    n, a, m, d, r = 64, 8, 8, 2, 4
    reps = anti_entropy_fleets(
        rng, n, a, m, d, r, base=3, novel=1, deferred_frac=0.3, dtype=dtype
    )
    stack = [tuple(np.asarray(x) for x in rep) for rep in reps]

    acc = stack[0]
    for i in range(1, r):
        acc = engine.orswot_merge(*acc, *stack[i])[:5]
    acc = engine.orswot_merge(*acc, *acc)[:5]  # defer plunger

    jacc = tuple(jnp.asarray(x) for x in stack[0])
    for i in range(1, r):
        jacc = orswot_ops.merge(*jacc, *(jnp.asarray(x) for x in stack[i]), m, d)[:5]
    jacc = orswot_ops.merge(*jacc, *jacc, m, d)[:5]
    jax.block_until_ready(jacc)

    for k, (x, y) in enumerate(zip(acc, jacc)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"plane {k} diverged"
        )


@pytest.mark.parametrize("dtype", DTYPES)
def test_orswot_merge_out_buffers(engines, dtype):
    """The out= reuse path (the wire loop's fold ping-pong) must be
    bit-identical to fresh allocation, reject shape/dtype mismatches,
    and reject buffers aliasing an input."""
    engine, *_ = engines
    from crdt_tpu.utils.testdata import anti_entropy_fleets

    rng = np.random.RandomState(5)
    n, a, m, d = 32, 8, 8, 2
    lhs, rhs = [
        tuple(np.asarray(x) for x in rep)
        for rep in anti_entropy_fleets(
            rng, n, a, m, d, 2, base=3, novel=1, deferred_frac=0.3,
            dtype=dtype,
        )
    ]
    want = engine.orswot_merge(*lhs, *rhs)

    out = (
        np.empty((n, a), dtype), np.empty((n, m), np.int32),
        np.empty((n, m, a), dtype), np.empty((n, d), np.int32),
        np.empty((n, d, a), dtype),
    )
    got = engine.orswot_merge(*lhs, *rhs, out=out)
    for x, y in zip(want, got):
        np.testing.assert_array_equal(x, y)
    assert got[0] is out[0]  # actually wrote into the caller's buffer

    # second reuse of the same buffers still exact (full overwrite)
    got2 = engine.orswot_merge(*rhs, *lhs, out=out)
    want2 = engine.orswot_merge(*rhs, *lhs)
    for x, y in zip(want2, got2):
        np.testing.assert_array_equal(x, y)

    with pytest.raises(ValueError, match="out\\[clock\\]"):
        engine.orswot_merge(
            *lhs, *rhs, out=(np.empty((n, a + 1), dtype),) + out[1:]
        )
    with pytest.raises(ValueError, match="aliases"):
        engine.orswot_merge(*lhs, *rhs, out=(lhs[0],) + out[1:])


def test_orswot_merge_out_rejects_mutual_aliasing(engines):
    """Same buffer passed as two outputs (ids/d_ids share shape+dtype
    when m == d) must be rejected, not silently corrupted."""
    engine, *_ = engines
    from crdt_tpu.utils.testdata import anti_entropy_fleets

    rng = np.random.RandomState(6)
    n, a, m, d = 8, 4, 2, 2  # m == d: ids/d_ids shapes coincide
    lhs, rhs = [
        tuple(np.asarray(x) for x in rep)
        for rep in anti_entropy_fleets(rng, n, a, m, d, 2, base=1, novel=0)
    ]
    ids_buf = np.empty((n, m), np.int32)
    out = (
        np.empty((n, a), np.uint32), ids_buf,
        np.empty((n, m, a), np.uint32), ids_buf,
        np.empty((n, d, a), np.uint32),
    )
    with pytest.raises(ValueError, match="alias each other"):
        engine.orswot_merge(*lhs, *rhs, out=out)
