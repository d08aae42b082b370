"""Multi-device sharding tests on the virtual 8-device CPU mesh.

Validates the collective-join layer (SURVEY.md §2.3, §5): the all-reduce-max
clock join, the ORSWOT all-gather + canonical-fold join with merge as the
combiner, and anti-entropy-to-fixpoint — all against scalar N-way merges.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_no_collectives

from crdt_tpu import Dot, Orswot, VClock
from crdt_tpu.batch import OrswotBatch, VClockBatch
from crdt_tpu.config import CrdtConfig
from crdt_tpu.parallel import (
    all_reduce_clock_join,
    allgather_join_orswot,
    anti_entropy,
    make_mesh,
    replicate,
    shard_batch,
    tree_reduce_merge,
)
from crdt_tpu.scalar.orswot import Add, Rm
from crdt_tpu.utils.interning import Universe

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh (see conftest)"
)


def small_universe():
    return Universe(CrdtConfig(num_actors=8, member_capacity=16, deferred_capacity=8))


def random_orswots(seed, n_replicas, n_objects):
    """n_replicas × n_objects scalar Orswots with random op histories."""
    rng = np.random.RandomState(seed)
    fleet = []
    for r in range(n_replicas):
        row = []
        for i in range(n_objects):
            s = Orswot()
            for _ in range(rng.randint(0, 8)):
                actor = int(rng.randint(0, 8))
                member = int(rng.randint(0, 8))
                counter = int(rng.randint(1, 6))
                if rng.rand() < 0.7:
                    s.apply(Add(dot=Dot(actor, counter), member=member))
                else:
                    s.apply(Rm(clock=Dot(actor, counter).to_vclock(), member=member))
            row.append(s)
        fleet.append(row)
    return fleet


def scalar_global_join(fleet):
    """Reference N-way join with defer plunger (`test/orswot.rs:53-62`)."""
    n_objects = len(fleet[0])
    out = []
    for i in range(n_objects):
        merged = Orswot()
        for row in fleet:
            merged.merge(row[i])
        merged.merge(Orswot())
        out.append(merged)
    return out


def test_all_reduce_clock_join():
    """8 replica shards of clocks join to the pointwise max everywhere."""
    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    rng = np.random.RandomState(0)
    n_objects = 16
    replicas = []
    for _ in range(8):
        replicas.append(
            [VClock.from_iter([(int(a), int(rng.randint(1, 9))) for a in rng.choice(8, 3)])
             for _ in range(n_objects)]
        )
    stacks = jnp.stack(
        [VClockBatch.from_scalar(r, uni).clocks for r in replicas]
    )  # [8, N, A]

    joined = all_reduce_clock_join(stacks, mesh, axis="replicas")
    expected = jnp.max(stacks, axis=0)
    # every replica shard holds the global join
    for r in range(8):
        np.testing.assert_array_equal(np.asarray(joined[r]), np.asarray(expected))


def test_allgather_join_orswot_matches_scalar():
    """All-gather + canonical fold with ORSWOT merge combiner == scalar
    N-way merge."""
    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    fleet = random_orswots(seed=3, n_replicas=8, n_objects=6)

    batches = [OrswotBatch.from_scalar(row, uni) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)

    joined = allgather_join_orswot(stacked, mesh, axis="replicas")

    # the join must be fully reduced on every device; flush deferred with
    # one plunger merge, then compare against the scalar N-way join
    expected = scalar_global_join(fleet)
    for r in range(8):
        shard = OrswotBatch(
            clock=joined.clock[r], ids=joined.ids[r], dots=joined.dots[r],
            d_ids=joined.d_ids[r], d_clocks=joined.d_clocks[r],
        )
        plunged = shard.merge(OrswotBatch.zeros(6, uni))
        got = plunged.to_scalar(uni)
        assert got == expected, f"replica shard {r} diverged"


@pytest.mark.parametrize("impl", ["unrolled", "pallas"])
def test_allgather_join_orswot_merge_impl_variants(impl):
    """The merge-impl variants (unrolled — the TPU default — and the
    fused pallas kernel, interpret-emulated on the CPU mesh) compose
    with the collective join: the combiner inside the all-gather fold
    routes through orswot_ops.merge via the explicit ``impl=`` argument
    (a static jit arg, so each impl compiles its own entry — no env vars
    or cache clearing), and must behave identically under shard_map's
    per-shard (rank-2) views.  u32 counters — the variants' supported
    width."""
    mesh = make_mesh({"replicas": 8})
    uni = Universe(CrdtConfig(num_actors=8, member_capacity=16,
                              deferred_capacity=8, counter_bits=32))
    fleet = random_orswots(seed=5, n_replicas=8, n_objects=6)

    batches = [OrswotBatch.from_scalar(row, uni) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    joined = allgather_join_orswot(stacked, mesh, axis="replicas", impl=impl)

    expected = scalar_global_join(fleet)
    shard = OrswotBatch(
        clock=joined.clock[0], ids=joined.ids[0], dots=joined.dots[0],
        d_ids=joined.d_ids[0], d_clocks=joined.d_clocks[0],
    )
    plunged = shard.merge(OrswotBatch.zeros(6, uni))
    assert plunged.to_scalar(uni) == expected


def test_allgather_join_map_matches_scalar():
    """Map collective join (`map.rs:192-269` combiner incl. nested value
    merge + reset-remove) == scalar N-way left fold, on every device."""
    import random as pyrandom

    from crdt_tpu import Dot, Map, MVReg, VClock
    from crdt_tpu.batch import MapBatch, MVRegKernel
    from crdt_tpu.parallel.collective import allgather_join_map
    from crdt_tpu.scalar.map import Rm as MapRm, Up
    from crdt_tpu.scalar.mvreg import Put

    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    rng = pyrandom.Random(23)
    n_objects = 4

    def random_map():
        m = Map(MVReg)
        for _ in range(rng.randrange(0, 8)):
            actor = rng.randrange(0, 8)
            counter = rng.randrange(1, 6)
            key = rng.randrange(0, 5)
            clock = VClock.from_iter([(actor, counter)])
            if rng.random() < 0.25:
                m.apply(MapRm(clock=clock, key=key))
            else:
                m.apply(
                    Up(dot=Dot(actor, counter), key=key,
                       op=Put(clock=clock, val=rng.randrange(0, 9)))
                )
        return m

    fleet = [[random_map() for _ in range(n_objects)] for _ in range(8)]
    val_kernel = MVRegKernel.from_config(uni.config)
    batches = [MapBatch.from_scalar(row, uni, val_kernel) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)

    joined = allgather_join_map(stacked, mesh, axis="replicas")

    expected = []
    for i in range(n_objects):
        acc = fleet[0][i].clone()
        for r in range(1, 8):
            acc.merge(fleet[r][i])
        expected.append(acc)

    for r in range(8):
        shard_state = jax.tree_util.tree_map(lambda x: x[r], joined.state)
        shard = MapBatch.from_state(shard_state, joined.kernel)
        got = shard.to_scalar(uni)
        assert got == expected, f"replica shard {r} diverged"


def test_anti_entropy_fixpoint_matches_scalar():
    uni = small_universe()
    fleet = random_orswots(seed=11, n_replicas=5, n_objects=8)
    batches = [OrswotBatch.from_scalar(row, uni) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)

    merged, rounds = anti_entropy(stacked)
    assert rounds <= 3
    got = merged.to_scalar(uni)
    expected = scalar_global_join(fleet)
    assert got == expected


def test_fold_reduce_matches_sequential():
    from crdt_tpu.parallel import fold_reduce_merge

    uni = small_universe()
    fleet = random_orswots(seed=5, n_replicas=7, n_objects=4)
    batches = [OrswotBatch.from_scalar(row, uni) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)

    def pair(a, b):
        return a.merge(b, check=False)

    merged = fold_reduce_merge(stacked, pair)
    # left fold == explicit sequential merge, bit for bit
    seq = batches[0]
    for b in batches[1:]:
        seq = seq.merge(b, check=False)
    for x, y in zip(jax.tree_util.tree_leaves(merged), jax.tree_util.tree_leaves(seq)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_tree_reduce_matches_fold_for_commutative_merge():
    """tree_reduce_merge == fold_reduce_merge for clock-shaped (truly
    commutative) joins, including odd replica counts (the halving carry)."""
    from crdt_tpu.parallel import fold_reduce_merge

    uni = small_universe()
    rng = np.random.RandomState(17)
    for n_replicas in (2, 5, 8):  # even, odd (carry path), power of two
        stacks = jnp.stack(
            [
                VClockBatch.from_scalar(
                    [
                        VClock.from_iter(
                            [(int(a), int(rng.randint(1, 9))) for a in rng.choice(8, 3)]
                        )
                        for _ in range(6)
                    ],
                    uni,
                ).clocks
                for _ in range(n_replicas)
            ]
        )  # [R, N, A]
        tree = tree_reduce_merge(stacks, jnp.maximum)
        fold = fold_reduce_merge(stacks, jnp.maximum)
        np.testing.assert_array_equal(np.asarray(tree), np.asarray(fold))
        np.testing.assert_array_equal(
            np.asarray(tree), np.asarray(jnp.max(stacks, axis=0))
        )


def test_replicate_places_full_copy_everywhere():
    mesh = make_mesh({"objects": 8})
    uni = small_universe()
    fleet = random_orswots(seed=21, n_replicas=1, n_objects=4)
    batch = OrswotBatch.from_scalar(fleet[0], uni)
    rep = replicate(batch, mesh)
    # fully-replicated sharding: every leaf is addressable whole on each device
    for leaf in jax.tree_util.tree_leaves(rep):
        assert leaf.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(rep.clock), np.asarray(batch.clock))


def test_sharded_pairwise_merge_no_collectives():
    """Object-axis sharding: pairwise merge of two sharded batches matches
    the unsharded result, and the shard_map-based merge compiles with zero
    cross-device traffic (objects are independent)."""
    mesh = make_mesh({"objects": 8})
    uni = small_universe()
    fleet = random_orswots(seed=9, n_replicas=2, n_objects=32)
    a = OrswotBatch.from_scalar(fleet[0], uni)
    b = OrswotBatch.from_scalar(fleet[1], uni)
    expected = a.merge(b).to_scalar(uni)

    a_sharded = shard_batch(a, mesh, "objects")
    b_sharded = shard_batch(b, mesh, "objects")
    # plain jit path: correct under sharding (the partitioner may insert a
    # scalar-sized collective for the deferred-dispatch predicate)
    got = a_sharded.merge(b_sharded).to_scalar(uni)
    assert got == expected

    # the headline zero-traffic claim lives in the shard_map path, where
    # the deferred/deferred-free dispatch is also decided per shard
    from crdt_tpu.parallel.collective import shard_local_pairwise_merge

    state5, overflow = shard_local_pairwise_merge(a_sharded, b_sharded, mesh, "objects")
    got_local = OrswotBatch(*state5).to_scalar(uni)
    assert got_local == expected
    assert not bool(np.asarray(overflow).any())

    m_cap, d_cap = a.ids.shape[-1], a.d_ids.shape[-1]
    from crdt_tpu.parallel.collective import shard_local_merge_fn

    compiled = shard_local_merge_fn(mesh, "objects", m_cap, d_cap).lower(
        tuple(jax.tree_util.tree_leaves(a_sharded)),
        tuple(jax.tree_util.tree_leaves(b_sharded)),
    ).compile()
    hlo = compiled.as_text()
    assert_no_collectives(hlo, "shard-local merge")


# -- LWWReg / MVReg / GSet collective joins ----------------------------------


def test_allgather_join_lww_matches_scalar():
    """Marker-argmax collective join (`lwwreg.rs:43-67`) == scalar N-way
    left fold, on every device (BASELINE config 5's join path)."""
    from crdt_tpu.batch import LWWRegBatch
    from crdt_tpu.parallel import allgather_join_lww
    from crdt_tpu.scalar.lwwreg import LWWReg

    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    rng = np.random.RandomState(11)
    n = 24
    # distinct markers per (replica, object) => no conflicts; value is a
    # function of the marker so ties (none here) would agree anyway
    markers = rng.permutation(8 * n).reshape(8, n) + 1
    fleet = [
        [LWWReg(val=int(markers[r, i]) * 7, marker=int(markers[r, i]))
         for i in range(n)]
        for r in range(8)
    ]

    batches = [LWWRegBatch.from_scalar(row, uni) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    joined, conflict = allgather_join_lww(stacked, mesh, axis="replicas")
    assert not bool(jnp.any(conflict))

    expected = []
    for i in range(n):
        acc = fleet[0][i].clone()
        for r in range(1, 8):
            acc.merge(fleet[r][i])
        expected.append(acc)
    for r in range(8):
        shard = LWWRegBatch(vals=joined.vals[r], markers=joined.markers[r])
        assert shard.to_scalar(uni) == expected, f"replica shard {r} diverged"


def test_allgather_join_lww_conflict_surfaces():
    """An equal-marker/different-value pair anywhere in the fold raises
    host-side and the bitmap pinpoints the register — including the
    intermediate-max case where the global max marker is unique but two
    earlier replicas collide (`lwwreg.rs:56-66` pairwise semantics)."""
    from crdt_tpu.batch import LWWRegBatch
    from crdt_tpu.error import ConflictingMarker
    from crdt_tpu.parallel import allgather_join_lww
    from crdt_tpu.scalar.lwwreg import LWWReg

    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    n = 4
    fleet = [[LWWReg(val=100 + r, marker=1 + r) for _ in range(n)] for r in range(8)]
    # register 2: replicas 3 and 4 share marker 50 with different values,
    # replica 7 holds the unique global max 99
    fleet[3][2] = LWWReg(val=111, marker=50)
    fleet[4][2] = LWWReg(val=222, marker=50)
    fleet[7][2] = LWWReg(val=333, marker=99)

    batches = [LWWRegBatch.from_scalar(row, uni) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    with pytest.raises(ConflictingMarker):
        allgather_join_lww(stacked, mesh, axis="replicas")

    joined, conflict = allgather_join_lww(stacked, mesh, axis="replicas", check=False)
    bitmap = np.asarray(conflict[0])
    assert bitmap.tolist() == [False, False, True, False]
    # scalar fold agrees that the walk conflicts at register 2
    acc = fleet[0][2].clone()
    with pytest.raises(ConflictingMarker):
        for r in range(1, 8):
            acc.merge(fleet[r][2])


def test_allgather_join_mvreg_matches_scalar():
    """Antichain gather-fold join (`mvreg.rs:121-153`) == scalar N-way left
    fold on every device; concurrent values from different replicas all
    survive, dominated ones collapse."""
    from crdt_tpu.batch import MVRegBatch
    from crdt_tpu.parallel import allgather_join_mvreg
    from crdt_tpu.scalar.mvreg import MVReg

    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    rng = np.random.RandomState(13)
    n = 6
    fleet = []
    for r in range(8):
        row = []
        for i in range(n):
            reg = MVReg()
            for _ in range(rng.randint(0, 3)):
                actor = int(rng.randint(0, 8))
                ctx = reg.read().derive_add_ctx(actor)
                reg.apply(reg.set(int(rng.randint(0, 50)), ctx))
            row.append(reg)
        fleet.append(row)

    batches = [MVRegBatch.from_scalar(row, uni) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    joined = allgather_join_mvreg(stacked, mesh, axis="replicas")

    expected = []
    for i in range(n):
        acc = fleet[0][i].clone()
        for r in range(1, 8):
            acc.merge(fleet[r][i])
        expected.append(acc)
    for r in range(8):
        shard = MVRegBatch(clocks=joined.clocks[r], vals=joined.vals[r])
        got = shard.to_scalar(uni)
        # MVReg equality is set-equality over (clock, val) pairs
        # (`mvreg.rs:74-96`)
        assert got == expected, f"replica shard {r} diverged"


def test_allgather_join_gset_matches_scalar():
    """Bitmap-OR all-reduce == scalar N-way union (`gset.rs:30-34`)."""
    from crdt_tpu.batch import GSetBatch
    from crdt_tpu.parallel import allgather_join_gset
    from crdt_tpu.scalar.gset import GSet

    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    rng = np.random.RandomState(17)
    n, cap = 10, 16
    fleet = [
        [GSet({int(m) for m in rng.choice(12, rng.randint(0, 6), replace=False)})
         for _ in range(n)]
        for _ in range(8)
    ]

    batches = [GSetBatch.from_scalar(row, uni, cap) for row in fleet]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    joined = allgather_join_gset(stacked, mesh, axis="replicas")

    expected = []
    for i in range(n):
        acc = fleet[0][i].clone()
        for r in range(1, 8):
            acc.merge(fleet[r][i])
        expected.append(acc)
    for r in range(8):
        shard = GSetBatch(bits=joined.bits[r])
        assert shard.to_scalar(uni) == expected, f"replica shard {r} diverged"


@pytest.mark.parametrize("seed", [29, 31])
def test_allgather_join_lww_random_histories(seed):
    """Randomized LWW fleets (distinct markers): collective join == scalar
    N-way fold on every replica row."""
    from crdt_tpu.batch import LWWRegBatch
    from crdt_tpu.parallel import allgather_join_lww
    from crdt_tpu.scalar.lwwreg import LWWReg

    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    rng = np.random.RandomState(seed)
    n = 10
    markers = rng.permutation(8 * n).reshape(8, n) + 1
    fleet = []
    for r in range(8):
        row = []
        for i in range(n):
            reg = LWWReg()
            m = int(markers[r, i])
            # the write plus an idempotent redelivery (equal marker, same
            # value — a no-op, not a conflict); markers are a global
            # permutation so there are no cross-replica ties
            reg.update(val=m * 13, marker=m)
            reg.update(val=m * 13, marker=m)
            row.append(reg)
        fleet.append(row)
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[LWWRegBatch.from_scalar(row, uni) for row in fleet],
    )
    joined, conflict = allgather_join_lww(stacked, mesh)
    assert not bool(jnp.any(conflict))
    expected = []
    for i in range(n):
        acc = fleet[0][i].clone()
        for r in range(1, 8):
            acc.merge(fleet[r][i])
        expected.append(acc)
    for r in range(8):
        got = LWWRegBatch(vals=joined.vals[r], markers=joined.markers[r]).to_scalar(uni)
        assert got == expected, f"replica shard {r} diverged (seed {seed})"


@pytest.mark.parametrize("seed", [37, 41])
def test_allgather_join_mvreg_random_histories(seed):
    """Randomized MVReg op histories incl. dominating overwrites: the
    collective join keeps exactly the mutually-undominated values the
    scalar N-way fold keeps."""
    from crdt_tpu.batch import MVRegBatch
    from crdt_tpu.parallel import allgather_join_mvreg
    from crdt_tpu.scalar.mvreg import MVReg

    mesh = make_mesh({"replicas": 8})
    uni = small_universe()
    rng = np.random.RandomState(seed)
    n = 6
    fleet = []
    for r in range(8):
        row = []
        for i in range(n):
            reg = MVReg()
            for _ in range(rng.randint(0, 4)):
                actor = int(rng.randint(0, 8))
                ctx = reg.read().derive_add_ctx(actor)
                reg.apply(reg.set(int(rng.randint(0, 40)), ctx))
            row.append(reg)
        fleet.append(row)
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[MVRegBatch.from_scalar(row, uni) for row in fleet],
    )
    joined = allgather_join_mvreg(stacked, mesh)
    expected = []
    for i in range(n):
        acc = fleet[0][i].clone()
        for r in range(1, 8):
            acc.merge(fleet[r][i])
        expected.append(acc)
    for r in range(8):
        got = MVRegBatch(clocks=joined.clocks[r], vals=joined.vals[r]).to_scalar(uni)
        assert got == expected, f"replica shard {r} diverged (seed {seed})"


def test_sharded_truncate_matches_unsharded():
    """Causal::truncate is elementwise over the object axis: on a sharded
    fleet it must match the unsharded result and, under ``shard_map``,
    compile with zero cross-device traffic (`orswot.rs:159-172`)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from crdt_tpu.batch.orswot_batch import _truncate

    mesh = make_mesh({"objects": 8})
    uni = small_universe()
    fleet = random_orswots(seed=21, n_replicas=1, n_objects=32)[0]
    batch = OrswotBatch.from_scalar(fleet, uni)

    # truncate each object by its own clock's GLB with a fixed horizon
    rng = np.random.RandomState(3)
    horizon = jnp.asarray(
        rng.randint(0, 4, size=batch.clock.shape), dtype=batch.clock.dtype
    )
    expected = batch.truncate(horizon).to_scalar(uni)

    sharded = shard_batch(batch, mesh, "objects")
    got = sharded.truncate(horizon).to_scalar(uni)
    assert got == expected

    m_cap, d_cap = batch.ids.shape[-1], batch.d_ids.shape[-1]
    spec = P("objects")
    fn = shard_map(
        partial(_truncate, m_cap=m_cap, d_cap=d_cap),
        mesh=mesh,
        in_specs=(spec,) * 6,
        out_specs=((spec,) * 5, spec),
        check_vma=False,
    )
    args = (sharded.clock, sharded.ids, sharded.dots,
            sharded.d_ids, sharded.d_clocks, horizon)
    (state5, overflow) = fn(*args)
    got_local = OrswotBatch(*state5).to_scalar(uni)
    assert got_local == expected

    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert_no_collectives(hlo, "shard-local truncate")
