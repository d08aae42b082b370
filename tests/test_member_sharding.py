"""Member-universe sharding (SURVEY.md §5's context-parallel analogue):
huge sets hash-partitioned across a mesh axis, merged shard-locally,
clocks joined globally — bit-equal to the scalar oracle.

Reference semantics being preserved: `/root/reference/src/orswot.rs:89-156`
(merge) and `orswot.rs:195-211` (deferred removes).
"""

import numpy as np
import pytest

from conftest import assert_no_collectives

import jax

from crdt_tpu.batch import OrswotBatch
from crdt_tpu.config import CrdtConfig
from crdt_tpu.parallel.member_sharding import (
    member_sharded_merge,
    partition_dense,
    rebroadcast_clock,
    sharded_apply_add,
    unpartition_dense,
)
from crdt_tpu.parallel.mesh import make_mesh
from crdt_tpu.scalar.orswot import Orswot
from crdt_tpu.utils.interning import Universe

N_SHARDS = 8
M_CAP = 64          # logical member capacity (exceeds any single shard's)
M_CAP_SHARD = 16    # per-device member table — 40-member sets don't fit one
D_CAP = 8
D_CAP_SHARD = 4


def big_universe():
    return Universe(
        CrdtConfig(num_actors=8, member_capacity=M_CAP, deferred_capacity=D_CAP)
    )


def build_replicas(seed, n_members=40, n_objects=4):
    """Two replica fleets of sets whose member count exceeds M_CAP_SHARD."""
    rng = np.random.RandomState(seed)
    fleets = [[], []]
    for _ in range(n_objects):
        base = [int(x) for x in rng.choice(1 << 16, size=n_members, replace=False)]
        for f in range(2):
            s = Orswot()
            for m in base:
                if rng.rand() < 0.8:  # each replica has most members
                    actor = int(rng.randint(0, 8))
                    ctx = s.value().derive_add_ctx(actor)
                    s.apply(s.add(m, ctx))
            # a few causal removes
            for m in base[:3]:
                if m in s.value().val and rng.rand() < 0.5:
                    s.apply(s.remove(m, s.contains(m).derive_rm_ctx()))
            fleets[f].append(s)
    return fleets


def to_sharded(states, uni, mesh):
    batch = OrswotBatch.from_scalar(states, uni)
    parts = partition_dense(
        batch.clock, batch.ids, batch.dots, batch.d_ids, batch.d_clocks,
        N_SHARDS, M_CAP_SHARD, D_CAP_SHARD,
    )
    from crdt_tpu.parallel.mesh import shard_batch  # noqa: F401  (spec helper below)
    from jax.sharding import NamedSharding, PartitionSpec as P

    put = lambda x: jax.device_put(
        jax.numpy.asarray(x), NamedSharding(mesh, P("members"))
    )
    return tuple(put(x) for x in parts)


def from_sharded(state, uni):
    arrays = unpartition_dense(*state, m_cap=M_CAP, d_cap=D_CAP)
    import jax.numpy as jnp

    return OrswotBatch(*(jnp.asarray(x) for x in arrays)).to_scalar(uni)


def scalar_merge(a_states, b_states):
    out = []
    for a, b in zip(a_states, b_states):
        m = a.clone()
        m.merge(b)
        out.append(m)
    return out


def test_huge_set_merge_matches_scalar_oracle():
    """A set larger than one device's member table merges bit-equal to the
    scalar reference across a member-sharded mesh."""
    mesh = make_mesh({"members": N_SHARDS})
    uni = big_universe()
    fleet_a, fleet_b = build_replicas(seed=11)
    assert max(len(s.entries) for s in fleet_a) > M_CAP_SHARD  # genuinely huge

    sharded_a = to_sharded(fleet_a, uni, mesh)
    sharded_b = to_sharded(fleet_b, uni, mesh)
    merged = member_sharded_merge(sharded_a, sharded_b, mesh, "members")
    got = from_sharded(merged, uni)
    want = scalar_merge(fleet_a, fleet_b)
    for g, w in zip(got, want):
        assert g.value().val == w.value().val
        assert g.clock == w.clock
        assert g.entries == w.entries


def test_partition_roundtrip_identity():
    mesh = make_mesh({"members": N_SHARDS})
    uni = big_universe()
    fleet_a, _ = build_replicas(seed=13, n_objects=2)
    batch = OrswotBatch.from_scalar(fleet_a, uni)
    parts = partition_dense(
        batch.clock, batch.ids, batch.dots, batch.d_ids, batch.d_clocks,
        N_SHARDS, M_CAP_SHARD, D_CAP_SHARD,
    )
    back = unpartition_dense(*parts, m_cap=M_CAP, d_cap=D_CAP)
    import jax.numpy as jnp

    restored = OrswotBatch(*(jnp.asarray(x) for x in back)).to_scalar(uni)
    for r, s in zip(restored, fleet_a):
        assert r == s


def test_deferred_remove_routes_and_resolves_across_shards():
    """A causally-future remove buffers on the owning member's shard and
    resolves once a merge brings the covering clock — the `orswot.rs:195-211`
    dance, shard-locally."""
    mesh = make_mesh({"members": N_SHARDS})
    uni = big_universe()

    # replica A: many members incl. the victim, with a clock the remover
    # hasn't seen; replica B: a fresh state carrying only a future remove
    a = Orswot()
    members = list(range(100, 140))
    for m in members:
        a.apply(a.add(m, a.value().derive_add_ctx("w1")))
    victim = members[5]

    # build the future remove against a *later* state of A
    a_future = a.clone()
    a_future.apply(a_future.add(999, a_future.value().derive_add_ctx("w2")))
    rm = a_future.remove(victim, a_future.contains(victim).derive_rm_ctx())

    b = Orswot()
    b.apply(rm)  # clock ahead of b's state ⇒ defers
    assert b.deferred

    want = a_future.clone()
    want.merge(b)
    want.merge(Orswot())  # plunger

    sharded_a = to_sharded([a_future], uni, mesh)
    sharded_b = to_sharded([b], uni, mesh)
    merged = member_sharded_merge(sharded_a, sharded_b, mesh, "members")
    empty = to_sharded([Orswot()], uni, mesh)
    merged = member_sharded_merge(merged, empty, mesh, "members")
    got = from_sharded(merged, uni)[0]
    assert victim not in got.value().val
    assert got.value().val == want.value().val
    assert got.entries == want.entries


def test_sharded_apply_add_then_merge_coherent():
    """Adds route to the owning shard; after the clock rebroadcast the
    sharded state merges identically to the scalar op path."""
    mesh = make_mesh({"members": N_SHARDS})
    uni = big_universe()
    for i in range(4):
        uni.actors.intern(i)

    s = Orswot()
    for m in range(200, 230):
        s.apply(s.add(m, s.value().derive_add_ctx(0)))
    sharded = to_sharded([s], uni, mesh)

    # one add per object (N=1): actor 1 adds member 777
    want = s.clone()
    ctx = want.value().derive_add_ctx(1)
    want.apply(want.add(777, ctx))

    actor_idx = np.array([uni.actors.intern(1)], dtype=np.int32)
    counter = np.asarray([ctx.dot.counter], dtype=np.asarray(sharded[0]).dtype)
    member_id = np.array([uni.members.intern(777)], dtype=np.int32)
    out = sharded_apply_add(
        sharded, jax.numpy.asarray(actor_idx), jax.numpy.asarray(counter),
        jax.numpy.asarray(member_id), mesh, "members",
    )
    got = from_sharded(out, uni)[0]
    assert got.value().val == want.value().val
    assert got.clock == want.clock

    # clock copies are coherent on every shard after rebroadcast
    clocks = np.asarray(out[0])
    for sh in range(1, N_SHARDS):
        np.testing.assert_array_equal(clocks[0], clocks[sh])


def test_apply_add_coherent_with_multiple_shard_rows_per_device():
    """n_shards > mesh size (K=2 shard rows per device): the clock
    rebroadcast must join across co-located rows too, not just
    row-for-row across devices."""
    mesh = make_mesh({"members": 4}, devices=jax.devices()[:4])  # 8 shards / 4 devices
    uni = big_universe()
    for i in range(4):
        uni.actors.intern(i)

    s = Orswot()
    for m in range(300, 330):
        s.apply(s.add(m, s.value().derive_add_ctx(0)))
    sharded = to_sharded([s], uni, mesh)

    want = s.clone()
    ctx = want.value().derive_add_ctx(1)
    want.apply(want.add(777, ctx))

    actor_idx = np.array([uni.actors.intern(1)], dtype=np.int32)
    counter = np.asarray([ctx.dot.counter], dtype=np.asarray(sharded[0]).dtype)
    member_id = np.array([uni.members.intern(777)], dtype=np.int32)
    out = sharded_apply_add(
        sharded, jax.numpy.asarray(actor_idx), jax.numpy.asarray(counter),
        jax.numpy.asarray(member_id), mesh, "members",
    )
    got = from_sharded(out, uni)[0]
    assert got.value().val == want.value().val
    assert got.clock == want.clock
    clocks = np.asarray(out[0])
    for sh in range(1, N_SHARDS):
        np.testing.assert_array_equal(clocks[0], clocks[sh])


def test_member_sharded_merge_emits_no_collectives():
    """The merge itself is provably shard-local (the collective lives only
    in rebroadcast_clock / value materialization)."""
    mesh = make_mesh({"members": N_SHARDS})
    uni = big_universe()
    fleet_a, fleet_b = build_replicas(seed=17, n_objects=2)
    sharded_a = to_sharded(fleet_a, uni, mesh)
    sharded_b = to_sharded(fleet_b, uni, mesh)

    import functools

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from crdt_tpu.ops import orswot_ops

    spec = P("members")

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=((spec,) * 5, (spec,) * 5),
        out_specs=(spec,) * 5,
        check_vma=False,
    )
    def _local(sa, sb):
        return orswot_ops.merge(*sa, *sb, M_CAP_SHARD, D_CAP_SHARD)[:5]

    hlo = _local.lower(tuple(sharded_a), tuple(sharded_b)).compile().as_text()
    assert_no_collectives(hlo, "member-sharded merge")
