"""Host-level join executor: elastic capacity recovery + transient retry
(SURVEY.md §5 'failure detection / elastic recovery')."""

import numpy as np
import pytest

from crdt_tpu import Orswot
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.config import CrdtConfig
from crdt_tpu.parallel import JoinError, JoinExecutor, JoinStats, join_all
from crdt_tpu.utils.interning import Universe


def _universe(m=2, d=2, a=8):
    return Universe(CrdtConfig(num_actors=a, member_capacity=m, deferred_capacity=d))


def _fleet(uni, rows):
    """rows: list of lists of (member, actor) adds — one Orswot per list."""
    out = []
    for row in rows:
        s = Orswot()
        for member, actor in row:
            s.apply(s.add(member, s.value().derive_add_ctx(actor)))
        out.append(s)
    return out


def test_join_all_matches_scalar_fold():
    uni = _universe(m=8)
    fleets = [
        _fleet(uni, [[("a", 0), ("b", 0)]]),
        _fleet(uni, [[("c", 1)]]),
        _fleet(uni, [[("a", 2), ("d", 2)]]),
    ]
    batches = [OrswotBatch.from_scalar(f, uni) for f in fleets]
    stats = JoinStats()
    joined = JoinExecutor().join_all(batches, stats=stats)
    assert stats.joins == 3  # 2 folds + plunger
    assert stats.overflow_regrows == 0
    expected = Orswot()
    for f in fleets:
        expected.merge(f[0])
    expected.merge(Orswot())
    assert joined.to_scalar(uni)[0] == expected


def test_overflow_triggers_regrowth():
    # capacity 2, but the union of members is 6 → must regrow to succeed
    uni = _universe(m=2)
    rows = [
        [[("a", 0), ("b", 0)]],
        [[("c", 1), ("d", 1)]],
        [[("e", 2), ("f", 2)]],
    ]
    batches = [OrswotBatch.from_scalar(_fleet(uni, r), uni) for r in rows]
    stats = JoinStats()
    joined = JoinExecutor().join_all(batches, stats=stats)
    assert stats.overflow_regrows >= 1
    assert stats.final_member_capacity >= 6
    assert joined.value_sets(uni)[0] == {"a", "b", "c", "d", "e", "f"}


def test_only_overflowed_axis_regrows():
    """A deferred-table overflow must not double the (much larger) member
    axis — the error names the axis and the executor grows only it."""
    from crdt_tpu.scalar.ctx import RmCtx
    from crdt_tpu.scalar.vclock import VClock

    uni = Universe(CrdtConfig(num_actors=8, member_capacity=4, deferred_capacity=1))

    def deferred_state(actor, counter, member):
        s = Orswot()
        c = VClock()
        c.witness(actor, counter)
        s.apply(s.remove(member, RmCtx(clock=c)))
        assert s.deferred
        return s

    batches = [
        OrswotBatch.from_scalar([deferred_state(1, 5, "x")], uni),
        OrswotBatch.from_scalar([deferred_state(2, 5, "y")], uni),
    ]
    stats = JoinStats()
    joined = JoinExecutor().join_all(batches, stats=stats)
    assert stats.overflow_regrows >= 1
    assert stats.final_deferred_capacity > 1
    assert stats.final_member_capacity == 4, "member axis grew needlessly"
    assert len([i for i in joined.to_scalar(uni)[0].deferred]) == 2


def test_regrow_with_tracing_enabled_does_not_collide_in_registry():
    """Regression: with spans enabled (CRDT_TRACE=1 / --metrics-port),
    the ``executor.regrow`` span forwards a histogram into the obs
    registry while the recovery counter lives under
    ``executor.recovery.regrow`` — the names must stay disjoint, or the
    registry's one-type-per-name claim raises ValueError out of
    ``join_all`` instead of recovering."""
    from crdt_tpu.obs import metrics as obs_metrics
    from crdt_tpu.utils import tracing

    uni = _universe(m=2)
    rows = [
        [[("a", 0), ("b", 0)]],
        [[("c", 1), ("d", 1)]],
        [[("e", 2), ("f", 2)]],
    ]
    batches = [OrswotBatch.from_scalar(_fleet(uni, r), uni) for r in rows]
    stats = JoinStats()
    tracing.enable(True)
    try:
        joined = JoinExecutor().join_all(batches, stats=stats)
    finally:
        tracing.enable(False)
    assert stats.overflow_regrows >= 1
    assert joined.value_sets(uni)[0] == {"a", "b", "c", "d", "e", "f"}
    snap = obs_metrics.registry().snapshot()
    assert snap["counters"]["executor.recovery.regrow"] >= 1
    assert snap["histograms"]["executor.regrow"]["count"] >= 1


def test_overflow_beyond_max_capacity_raises():
    uni = _universe(m=2)
    rows = [
        [[("a", 0), ("b", 0)]],
        [[("c", 1), ("d", 1)]],
        [[("e", 2), ("f", 2)]],
    ]
    batches = [OrswotBatch.from_scalar(_fleet(uni, r), uni) for r in rows]
    with pytest.raises(JoinError, match="max_capacity"):
        JoinExecutor(max_capacity=4).join_all(batches)


def test_transient_failures_requeued():
    uni = _universe(m=8)
    batches = [
        OrswotBatch.from_scalar(_fleet(uni, [[("a", 0)]]), uni),
        OrswotBatch.from_scalar(_fleet(uni, [[("b", 1)]]), uni),
    ]

    class Flaky:
        """Duck-typed batch whose merge fails transiently twice."""

        def __init__(self, inner, failures):
            self.inner = inner
            self.failures = failures

        member_capacity = property(lambda self: self.inner.member_capacity)
        deferred_capacity = property(lambda self: self.inner.deferred_capacity)

        def with_capacity(self, m, d):
            return Flaky(self.inner.with_capacity(m, d), self.failures)

        def merge(self, other, check=True):
            if self.failures:
                self.failures.pop()
                raise RuntimeError("simulated device preemption")
            inner = other.inner if isinstance(other, Flaky) else other
            return Flaky(self.inner.merge(inner, check=check), self.failures)

    stats = JoinStats()
    joined = JoinExecutor(max_retries=2, retry_backoff_s=0).join_all(
        [Flaky(batches[0], ["x", "y"]), Flaky(batches[1], [])], stats=stats
    )
    assert stats.transient_retries == 2
    assert joined.inner.value_sets(uni)[0] == {"a", "b"}


def test_transient_failures_exhaust_retries():
    uni = _universe(m=8)
    b = OrswotBatch.from_scalar(_fleet(uni, [[("a", 0)]]), uni)

    class AlwaysDown:
        member_capacity = 8
        deferred_capacity = 2

        def with_capacity(self, m, d):
            return self

        def merge(self, other, check=True):
            raise RuntimeError("device gone")

    with pytest.raises(JoinError, match="retries"):
        JoinExecutor(max_retries=1, retry_backoff_s=0).join_all([AlwaysDown(), b])


@pytest.mark.parametrize("msg", [
    "RESOURCE_EXHAUSTED: Out of memory while trying to allocate 4.1G",
    "Resource exhausted: HBM",
])
def test_device_oom_is_not_retried(msg):
    """An HBM OOM is deterministic at a given shape: it surfaces as
    itself at once, never requeued or hidden behind JoinError."""
    uni = _universe(m=8)
    b = OrswotBatch.from_scalar(_fleet(uni, [[("a", 0)]]), uni)
    calls = []

    class OutOfMemory:
        member_capacity = 8
        deferred_capacity = 2

        def with_capacity(self, m, d):
            return self

        def merge(self, other, check=True):
            calls.append(1)
            raise RuntimeError(msg)

    with pytest.raises(RuntimeError, match="(?i)exhausted") as exc:
        JoinExecutor(max_retries=3, retry_backoff_s=0).join_all(
            [OutOfMemory(), b])
    assert not isinstance(exc.value, JoinError)
    assert len(calls) == 1


def test_mismatched_capacities_equalized():
    uni = _universe(m=4)
    b_small = OrswotBatch.from_scalar(_fleet(uni, [[("a", 0)]]), uni)
    b_big = OrswotBatch.from_scalar(
        _fleet(uni, [[("b", 1), ("c", 1), ("d", 1)]]), uni
    ).with_capacity(8, 4)
    joined = join_all([b_small, b_big])
    assert joined.member_capacity == 8  # equalized up, not down
    assert joined.value_sets(uni)[0] == {"a", "b", "c", "d"}


def test_with_capacity_replica_stacked():
    """Regrowth must handle arbitrary leading batch axes (replica stacks)."""
    import jax
    import jax.numpy as jnp

    uni = _universe(m=2)
    rows = [OrswotBatch.from_scalar(_fleet(uni, [[("a", 0)]]), uni) for _ in range(3)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)
    grown = stacked.with_capacity(4, 4)
    assert grown.ids.shape == (3, 1, 4)
    assert grown.dots.shape == (3, 1, 4, uni.config.num_actors)
    assert grown.d_clocks.shape == (3, 1, 4, uni.config.num_actors)
    # live slots untouched
    assert jnp.array_equal(grown.ids[..., :2], stacked.ids)


def test_with_capacity_cannot_shrink():
    uni = _universe(m=4)
    b = OrswotBatch.from_scalar(_fleet(uni, [[("a", 0)]]), uni)
    with pytest.raises(ValueError, match="shrink"):
        b.with_capacity(2, 2)


def test_non_overflow_value_errors_propagate():
    uni = _universe(m=8)
    b = OrswotBatch.from_scalar(_fleet(uni, [[("a", 0)]]), uni)

    class Broken:
        member_capacity = 8
        deferred_capacity = 2

        def with_capacity(self, m, d):
            return self

        def merge(self, other, check=True):
            raise ValueError("shape mismatch")

    with pytest.raises(ValueError, match="shape mismatch"):
        JoinExecutor().join_all([Broken(), b])


class TestTreeStrategy:
    """join_all with strategy='tree' — the join_fleet schedule behind the
    same elastic recoveries as the sequential fold."""

    def _fleets(self, member_lists, uni):
        from crdt_tpu.batch import OrswotBatch
        from crdt_tpu.scalar.orswot import Orswot

        fleets = []
        for r, members in enumerate(member_lists):
            row = []
            for i, ms in enumerate(members):
                s = Orswot()
                for m in ms:
                    s.apply(s.add(m, s.value().derive_add_ctx(f"n{r}")))
                row.append(s)
            fleets.append(OrswotBatch.from_scalar(row, uni))
        return fleets

    def test_matches_sequential_strategy(self):
        from crdt_tpu.config import CrdtConfig
        from crdt_tpu.parallel.executor import JoinExecutor, JoinStats
        from crdt_tpu.utils.interning import Universe

        uni = Universe(CrdtConfig(num_actors=8, member_capacity=16,
                                  deferred_capacity=4))
        members = [
            [[f"a{i}", f"b{(i + r) % 5}"] for i in range(6)] for r in range(5)
        ]
        seq = JoinExecutor(strategy="sequential").join_all(
            self._fleets(members, uni)
        )
        stats = JoinStats()
        tree = JoinExecutor(strategy="tree").join_all(
            self._fleets(members, uni), stats=stats
        )
        assert tree.value_sets(uni) == seq.value_sets(uni)
        assert stats.joins == 5  # 4 tree merges + plunger

    def test_tree_overflow_regrows_all_fleets(self):
        from crdt_tpu.config import CrdtConfig
        from crdt_tpu.parallel.executor import JoinExecutor, JoinStats
        from crdt_tpu.utils.interning import Universe

        # disjoint members force the union past the starting capacity
        uni = Universe(CrdtConfig(num_actors=8, member_capacity=2,
                                  deferred_capacity=2))
        members = [[[f"r{r}m{j}" for j in range(2)] for _ in range(3)]
                   for r in range(4)]
        stats = JoinStats()
        out = JoinExecutor(strategy="tree").join_all(
            self._fleets(members, uni), stats=stats
        )
        assert stats.overflow_regrows >= 1
        assert out.member_capacity > 2
        got = out.value_sets(uni)
        want = {f"r{r}m{j}" for r in range(4) for j in range(2)}
        assert all(s == want for s in got)

    def test_auto_resolves_by_backend(self):
        from crdt_tpu.parallel.executor import JoinExecutor

        ex = JoinExecutor(strategy="auto")

        class HasFleet:
            @classmethod
            def join_fleet(cls, *a, **k):  # pragma: no cover - marker only
                raise NotImplementedError

        import jax

        expected = jax.default_backend() == "tpu"
        assert ex._use_tree([HasFleet(), HasFleet()]) is expected
        assert JoinExecutor(strategy="sequential")._use_tree(
            [HasFleet(), HasFleet()]
        ) is False
        import pytest

        with pytest.raises(ValueError, match="strategy"):
            JoinExecutor(strategy="bogus")._use_tree([HasFleet(), HasFleet()])

    def test_forced_tree_without_join_fleet_raises(self):
        import pytest

        from crdt_tpu.parallel.executor import JoinExecutor

        class NoFleet:
            pass

        with pytest.raises(ValueError, match="join_fleet"):
            JoinExecutor(strategy="tree")._use_tree([NoFleet(), NoFleet()])

    def test_module_level_join_all_forwards_strategy(self):
        from crdt_tpu.batch import OrswotBatch
        from crdt_tpu.config import CrdtConfig
        from crdt_tpu.parallel.executor import join_all
        from crdt_tpu.scalar.orswot import Orswot
        from crdt_tpu.utils.interning import Universe

        uni = Universe(CrdtConfig(num_actors=4, member_capacity=8,
                                  deferred_capacity=2))
        def fleet(tag):
            row = []
            for i in range(3):
                s = Orswot()
                s.apply(s.add(f"{tag}{i}", s.value().derive_add_ctx(tag)))
                row.append(s)
            return OrswotBatch.from_scalar(row, uni)

        out = join_all([fleet("x"), fleet("y")], strategy="tree")
        assert out.value_sets(uni) == [{f"x{i}", f"y{i}"} for i in range(3)]


# -- MVReg elasticity (the antichain axis under the generic protocol) --------


def _concurrent_regs(n_actors):
    """One register per replica, all written concurrently by distinct
    actors — the N-way join's antichain holds all N values."""
    from crdt_tpu.scalar.mvreg import MVReg

    regs = []
    for actor in range(n_actors):
        r = MVReg()
        r.apply(r.set(f"v{actor}", r.read().derive_add_ctx(actor)))
        regs.append(r)
    return regs


def test_mvreg_overflow_triggers_regrowth():
    """mv_capacity 2, five concurrent values: the executor must regrow the
    antichain axis (reported under the protocol's member slot) and the
    joined register must hold all five concurrent values."""
    from crdt_tpu.batch import MVRegBatch

    uni = Universe(CrdtConfig(num_actors=8, mv_capacity=2))
    regs = _concurrent_regs(5)
    batches = [MVRegBatch.from_scalar([r], uni) for r in regs]
    stats = JoinStats()
    joined = JoinExecutor().join_all(batches, plunger=False, stats=stats)
    assert stats.overflow_regrows >= 1
    assert stats.final_member_capacity >= 5
    assert stats.final_deferred_capacity == 0

    expected = regs[0].clone()
    for r in regs[1:]:
        expected.merge(r)
    got = joined.to_scalar(uni)[0]
    assert got == expected and len(got.vals) == 5


def test_mvreg_with_capacity_contract():
    from crdt_tpu.batch import MVRegBatch
    from crdt_tpu.error import CapacityOverflowError

    uni = Universe(CrdtConfig(num_actors=8, mv_capacity=2))
    regs = _concurrent_regs(3)
    a = MVRegBatch.from_scalar([regs[0]], uni)
    b = MVRegBatch.from_scalar([regs[1]], uni)
    c = MVRegBatch.from_scalar([regs[2]], uni)
    with pytest.raises(CapacityOverflowError) as ei:
        a.merge(b).merge(c)
    assert ei.value.member and not ei.value.deferred

    grown = a.with_capacity(4)
    assert grown.member_capacity == 4 and grown.deferred_capacity == 0
    # padded slots are dead (empty clocks); state is unchanged
    assert grown.to_scalar(uni) == a.to_scalar(uni)
    with pytest.raises(ValueError, match="cannot shrink"):
        grown.with_capacity(2)
    with pytest.raises(ValueError, match="no deferred axis"):
        a.with_capacity(4, 2)


# -- Map elasticity (key + deferred + NESTED value axes grow together) -------


def _map_writer(key_vals, actor):
    """A Map<int, MVReg> with one Put per (key, val), all by ``actor``."""
    from crdt_tpu import Map, MVReg
    from crdt_tpu.scalar.map import Up
    from crdt_tpu.scalar.mvreg import Put
    from crdt_tpu.scalar.vclock import Dot, VClock

    m = Map(MVReg)
    for c, (key, val) in enumerate(key_vals, start=1):
        m.apply(Up(dot=Dot(actor, c), key=key,
                   op=Put(clock=VClock({actor: c}), val=val)))
    return m


def test_map_key_overflow_triggers_regrowth():
    """key_capacity 2, six distinct keys across the fleet: the executor
    regrows the key axis and the joined map matches the scalar fold."""
    from crdt_tpu.batch import MapBatch, MVRegKernel

    uni = Universe(CrdtConfig(num_actors=8, key_capacity=2, mv_capacity=4,
                              deferred_capacity=2))
    vk = MVRegKernel.from_config(uni.config)
    maps = [
        _map_writer([(0, 1), (1, 2)], actor=0),
        _map_writer([(2, 3), (3, 4)], actor=1),
        _map_writer([(4, 5), (5, 6)], actor=2),
    ]
    batches = [MapBatch.from_scalar([m], uni, vk) for m in maps]
    stats = JoinStats()
    joined = JoinExecutor().join_all(batches, plunger=False, stats=stats)
    assert stats.overflow_regrows >= 1
    assert stats.final_member_capacity >= 6

    expected = maps[0].clone()
    for m in maps[1:]:
        expected.merge(m)
    assert joined.to_scalar(uni)[0] == expected


def test_map_nested_value_overflow_triggers_regrowth():
    """mv_capacity 1, three concurrent writers to the SAME key: the
    overflow is in the NESTED antichain, which only the scaled value
    kernel can absorb — the collapsed flag must still converge."""
    from crdt_tpu.batch import MapBatch, MVRegKernel

    uni = Universe(CrdtConfig(num_actors=8, key_capacity=4, mv_capacity=1,
                              deferred_capacity=2))
    vk = MVRegKernel.from_config(uni.config)
    maps = [_map_writer([(7, 10 + actor)], actor=actor) for actor in range(3)]
    batches = [MapBatch.from_scalar([m], uni, vk) for m in maps]
    stats = JoinStats()
    joined = JoinExecutor().join_all(batches, plunger=False, stats=stats)
    assert stats.overflow_regrows >= 1

    expected = maps[0].clone()
    for m in maps[1:]:
        expected.merge(m)
    got = joined.to_scalar(uni)[0]
    assert got == expected
    # all three concurrent values survive in the nested antichain
    assert sorted(got.entries[7].val.read().val) == [10, 11, 12]


def test_map_with_capacity_contract():
    from crdt_tpu.batch import MapBatch, MVRegKernel

    uni = Universe(CrdtConfig(num_actors=8, key_capacity=2, mv_capacity=2,
                              deferred_capacity=2))
    vk = MVRegKernel.from_config(uni.config)
    b = MapBatch.from_scalar([_map_writer([(0, 1)], actor=0)], uni, vk)
    grown = b.with_capacity(5, 2)
    # named axes pad EXACTLY (executor max_capacity bound holds for them);
    # nested antichain scales by the key factor ceil(5/2)=3
    assert grown.member_capacity == 5 and grown.deferred_capacity == 2
    assert grown.kernel.val_kernel.mv_capacity == 6
    assert grown.to_scalar(uni) == b.to_scalar(uni)
    with pytest.raises(ValueError, match="cannot shrink"):
        grown.with_capacity(2, 2)
    # capacity-mismatched batches unify automatically on merge
    merged = grown.merge(b)
    assert merged.kernel == grown.kernel
    assert merged.to_scalar(uni) == b.to_scalar(uni)


def test_map_merge_unifies_path_dependent_kernels():
    """Stepwise vs one-shot regrowth compound the NESTED capacities
    differently; merge must unify to the pointwise max, not raise —
    the shape JoinExecutor(max_capacity=...) produces when a clamp makes
    one side regrow in more steps than the other."""
    from crdt_tpu.batch import MapBatch, MVRegKernel
    from crdt_tpu.scalar.mvreg import MVReg

    uni = Universe(CrdtConfig(num_actors=8, key_capacity=2, mv_capacity=2,
                              deferred_capacity=2))
    vk = MVRegKernel.from_config(uni.config)
    a = MapBatch.from_scalar([_map_writer([(0, 1)], actor=0)], uni, vk)
    b = MapBatch.from_scalar([_map_writer([(1, 2)], actor=1)], uni, vk)
    a2 = a.with_capacity(4, 4).with_capacity(6, 6)   # nested mv 2->4->8
    b2 = b.with_capacity(6, 6)                       # nested mv 2->6
    assert a2.kernel != b2.kernel
    merged = a2.merge(b2)
    assert merged.kernel.val_kernel.mv_capacity == 8  # pointwise max
    want = _map_writer([(0, 1)], actor=0)
    want.merge(_map_writer([(1, 2)], actor=1))
    assert merged.to_scalar(uni)[0] == want

    # a genuinely incompatible kernel still raises
    other_uni = Universe(CrdtConfig(num_actors=4, key_capacity=2,
                                    mv_capacity=2, deferred_capacity=2))
    c = MapBatch.from_scalar(
        [_map_writer([(0, 1)], actor=0)], other_uni,
        MVRegKernel.from_config(other_uni.config),
    )
    with pytest.raises(ValueError, match="incompatible"):
        a2.merge(c)
