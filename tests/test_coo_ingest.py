"""Columnar (COO) ingest/egress on OrswotBatch.

`from_coo` must build the same CRDT states `from_scalar` builds (slot
order may differ — canonical ascending-id vs insertion order — which is
internal representation, not state), and `from_coo(to_coo(b))` must be a
state-equivalent round trip including deferred rows.
"""

import numpy as np
import pytest

from crdt_tpu.batch import OrswotBatch
from crdt_tpu.config import CrdtConfig
from crdt_tpu.scalar.orswot import Orswot
from crdt_tpu.scalar.vclock import VClock
from crdt_tpu.utils.interning import Universe


def _universe(m=4, d=2):
    return Universe(CrdtConfig(num_actors=8, member_capacity=m,
                               deferred_capacity=d, counter_bits=32))


def _random_states(rng, n, uni):
    states = []
    for _ in range(n):
        s = Orswot()
        for _ in range(rng.randint(0, 4)):
            actor, member = int(rng.randint(0, 8)), int(rng.randint(0, 12))
            ctx = s.value().derive_add_ctx(actor)
            s.apply(s.add(member, ctx))
        if rng.rand() < 0.4 and s.entries:
            # a causally-future remove that defers
            member = next(iter(s.entries))
            future = VClock({int(rng.randint(0, 8)): int(rng.randint(50, 60))})
            s.apply_remove(member, future)
        states.append(s)
    return states


def _coo_from_scalars(states, uni):
    """Columnar coordinates as a data pipeline would produce them."""
    co, ca, cc = [], [], []
    do, dm, da, dc = [], [], [], []
    qo, qr, qm = [], [], []
    ho, hr, ha, hc = [], [], [], []
    for i, s in enumerate(states):
        for actor, counter in s.clock.dots.items():
            co.append(i); ca.append(uni.actor_idx(actor)); cc.append(counter)
        for member, vc in s.entries.items():
            for actor, counter in vc.dots.items():
                do.append(i); dm.append(uni.member_id(member))
                da.append(uni.actor_idx(actor)); dc.append(counter)
        row = 0
        for ck, members in s.deferred.items():
            for member in members:
                qo.append(i); qr.append(row); qm.append(uni.member_id(member))
                for actor, counter in ck:
                    ho.append(i); hr.append(row)
                    ha.append(uni.actor_idx(actor)); hc.append(counter)
                row += 1
    arr = lambda xs, dt: np.asarray(xs, dtype=dt)
    return (
        (arr(co, np.int64), arr(ca, np.int32), arr(cc, np.uint32)),
        (arr(do, np.int64), arr(dm, np.int32), arr(da, np.int32), arr(dc, np.uint32)),
        (arr(qo, np.int64), arr(qr, np.int32), arr(qm, np.int32)),
        (arr(ho, np.int64), arr(hr, np.int32), arr(ha, np.int32), arr(hc, np.uint32)),
    )


def test_from_coo_matches_from_scalar():
    rng = np.random.RandomState(31)
    uni = _universe()
    states = _random_states(rng, 40, uni)
    want = OrswotBatch.from_scalar(states, uni)

    clock_c, dot_c, defm, defc = _coo_from_scalars(states, uni)
    got = OrswotBatch.from_coo(
        40, uni, clock_coords=clock_c, dot_coords=dot_c,
        deferred_members=defm, deferred_coords=defc,
    )
    # states must be equal; slot order is internal (canonical ascending id
    # for from_coo vs insertion order for from_scalar), so compare as CRDTs
    assert got.to_scalar(uni) == want.to_scalar(uni)


def test_coo_roundtrip():
    rng = np.random.RandomState(37)
    uni = _universe()
    states = _random_states(rng, 25, uni)
    batch = OrswotBatch.from_scalar(states, uni)
    clock_c, dot_c, defm, defc = batch.to_coo()
    back = OrswotBatch.from_coo(
        25, uni, clock_coords=clock_c, dot_coords=dot_c,
        deferred_members=defm, deferred_coords=defc,
    )
    assert back.to_scalar(uni) == batch.to_scalar(uni)


def test_from_coo_duplicate_coords_join_by_max():
    uni = _universe()
    actor = uni.actor_idx("a2")
    member = uni.member_id("widget")
    got = OrswotBatch.from_coo(
        1, uni,
        clock_coords=(np.array([0, 0]), np.array([actor, actor]), np.array([5, 9])),
        dot_coords=(np.array([0, 0]), np.array([member, member]),
                    np.array([actor, actor]), np.array([9, 5])),
    )
    s = got.to_scalar(uni)[0]
    assert s.clock.dots == {"a2": 9}
    assert s.entries == {"widget": VClock({"a2": 9})}


def test_from_coo_member_overflow_raises():
    uni = _universe(m=2)
    with pytest.raises(ValueError, match="member_capacity"):
        OrswotBatch.from_coo(
            1, uni,
            clock_coords=(np.array([]), np.array([]), np.array([])),
            dot_coords=(np.zeros(3, np.int64), np.array([1, 2, 3]),
                        np.zeros(3, np.int32), np.ones(3, np.uint32)),
        )


def test_from_coo_rejects_half_a_deferred_pair():
    uni = _universe()
    empty3 = (np.array([]), np.array([]), np.array([]))
    empty4 = empty3 + (np.array([]),)
    with pytest.raises(ValueError, match="supplied together"):
        OrswotBatch.from_coo(
            1, uni, clock_coords=empty3, dot_coords=empty4,
            deferred_members=(np.array([0]), np.array([0]), np.array([1])),
        )


def test_from_coo_rejects_negative_member_and_row():
    uni = _universe()
    empty3 = (np.array([]), np.array([]), np.array([]))
    with pytest.raises(ValueError, match="negative member id"):
        OrswotBatch.from_coo(
            1, uni, clock_coords=empty3,
            dot_coords=(np.array([0]), np.array([-1]),
                        np.array([0]), np.array([5])),
        )
    with pytest.raises(ValueError, match="row indices"):
        OrswotBatch.from_coo(
            1, uni, clock_coords=empty3,
            dot_coords=empty3 + (np.array([]),),
            deferred_members=(np.array([0]), np.array([-1]), np.array([1])),
            deferred_coords=(np.array([0]), np.array([0]),
                             np.array([0]), np.array([5])),
        )


def test_from_coo_deferred_row_overflow_raises():
    uni = _universe(d=1)
    with pytest.raises(ValueError, match="deferred_capacity"):
        OrswotBatch.from_coo(
            1, uni,
            clock_coords=(np.array([]), np.array([]), np.array([])),
            dot_coords=(np.array([]), np.array([]), np.array([]), np.array([])),
            deferred_members=(np.array([0]), np.array([1]), np.array([4])),
            deferred_coords=(np.array([0]), np.array([1]),
                             np.array([0]), np.array([7])),
        )

def test_from_coo_rejects_negative_deferred_member():
    """A -1 (EMPTY) deferred member id would make the row invisible to
    kernels while its clock still scatters into d_clocks (advisor r2)."""
    uni = _universe()
    empty3 = (np.array([]), np.array([]), np.array([]))
    with pytest.raises(ValueError, match="negative member id.*deferred"):
        OrswotBatch.from_coo(
            1, uni, clock_coords=empty3,
            dot_coords=empty3 + (np.array([]),),
            deferred_members=(np.array([0]), np.array([0]), np.array([-1])),
            deferred_coords=(np.array([0]), np.array([0]),
                             np.array([0]), np.array([5])),
        )


def test_from_coo_rejects_conflicting_deferred_member_assignment():
    """Duplicate (obj, row) keys naming different members must raise, not
    silently last-write-win (deferred rows are assignments, not lattice
    cells — advisor r2)."""
    uni = _universe()
    empty3 = (np.array([]), np.array([]), np.array([]))
    with pytest.raises(ValueError, match="conflicting deferred_members"):
        OrswotBatch.from_coo(
            2, uni, clock_coords=empty3,
            dot_coords=empty3 + (np.array([]),),
            deferred_members=(np.array([1, 0, 1]), np.array([0, 0, 0]),
                              np.array([3, 2, 4])),
            deferred_coords=(np.array([1, 0, 1]), np.array([0, 0, 0]),
                             np.array([0, 1, 2]), np.array([5, 5, 5])),
        )
    # duplicate (obj, row) with the SAME member id is idempotent re-ingest,
    # not a conflict
    b = OrswotBatch.from_coo(
        1, uni, clock_coords=empty3,
        dot_coords=empty3 + (np.array([]),),
        deferred_members=(np.array([0, 0]), np.array([0, 0]),
                          np.array([3, 3])),
        deferred_coords=(np.array([0, 0]), np.array([0, 0]),
                         np.array([0, 0]), np.array([5, 9])),
    )
    assert int(np.asarray(b.d_ids)[0, 0]) == 3
    assert int(np.asarray(b.d_clocks)[0, 0, 0]) == 9


class TestDeviceCellPaths:
    """The jitted compaction/expansion paths (`via_device=True`) exist so
    only compact columns cross the host<->device boundary on accelerator
    backends (dense planes are ~20x the column bytes).  Under
    the CPU test backend they run the same jitted kernels and must be
    bit-identical to the host numpy paths."""

    def _planes(self, b):
        return (b.clock, b.ids, b.dots, b.d_ids, b.d_clocks)

    def test_from_scalar_device_expand_matches_host(self):
        rng = np.random.RandomState(7)
        uni = _universe()
        states = _random_states(rng, 40, uni)
        host = OrswotBatch.from_scalar(states, uni, via_device=False)
        dev = OrswotBatch.from_scalar(states, uni, via_device=True)
        for h, d in zip(self._planes(host), self._planes(dev)):
            assert np.array_equal(np.asarray(h), np.asarray(d))

    def test_from_coo_device_expand_matches_host_with_duplicates(self):
        uni = _universe()
        actor = uni.actor_idx("a2")
        member = uni.member_id("widget")
        kw = dict(
            clock_coords=(np.array([0, 0]), np.array([actor, actor]),
                          np.array([5, 9])),
            dot_coords=(np.array([0, 0]), np.array([member, member]),
                        np.array([actor, actor]), np.array([9, 5])),
        )
        host = OrswotBatch.from_coo(1, uni, via_device=False, **kw)
        dev = OrswotBatch.from_coo(1, uni, via_device=True, **kw)
        for h, d in zip(self._planes(host), self._planes(dev)):
            assert np.array_equal(np.asarray(h), np.asarray(d))

    def test_to_scalar_device_compact_matches_host(self):
        rng = np.random.RandomState(11)
        uni = _universe()
        states = _random_states(rng, 40, uni)
        batch = OrswotBatch.from_scalar(states, uni)
        assert batch.to_scalar(uni, via_device=True) == batch.to_scalar(
            uni, via_device=False
        )

    def test_to_coo_device_compact_matches_host(self):
        rng = np.random.RandomState(13)
        uni = _universe()
        states = _random_states(rng, 30, uni)
        batch = OrswotBatch.from_scalar(states, uni)
        for host_cols, dev_cols in zip(
            batch.to_coo(via_device=False), batch.to_coo(via_device=True)
        ):
            for h, d in zip(host_cols, dev_cols):
                assert np.array_equal(np.asarray(h), np.asarray(d))

    def test_empty_batch_device_paths(self):
        uni = _universe()
        batch = OrswotBatch.zeros(3, uni)
        assert batch.to_scalar(uni, via_device=True) == [
            Orswot(), Orswot(), Orswot()
        ]
        for cols in batch.to_coo(via_device=True):
            for c in cols:
                assert np.asarray(c).shape[0] == 0

    def test_from_coo_device_accepts_lists_and_empty_columns(self):
        # np.asarray([]) is float64; the device path must still index
        # planes with integer coordinates (code-review regression)
        uni = _universe()
        b = OrswotBatch.from_coo(
            2, uni, clock_coords=([], [], []), dot_coords=([], [], [], []),
            via_device=True,
        )
        assert b.to_scalar(uni) == [Orswot(), Orswot()]
        actor = uni.actor_idx("a1")
        member = uni.member_id("w")
        b2 = OrswotBatch.from_coo(
            2, uni,
            clock_coords=([0], [actor], [3]),
            dot_coords=([0], [member], [actor], [3]),
            via_device=True,
        )
        s = b2.to_scalar(uni)[0]
        assert s.entries == {"w": VClock({"a1": 3})}


def test_to_scalar_sliced_path_matches_monolithic(monkeypatch):
    """The host-path egress slicing (perf: superlinear per-call cost)
    must be invisible: sliced output == monolithic output, including a
    non-multiple tail slice and deferred rows."""
    import numpy as np

    from crdt_tpu.batch import orswot_batch as ob
    from crdt_tpu.batch.orswot_batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.scalar.orswot import Orswot
    from crdt_tpu.scalar.vclock import VClock
    from crdt_tpu.utils.interning import Universe

    rng = np.random.RandomState(9)
    states = []
    for i in range(23):
        s = Orswot()
        actor = int(rng.randint(0, 4))
        s.clock = VClock({actor: int(rng.randint(1, 9))})
        s.entries[int(rng.randint(0, 50))] = s.clock.clone()
        if i % 5 == 0:  # causally-future deferred remove
            s.deferred[VClock({actor: 99}).key()] = {int(rng.randint(0, 50))}
        states.append(s)

    uni = Universe(CrdtConfig(num_actors=4, member_capacity=4, deferred_capacity=2))
    batch = OrswotBatch.from_scalar(states, uni)

    # via_device pinned False so the sliced HOST path runs even when the
    # ambient backend is an accelerator (auto-detect would skip it)
    monolithic = batch.to_scalar(uni, via_device=False)
    monkeypatch.setattr(ob, "_EGRESS_SLICE", 4)  # force slicing + tail merge
    sliced = batch.to_scalar(uni, via_device=False)
    assert sliced == monolithic == states
    # 23 = 5 full slices of 4 + remainder 3 > slice/2=2 → own slice; also
    # cover the merge-into-previous case
    monkeypatch.setattr(ob, "_EGRESS_SLICE", 10)  # 23 → 10 + 13 (merged tail)
    assert batch.to_scalar(uni, via_device=False) == states
