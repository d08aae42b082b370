"""The ORSWOT row view the serve gather reads (crdt_tpu/serve/query.py).

Pins: (1) a gather from the view answers row for row what the scalar
``ReadCtx`` loop answers, and what a plain ``jnp.take`` gather of the
original planes answers — value reads, live and absent ``contains``
probes, empty slots, counters at the top of u32, padded filler rows, and
row widths on and off the 128-lane tile; (2) the view is built once per
snapshot: repeated frames hit, two threads racing on a fresh snapshot
build one, a write's new snapshot rebuilds with the new rows, the node
drops the old view before its fold, and a view dies with its snapshot.
"""

import gc
import sys
import threading
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu import serve
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.cluster import ClusterNode
from crdt_tpu.config import CrdtConfig
from crdt_tpu.oplog import OpLog
from crdt_tpu.scalar.orswot import Orswot
from crdt_tpu.serve import query
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe

pytestmark = pytest.mark.serve

TOP_U32 = 2**32 - 2


def _uni(a=8, m=16, bits=32):
    return Universe.identity(CrdtConfig(
        num_actors=a, member_capacity=m, deferred_capacity=2,
        counter_bits=bits))


def _scalar_fleet(n, seed, actors=4, members=24):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        s = Orswot()
        for _ in range(rng.randint(0, 7)):  # some sets stay empty
            s.apply(s.add(int(rng.randint(0, members)),
                          s.value().derive_add_ctx(int(
                              rng.randint(0, actors)))))
        if rng.rand() < 0.5 and s.value().val:
            m = sorted(s.value().val)[0]
            s.apply(s.remove(m, s.value().derive_rm_ctx()))
        out.append(s)
    return out


def _dense_fleet(n, a, m, dtype, seed):
    """Planes drawn directly: counters over the whole u32 range up to
    ``2**32 - 2``, member ids unique per row with empty slots between."""
    rng = np.random.RandomState(seed)
    clock = rng.randint(0, TOP_U32 + 1, (n, a), dtype=np.uint64)
    clock[0] = TOP_U32
    ids = np.full((n, m), -1, np.int32)
    dots = np.zeros((n, m, a), np.uint64)
    for i in range(n):
        live = rng.rand(m) < 0.6
        ids[i, live] = i * m + np.nonzero(live)[0]
        dots[i, live] = np.minimum(
            clock[i], rng.randint(0, TOP_U32 + 1, (int(live.sum()), a),
                                  dtype=np.uint64))
    dots[0, ids[0] != -1] = TOP_U32
    d_ids = np.full((n, 2), -1, np.int32)
    d_clocks = np.zeros((n, 2, a), np.uint64)
    return OrswotBatch(*(jnp.asarray(p.astype(dtype) if p.dtype == np.uint64
                                     else p)
                         for p in (clock, ids, dots, d_ids, d_clocks)))


def _take_reference(batch, obj, member):
    """The read semantics over a plain ``jnp.take`` of the original
    planes, finished on the host: (val, add, rm, member ids, count)."""
    crow = np.asarray(jnp.take(batch.clock, obj, axis=0), np.uint64)
    idrow = np.asarray(jnp.take(batch.ids, obj, axis=0), np.int32)
    dotrow = np.asarray(jnp.take(batch.dots, obj, axis=0), np.uint64)
    live = idrow != -1
    hit = live & (idrow == member[:, None]) & (member[:, None] >= 0)
    mclock = (dotrow * hit[:, :, None]).sum(axis=1, dtype=np.uint64)
    value_read = member < 0
    count = live.sum(axis=1).astype(np.uint64)
    val = np.where(value_read, count, hit.any(axis=1).astype(np.uint64))
    rm = np.where(value_read[:, None], crow, mclock)
    return val, crow, rm, idrow, count


def _probes(batch, rng, b):
    """``b`` reads: a third ``value()``, a third live members, a third
    absent ids."""
    n = batch.clock.shape[0]
    obj = rng.randint(0, n, b)
    ids = np.asarray(batch.ids)
    member = np.full(b, serve.NO_MEMBER, np.int32)
    kind = rng.randint(0, 3, b)
    for i in np.nonzero(kind == 1)[0]:
        row = ids[obj[i]]
        if (row != -1).any():
            member[i] = rng.choice(row[row != -1])
    member[kind == 2] = rng.randint(1 << 24, 1 << 25, int((kind == 2).sum()))
    return obj, member


@pytest.mark.parametrize("a,m,bits", [
    (8, 16, 32),    # 152 lanes of data, padded to 256
    (2, 42, 32),    # exactly 128 lanes: no padding
    (64, 16, 32),   # the ★ width: 1,104 lanes padded to 1,152
    (8, 16, 64),    # u64 counters: ids carried in 64 bits
])
def test_view_gather_matches_plain_take(a, m, bits):
    dtype = np.uint32 if bits == 32 else np.uint64
    batch = _dense_fleet(40, a, m, dtype, seed=a + m + bits)
    view = query.build_view(batch)
    assert view.rows.shape == (40, query._view_width(a, m))
    assert view.rows.dtype == batch.clock.dtype
    rng = np.random.RandomState(7)
    for b in (1, 13, 300):  # 13 and 300 pad with filler rows
        obj, member = _probes(batch, rng, b)
        obj[0], member[0] = 0, serve.NO_MEMBER   # the row at 2**32 - 2
        frame = serve.gather(batch, obj, member=member)
        val, add, rm, ids, count = _take_reference(batch, obj, member)
        assert np.array_equal(frame.val, val)
        assert np.array_equal(frame.add_clock, add)
        assert np.array_equal(frame.rm_clock, rm)
        assert np.array_equal(frame.extras["members"], ids)
        assert np.array_equal(frame.extras["count"], count)
        assert frame.add_clock.dtype == np.uint64
    assert np.array_equal(np.asarray(view.rows)[:, :a],
                          np.asarray(batch.clock))
    assert int(np.asarray(view.rows)[0, 0]) == TOP_U32


def _row(vc, width):
    r = np.zeros(width, np.uint64)
    for actor, cnt in vc.dots.items():
        r[int(actor)] = cnt
    return r


def test_view_gather_matches_scalar_read_ctx():
    uni = _uni()
    sets = _scalar_fleet(48, seed=5)
    assert any(not s.value().val for s in sets)  # empty sets are read too
    batch = OrswotBatch.from_scalar(sets, uni)
    rng = np.random.RandomState(6)
    obj, member = _probes(batch, rng, 777)
    frame = serve.gather(batch, obj, member=member)
    for i in range(len(obj)):
        s = sets[int(obj[i])]
        if member[i] == serve.NO_MEMBER:
            rc, want = s.value(), len(s.value().val)
        else:
            rc = s.contains(int(member[i]))
            want = int(bool(rc.val))
        assert int(frame.val[i]) == want, i
        assert np.array_equal(frame.add_clock[i], _row(rc.add_clock, 8)), i
        assert np.array_equal(frame.rm_clock[i], _row(rc.rm_clock, 8)), i


# ---------------------------------------------------------------------------
# one view per snapshot
# ---------------------------------------------------------------------------


def _node():
    uni = _uni()
    batch = OrswotBatch.from_scalar(_scalar_fleet(16, seed=9), uni)
    return ClusterNode("v0", batch, uni, oplog=OpLog(uni))


def _views(before):
    d = tracing.counters_since(before)
    return d.get("serve.view.builds", 0), d.get("serve.view.hits", 0)


def test_one_build_then_hits_per_snapshot():
    node = _node()
    before = tracing.counters()
    for _ in range(4):
        node.serve_reads(serve.ReadRequest.reads(np.arange(8)))
    assert _views(before) == (1, 3)


def test_racing_first_reads_build_once():
    node = _node()
    loop = serve.ServeLoop(node)
    req = serve.ReadRequest.reads(np.arange(16), member=3)
    start = threading.Barrier(4)
    frames, errors = [], []

    def client():
        try:
            start.wait(timeout=10)
            frames.append(loop.serve(req))
        except BaseException as e:  # surfaced below
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        before = tracing.counters()
        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert _views(before) == (1, 3)
    for f in frames[1:]:
        assert np.array_equal(f.val, frames[0].val)
        assert np.array_equal(f.rm_clock, frames[0].rm_clock)


def test_write_rebuilds_and_releases_the_old_view():
    node = _node()
    loop = serve.ServeLoop(node)
    req = serve.ReadRequest.reads([3], member=900)
    before = tracing.counters()
    assert int(loop.serve(req).val[0]) == 0
    old = weakref.ref(node.serve_views._slot[1].rows)

    # the node drops the view before its fold allocates the new
    # snapshot, while the old one is still held, not at the next read
    node._ensure_oplog()
    apply_ops, held = node._applier.apply_ops, []

    def fold(batch, ops):
        held.append(node.serve_views._slot)
        return apply_ops(batch, ops)

    node._applier.apply_ops = fold
    node.submit_writes(np.array([3], np.int64), np.array([900], np.int32),
                       actor=2)
    assert held == [None]
    gc.collect()
    assert old() is None
    frame = loop.serve(req)
    assert int(frame.val[0]) == 1            # the new snapshot's rows
    assert int(frame.rm_clock[0, 2]) > 0     # the new member's dot
    assert _views(before) == (2, 0)


def test_view_dies_with_its_snapshot():
    """A bare gather reads through the module's cache: one build, then
    hits; the view is dropped once its snapshot is collected."""
    batch = OrswotBatch.from_scalar(_scalar_fleet(8, seed=3), _uni())
    before = tracing.counters()
    for _ in range(3):
        serve.gather(batch, np.arange(8))
    assert _views(before) == (1, 2)
    rows = weakref.ref(query._VIEWS._slot[1].rows)
    del batch
    gc.collect()
    assert rows() is None
    assert query._VIEWS._slot is None
