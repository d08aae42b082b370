"""The ORSWOT fleet lifecycle at the north-star width, end to end.

One fleet shape (``CrdtConfig.tpu_default()``: A=64 actors, M=16 member
slots, D=2 deferred slots, u32 counters — 4,936 B of dense state per
replica-object), driven through the public API the way users run it,
each step held exactly to an independent reference:

* **fold** — R replica fleets joined to fixpoint by
  ``OrswotBatch.join_fleet``; the whole fixpoint byte-identical to the
  native C++ fold of the same planes in the same tree order, and a
  sample equal in ``value()`` and set clock to the scalar ``Orswot``
  left fold plus the plunger.
* **replicate** — ``to_wire`` → ``PipelinedWireLoop`` fold →
  ``to_wire``; a sample byte-identical to the scalar fold, and every
  blob parsed by the native decoder.
* **sync** — two ``SyncSession``\\ s over ``socket.socketpair()``, one
  thread each, on the folded fleet and a copy that differs in 1% of its
  rows: both converge on exactly the planted rows, ship no full state,
  and end byte-identical to ``a.merge(b)``.
* **serve** — one mixed batch of reads through ``serve.QueryEngine``
  against the folded fleet, equal row for row to the scalar ``ReadCtx``
  loop.
* **mesh** — two fleets sharded over four devices by
  ``mesh.ShardedBatch.shard`` and joined by ``mesh.anti_entropy_step``;
  every plane of the inputs and the output sits on four devices, and
  the digests are byte-identical to each shard's rows merged by
  ``OrswotBatch.merge`` on one device and hashed by ``digest_of``.
"""

import functools
import socket
import threading

import numpy as np
import pytest

# the north-star width: BASELINE.md's ★ row, CrdtConfig.tpu_default()
A, M, D = 64, 16, 2
# fleet shape of utils/testdata: shared members + one novel per replica
BASE, NOVEL = 6, 1
DEFERRED_FRAC = 0.25
_PLANES = ("clock", "ids", "dots", "d_ids", "d_clocks")


def _universe():
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.utils.interning import Universe

    return Universe.identity(CrdtConfig.tpu_default(
        num_actors=A, member_capacity=M, deferred_capacity=D))


def _planes(batch) -> tuple:
    return (batch.clock, batch.ids, batch.dots, batch.d_ids, batch.d_clocks)


def _host(batch) -> tuple:
    return tuple(np.asarray(p) for p in _planes(batch))


def _native_tree_fold(fleets: list) -> tuple:
    """The C++ engine folding host planes in ``join_fleet``'s tree
    order: neighbours pairwise per level, the odd one carried, then the
    self-merge plunger."""
    from crdt_tpu.native import engine

    level = list(fleets)
    while len(level) > 1:
        nxt = [engine.orswot_merge(*level[i], *level[i + 1])[:5]
               for i in range(0, len(level) - 1, 2)]
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return engine.orswot_merge(*level[0], *level[0])[:5]


def _scalar_row(planes: tuple, obj: int):
    from crdt_tpu.utils.testdata import dense_row_to_scalar

    return dense_row_to_scalar(*(p[obj] for p in planes))


def fold_fleets(n: int, r: int, seed: int, sample: int):
    """Build ``r`` fleets of ``n`` objects on the device, join them to
    fixpoint, and check the fixpoint.  Returns ``(folded, resident)``,
    ``resident`` the bytes of the input planes."""
    import jax

    from crdt_tpu import Orswot
    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.utils.testdata import build_fleet_planes, fleet_columns

    rng = np.random.RandomState(seed)
    cols = fleet_columns(rng, n, A, M, D, r, base=BASE, novel=NOVEL,
                         deferred_frac=DEFERRED_FRAC)
    build = jax.jit(functools.partial(
        build_fleet_planes, a=A, m_cap=M, d=D, base=BASE, novel=NOVEL))
    stacked = build(cols)
    fleets = [OrswotBatch(*(x[i] for x in stacked)) for i in range(r)]
    del stacked
    host_fleets = [_host(f) for f in fleets]
    resident = sum(int(p.nbytes) for f in host_fleets for p in f)

    folded = OrswotBatch.join_fleet(fleets)
    del fleets

    got = _host(folded)
    want = _native_tree_fold(host_fleets)
    for name, g, w in zip(_PLANES, got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w), \
            f"fold: plane {name} differs from the native fold"
    for obj in rng.choice(n, size=min(sample, n), replace=False):
        ref = Orswot()
        for f in host_fleets:
            ref.merge(_scalar_row(f, obj))
        ref.merge(Orswot())  # defer plunger
        dev = _scalar_row(got, obj)
        assert dev.value().val == ref.value().val and dev.clock == ref.clock, \
            f"fold: object {obj} differs from the scalar left fold"
    return folded, resident


def replicate_fleets(n: int, r: int, seed: int, sample: int) -> dict:
    """Wire blobs in → pipelined fold → wire blobs out; returns the
    loop's result."""
    from crdt_tpu.batch import OrswotBatch, PipelinedWireLoop
    from crdt_tpu.utils.serde import from_binary, to_binary
    from crdt_tpu.utils.testdata import anti_entropy_fleets

    uni = _universe()
    rng = np.random.RandomState(seed + 1)
    reps = anti_entropy_fleets(rng, n, A, M, D, r, base=BASE, novel=NOVEL,
                               deferred_frac=DEFERRED_FRAC)
    blobs = [OrswotBatch(*rep).to_wire(uni) for rep in reps]
    del reps

    res = PipelinedWireLoop(uni).run([blobs])
    out = res["out_blobs"]
    assert len(out) == n, f"replicate: {len(out)} blobs out for {n} in"
    for obj in rng.choice(n, size=min(sample, n), replace=False):
        acc = from_binary(blobs[0][obj])
        for b in blobs[1:]:
            acc.merge(from_binary(b[obj]))
        acc.merge(acc.clone())  # defer plunger, as the loop
        assert to_binary(acc) == out[obj], \
            f"replicate: object {obj} blob differs from the scalar fold"
    return res


def _plant(base, frac: float, seed: int):
    """A copy of ``base`` that differs in ``frac`` of its rows: one fresh
    add of an already-live member on each planted row, applied through
    ``OrswotBatch.apply_add``.  Returns ``(copy, rows)``."""
    import jax.numpy as jnp

    from crdt_tpu.batch import OrswotBatch

    host = [np.array(p) for p in _planes(base)]
    n = host[0].shape[0]
    rng = np.random.RandomState(seed + 2)
    live = np.nonzero(host[1][:, 0] != -1)[0]
    rows = np.sort(rng.choice(live, size=max(1, int(n * frac)),
                              replace=False))
    actor = rng.randint(0, A, size=rows.size).astype(np.int32)
    counter = host[0][rows, actor] + 1
    member = host[1][rows, 0]
    sub = OrswotBatch(*(jnp.asarray(p[rows]) for p in host))
    sub = sub.apply_add(actor, counter, member)
    for p, q in zip(host, _host(sub)):
        p[rows] = q
    return OrswotBatch(*(jnp.asarray(p) for p in host)), rows


def sync_fleets(base, frac: float, seed: int, timeout_s: float) -> int:
    """Digest/delta sync of ``base`` against a copy diverging in ``frac``
    of its rows, two sessions over a socketpair; returns the planted
    row count."""
    from crdt_tpu.cluster.transport import TcpTransport
    from crdt_tpu.sync import SyncSession

    uni = _universe()
    other, rows = _plant(base, frac, seed)
    want = base.merge(other).to_wire(uni)

    sessions = {"a": SyncSession(base, uni, peer="b"),
                "b": SyncSession(other, uni, peer="a")}
    sock_a, sock_b = socket.socketpair()
    transports = {"a": TcpTransport(sock_a), "b": TcpTransport(sock_b)}
    reports, errors = {}, []

    def run(side):
        try:
            reports[side] = sessions[side].sync(transports[side])
        except BaseException as e:  # re-raised on the main thread
            errors.append(e)

    # daemon: a hung session must not keep the test process
    threads = [threading.Thread(target=run, args=(s,), name=f"sync-{s}",
                                daemon=True)
               for s in ("a", "b")]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout_s)
    finally:
        for tr in transports.values():
            tr.close()
    assert not any(t.is_alive() for t in threads), "sync: a session hung"
    if errors:
        raise errors[0]
    for side, rep in sorted(reports.items()):
        assert rep.converged, f"sync: side {side} did not converge"
        assert rep.diverged == rows.size, \
            f"sync: side {side} saw {rep.diverged} diverged rows, " \
            f"planted {rows.size}"
        assert rep.full_bytes_sent == 0, \
            f"sync: side {side} shipped {rep.full_bytes_sent} B of full state"
        assert sessions[side].batch.to_wire(uni) == want, \
            f"sync: side {side} differs from a.merge(b)"
    return int(rows.size)


def serve_fleet(folded, reads: int, seed: int) -> None:
    """One mixed ``value()``/``contains`` batch through the query engine,
    against the scalar ``ReadCtx`` loop on the same rows."""
    from crdt_tpu import serve
    from crdt_tpu.serve.query import row_to_vclock

    host = _host(folded)
    n = host[0].shape[0]
    rng = np.random.RandomState(seed + 3)
    obj = rng.randint(0, n, size=reads)
    # half whole-object reads; the rest probe a live member or an
    # absent id
    slot = rng.randint(0, M, size=reads)
    member = host[1][obj, slot]
    absent = member == -1
    member[absent] = rng.randint(1 << 24, 1 << 25, size=int(absent.sum()))
    member[rng.rand(reads) < 0.5] = serve.NO_MEMBER
    member = member.astype(np.int32)

    engine = serve.QueryEngine({serve.K_ORSWOT: folded})
    frame = engine.gather(obj, kind=serve.K_ORSWOT, member=member)
    for i in range(reads):
        s = _scalar_row(host, obj[i])
        if member[i] == serve.NO_MEMBER:
            rc = s.value()
            val = len(rc.val)
        else:
            rc = s.contains(int(member[i]))
            val = int(bool(rc.val))
        assert (int(frame.val[i]) == val
                and row_to_vclock(frame.add_clock[i]) == rc.add_clock
                and row_to_vclock(frame.rm_clock[i]) == rc.rm_clock), \
            f"serve: read {i} (object {obj[i]}, member {member[i]}) " \
            "differs from the scalar ReadCtx"


def mesh_fleets(n: int, shards: int, seed: int) -> int:
    """Two ``n``-object fleets sharded over ``shards`` devices, one
    ``anti_entropy_step``; digests against a per-shard single-device
    control.  Returns the devices the step's output sits on."""
    import jax.numpy as jnp

    from crdt_tpu import mesh
    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.sync.digest import digest_of
    from crdt_tpu.utils.testdata import anti_entropy_fleets

    uni = _universe()
    rng = np.random.RandomState(seed + 4)
    host_a, host_b = anti_entropy_fleets(
        rng, n, A, M, D, 2, base=BASE, novel=NOVEL,
        deferred_frac=DEFERRED_FRAC)
    per = n // shards
    assert per * shards == n, f"mesh: {n} objects do not split {shards} ways"
    # the largest power-of-two granule dividing a shard: no padding rows
    granule = per & -per
    sa = mesh.ShardedBatch.shard(OrswotBatch(*host_a), uni, shards=shards,
                                 granule=granule)
    sb = mesh.ShardedBatch.shard(OrswotBatch(*host_b), uni, shards=shards,
                                 granule=granule)
    step = mesh.anti_entropy_step(sa, sb)
    # every plane on its own: inputs placed right prove nothing of where
    # the step put its output
    for which, b in (("a", sa), ("b", sb), ("step", step.batch)):
        for name, p in zip(_PLANES, _planes(b.device)):
            placed = {s.device for s in p.addressable_shards}
            assert len(placed) == shards \
                and not p.sharding.is_fully_replicated, \
                f"mesh: {which} plane {name} sits on {len(placed)} " \
                f"devices, expected {shards} shards"

    for s, (lo, hi) in enumerate(step.batch.layout.ranges()):
        ca = OrswotBatch(*(jnp.asarray(p[lo:hi]) for p in host_a))
        cb = OrswotBatch(*(jnp.asarray(p[lo:hi]) for p in host_b))
        want = digest_of(ca.merge(cb), uni)
        assert np.array_equal(np.asarray(want, np.uint64),
                              step.digests[lo:hi]), \
            f"mesh: shard {s} digests differ from the single-device merge"
    return len(placed)


@pytest.fixture(scope="module")
def folded():
    return fold_fleets(512, 8, seed=0, sample=32)


def test_fold_phase_parity(folded):
    batch, resident = folded
    assert int(batch.clock.shape[0]) == 512
    # 8 fleets at the north-star width: 4,936 B per replica-object
    assert resident == 8 * 512 * 4936


def test_replicate_phase_parity():
    res = replicate_fleets(512, 4, seed=0, sample=32)
    assert res["ingest_native_fraction"] == 1.0


def test_sync_phase_parity(folded):
    assert sync_fleets(folded[0], 0.01, seed=0, timeout_s=120.0) == 5


def test_serve_phase_parity(folded):
    serve_fleet(folded[0], 512, seed=0)


@pytest.mark.mesh
def test_mesh_phase_parity():
    assert mesh_fleets(1024, 4, seed=0) == 4
