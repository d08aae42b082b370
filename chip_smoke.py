#!/usr/bin/env python3
"""Bring-up smoke of the ORSWOT replication path on the chip.

Drives the fleet lifecycle users run, through the public API, at the
north-star width (``CrdtConfig.tpu_default()``: A=64 actors, M=16
member slots, D=2 deferred slots, u32 counters — 4,936 B of dense
state per replica-object), in ONE process:

* **fold** — R=8 replica fleets × 125,000 objects (1M replica-objects,
  ~4.94 GB resident) joined to fixpoint by ``OrswotBatch.join_fleet``;
  the whole fixpoint byte-identical to the native C++ fold of the same
  planes in the same tree order, and a sample equal in ``value()`` and
  set clock to the scalar ``Orswot`` left fold plus the plunger.
* **replicate** — 65,536 objects: ``to_wire`` → ``PipelinedWireLoop``
  fold → ``to_wire``; a sample byte-identical to the scalar fold, and
  every blob parsed by the native decoder.
* **sync** — two ``SyncSession``\\ s over ``socket.socketpair()``, one
  thread each, on the folded fleet and a copy that differs in 1% of
  its rows: both converge on exactly the planted rows, ship no full
  state, and end byte-identical to ``a.merge(b)``.
* **serve** — one mixed batch of 4,096 reads through
  ``serve.QueryEngine`` against the folded fleet, equal row for row to
  the scalar ``ReadCtx`` loop.

``--chips 4`` runs only the mesh phase: two 2,000,000-object fleets
(~19.7 GB together, more than one chip holds) sharded over four chips
by ``mesh.ShardedBatch.shard`` and joined by ``mesh.anti_entropy_step``;
its digests must be byte-identical to each shard's rows merged by
``OrswotBatch.merge`` on one device and hashed by ``digest_of``.

Each phase prints one JSON line (compile and run seconds, parity,
peak device bytes); the last line is the run's verdict.  Any mismatch
or exception ends the run with a non-zero exit, and so does a backend
that is not a TPU.  Data is made from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import traceback

import numpy as np

# the north-star width: BASELINE.md's ★ row, CrdtConfig.tpu_default()
A, M, D = 64, 16, 2
# fleet shape of utils/testdata: shared members + one novel per replica
BASE, NOVEL = 6, 1
DEFERRED_FRAC = 0.25
_COMPILE_EVENTS = (
    "/jax/core/compile/",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class ParityError(RuntimeError):
    """A phase's output disagreed with its reference."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise ParityError(what)


class _Clock:
    """Wall seconds of a block, and the seconds of it jax spent
    compiling: the union of the compile-side intervals jax reported
    while it ran (tracing, lowering, backend compiles and persistent
    cache reads, on any thread; nested traces count once).  ``run`` is
    wall minus compile."""

    def __enter__(self):
        import jax.monitoring

        self._spans = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        self._t0 = time.perf_counter()
        return self

    def _on(self, name, secs, **_kw):
        if name.startswith(_COMPILE_EVENTS):
            end = time.perf_counter()
            with self._lock:
                self._spans.append((end - secs, end))

    def __exit__(self, *exc):
        import jax.monitoring

        self._t1 = time.perf_counter()
        jax.monitoring.unregister_event_duration_listener(self._on)
        self.wall = self._t1 - self._t0
        covered, reach = 0.0, self._t0
        for lo, hi in sorted(self._spans):
            lo, hi = max(lo, reach), min(hi, self._t1)
            if hi > lo:
                covered += hi - lo
                reach = hi
        self.compile = covered
        return False

    @property
    def run(self) -> float:
        return self.wall - self.compile


def _universe():
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.utils.interning import Universe

    return Universe.identity(CrdtConfig.tpu_default(
        num_actors=A, member_capacity=M, deferred_capacity=D))


_PLANES = ("clock", "ids", "dots", "d_ids", "d_clocks")


def _planes(batch) -> tuple:
    return (batch.clock, batch.ids, batch.dots, batch.d_ids, batch.d_clocks)


def _host(batch) -> tuple:
    return tuple(np.asarray(p) for p in _planes(batch))


def _native_tree_fold(fleets: list) -> tuple:
    """The C++ engine folding host planes in ``join_fleet``'s tree
    order: neighbours pairwise per level, the odd one carried, then
    the self-merge plunger."""
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


def fold_phase(n: int, r: int, seed: int, sample: int):
    """Build ``r`` fleets of ``n`` objects on the device, join them to
    fixpoint, and check the fixpoint.  Returns ``(folded, record)``."""
    import functools

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
    jax.block_until_ready(fleets)
    host_fleets = [_host(f) for f in fleets]
    resident = sum(int(p.nbytes) for f in host_fleets for p in f)

    with _Clock() as c:
        folded = OrswotBatch.join_fleet(fleets)
        jax.block_until_ready(folded)
    del fleets

    got = _host(folded)
    want = _native_tree_fold(host_fleets)
    for name, g, w in zip(_PLANES, got, want):
        _check(g.dtype == w.dtype and np.array_equal(g, w),
               f"fold: plane {name} differs from the native fold")
    for obj in rng.choice(n, size=min(sample, n), replace=False):
        ref = Orswot()
        for f in host_fleets:
            ref.merge(_scalar_row(f, obj))
        ref.merge(Orswot())  # defer plunger
        dev = _scalar_row(got, obj)
        _check(dev.value().val == ref.value().val and dev.clock == ref.clock,
               f"fold: object {obj} differs from the scalar left fold")
    return folded, {
        "phase": "fold", "replicas": r, "objects": n,
        "resident_bytes": resident, "fold_s": c.run,
        "fold_compile_s": c.compile, "parity": "ok",
    }


def replicate_phase(n: int, r: int, seed: int, sample: int) -> dict:
    """Wire blobs in → pipelined fold → wire blobs out."""
    from crdt_tpu.batch import OrswotBatch, PipelinedWireLoop
    from crdt_tpu.utils.serde import from_binary, to_binary
    from crdt_tpu.utils.testdata import anti_entropy_fleets

    uni = _universe()
    rng = np.random.RandomState(seed + 1)
    reps = anti_entropy_fleets(rng, n, A, M, D, r, base=BASE, novel=NOVEL,
                               deferred_frac=DEFERRED_FRAC)
    blobs = [OrswotBatch(*rep).to_wire(uni) for rep in reps]
    del reps

    with _Clock() as c:
        res = PipelinedWireLoop(uni).run([blobs])
    out = res["out_blobs"]
    _check(len(out) == n, f"replicate: {len(out)} blobs out for {n} in")
    _check(res["ingest_native_fraction"] == 1.0,
           f"replicate: ingest_native_fraction "
           f"{res['ingest_native_fraction']} != 1.0")
    for obj in rng.choice(n, size=min(sample, n), replace=False):
        acc = from_binary(blobs[0][obj])
        for b in blobs[1:]:
            acc.merge(from_binary(b[obj]))
        acc.merge(acc.clone())  # defer plunger, as the loop
        _check(to_binary(acc) == out[obj],
               f"replicate: object {obj} blob differs from the scalar fold")
    return {
        "phase": "replicate", "replicas": r, "objects": n,
        "loop_s": c.run, "loop_compile_s": c.compile,
        "fold_path": res["fold_path"],
        "ingest_native_fraction": res["ingest_native_fraction"],
        "parity": "ok",
    }


def _plant(base, frac: float, seed: int):
    """A copy of ``base`` that differs in ``frac`` of its rows: one
    fresh add of an already-live member on each planted row, applied
    through ``OrswotBatch.apply_add``.  Returns ``(copy, rows)``."""
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


def sync_phase(base, frac: float, seed: int, timeout_s: float = 300.0
               ) -> dict:
    """Digest/delta sync of ``base`` against a copy diverging in
    ``frac`` of its rows, two sessions over a socketpair."""
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

    # daemon: a hung session must not keep the process (and the chip)
    threads = [threading.Thread(target=run, args=(s,), name=f"sync-{s}",
                                daemon=True)
               for s in ("a", "b")]
    try:
        with _Clock() as c:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=timeout_s)
    finally:
        for tr in transports.values():
            tr.close()
    _check(not any(t.is_alive() for t in threads), "sync: a session hung")
    if errors:
        raise errors[0]
    for side, rep in sorted(reports.items()):
        _check(rep.converged, f"sync: side {side} did not converge")
        _check(rep.diverged == rows.size,
               f"sync: side {side} saw {rep.diverged} diverged rows, "
               f"planted {rows.size}")
        _check(rep.full_bytes_sent == 0,
               f"sync: side {side} shipped {rep.full_bytes_sent} B of "
               "full state")
        _check(sessions[side].batch.to_wire(uni) == want,
               f"sync: side {side} differs from a.merge(b)")
    return {
        "phase": "sync", "objects": int(base.clock.shape[0]),
        "planted": int(rows.size), "sync_s": c.run,
        "sync_compile_s": c.compile,
        "delta_bytes": int(reports["a"].delta_bytes_sent
                           + reports["b"].delta_bytes_sent),
        "parity": "ok",
    }


def serve_phase(folded, reads: int, seed: int) -> dict:
    """One mixed ``value()``/``contains`` batch through the query
    engine, against the scalar ``ReadCtx`` loop on the same rows."""
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
    with _Clock() as c:
        frame = engine.gather(obj, kind=serve.K_ORSWOT, member=member)
    for i in range(reads):
        s = _scalar_row(host, obj[i])
        if member[i] == serve.NO_MEMBER:
            rc = s.value()
            val = len(rc.val)
        else:
            rc = s.contains(int(member[i]))
            val = int(bool(rc.val))
        _check(int(frame.val[i]) == val
               and row_to_vclock(frame.add_clock[i]) == rc.add_clock
               and row_to_vclock(frame.rm_clock[i]) == rc.rm_clock,
               f"serve: read {i} (object {obj[i]}, member {member[i]}) "
               "differs from the scalar ReadCtx")
    return {
        "phase": "serve", "reads": reads, "gather_s": c.run,
        "gather_compile_s": c.compile, "parity": "ok",
    }


def mesh_phase(n: int, shards: int, seed: int) -> dict:
    """Two ``n``-object fleets sharded over ``shards`` devices, one
    ``anti_entropy_step``; digests against a per-shard single-device
    control run after the sharded planes are freed."""
    import jax
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
    _check(per * shards == n, f"mesh: {n} objects do not split {shards} ways")
    # the largest power-of-two granule dividing a shard: no padding rows
    granule = per & -per
    sa = mesh.ShardedBatch.shard(OrswotBatch(*host_a), uni, shards=shards,
                                 granule=granule)
    sb = mesh.ShardedBatch.shard(OrswotBatch(*host_b), uni, shards=shards,
                                 granule=granule)
    # the shard's device_put is asynchronous: land it before the clock
    jax.block_until_ready(_planes(sa.device) + _planes(sb.device))
    with _Clock() as c:
        step = mesh.anti_entropy_step(sa, sb)
        jax.block_until_ready(step.batch.device)
    # every plane on its own: inputs placed right prove nothing of
    # where the step put its output
    for which, b in (("a", sa), ("b", sb), ("step", step.batch)):
        for name, p in zip(_PLANES, _planes(b.device)):
            placed = {s.device for s in p.addressable_shards}
            _check(len(placed) == shards
                   and not p.sharding.is_fully_replicated,
                   f"mesh: {which} plane {name} sits on {len(placed)} "
                   f"devices, expected {shards} shards")
    digests = step.digests
    ranges = step.batch.layout.ranges()
    del step, sa, sb

    for s, (lo, hi) in enumerate(ranges):
        ca = OrswotBatch(*(jnp.asarray(p[lo:hi]) for p in host_a))
        cb = OrswotBatch(*(jnp.asarray(p[lo:hi]) for p in host_b))
        want = digest_of(ca.merge(cb), uni)
        del ca, cb
        _check(np.array_equal(np.asarray(want, np.uint64), digests[lo:hi]),
               f"mesh: shard {s} digests differ from the single-device merge")
    return {
        "phase": "mesh", "objects": n, "shards": shards,
        "devices": len(placed), "step_s": c.run,
        "step_compile_s": c.compile, "parity": "ok",
    }


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _report(devices, clock: _Clock, record: dict) -> None:
    record = dict(record, compile_s=clock.compile, wall_s=clock.wall,
                  peak_bytes=_peak_bytes(devices))
    print(json.dumps(record), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase, over four chips")
    args = ap.parse_args(argv)

    import jax

    from crdt_tpu.config import use_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform "
            f"{devices[0].platform!r}); this smoke runs only on the chip")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {len(devices)}")
    devices = devices[:args.chips]
    use_compile_cache()

    if args.chips == 4:
        with _Clock() as c:
            rec = mesh_phase(2_000_000, 4, args.seed)
        _report(devices, c, rec)
    else:
        with _Clock() as c:
            folded, rec = fold_phase(125_000, 8, args.seed, sample=256)
        _report(devices, c, rec)
        with _Clock() as c:
            rec = replicate_phase(65_536, 4, args.seed, sample=256)
        _report(devices, c, rec)
        with _Clock() as c:
            rec = sync_phase(folded, 0.01, args.seed)
        _report(devices, c, rec)
        with _Clock() as c:
            rec = serve_phase(folded, 4_096, args.seed)
        _report(devices, c, rec)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


def _run_cli(argv=None) -> None:
    """``main``, ending the process at once when it fails.  A normal
    exit runs the atexit hooks (jax's backend teardown among them), and
    one that waits on a thread a failed phase left blocked keeps the
    process, and the chip, until it is killed: the first chip run's
    hung sync printed its error, then held the chip until the 1500 s
    limit of its call."""
    try:
        code = main(argv)
    except SystemExit as e:
        code = e.code
    except BaseException:
        traceback.print_exc()
        code = 1
    if code in (0, None):
        sys.exit(0)
    if not isinstance(code, int):
        print(code, file=sys.stderr)
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    _run_cli()
