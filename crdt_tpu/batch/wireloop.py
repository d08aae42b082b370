"""Pipelined wire replication loop — overlap host parse with folds.

The replication story is "serialize, ship, merge" (the reference
delegates transport, `/root/reference/src/lib.rs:62-83`); at fleet scale
the user-facing loop is *wire blobs in → anti-entropy fold → wire blobs
out*, processed in device-sized chunks.  The serial form of that loop —
``from_wire`` per replica fleet, then fold, then ``to_wire`` — measured
**13,908 merges/s** against a 3.17M merges/s fold kernel in the same
CPU run (round 5): ingest was 87% of wall clock, ~160× off the wire
microbench.  Profiling found the collapse was NOT a silent
Python fallback (the native parser accepts 100% of e2e-shaped blobs —
the ``native_fraction`` counters now prove that from the result
alone); it was **allocation churn**: every ``from_wire`` call allocated
a fresh ~300 MB dense plane set per fleet, page-faulting ~2.5 GB of
zeroed memory per chunk and freeing it again, which measured 27× slower
than the identical parse into warm buffers (see docs/GUIDE.md "wire-loop
pipeline").

:class:`PipelinedWireLoop` rebuilds the loop around that finding:

* **Staging-buffer reuse** — a pool of preallocated staging sets,
  sized at first use and reused, so no allocation happens in steady
  state.  The native CPU fold parses into dense plane sets (default 3:
  one being parsed into, up to two held as fold inputs; the native
  parser clears each object's rows itself,
  ``engine.orswot_ingest_wire(..., out=...)``).  The device fold parses
  into compact cell sets (:class:`~crdt_tpu.batch.wirebulk.
  OrswotCells`: id rows plus the nonzero counters, ≈ 24× smaller than
  the planes at the ★ fleet's sparsity), ships those and densifies them
  on the device; at that size the pool holds a whole round ahead
  (``r + 1`` sets), so the next round's parse runs under this round's
  fetch and encode.
* **Parse/fold overlap** — a background thread parses fleet ``k+1``
  into a free staging set while the main thread folds fleet ``k``
  (the ctypes call into the OpenMP parser releases the GIL, so the
  overlap is real on multicore hosts; device folds dispatch
  asynchronously on accelerator backends).
* **Ping-pong fold accumulators** — the C merge kernel fully overwrites
  its outputs, so two reusable buffer sets absorb the whole fold with
  zero allocations (`engine.orswot_merge(out=...)`).
* **Instrumentation** — per-stage wall times and native-vs-fallback
  blob counts (via :mod:`crdt_tpu.utils.tracing` counters) are returned
  with the result, so a caller can report ``native_fraction`` per
  stage.  The loop also publishes live gauges
  (``wireloop.staging_free`` — free staging sets, ``wireloop.
  parsed_depth`` — parsed fleets waiting for the fold) to the obs
  registry, and a fold blocked on the parser for longer than
  ``stall_threshold_s`` leaves a ``wireloop.stall`` flight-recorder
  event: an operator watching ``/metrics`` sees a parse-bound loop as
  ``staging_free == 0`` plus a stall count, without attaching a
  profiler.  With tracing on, each host leg is a ``wireloop.*`` span
  (parse, wait_parsed, put, dispatch, wait, fetch, encode), on the
  profiler's clock when a trace is captured; ``wireloop.intern`` marks
  a named parse adopting the names it interned.  Always-on counters say
  how fleets reached the fold: ``wireloop.put.compact`` (as cells,
  densified on the device), ``wireloop.put.dense`` (as dense planes:
  every fleet of the native fold, which merges host planes and puts
  nothing), and ``wireloop.put.bytes`` (host bytes handed to
  ``device_put``).

The benchmark's ``wire.star`` and ``wire.named`` cells (``benchmark/``)
and ``examples/anti_entropy.py`` drive this one implementation.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Optional, Sequence

import numpy as np

from ..config import counter_dtype
from ..utils import tracing


def _fold_merge_kernel(m_cap: int, d_cap: int):
    """The loop's jitted pairwise fold merge, shared across loop
    instances per (m_cap, d_cap) via the jit cache of ONE function
    object — and registered with the runtime kernel observatory
    (``batch.wireloop.fold_merge``)."""
    import functools

    import jax

    from ..obs.kernels import observed_kernel
    from ..ops import orswot_ops

    key = (m_cap, d_cap)
    fn = _FOLD_MERGE_CACHE.get(key)
    if fn is None:
        fn = observed_kernel("batch.wireloop.fold_merge")(jax.jit(
            functools.partial(orswot_ops.merge, m_cap=m_cap, d_cap=d_cap)))
        _FOLD_MERGE_CACHE[key] = fn
    return fn


_FOLD_MERGE_CACHE: dict = {}
from ..utils.interning import Universe

_SENTINEL = object()


def _native_fold_engine():
    """The native engine module when its merge kernel is usable, else
    None (same probe discipline as wirebulk.probe_engine: an old .so may
    load yet lack newer entry points)."""
    try:
        from ..native import engine

        engine._fn("orswot_merge", np.uint32)
        return engine
    except (ImportError, OSError, RuntimeError, AttributeError, TypeError):
        return None


class PipelinedWireLoop:
    """Double-buffered ORSWOT wire replication: blobs in → fold → blobs
    out, with host parse overlapped against the fold.

    One instance owns the staging/accumulator buffer pools for a fixed
    ``universe`` (identity universes and universes named by ``str`` /
    ``bytes`` take the native parse/encode fast path, a named parse
    interning unseen names as it goes; any other universe still works
    through the Python codec, just without the zero-allocation steady
    state).  ``run`` processes any number of rounds; buffers are sized on
    first use and reused across rounds and across ``run`` calls.

    ``fold_path``: ``"native"`` (C++ row kernels, the CPU best engine),
    ``"jnp"`` (jitted device merge, async dispatch), or None to pick
    native when available on a CPU backend, jnp otherwise.  The jnp fold
    stages fleets as compact cells and densifies them on the device;
    ``staging_sets`` is the pool's floor, which the compact pool raises
    to a round ahead (``r + 1``).
    """

    def __init__(self, universe: Universe, *, fold_path: Optional[str] = None,
                 staging_sets: int = 3, stall_threshold_s: float = 0.1):
        if staging_sets < 2:
            raise ValueError("pipelining needs at least 2 staging sets")
        self.universe = universe
        self.cfg = universe.config
        self._staging_sets = staging_sets
        # a fold wait on the parser above this leaves a wireloop.stall
        # event in the flight recorder (0 disables the event, not the wait)
        self.stall_threshold_s = stall_threshold_s
        self._staging: list = []
        self._pingpong: list[tuple] = []
        self._n: Optional[int] = None
        import jax

        on_cpu = jax.default_backend() == "cpu"
        if fold_path is None:
            engine = _native_fold_engine() if on_cpu else None
            fold_path = "native" if engine is not None else "jnp"
        if fold_path not in ("native", "jnp"):
            raise ValueError(f"fold_path {fold_path!r} is not native/jnp")
        self.fold_path = fold_path
        self._engine = _native_fold_engine() if fold_path == "native" else None
        if fold_path == "native" and self._engine is None:
            raise RuntimeError("fold_path='native' but the native engine "
                               "is unavailable")
        self._jit_merge = None
        self._overflow = None  # jnp path: lazily ORed bool[2] flags
        # the CPU backend may alias an aligned host buffer instead of
        # copying it, and a put staging set goes back to the parser
        self._copy_before_put = on_cpu

    # -- buffers -------------------------------------------------------------

    def _plane_set(self, n: int) -> tuple:
        cfg = self.cfg
        dt = counter_dtype(cfg)
        a, m, d = cfg.num_actors, cfg.member_capacity, cfg.deferred_capacity
        return (
            np.zeros((n, a), dtype=dt),
            np.full((n, m), -1, dtype=np.int32),
            np.zeros((n, m, a), dtype=dt),
            np.full((n, d), -1, dtype=np.int32),
            np.zeros((n, d, a), dtype=dt),
        )

    def _ensure_buffers(self, n: int, r: int) -> None:
        """Staging sets for fleets of ``n`` objects, rounds of ``r``:
        the pool only grows while ``n`` holds, so sets once handed out
        are never reallocated."""
        from .wirebulk import OrswotCells

        compact = self.fold_path == "jnp"
        want = max(self._staging_sets, r + 1) if compact \
            else self._staging_sets
        if self._n != n:
            self._n = n
            self._staging = []
            self._pingpong = [] if compact else \
                [self._plane_set(n) for _ in range(2)]
        while len(self._staging) < want:
            self._staging.append(OrswotCells(n, self.cfg) if compact
                                 else self._plane_set(n))

    # -- stages --------------------------------------------------------------

    def _parse_into(self, blobs: Sequence[bytes], staging) -> None:
        """Decode ``blobs`` into the ``staging`` set: compact cells on
        the jnp fold, dense planes on the native one (native fast path
        with per-blob triage; full Python route when the fast path does
        not apply)."""
        from .wirebulk import orswot_cells_from_wire, orswot_planes_from_wire

        if self.fold_path == "jnp":
            orswot_cells_from_wire(blobs, self.universe, staging)
            return
        planes = orswot_planes_from_wire(blobs, self.universe, out=staging)
        if planes is None:
            # no native fast path: decode in Python and copy into the
            # staging set so the fold sees one buffer discipline
            from ..utils.serde import from_binary
            from .orswot_batch import OrswotBatch

            sub = OrswotBatch.from_scalar(
                [from_binary(b) for b in blobs], self.universe
            )
            for dst, src in zip(staging, (sub.clock, sub.ids, sub.dots,
                                          sub.d_ids, sub.d_clocks)):
                np.copyto(dst, np.asarray(src))

    def _merge_native(self, acc: tuple, rhs: tuple, out: tuple) -> tuple:
        res = self._engine.orswot_merge(*acc, *rhs, out=out)
        if res[5].any():
            from ..error import raise_for_overflow

            raise_for_overflow(res[5], "wire-loop fold")
        return res[:5]

    def _merge_jnp(self, acc: tuple, rhs: tuple) -> tuple:
        """One async-dispatched device merge; overflow flags accumulate
        in ``self._overflow`` (checked once per round, at the egress
        sync, so no host round-trip lands mid-fold)."""
        if self._jit_merge is None:
            cfg = self.cfg
            self._jit_merge = _fold_merge_kernel(
                cfg.member_capacity, cfg.deferred_capacity)
        with tracing.span("wireloop.dispatch"):
            out = self._jit_merge(*acc, *rhs)
            ov = out[5].reshape(-1, 2).any(axis=0)
            self._overflow = ov if self._overflow is None else \
                (self._overflow | ov)
        return out[:5]

    def _egress(self, acc: tuple) -> list[bytes]:
        from .wirebulk import orswot_planes_to_wire

        with tracing.span("wireloop.fetch"):
            planes = tuple(np.asarray(x) for x in acc)
        with tracing.span("wireloop.encode"):
            blobs = orswot_planes_to_wire(*planes, self.universe)
            if blobs is not None:
                return blobs
            # Python route (keys neither identity ints nor names / u64
            # zigzag overflow) — already counted by orswot_planes_to_wire
            from ..utils.serde import to_binary
            from .orswot_batch import OrswotBatch

            batch = OrswotBatch(*(np.ascontiguousarray(p) for p in planes))
            return [to_binary(s) for s in batch.to_scalar(self.universe)]

    # -- the loop ------------------------------------------------------------

    def run(self, rounds: Iterable[Sequence[Sequence[bytes]]], *,
            overlap: bool = True, collect: str = "last",
            on_round: Optional[Callable[[int, list], None]] = None) -> dict:
        """Process ``rounds`` of replica-fleet blobs through parse →
        fold-to-fixpoint (left fold + defer-plunger self-merge) → egress.

        Each round is a sequence of ``r`` blob lists (one per replica
        fleet, equal lengths).  With ``overlap=True`` a background
        thread stays one fleet ahead of the fold; ``overlap=False`` runs
        the identical staged code serially (the other side of an overlap
        A/B).

        ``collect``: ``"last"`` keeps only the final round's egressed
        blobs (bounded memory at scale), ``"all"`` keeps every
        round's, ``"none"`` keeps none.  ``on_round(i, blobs)`` sees
        each round's output either way.

        Returns ``{"out_blobs", "rounds", "merges", "objects",
        "pipeline", "fold_path", "stage_s": {parse, fold, egress},
        "e2e_s", "wire_counters", "ingest_native_fraction",
        "egress_native_fraction"}`` — ``stage_s`` are per-stage wall
        sums (with overlap they can exceed ``e2e_s``; that surplus IS
        the overlap won), counters/fractions are the tracing deltas for
        this call."""
        if collect not in ("last", "all", "none"):
            raise ValueError(f"collect {collect!r} is not last/all/none")
        rounds = list(rounds)
        stage_s = {"parse": 0.0, "fold": 0.0, "egress": 0.0}
        counters_before = tracing.counters()
        out_blobs: list = []
        all_blobs: list = []
        merges = objects = 0
        t_all0 = time.perf_counter()

        free_q: "queue.Queue" = queue.Queue()
        parsed_q: "queue.Queue" = queue.Queue()

        def parse_one(blobs, staging):
            t0 = time.perf_counter()
            with tracing.span("wireloop.parse"):
                self._parse_into(blobs, staging)
            stage_s["parse"] += time.perf_counter() - t0

        def worker():
            try:
                for blobs in fleet_stream:
                    staging = free_q.get()
                    if staging is _SENTINEL:
                        return
                    parse_one(blobs, staging)
                    parsed_q.put(staging)
                parsed_q.put(_SENTINEL)
            except BaseException as e:  # surfaced in the main thread
                parsed_q.put(e)

        n_rounds = len(rounds)
        fleet_stream = [blobs for rnd in rounds for blobs in rnd]
        if not fleet_stream:
            return {
                "out_blobs": [], "rounds": 0, "merges": 0, "objects": 0,
                "pipeline": "overlapped" if overlap else "serial",
                "fold_path": self.fold_path,
                "stage_s": {k: 0.0 for k in stage_s}, "e2e_s": 0.0,
                "wire_counters": {}, "ingest_native_fraction": None,
                "egress_native_fraction": None,
            }
        n = len(fleet_stream[0])
        if any(len(b) != n for b in fleet_stream):
            raise ValueError("all fleets must hold the same object count")
        self._ensure_buffers(n, max(len(rnd) for rnd in rounds))
        for st in self._staging:
            free_q.put(st)

        thread = None
        stream_iter = iter(fleet_stream)
        if overlap:
            thread = threading.Thread(target=worker, daemon=True,
                                      name="wireloop-parse")
            thread.start()

        from ..obs import events as obs_events
        from ..obs import metrics as obs_metrics

        reg = obs_metrics.registry()
        g_free = reg.gauge("wireloop.staging_free")
        g_depth = reg.gauge("wireloop.parsed_depth")

        def update_gauges():
            # qsize is advisory under concurrency, which is exactly what
            # a gauge is — last write wins, scrapes see the latest level
            g_free.set(free_q.qsize())
            g_depth.set(parsed_q.qsize())

        def next_staged():
            if overlap:
                t_wait0 = time.perf_counter()
                with tracing.span("wireloop.wait_parsed"):
                    item = parsed_q.get()
                waited = time.perf_counter() - t_wait0
                if self.stall_threshold_s and waited > self.stall_threshold_s:
                    # the fold outran the parser: record the stall so a
                    # parse-bound loop is visible from /events, not just
                    # from a post-hoc stage_s diff
                    tracing.count("wireloop.stalls")
                    obs_events.record(
                        "wireloop.stall", waited_s=round(waited, 4),
                        staging_free=free_q.qsize(),
                    )
                update_gauges()
                if isinstance(item, BaseException):
                    raise item
                return item
            blobs = next(stream_iter, _SENTINEL)
            if blobs is _SENTINEL:
                return _SENTINEL
            staging = free_q.get()
            parse_one(blobs, staging)
            update_gauges()
            return staging

        try:
            for ri, rnd in enumerate(rounds):
                r = len(rnd)
                acc = None
                acc_staging = None  # staging set acc still aliases
                pp = 0
                t0 = time.perf_counter()
                for fi in range(r):
                    staged = next_staged()
                    assert staged is not _SENTINEL
                    if self.fold_path == "jnp":
                        planes = self._put_device(staged)
                        # its copy has landed: the parser may reuse it
                        free_q.put(staged)
                        acc = planes if acc is None else \
                            self._merge_jnp(acc, planes)
                        continue
                    tracing.count("wireloop.put.dense")
                    if acc is None:
                        acc, acc_staging = staged, staged
                        continue
                    acc = self._merge_native(acc, staged, self._pingpong[pp])
                    pp ^= 1
                    # both consumed buffer sets go back to the parser
                    if acc_staging is not None:
                        free_q.put(acc_staging)
                        acc_staging = None
                    free_q.put(staged)
                # defer plunger: one self-merge flushes deferred removes
                if self.fold_path == "native":
                    acc = self._merge_native(acc, acc, self._pingpong[pp])
                    pp ^= 1
                else:
                    acc = self._merge_jnp(acc, acc)
                if acc_staging is not None:
                    # r == 1: the plunger read straight from staging
                    free_q.put(acc_staging)
                    acc_staging = None
                stage_s["fold"] += time.perf_counter() - t0
                merges += n * r
                objects += n

                t0 = time.perf_counter()
                if self._overflow is not None:
                    # jnp path: the round's fold finishes here, so the
                    # fetch that follows times the copy alone; then one
                    # deferred overflow check per round
                    import jax

                    from ..error import raise_for_overflow

                    with tracing.span("wireloop.wait"):
                        jax.block_until_ready(acc)
                        ov, self._overflow = self._overflow, None
                        raise_for_overflow(ov, "wire-loop fold")
                blobs_out = self._egress(acc)
                stage_s["egress"] += time.perf_counter() - t0
                if on_round is not None:
                    on_round(ri, blobs_out)
                if collect == "all":
                    all_blobs.append(blobs_out)
                elif collect == "last":
                    out_blobs = blobs_out
        finally:
            if thread is not None:
                free_q.put(_SENTINEL)  # unblock a parser waiting for buffers
                thread.join(timeout=30)
                if thread.is_alive():
                    # a worker still parsing (main thread raised mid-fold
                    # on a slow parse) may write into the staging planes
                    # for a while yet — orphan the whole pool so the next
                    # run() allocates fresh buffers instead of handing
                    # the zombie's targets to a new worker
                    self._staging = []
                    self._pingpong = []
                    self._n = None

        e2e_s = time.perf_counter() - t_all0
        deltas = tracing.counters_since(counters_before)
        return {
            "out_blobs": all_blobs if collect == "all" else out_blobs,
            "rounds": n_rounds,
            "merges": merges,
            "objects": objects,
            "pipeline": "overlapped" if overlap else "serial",
            "fold_path": self.fold_path,
            "stage_s": {k: round(v, 4) for k, v in stage_s.items()},
            "e2e_s": round(e2e_s, 4),
            "wire_counters": deltas,
            "ingest_native_fraction": tracing.native_fraction(
                deltas, "wire.orswot.from_wire"
            ),
            "egress_native_fraction": tracing.native_fraction(
                deltas, "wire.orswot.to_wire"
            ),
        }

    def _put_device(self, cells) -> tuple:
        """One compact staging set → dense device planes for the jnp
        fold: its id rows and power-of-two padded cell columns are
        copied to the device, and :func:`~crdt_tpu.batch.orswot_batch.
        _densify_cells` is dispatched behind them.

        ``device_put`` copies host numpy buffers into the backend's own
        allocations (on the CPU backend, which may alias an aligned host
        buffer instead, the arrays are copied on the host first), so
        once the copy completes the set is safe to hand back to the
        parser; blocking here costs only the copy, ≈ 3 MB per ★ fleet —
        the densify and the merges still chain asynchronously."""
        import jax

        from .orswot_batch import _densify_cells

        with tracing.span("wireloop.put"):
            host = cells.padded()
            if self._copy_before_put:
                host = tuple(np.array(p) for p in host)
            moved = jax.device_put(host)
            jax.block_until_ready(moved)
        tracing.count("wireloop.put.compact")
        tracing.count("wireloop.put.bytes", sum(p.nbytes for p in host))
        cfg = self.cfg
        with tracing.span("wireloop.dispatch"):
            return _densify_cells(*moved, a=cfg.num_actors,
                                  m=cfg.member_capacity,
                                  d=cfg.deferred_capacity)


class PipelinedOpLoop:
    """Pipelined op-frame ingest: decode op frames on a background
    thread while the main thread scatter-folds already-decoded batches
    — the op-path sibling of :class:`PipelinedWireLoop`, reusing its
    staging discipline (a bounded decode queue IS the staging pool: at
    most ``depth`` decoded batches are ever buffered, so a slow fold
    backpressures the parser instead of ballooning host memory) and its
    telemetry (``wireloop.staging_free`` / ``wireloop.parsed_depth``
    gauges, ``wireloop.stall`` events past ``stall_threshold_s``).

    The overlap is real on multicore hosts: frame decode is pure
    numpy/zlib on the host, while the fold is one jitted scatter per
    batch (:meth:`crdt_tpu.oplog.OpApplier.apply_ops`) that dispatches
    asynchronously on accelerator backends.
    """

    def __init__(self, universe: Universe, *, applier=None, depth: int = 4,
                 stall_threshold_s: float = 0.1):
        from ..oplog.apply import OpApplier

        if depth < 2:
            raise ValueError("pipelining needs a decode queue depth >= 2")
        self.universe = universe
        self.applier = applier if applier is not None else OpApplier(universe)
        self.depth = depth
        self.stall_threshold_s = stall_threshold_s

    def run(self, batch, frames: Iterable[bytes], *,
            overlap: bool = True) -> tuple:
        """Fold every op frame of ``frames`` into ``batch`` (decode →
        ``apply_ops`` per frame, decode running one frame ahead when
        ``overlap``).  Returns ``(folded_batch, stats)`` with
        ``stats = {"frames", "ops", "applied", "duplicates",
        "still_parked", "pipeline", "stage_s": {parse, fold},
        "e2e_s"}`` — the same per-stage accounting the wire loop
        reports, so a caller can see whether the overlap won."""
        from ..oplog.wire import decode_ops_frame

        frames = list(frames)
        stage_s = {"parse": 0.0, "fold": 0.0}
        stats = {"frames": len(frames), "ops": 0, "applied": 0,
                 "duplicates": 0}
        t_all0 = time.perf_counter()
        num_actors = self.universe.config.num_actors

        from ..obs import events as obs_events
        from ..obs import metrics as obs_metrics

        reg = obs_metrics.registry()
        g_free = reg.gauge("wireloop.staging_free")
        g_depth = reg.gauge("wireloop.parsed_depth")

        def decode_one(frame):
            t0 = time.perf_counter()
            ops = decode_ops_frame(frame, num_actors=num_actors)
            stage_s["parse"] += time.perf_counter() - t0
            return ops

        if overlap:
            parsed_q: "queue.Queue" = queue.Queue(maxsize=self.depth)

            def worker():
                try:
                    for frame in frames:
                        parsed_q.put(decode_one(frame))
                    parsed_q.put(_SENTINEL)
                except BaseException as e:  # surfaced in the main thread
                    parsed_q.put(e)

            thread = threading.Thread(target=worker, daemon=True,
                                      name="oploop-decode")
            thread.start()

            def staged():
                while True:
                    t0 = time.perf_counter()
                    item = parsed_q.get()
                    waited = time.perf_counter() - t0
                    if self.stall_threshold_s \
                            and waited > self.stall_threshold_s:
                        tracing.count("wireloop.stalls")
                        obs_events.record(
                            "wireloop.stall", waited_s=round(waited, 4),
                            staging_free=self.depth - parsed_q.qsize(),
                        )
                    g_free.set(self.depth - parsed_q.qsize())
                    g_depth.set(parsed_q.qsize())
                    if item is _SENTINEL:
                        return
                    if isinstance(item, BaseException):
                        raise item
                    yield item

            stream = staged()
        else:
            stream = (decode_one(f) for f in frames)

        try:
            for ops in stream:
                t0 = time.perf_counter()
                batch, report = self.applier.apply_ops(batch, ops)
                stage_s["fold"] += time.perf_counter() - t0
                stats["ops"] += report.ops
                stats["applied"] += report.applied
                stats["duplicates"] += report.duplicates
        finally:
            if overlap:
                # drain so an abandoned worker never blocks on a full
                # queue holding stale buffers
                while True:
                    try:
                        parsed_q.get_nowait()
                    except queue.Empty:
                        break
                thread.join(timeout=30)

        stats["still_parked"] = len(self.applier.parked)
        stats["pipeline"] = "overlapped" if overlap else "serial"
        stats["stage_s"] = {k: round(v, 4) for k, v in stage_s.items()}
        stats["e2e_s"] = round(time.perf_counter() - t_all0, 4)
        return batch, stats
