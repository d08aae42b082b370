"""Shared scaffolding for the native bulk wire paths.

Each batch type's ``from_wire``/``to_wire`` follows the same shape
(`OrswotBatch.from_wire` is the reference implementation): probe the
native engine + identity universe, concatenate blobs, parse in
parallel, patch/raise per the status array, fall back to the Python
codec whenever the fast path cannot apply.  This module holds the
pieces that are identical across types so they cannot drift — including
the whole counter-plane ingest/egress flow (status triage, per-blob
patch splice, the u64-zigzag egress guard) shared by the clock-shaped
legs (VClock / GCounter / PNCounter).
"""

from __future__ import annotations

from typing import Sequence

from ..error import WireFormatError

WIRE_TAG_VCLOCK = 0x20    # serde.py _T_VCLOCK
WIRE_TAG_GCOUNTER = 0x22  # serde.py _T_GCOUNTER

# leg labels for the tag-parameterized clockish codec's counters
_TAG_LEG = {WIRE_TAG_VCLOCK: "vclock", WIRE_TAG_GCOUNTER: "gcounter"}


def record_wire(leg: str, direction: str, *, native: int = 0,
                fallback: int = 0, reason: str | None = None) -> None:
    """Count native-vs-fallback blobs for one bulk wire call.

    Feeds the always-on counters in :mod:`crdt_tpu.utils.tracing` under
    ``wire.<leg>.<direction>.{native,fallback}`` plus a
    ``...fallback_reason.<reason>`` detail counter, so the bench can
    report a per-stage ``native_fraction`` and a silent-fallback
    regression is visible from the JSON artifact alone (the round-5 e2e
    ingest collapse was initially blamed on exactly such an invisible
    fallback).  Reasons in use: ``no_engine`` (native library absent or
    symbol missing), ``non_identity`` (universe is not identity-interned;
    the legs without a named codec), ``key_type`` (ORSWOT: keys neither
    identity ints nor ``str``/``bytes`` names), ``unnamed_id`` (ORSWOT
    egress: a row holds an id its universe has no name for), ``grammar``
    (per-blob status==1 splice), ``overflow_zigzag`` (u64 counters past
    the native encoder's range).

    A reasoned fallback also lands in the flight recorder (kind
    ``wire.fallback``) — one event per bulk call, so the recorder shows
    WHEN the native path was lost, which the monotonic counters alone
    cannot."""
    from ..obs import events as obs_events
    from ..utils import tracing

    prefix = f"wire.{leg}.{direction}"
    tracing.count(f"{prefix}.native", native)
    tracing.count(f"{prefix}.fallback", fallback)
    if reason is not None and fallback:
        tracing.count(f"{prefix}.fallback_reason.{reason}", fallback)
        obs_events.record("wire.fallback", leg=leg, direction=direction,
                          reason=reason, blobs=fallback)


def probe_engine(universe, fn_name: str, dtype=None):
    """The native engine module when the fast path applies, else None.

    Applies = identity universe AND the .so loads AND it exports the
    required symbol (an .so built from older sources loads fine but
    lacks newer entry points).  ``dtype=None`` probes a
    dtype-independent symbol (no u32/u64 suffix — the GSet bitmap
    codec)."""
    if not universe.is_identity:
        return None
    try:
        from ..native import engine

        if dtype is None:
            engine._fn_raw(fn_name)
        else:
            engine._fn(fn_name, dtype)
        return engine
    except (ImportError, OSError, RuntimeError, AttributeError, TypeError):
        return None


def concat_blobs(blobs: Sequence[bytes]):
    """``(buf, offsets)`` for the C parsers: one contiguous buffer plus
    int64[n+1] blob boundaries."""
    import numpy as np

    n = len(blobs)
    buf = b"".join(blobs)
    offsets = np.zeros(n + 1, dtype=np.int64)
    # map(len) rather than a generator: half the time under the
    # interpreter lock, which a wire loop's main thread waits on
    np.cumsum(np.fromiter(map(len, blobs), dtype=np.int64, count=n),
              out=offsets[1:])
    return buf, offsets


def slice_blobs(buf, offsets) -> list[bytes]:
    """Concatenated encoder output → per-object bytes (one copy per
    blob via a memoryview, no whole-buffer intermediate)."""
    mv = memoryview(buf)
    off = offsets.tolist()
    return [bytes(mv[off[i]:off[i + 1]]) for i in range(len(off) - 1)]


def fallback_reason(universe) -> str:
    """Why :func:`probe_engine` returned None — counter detail for
    :func:`record_wire` (``non_identity`` dominates: a present engine is
    still unusable without identity interning)."""
    return "non_identity" if not universe.is_identity else "no_engine"


def planes_from_wire(blobs, universe, probe_name, ingest, planes_of_scalars,
                     leg: str = "counters"):
    """Dense counter planes from wire blobs — the shared ingest flow of
    the clock-shaped legs.

    ``ingest(engine, buf, offsets, cfg, dtype) -> (planes, status)``
    runs the type's native parser; ``planes_of_scalars(scalars)`` maps
    decoded scalar states to dense planes (the calling class's
    ``from_scalar(...)`` planes) and serves both the no-engine full
    fallback and the per-blob patch path, so the result always equals
    the pure-Python decode.  ``leg`` labels the native/fallback
    counters (:func:`record_wire`)."""
    import numpy as np

    from ..config import counter_dtype
    from ..utils.serde import from_binary

    cfg = universe.config
    engine = probe_engine(universe, probe_name, counter_dtype(cfg))
    if engine is None:
        record_wire(leg, "from_wire", fallback=len(blobs),
                    reason=fallback_reason(universe))
        return planes_of_scalars([from_binary(b) for b in blobs])
    buf, offsets = concat_blobs(blobs)
    planes, status = ingest(engine, buf, offsets, cfg, counter_dtype(cfg))
    n_fb = 0
    if status.any():
        hard = np.nonzero(status > 1)[0]
        if hard.size:
            first = int(hard[0])
            raise WireFormatError(
                f"object {first}: actor outside the identity registry "
                f"range [0, {cfg.num_actors})"
            )
        fb = np.nonzero(status == 1)[0].tolist()
        n_fb = len(fb)
        sub = np.asarray(planes_of_scalars([from_binary(blobs[i]) for i in fb]))
        planes[np.asarray(fb, dtype=np.int64)] = sub
    record_wire(leg, "from_wire", native=len(blobs) - n_fb, fallback=n_fb,
                reason="grammar")
    return planes


def counters_overflow_zigzag(planes) -> bool:
    """The shared u64 egress guard: True when any 8-byte counter plane
    holds a value at/above 2^63, whose zigzag encoding overflows the C
    emitter's uint64 (such states must take the Python encoder).

    4-byte planes can never overflow — they are skipped without the
    full-plane ``max`` scan, so u32 configs pay nothing here.  Accepts
    host or device arrays; the reduction runs where the plane lives and
    only the scalar crosses to the host."""
    for p in planes:
        if p.dtype.itemsize != 8 or p.size == 0:
            continue
        if int(p.max()) >= 1 << 63:
            return True
    return False


def planes_to_wire(planes, universe, probe_name, encode, python_path,
                   leg: str = "counters"):
    """Wire blobs from dense counter planes — the shared egress flow,
    byte-identical to the scalar ``to_binary``.

    ``encode(engine, planes) -> (buf, offsets)`` runs the type's native
    encoder; ``python_path()`` is the full fallback: non-identity
    universes, missing engine, or the :func:`counters_overflow_zigzag`
    guard.  ``leg`` labels the native/fallback counters."""
    import numpy as np

    from ..config import counter_dtype

    n = planes.shape[0]
    if n == 0:
        return []
    engine = probe_engine(universe, probe_name, counter_dtype(universe.config))
    reason = fallback_reason(universe)
    host = None
    if engine is not None:
        host = np.asarray(planes)
        if counters_overflow_zigzag((host,)):
            engine = None
            reason = "overflow_zigzag"
    if engine is None:
        record_wire(leg, "to_wire", fallback=n, reason=reason)
        return python_path()
    buf, offsets = encode(engine, host)
    record_wire(leg, "to_wire", native=n)
    return slice_blobs(buf, offsets)


# ---- ORSWOT shared triage (OrswotBatch.from_wire + PipelinedWireLoop) ------


def named_engine(universe, fn_name: str, dtype):
    """``(engine, (actor table, member table), None)`` when the named
    codec applies to ``universe``, else ``(None, None, reason)``.

    Applies = a universe whose actor and member registries are
    :class:`~crdt_tpu.utils.interning.Registry` holding only ``str`` /
    ``bytes`` values, and an engine that exports ``fn_name``.  Reasons:
    ``key_type`` (a registry holds another type of value, or is not such
    a registry), ``no_engine``."""
    regs = (universe.actors, universe.members)
    if not all(hasattr(r, "native_names") for r in regs):
        return None, None, "key_type"
    try:
        from ..native import engine

        engine._fn(fn_name, dtype)
        tables = tuple(r.native_names() for r in regs)
    except (ImportError, OSError, RuntimeError, AttributeError, TypeError):
        return None, None, "no_engine"
    if None in tables:
        return None, None, "key_type"
    return engine, tables, None


def _registries(universe) -> list:
    """The universe's registries, each once (actors and members may be
    one registry)."""
    regs = [universe.actors]
    if universe.members is not universe.actors:
        regs.append(universe.members)
    return regs


def adopt_interned(universe) -> int:
    """Append the names a native parse interned to the universe's
    registries (the ``wireloop.intern`` span, counted under
    ``wire.names.interned``); returns how many."""
    from ..utils import tracing

    regs = _registries(universe)
    new = sum(r.native_backlog() for r in regs)
    if new:
        with tracing.span("wireloop.intern"):
            for r in regs:
                r.adopt_native()
        tracing.count("wire.names.interned", new)
    return new


def _scalar_rows(blobs, idx, universe, planes, rows=None) -> None:
    """Decode blobs ``idx`` with the Python codec and splice their rows
    into ``planes`` at ``rows`` (default ``idx``); raises exactly where
    the scalar path would, with the caller's blob indices."""
    import numpy as np

    from ..utils.serde import from_binary
    from .orswot_batch import OrswotBatch

    try:
        sub = OrswotBatch.from_scalar(
            [from_binary(blobs[i]) for i in idx], universe, via_device=False
        )
    except (ValueError, TypeError) as e:
        # from_scalar reports indices relative to the fallback sublist;
        # translate so the operator can find the blob
        try:
            err = type(e)(
                f"{e} [object indices above are relative to the "
                f"python-fallback sublist; its blob indices are "
                f"{idx[:16]}{'...' if len(idx) > 16 else ''}]"
            )
        except TypeError:  # an exception type with arguments of its own
            raise e from None
        raise err from None
    rows = np.asarray(idx if rows is None else rows, dtype=np.int64)
    for dst, src in zip(planes, (sub.clock, sub.ids, sub.dots, sub.d_ids,
                                 sub.d_clocks)):
        dst[rows] = np.asarray(src)


def _intern_pending(blobs, buf, offsets, pending, universe, planes,
                    status, engine, rows=None) -> list:
    """The serial pass of the named ingest over blobs ``pending``
    (status 1 or 5 after the parallel pass, ascending): each is parsed
    again natively, interning its unseen names in blob order; one the
    native grammar refuses is decoded in Python at its turn, so the ids
    come out as ``Registry.intern`` would hand them out to
    ``from_scalar([from_binary(b) for b in blobs])`` (which takes the
    unseen members buffered under one deferred clock in set order, this
    pass in wire order).  ``rows``: where ``pending``'s blobs sit in
    ``buf`` / ``offsets``, ``planes`` and ``status`` when those hold
    only them (default: ``pending`` itself, the whole fleet's).  Returns
    the blob indices the Python codec decoded."""
    fallback: list = []
    regs = _registries(universe)
    pending = [int(i) for i in pending]
    rows = pending if rows is None else [int(j) for j in rows]
    with regs[0].lock, regs[-1].lock:
        try:
            pos = 0
            while pos < len(pending):
                tables = (universe.actors.native_names(),
                          universe.members.native_names())
                if None in tables:
                    # the Python codec interned a value that is not a
                    # name: the rest decode in Python, in order
                    _scalar_rows(blobs, pending[pos:], universe, planes,
                                 rows[pos:])
                    status[rows[pos:]] = 0
                    fallback.extend(pending[pos:])
                    break
                pos += engine.orswot_intern_named(
                    buf, offsets, rows[pos:], planes, status, *tables)
                adopt_interned(universe)
                last = rows[pos - 1]
                if status[last] == 1:
                    _scalar_rows(blobs, [pending[pos - 1]], universe, planes,
                                 [last])
                    status[last] = 0
                    fallback.append(pending[pos - 1])
        finally:
            adopt_interned(universe)
    return fallback


def _raise_hard_status(status, cfg, actor_range: str) -> None:
    """Raise ``WireFormatError`` for the first blob whose status is a
    hard error (2 member overflow, 3 deferred overflow, 4 actor)."""
    import numpy as np

    hard = np.nonzero(status > 1)[0]
    if not hard.size:
        return
    first = int(hard[0])
    code = int(status[first])
    if code == 2:
        raise WireFormatError(
            f"object {first}: members > member_capacity "
            f"{cfg.member_capacity}"
        )
    if code == 3:
        raise WireFormatError(
            f"object {first}: deferred rows > deferred_capacity "
            f"{cfg.deferred_capacity}"
        )
    raise WireFormatError(f"object {first}: actor outside {actor_range}")


def orswot_planes_from_wire(blobs, universe, out=None):
    """Dense ORSWOT planes (host numpy) straight from wire blobs, with
    the full status triage — the shared ingest core of
    ``OrswotBatch.from_wire``, :class:`crdt_tpu.batch.wireloop.
    PipelinedWireLoop` and delta sync.

    Identity universes take the native integer-keyed parser; universes
    whose actors and members are ``str`` / ``bytes`` names take the
    native named parser, which interns unseen names in blob order
    (:func:`_intern_pending`).  Returns ``(clock, ids, dots, d_ids,
    d_clocks)``, or ``None`` when no native parser applies (missing
    engine, or keys of another type) — the caller then takes its own
    full-Python route.  Every outcome is counted under the
    ``wire.orswot.from_wire`` counters (:func:`record_wire`).

    ``out``: optional preallocated plane 5-tuple to parse into, for
    buffer REUSE across calls — fresh per-call plane allocations
    page-fault GBs at north-star chunk scale and were the measured e2e
    ingest collapse (docs/GUIDE.md).

    Hard statuses raise ``WireFormatError`` with the caller's blob
    index; blobs outside the native grammar are decoded by the Python
    codec and their rows spliced in, so the result equals the
    pure-Python decode — for a named universe up to the ids of unseen
    members buffered under one deferred clock, which this parse hands
    out in wire order and ``from_scalar`` in set order (same states,
    same names)."""
    import numpy as np

    from ..config import counter_dtype

    cfg = universe.config
    dt = counter_dtype(cfg)
    if universe.is_identity:
        engine = probe_engine(universe, "orswot_ingest_wire", dt)
        reason = "no_engine"
    else:
        engine, tables, reason = named_engine(universe,
                                              "orswot_ingest_named", dt)
    if engine is None:
        record_wire("orswot", "from_wire", fallback=len(blobs), reason=reason)
        return None
    buf, offsets = concat_blobs(blobs)
    if universe.is_identity:
        *planes, status = engine.orswot_ingest_wire(
            buf, offsets, cfg.num_actors, cfg.member_capacity,
            cfg.deferred_capacity, dt, out=out,
        )
        # hard errors first, reported with the CALLER's blob index;
        # status 1 (outside the fast-path grammar): decoded in Python
        # (raises exactly where the scalar path would, e.g. non-int
        # members against an identity registry)
        _raise_hard_status(status, cfg, "the identity registry range "
                           f"[0, {cfg.num_actors})")
        fb = np.nonzero(status == 1)[0].tolist()
        if fb:
            _scalar_rows(blobs, fb, universe, planes)
    else:
        *planes, status = engine.orswot_ingest_named(
            buf, offsets, cfg.num_actors, cfg.member_capacity,
            cfg.deferred_capacity, dt, *tables, out=out,
        )
        fb = []
        pending = np.nonzero((status == 1) | (status == 5))[0]
        if pending.size:
            fb = _intern_pending(blobs, buf, offsets, pending, universe,
                                 planes, status, engine)
        _raise_hard_status(status, cfg, f"the {cfg.num_actors} actor "
                           "columns (actor registry full)")
    record_wire("orswot", "from_wire", native=len(blobs) - len(fb),
                fallback=len(fb), reason="grammar")
    return tuple(planes)


class OrswotCells:
    """One fleet of ``n`` ORSWOT objects as compact cells on the host,
    reused from parse to parse: the member and deferred id rows dense
    (``ids[n, M]``, ``d_ids[n, D]``), and each nonzero counter one cell,
    ``idx[j]`` its flat index into the plane-major space
    ``[clock n*A | dots n*M*A | d_clocks n*D*A]`` and ``val[j]`` the
    counter, for ``j < count``; a fleet whose flat space outgrows the
    int32 index is refused, to be folded in slices.  The cell columns
    have a power-of-two
    length and grow only when a fleet holds more cells, so a warm set
    allocates nothing.  ``orswot_batch._densify_cells`` turns
    :meth:`padded` back into dense planes on the device."""

    CELLS_PER_OBJECT = 16  # first sizing; the ★ fleet holds ≈ 13

    def __init__(self, n: int, cfg):
        import numpy as np

        from ..config import counter_dtype
        from .orswot_batch import _next_pow2

        a, m, d = cfg.num_actors, cfg.member_capacity, cfg.deferred_capacity
        per_object = (1 + m + d) * a
        if n * per_object > 2**31 - 1:
            raise ValueError(
                f"{n} objects of {per_object} counters each outgrow the "
                "int32 cell index; fold them in slices of at most "
                f"{(2**31 - 1) // per_object} objects")
        self.shape = (n, a, m, d)
        self.ids = np.full((n, m), -1, dtype=np.int32)
        self.d_ids = np.full((n, d), -1, dtype=np.int32)
        self.status = np.zeros(n, dtype=np.uint8)
        cap = _next_pow2(n * self.CELLS_PER_OBJECT)
        self.idx = np.zeros(cap, dtype=np.int32)
        self.val = np.zeros(cap, dtype=counter_dtype(cfg))
        self.count = 0

    def reserve(self, need: int) -> None:
        """Grow the cell columns to hold ``need`` cells, keeping the
        first ``count``."""
        import numpy as np

        from .orswot_batch import _next_pow2

        if need <= self.idx.shape[0]:
            return
        cap = _next_pow2(need)
        for name in ("idx", "val"):
            old = getattr(self, name)
            new = np.zeros(cap, dtype=old.dtype)
            new[:self.count] = old[:self.count]
            setattr(self, name, new)

    def add_rows(self, objs, planes) -> None:
        """Write objects ``objs`` from dense ``planes`` whose row ``j``
        is object ``objs[j]`` (rows the native parse left empty)."""
        import numpy as np

        n, a, m, d = self.shape
        objs = np.asarray(objs, dtype=np.int64)
        clock, ids, dots, d_ids, d_clocks = (np.asarray(p) for p in planes)
        self.ids[objs] = ids
        self.d_ids[objs] = d_ids
        j, c = np.nonzero(clock)
        parts = [(objs[j] * a + c, clock[j, c])]
        j, s, c = np.nonzero(dots)
        parts.append((n * a + (objs[j] * m + s) * a + c, dots[j, s, c]))
        j, r, c = np.nonzero(d_clocks)
        parts.append((n * a * (1 + m) + (objs[j] * d + r) * a + c,
                      d_clocks[j, r, c]))
        self.reserve(self.count + sum(len(i) for i, _ in parts))
        for i, v in parts:
            end = self.count + len(i)
            self.idx[self.count:end] = i
            self.val[self.count:end] = v
            self.count = end

    def padded(self) -> tuple:
        """``(ids, d_ids, idx, val)`` with the cell columns cut to the
        next power of two of ``count``, padded with cells (0, 0), which
        a max-scatter leaves as they are: the device program's shapes
        take a few sizes only."""
        from .orswot_batch import _next_pow2

        k = self.count
        p = _next_pow2(k)
        self.idx[k:p] = 0
        self.val[k:p] = 0
        return self.ids, self.d_ids, self.idx[:p], self.val[:p]


def orswot_cells_from_wire(blobs, universe, cells: OrswotCells) -> OrswotCells:
    """Parse ``blobs`` into the compact ``cells`` of
    :class:`OrswotCells` — the device fold's ingest, with the status
    triage of :func:`orswot_planes_from_wire` and the same states.

    Identity and named universes take the native cell parse (one pass,
    its output columns grown only when a fleet overflows them); blobs it
    refuses, and a named fleet's blobs holding unseen names, are decoded
    as :func:`orswot_planes_from_wire` decodes them, into a small dense
    plane set of those rows only, whose cells are then appended.  Any
    other universe (or no engine) decodes the whole fleet in Python the
    same way.  Hard statuses raise ``WireFormatError`` with the caller's
    blob index.  Counted under ``wire.orswot.from_wire``."""
    import numpy as np

    from ..config import counter_dtype
    from ..utils.serde import from_binary
    from .orswot_batch import OrswotBatch, _np_planes

    cfg = universe.config
    dt = counter_dtype(cfg)
    a, m, d = cfg.num_actors, cfg.member_capacity, cfg.deferred_capacity
    n = len(blobs)
    cells.count = 0
    tables = None
    if universe.is_identity:
        engine = probe_engine(universe, "orswot_ingest_cells", dt)
        reason = "no_engine"
    else:
        engine, tables, reason = named_engine(universe,
                                              "orswot_ingest_cells", dt)
    if engine is None:
        record_wire("orswot", "from_wire", fallback=n, reason=reason)
        sub = OrswotBatch.from_scalar([from_binary(b) for b in blobs],
                                      universe, via_device=False)
        cells.add_rows(np.arange(n), (sub.clock, sub.ids, sub.dots,
                                      sub.d_ids, sub.d_clocks))
        return cells
    buf, offsets = concat_blobs(blobs)
    while True:
        k = engine.orswot_ingest_cells(
            buf, offsets, a, m, d, cells.ids, cells.d_ids, cells.idx,
            cells.val, cells.status, tables)
        if k <= cells.idx.shape[0]:
            break
        cells.reserve(k)
    cells.count = k
    status = cells.status
    if universe.is_identity:
        _raise_hard_status(status, cfg, "the identity registry range "
                           f"[0, {cfg.num_actors})")
        fb = np.nonzero(status == 1)[0].tolist()
        if fb:
            small = _np_planes(len(fb), cfg)
            _scalar_rows(blobs, fb, universe, small, np.arange(len(fb)))
            cells.add_rows(fb, small)
    else:
        fb = []
        pending = np.nonzero((status == 1) | (status == 5))[0]
        if pending.size:
            sub_buf, sub_offsets = concat_blobs([blobs[i] for i in pending])
            small = _np_planes(pending.size, cfg)
            sub_status = status[pending]
            fb = _intern_pending(blobs, sub_buf, sub_offsets, pending,
                                 universe, small, sub_status, engine,
                                 rows=np.arange(pending.size))
            status[pending] = sub_status
            cells.add_rows(pending, small)
        _raise_hard_status(status, cfg, f"the {cfg.num_actors} actor "
                           "columns (actor registry full)")
    record_wire("orswot", "from_wire", native=n - len(fb),
                fallback=len(fb), reason="grammar")
    return cells


def _repr_rank(universe, width: int):
    """int32[width]: each actor column's rank by ``repr`` of its name,
    the pair order of a deferred remove's ClockKey
    (``VClock.key``)."""
    import numpy as np

    names = universe.actors.values()[:width]
    rank = np.full(width, np.iinfo(np.int32).max, dtype=np.int32)
    order = sorted(range(len(names)), key=lambda i: repr(names[i]))
    rank[order] = np.arange(len(order), dtype=np.int32)
    return rank


def orswot_planes_to_wire(clock, ids, dots, d_ids, d_clocks, universe):
    """Wire blobs from dense host ORSWOT planes — the shared egress core
    of ``OrswotBatch.to_wire`` and the pipelined wire loop, identity or
    named universes alike.

    Returns the blob list, or ``None`` when the Python encoder must run
    (missing engine, keys that are neither identity ints nor names, a
    row holding an id without a name, or the u64 zigzag-overflow guard)
    — the caller serializes via ``to_binary`` then.  Outcomes are
    counted under ``wire.orswot.to_wire``."""
    from ..config import counter_dtype

    n = clock.shape[0]
    if n == 0:
        return []
    dt = counter_dtype(universe.config)
    if universe.is_identity:
        engine = probe_engine(universe, "orswot_encode_wire", dt)
        reason = "no_engine"
    else:
        engine, tables, reason = named_engine(universe,
                                              "orswot_encode_named", dt)
    if engine is not None and counters_overflow_zigzag(
        (clock, dots, d_clocks)
    ):
        # zigzag of a >=2^63 counter exceeds u64; to_binary's big-int
        # varints handle it — take the Python path
        engine = None
        reason = "overflow_zigzag"
    if engine is not None:
        if universe.is_identity:
            encoded = engine.orswot_encode_wire(clock, ids, dots, d_ids,
                                                d_clocks)
        else:
            encoded = engine.orswot_encode_named(
                clock, ids, dots, d_ids, d_clocks, *tables,
                _repr_rank(universe, clock.shape[1]))
            reason = "unnamed_id"
        if encoded is not None:
            record_wire("orswot", "to_wire", native=n)
            return slice_blobs(*encoded)
    record_wire("orswot", "to_wire", fallback=n, reason=reason)
    return None


def clockish_from_wire(blobs, universe, tag, planes_of_scalars):
    """``[N, A]`` planes from pure-clock-body blobs — the VClock/GCounter
    legs' tag-parameterized specialization of :func:`planes_from_wire`."""
    return planes_from_wire(
        blobs, universe, "clockish_ingest_wire",
        lambda engine, buf, offsets, cfg, dt: engine.clockish_ingest_wire(
            buf, offsets, tag, cfg.num_actors, dt
        ),
        planes_of_scalars,
        leg=_TAG_LEG.get(tag, "counters"),
    )


def clockish_to_wire(clocks, universe, tag, python_path):
    """Egress counterpart of :func:`clockish_from_wire`."""
    return planes_to_wire(
        clocks, universe, "clockish_encode_wire",
        lambda engine, host: engine.clockish_encode_wire(host, tag),
        python_path,
        leg=_TAG_LEG.get(tag, "counters"),
    )
