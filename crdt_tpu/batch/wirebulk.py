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
    symbol missing), ``non_identity`` (universe is not identity-interned),
    ``grammar`` (per-blob status==1 splice), ``overflow_zigzag`` (u64
    counters past the native encoder's range).

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
    np.cumsum(
        np.fromiter((len(b) for b in blobs), dtype=np.int64, count=n),
        out=offsets[1:],
    )
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


def orswot_planes_from_wire(blobs, universe, out=None):
    """Dense ORSWOT planes (host numpy) straight from wire blobs, with
    the full status triage — the shared ingest core of
    ``OrswotBatch.from_wire`` and :class:`crdt_tpu.batch.wireloop.
    PipelinedWireLoop`.

    Returns ``(clock, ids, dots, d_ids, d_clocks)``, or ``None`` when
    the native fast path does not apply at all (missing engine /
    non-identity universe) — the caller then takes its own full-Python
    route.  Every outcome is counted under the ``wire.orswot.from_wire``
    counters (:func:`record_wire`).

    ``out``: optional preallocated plane 5-tuple passed through to
    ``engine.orswot_ingest_wire`` for buffer REUSE across calls — fresh
    per-call plane allocations page-fault GBs at north-star chunk scale
    and were the measured e2e ingest collapse (docs/GUIDE.md).

    Hard statuses raise ``ValueError`` with the caller's blob index;
    status==1 blobs (structure outside the fast-path grammar) are
    decoded by the Python codec and their rows spliced in, so the result
    always equals the pure-Python decode."""
    import numpy as np

    from ..config import counter_dtype

    cfg = universe.config
    engine = probe_engine(universe, "orswot_ingest_wire", counter_dtype(cfg))
    if engine is None:
        record_wire("orswot", "from_wire", fallback=len(blobs),
                    reason=fallback_reason(universe))
        return None
    buf, offsets = concat_blobs(blobs)
    clock, ids, dots, d_ids, d_clocks, status = engine.orswot_ingest_wire(
        buf, offsets, cfg.num_actors, cfg.member_capacity,
        cfg.deferred_capacity, counter_dtype(cfg), out=out,
    )
    n_fb = 0
    if status.any():
        # hard errors first, reported with the CALLER's blob index
        hard = np.nonzero(status > 1)[0]
        if hard.size:
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
            raise WireFormatError(
                f"object {first}: actor outside the identity registry "
                f"range [0, {cfg.num_actors})"
            )
        # code 1: structure outside the fast-path grammar — decode those
        # blobs in Python and patch their rows (raises exactly where the
        # scalar path would, e.g. non-int members against an identity
        # registry)
        from ..utils.serde import from_binary
        from .orswot_batch import OrswotBatch

        fb = np.nonzero(status == 1)[0].tolist()
        n_fb = len(fb)
        try:
            sub = OrswotBatch.from_scalar(
                [from_binary(blobs[i]) for i in fb], universe
            )
        except (ValueError, TypeError) as e:
            # from_scalar reports indices relative to the fallback
            # sublist; translate so the operator can find the blob
            raise type(e)(
                f"{e} [object indices above are relative to the "
                f"python-fallback sublist; its blob indices are "
                f"{fb[:16]}{'...' if len(fb) > 16 else ''}]"
            ) from None
        idx = np.asarray(fb, dtype=np.int64)
        clock[idx] = np.asarray(sub.clock)
        ids[idx] = np.asarray(sub.ids)
        dots[idx] = np.asarray(sub.dots)
        d_ids[idx] = np.asarray(sub.d_ids)
        d_clocks[idx] = np.asarray(sub.d_clocks)
    record_wire("orswot", "from_wire", native=len(blobs) - n_fb,
                fallback=n_fb, reason="grammar")
    return clock, ids, dots, d_ids, d_clocks


def orswot_planes_to_wire(clock, ids, dots, d_ids, d_clocks, universe):
    """Wire blobs from dense host ORSWOT planes — the shared egress core
    of ``OrswotBatch.to_wire`` and the pipelined wire loop.

    Returns the blob list, or ``None`` when the Python encoder must run
    (missing engine / non-identity universe / the u64 zigzag-overflow
    guard) — the caller serializes via ``to_binary`` then.  Outcomes are
    counted under ``wire.orswot.to_wire``."""
    from ..config import counter_dtype

    n = clock.shape[0]
    if n == 0:
        return []
    engine = probe_engine(
        universe, "orswot_encode_wire", counter_dtype(universe.config)
    )
    reason = fallback_reason(universe)
    if engine is not None and counters_overflow_zigzag(
        (clock, dots, d_clocks)
    ):
        # zigzag of a >=2^63 counter exceeds u64; to_binary's big-int
        # varints handle it — take the Python path
        engine = None
        reason = "overflow_zigzag"
    if engine is None:
        record_wire("orswot", "to_wire", fallback=n, reason=reason)
        return None
    buf, offsets = engine.orswot_encode_wire(clock, ids, dots, d_ids, d_clocks)
    record_wire("orswot", "to_wire", native=n)
    return slice_blobs(buf, offsets)


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
