"""Batched reads: jitted gather kernels over the dense planes (L2).

The reference's client protocol is a read-modify-write loop anchored on
``ReadCtx { add_clock, rm_clock, val }`` (`ctx.rs:12-21`): every read
returns the causal metadata a client needs to derive its next
:class:`~crdt_tpu.scalar.ctx.AddCtx` / :class:`~crdt_tpu.scalar.ctx.
RmCtx`.  The scalar module does this one object at a time with dict
clones; at serve scale a read batch is thousands of ``(object, kind)``
rows per step, so this module resolves whole batches with ONE jitted
gather per CRDT kind, straight from the dense planes:

* ORSWOT — ``contains(member)`` (rm clock = the member's witnessing
  dots row, `orswot.rs:214-224`) and ``value()`` (rm clock = the set
  clock, `orswot.rs:227-233`; ``member = NO_MEMBER`` selects it),
* G/PN counters — row sums with the count plane as both clocks (the
  plane IS the AddCtx base the op path derives against),
* LWW registers — value + marker, clockless,
* MV registers — per-slot values + the folded register clock
  (`mvreg.rs:201-222`),
* Maps — ``get(key)`` / ``len()`` (`map.rs:282-302`).

Results land in a columnar :class:`ResultFrame`, every row stamped
with the add/rm clocks — parity-pinned row-for-row against the scalar
``ReadCtx`` loop (tests/test_serve.py), so a remove derived from a
gathered row is byte-identical to one derived from a scalar clone.

The ORSWOT gather reads an object-major row view of the snapshot
(:class:`RowView`), built once per snapshot and kept by a
:class:`ViewCache`: the device stores the planes object-minor, and a
row gather straight from them relayouts each whole plane on every call.

Batch sizes pad to the next power of two (floor :data:`PAD_FLOOR`) so
the jit cache walks a log-bounded ladder, the same discipline as the
op-path scatter (`oplog/apply.py`).  Every jit site here has a
manifest row (``serve.gather.*``, ``serve.view.orswot``,
`analysis/kernels.py`).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import weakref
from typing import Any, Dict, NamedTuple, Optional

import numpy as np

from ..utils import tracing

#: read kinds — the ``kind`` column of a read batch.  Disjoint small
#: ints so mixed-kind batches stay columnar on the wire.
K_ORSWOT = 0
K_GCOUNTER = 1
K_PNCOUNTER = 2
K_LWW = 3
K_MVREG = 4
K_MAP = 5

KIND_NAMES = {
    K_ORSWOT: "orswot", K_GCOUNTER: "gcounter", K_PNCOUNTER: "pncounter",
    K_LWW: "lww", K_MVREG: "mvreg", K_MAP: "map",
}
READ_KINDS = tuple(sorted(KIND_NAMES))

#: ``member`` column sentinel: a whole-object read — ORSWOT ``value()``
#: / map ``len()`` — instead of a membership/key probe.
NO_MEMBER = -1

#: per-row result statuses (consistency post-filters write these)
ST_OK = 0
ST_NOT_STABLE = 1
STATUSES = (ST_OK, ST_NOT_STABLE)

#: smallest padded gather batch — below this every batch shares one
#: lowering
PAD_FLOOR = 8


def _next_pow2(b: int) -> int:
    n = PAD_FLOOR
    while n < b:
        n <<= 1
    return n


def _pad_rows(obj: np.ndarray, member: Optional[np.ndarray] = None):
    """Pad a read batch to the power-of-two ladder: object 0 /
    ``NO_MEMBER`` filler rows (harmless gathers, sliced off after)."""
    b = obj.shape[0]
    bp = _next_pow2(b)
    if bp != b:
        obj = np.concatenate([obj, np.zeros(bp - b, obj.dtype)])
        if member is not None:
            member = np.concatenate(
                [member, np.full(bp - b, NO_MEMBER, member.dtype)])
    return obj, member


#: objects per step of the row-view build: its temporaries are a chunk
#: of rows (302 MB planned at the ★ width, u32), not a whole plane
VIEW_CHUNK = 32768


def _view_width(a: int, m: int) -> int:
    """Lanes of one view row: the clock, the member ids and the dots,
    padded to a whole number of 128-lane tiles."""
    return -(-(a + m + m * a) // 128) * 128


def _signed(dtype):
    import jax.numpy as jnp

    return jnp.int64 if jnp.dtype(dtype).itemsize == 8 else jnp.int32


@functools.lru_cache(maxsize=None)
def _view_kernel():
    """The jitted ORSWOT row-view build: ``(clock[N,A], ids[N,M],
    dots[N,M,A])`` → ``rows[N,W]`` in the counter dtype, each object's
    row its clock, its member ids (a lossless bitcast) and its dots,
    zero-padded to :func:`_view_width`.

    The device keeps the planes object-minor (the object axis on the
    lanes), which a row gather cannot read without relayouting the
    whole plane; the row view is that relayout, paid once per snapshot.
    It is written :data:`VIEW_CHUNK` objects at a time so its
    temporaries stay one chunk; the last chunk is clamped to end at N
    and rewrites rows it shares with the one before with the same
    values."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..obs.kernels import observed_kernel

    def build(clock, ids, dots):
        n, a = clock.shape
        m = ids.shape[1]
        dt = clock.dtype
        w = _view_width(a, m)
        c = min(VIEW_CHUNK, n)

        def step(i, rows):
            at = jnp.minimum(i * c, n - c)
            cl, idc, dc = (lax.dynamic_slice_in_dim(p, at, c, 0)
                           for p in (clock, ids, dots))
            chunk = jnp.concatenate(
                [cl, lax.bitcast_convert_type(idc.astype(_signed(dt)), dt),
                 dc.reshape(c, m * a),
                 jnp.zeros((c, w - a - m - m * a), dt)], axis=1)
            return lax.dynamic_update_slice_in_dim(rows, chunk, at, 0)

        return lax.fori_loop(0, -(-n // c), step, jnp.zeros((n, w), dt))

    return observed_kernel("serve.view.orswot")(jax.jit(build))


@functools.lru_cache(maxsize=None)
def _orswot_kernel(a: int, m: int):
    """ONE jitted ORSWOT read gather over the row view (``a`` actors,
    ``m`` member slots): ``(rows[N,W], obj[B], member[B])`` → per-row
    val, add clock row, rm clock row, member-id row, and live-member
    count.  ``member >= 0`` rows are ``contains`` probes (rm = the
    matched slot's witnessing dots, zeros when absent — the empty
    ``VClock()`` of `orswot.rs:214-224`); ``NO_MEMBER`` rows are
    ``value()`` reads (rm = the set clock)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..obs.kernels import observed_kernel
    from ..ops import orswot_ops

    def kernel(rows, obj, member):
        row = jnp.take(rows, obj, axis=0)                 # [B, W]
        crow = row[:, :a]                                 # [B, A]
        idrow = lax.bitcast_convert_type(                 # [B, M]
            row[:, a:a + m], _signed(rows.dtype)).astype(jnp.int32)
        dotrow = row[:, a + m:a + m + m * a].reshape(-1, m, a)
        want = member[:, None]
        hit = (idrow == want) & (want >= 0) \
            & (idrow != orswot_ops.EMPTY)                 # [B, M]
        has = jnp.any(hit, axis=1)
        # at most one slot matches (ids are unique per row), so a
        # masked sum IS the member's witnessing clock
        mclock = jnp.sum(
            jnp.where(hit[:, :, None], dotrow, jnp.zeros_like(dotrow)),
            axis=1)
        value_read = member < jnp.int32(0)
        rm = jnp.where(value_read[:, None], crow, mclock)
        count = jnp.sum(idrow != orswot_ops.EMPTY, axis=1) \
            .astype(jnp.uint64)
        val = jnp.where(value_read, count, has.astype(jnp.uint64))
        return val, crow, rm, idrow, count

    return observed_kernel("serve.gather.orswot")(jax.jit(kernel))


@functools.lru_cache(maxsize=None)
def _counter_kernel():
    """ONE jitted counter gather shared by G- and PN-counters:
    ``(plane[N,W], obj[B])`` → row sums + the gathered rows (the
    count plane is both the value and the AddCtx base,
    `gcounter.rs:26-28`).  PN calls it once per sign plane."""
    import jax
    import jax.numpy as jnp

    from ..obs.kernels import observed_kernel

    def kernel(plane, obj):
        row = jnp.take(plane, obj, axis=0)
        return jnp.sum(row, axis=1), row

    return observed_kernel("serve.gather.counter")(jax.jit(kernel))


@functools.lru_cache(maxsize=None)
def _lww_kernel():
    """ONE jitted LWW gather: values + conflict markers (LWW carries
    no causal clock — `lwwreg.rs` reads are marker-ordered)."""
    import jax
    import jax.numpy as jnp

    from ..obs.kernels import observed_kernel

    def kernel(vals, markers, obj):
        return jnp.take(vals, obj, axis=0), jnp.take(markers, obj, axis=0)

    return observed_kernel("serve.gather.lww")(jax.jit(kernel))


@functools.lru_cache(maxsize=None)
def _mvreg_kernel():
    """ONE jitted MV-register gather: per-slot values + slot clocks +
    the folded register clock (`mvreg.rs:201-222` — read returns every
    concurrent value under the join of their clocks)."""
    import jax
    import jax.numpy as jnp

    from ..obs.kernels import observed_kernel

    def kernel(clocks, vals, obj):
        c = jnp.take(clocks, obj, axis=0)                 # [B, K, A]
        v = jnp.take(vals, obj, axis=0)                   # [B, K]
        fold = jnp.max(c, axis=1)                         # [B, A]
        live = jnp.any(c != 0, axis=2)                    # [B, K]
        count = jnp.sum(live, axis=1).astype(jnp.uint64)
        return v, c, fold, live, count

    return observed_kernel("serve.gather.mvreg")(jax.jit(kernel))


@functools.lru_cache(maxsize=None)
def _map_kernel():
    """ONE jitted map gather: ``get(key)`` rows (rm = the entry's
    clock, zeros when absent — `map.rs:291-302`) and ``len()`` rows
    (``NO_MEMBER``; add = rm = the map clock, `map.rs:282-288`)."""
    import jax
    import jax.numpy as jnp

    from ..obs.kernels import observed_kernel

    def kernel(clock, keys, eclocks, obj, key):
        crow = jnp.take(clock, obj, axis=0)               # [B, A]
        krow = jnp.take(keys, obj, axis=0)                # [B, K]
        erow = jnp.take(eclocks, obj, axis=0)             # [B, K, A]
        want = key[:, None]
        hit = (krow == want) & (want >= 0)
        has = jnp.any(hit, axis=1)
        eclk = jnp.sum(
            jnp.where(hit[:, :, None], erow, jnp.zeros_like(erow)),
            axis=1)
        len_read = key < jnp.int32(0)
        count = jnp.sum(krow >= 0, axis=1).astype(jnp.uint64)
        rm = jnp.where(len_read[:, None], crow, eclk)
        val = jnp.where(len_read, count, has.astype(jnp.uint64))
        return val, crow, rm, count

    return observed_kernel("serve.gather.map")(jax.jit(kernel))


@dataclasses.dataclass
class ReadRequest:
    """One columnar read batch: ``(object, kind)`` rows plus an
    optional member/key probe column and a session-consistency mode
    (:mod:`crdt_tpu.serve.consistency`).  ``require`` is the mode's
    clock floor — a writer's ack version vector for read-your-writes,
    the client's held token for monotonic reads."""

    obj: np.ndarray                     # int64[B]
    kind: np.ndarray                    # uint8[B] (READ_KINDS)
    member: np.ndarray                  # int32[B]; NO_MEMBER = whole-object
    mode: str = "eventual"
    require: Optional[np.ndarray] = None  # uint64[W] version-vector floor

    def __post_init__(self):
        self.obj = np.asarray(self.obj, np.int64).reshape(-1)
        self.kind = np.broadcast_to(
            np.asarray(self.kind, np.uint8), self.obj.shape).copy()
        self.member = np.broadcast_to(
            np.asarray(self.member, np.int32), self.obj.shape).copy()
        if self.require is not None:
            self.require = np.asarray(self.require, np.uint64).reshape(-1)

    def __len__(self) -> int:
        return int(self.obj.shape[0])

    @classmethod
    def reads(cls, obj, *, kind: int = K_ORSWOT, member=NO_MEMBER,
              mode: str = "eventual", require=None) -> "ReadRequest":
        return cls(obj=np.asarray(obj, np.int64).reshape(-1), kind=kind,
                   member=member, mode=mode, require=require)


@dataclasses.dataclass
class ResultFrame:
    """The columnar answer to a :class:`ReadRequest`: echoed keys, a
    per-row status, the value column, and the add/rm clock rows —
    exactly the scalar ``ReadCtx`` triple, batched.  ``token`` is the
    monotonic-reads clock token (the version vector of the snapshot
    every row was gathered from); a client hands it back as the next
    request's ``require``.  ``extras`` carries per-kind columns that
    never ride the wire (ORSWOT member rows, MV slot values/clocks)."""

    obj: np.ndarray                     # int64[B]
    kind: np.ndarray                    # uint8[B]
    member: np.ndarray                  # int32[B]
    status: np.ndarray                  # uint8[B] (ST_*)
    val: np.ndarray                     # uint64[B]
    add_clock: np.ndarray               # uint64[B, W]
    rm_clock: np.ndarray                # uint64[B, W]
    token: np.ndarray                   # uint64[W]
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __len__(self) -> int:
        return int(self.obj.shape[0])

    def read_ctx(self, i: int, universe=None):
        """Row ``i`` as a scalar :class:`~crdt_tpu.scalar.ctx.ReadCtx`
        — the bridge back into the reference's clone-derive-apply loop
        (``derive_add_ctx`` / ``derive_rm_ctx`` work unchanged)."""
        from ..scalar.ctx import ReadCtx

        return ReadCtx(
            add_clock=row_to_vclock(self.add_clock[i], universe),
            rm_clock=row_to_vclock(self.rm_clock[i], universe),
            val=int(self.val[i]),
        )


def row_to_vclock(row, universe=None):
    """A dense clock row as a scalar :class:`~crdt_tpu.scalar.vclock.
    VClock` (actor names resolved through ``universe.actors`` when
    given, dense column indices otherwise — the identity-universe
    convention every test fleet uses)."""
    from ..scalar.vclock import VClock

    row = np.asarray(row, np.uint64).reshape(-1)
    vc = VClock()
    for i in np.nonzero(row)[0]:
        name = universe.actors.lookup(int(i)) if universe is not None \
            else int(i)
        vc.dots[name] = int(row[i])
    return vc


class RowView(NamedTuple):
    """An ORSWOT snapshot's object-major row view (:func:`_view_kernel`)
    and the widths that slice its rows."""

    rows: Any                           # [N, W], counter dtype
    actors: int
    members: int


def build_view(batch) -> RowView:
    """Build ``batch``'s row view, waiting for the device to finish."""
    import jax

    with tracing.span("serve.view.build"):
        rows = jax.block_until_ready(
            _view_kernel()(batch.clock, batch.ids, batch.dots))
    tracing.count("serve.view.builds")
    return RowView(rows, int(batch.clock.shape[1]), int(batch.ids.shape[1]))


class ViewCache:
    """The row view of the last ORSWOT snapshot served: one slot, keyed
    by the snapshot's identity.  A batch is immutable and every write
    makes a new one, so a view is never stale.  The slot holds the
    snapshot weakly and its view strongly, and drops the view when the
    snapshot is collected, when :meth:`release` is called (a node does
    so before each fold, so the old batch, the new one and a view are
    never held together), and before a new snapshot's view is built,
    so one view is held at a time.  Thread-safe: concurrent first reads of a snapshot build it
    once; the others wait for that build."""

    def __init__(self):
        self._lock = threading.Lock()
        self._slot = None                   # (weakref to batch, RowView)

    def view(self, batch) -> RowView:
        with self._lock:
            slot = self._slot
            if slot is not None and slot[0]() is batch:
                tracing.count("serve.view.hits")
                return slot[1]
            # drop every reference first: the old view's device memory
            # is freed before the new one is allocated
            self._slot = slot = None
            view = build_view(batch)
            self._slot = (weakref.ref(batch, self._forget), view)
            return view

    def release(self) -> None:
        """Drop the held view; the next read rebuilds it."""
        with self._lock:
            self._slot = None

    def _forget(self, ref) -> None:
        # the snapshot was collected: its view goes with it.  If the lock
        # is taken, its holder is replacing or dropping this slot anyway
        # (a dead snapshot never hits), so never wait: the last reference
        # to a batch may fall on a thread that holds other locks
        if self._lock.acquire(blocking=False):
            try:
                if self._slot is not None and self._slot[0] is ref:
                    self._slot = None  # crdtlint: disable=lock-discipline — held: acquired without waiting
            finally:
                self._lock.release()


#: the view cache of a :func:`gather` called without one
_VIEWS = ViewCache()


# Each kind's gather is two halves: ``dispatch`` pads the batch, moves
# the indices to the device and calls the jitted kernel (async);
# ``rows`` copies the kernel's outputs to the host and cuts the padding
# off.  ``gather`` times each half, and the wait between them, as a leg.
# The ORSWOT halves read the snapshot's row view, not the batch.

def _dispatch_orswot(view, obj, member):
    import jax.numpy as jnp

    obj_p, mem_p = _pad_rows(obj, member)
    return _orswot_kernel(view.actors, view.members)(
        view.rows, jnp.asarray(obj_p), jnp.asarray(mem_p))


def _rows_orswot(out, b):
    val, add, rm, ids, count = out
    return (np.asarray(val, np.uint64)[:b],
            np.asarray(add, np.uint64)[:b],
            np.asarray(rm, np.uint64)[:b],
            {"members": np.asarray(ids, np.int32)[:b],
             "count": np.asarray(count, np.uint64)[:b]})


def _dispatch_gcounter(batch, obj, member):
    import jax.numpy as jnp

    obj_p, _ = _pad_rows(obj)
    return _counter_kernel()(batch.clocks, jnp.asarray(obj_p))


def _rows_gcounter(out, b):
    val, row = out
    row = np.asarray(row, np.uint64)[:b]
    return np.asarray(val, np.uint64)[:b], row, row.copy(), {}


def _dispatch_pncounter(batch, obj, member):
    import jax.numpy as jnp

    obj_p, _ = _pad_rows(obj)
    kern = _counter_kernel()
    jobj = jnp.asarray(obj_p)
    return (*kern(batch.planes[:, 0, :], jobj),
            *kern(batch.planes[:, 1, :], jobj))


def _rows_pncounter(out, b):
    p_sum, p_row, n_sum, n_row = out
    p_sum = np.asarray(p_sum, np.uint64)[:b]
    n_sum = np.asarray(n_sum, np.uint64)[:b]
    # P − N in two's complement (`pncounter.rs:117-119`; reinterpret as
    # int64 for the signed value)
    val = p_sum - n_sum
    clock = np.concatenate(
        [np.asarray(p_row, np.uint64)[:b], np.asarray(n_row, np.uint64)[:b]],
        axis=1)  # [B, 2A] — the _clock_plane flattening convention
    return val, clock, clock.copy(), {"p": p_sum, "n": n_sum}


def _dispatch_lww(batch, obj, member):
    import jax.numpy as jnp

    obj_p, _ = _pad_rows(obj)
    return _lww_kernel()(batch.vals, batch.markers, jnp.asarray(obj_p))


def _rows_lww(out, b):
    vals, markers = out
    zeros = np.zeros((b, 0), np.uint64)  # clockless
    return (np.asarray(vals, np.uint64)[:b], zeros, zeros.copy(),
            {"marker": np.asarray(markers, np.uint64)[:b]})


def _dispatch_mvreg(batch, obj, member):
    import jax.numpy as jnp

    obj_p, _ = _pad_rows(obj)
    return _mvreg_kernel()(batch.clocks, batch.vals, jnp.asarray(obj_p))


def _rows_mvreg(out, b):
    vals, clocks, fold, live, count = out
    fold = np.asarray(fold, np.uint64)[:b]
    return (np.asarray(count, np.uint64)[:b], fold, fold.copy(),
            {"mv_vals": np.asarray(vals)[:b],
             "mv_clocks": np.asarray(clocks, np.uint64)[:b],
             "mv_live": np.asarray(live, bool)[:b]})


def _dispatch_map(batch, obj, member):
    import jax.numpy as jnp

    obj_p, key_p = _pad_rows(obj, member)
    return _map_kernel()(batch.clock, batch.keys, batch.entry_clocks,
                         jnp.asarray(obj_p), jnp.asarray(key_p))


def _rows_map(out, b):
    val, add, rm, count = out
    return (np.asarray(val, np.uint64)[:b],
            np.asarray(add, np.uint64)[:b],
            np.asarray(rm, np.uint64)[:b],
            {"count": np.asarray(count, np.uint64)[:b]})


#: kind → (dispatch, rows)
_GATHERS = {
    K_ORSWOT: (_dispatch_orswot, _rows_orswot),
    K_GCOUNTER: (_dispatch_gcounter, _rows_gcounter),
    K_PNCOUNTER: (_dispatch_pncounter, _rows_pncounter),
    K_LWW: (_dispatch_lww, _rows_lww),
    K_MVREG: (_dispatch_mvreg, _rows_mvreg),
    K_MAP: (_dispatch_map, _rows_map),
}


def infer_kind(batch) -> int:
    """The read kind of a dense batch by type."""
    from ..batch.gcounter_batch import GCounterBatch
    from ..batch.lwwreg_batch import LWWRegBatch
    from ..batch.map_batch import MapBatch
    from ..batch.mvreg_batch import MVRegBatch
    from ..batch.orswot_batch import OrswotBatch
    from ..batch.pncounter_batch import PNCounterBatch

    for cls, kind in ((OrswotBatch, K_ORSWOT), (GCounterBatch, K_GCOUNTER),
                      (PNCounterBatch, K_PNCOUNTER), (LWWRegBatch, K_LWW),
                      (MVRegBatch, K_MVREG), (MapBatch, K_MAP)):
        if isinstance(batch, cls):
            return kind
    raise TypeError(
        f"no serve gather for {type(batch).__name__} "
        f"(served kinds: {sorted(KIND_NAMES.values())})"
    )


def gather(batch, obj, *, member=None, kind: Optional[int] = None,
           views: Optional[ViewCache] = None) -> ResultFrame:
    """Resolve one single-kind read batch against ``batch`` — one
    jitted gather regardless of batch size.  ``member`` probes
    membership (ORSWOT) / keys (map); ``NO_MEMBER`` rows read the
    whole object.  An ORSWOT gather reads ``batch``'s row view from
    ``views`` (by default the module's own :class:`ViewCache`).  The
    frame's ``token`` is left empty — the serve loop stamps it from the
    snapshot's version vector."""
    obj = np.asarray(obj, np.int64).reshape(-1)
    if kind is None:
        kind = infer_kind(batch)
    if kind not in _GATHERS:
        raise ValueError(f"unknown read kind {kind}")
    member = np.full(obj.shape, NO_MEMBER, np.int32) if member is None \
        else np.broadcast_to(np.asarray(member, np.int32), obj.shape).copy()
    b = obj.shape[0]
    n = _plane_rows(batch, kind)
    if b and (obj.min() < 0 or obj.max() >= n):
        raise IndexError(
            f"read object {int(obj.min()) if obj.min() < 0 else int(obj.max())} "
            f"outside the fleet's dense axis [0, {n})"
        )
    if b == 0:
        val = np.zeros(0, np.uint64)
        add = rm = np.zeros((0, 0), np.uint64)
        extras = {}
    else:
        import jax

        dispatch, rows = _GATHERS[kind]
        src = batch
        if kind == K_ORSWOT:
            src = (_VIEWS if views is None else views).view(batch)
        with tracing.span("serve.leg.dispatch"):
            out = dispatch(src, obj, member)
        # the gather holds what it reads; a view released meanwhile is
        # freed once the gather is done with it, not with this frame
        del src
        with tracing.span("serve.leg.wait"):
            jax.block_until_ready(out)
        with tracing.span("serve.leg.fetch"):
            val, add, rm, extras = rows(out, b)
    tracing.count("serve.reads", b)
    tracing.count("serve.batches")
    return ResultFrame(
        obj=obj, kind=np.full(b, kind, np.uint8), member=member,
        status=np.zeros(b, np.uint8), val=val,
        add_clock=add, rm_clock=rm,
        token=np.zeros(0, np.uint64), extras=extras,
    )


def _plane_rows(batch, kind: int) -> int:
    plane = {K_ORSWOT: "clock", K_GCOUNTER: "clocks", K_PNCOUNTER: "planes",
             K_LWW: "vals", K_MVREG: "vals", K_MAP: "clock"}[kind]
    return int(getattr(batch, plane).shape[0])


class QueryEngine:
    """Mixed-kind read batches over a set of dense batches — one
    gather per kind present, scattered back into one frame (the
    columnar ``(object, kind)`` dispatch of the serve path).  Holds
    ``{kind: batch}``; a bare batch serves its own kind only."""

    def __init__(self, batches):
        if not isinstance(batches, dict):
            batches = {infer_kind(batches): batches}
        for k in batches:
            if k not in _GATHERS:
                raise ValueError(f"unknown read kind {k}")
        self.batches = dict(batches)
        self.views = ViewCache()

    def width(self) -> int:
        """The widest clock row any served kind produces."""
        w = 0
        for kind, batch in self.batches.items():
            if kind == K_ORSWOT or kind == K_MAP:
                w = max(w, int(batch.clock.shape[1]))
            elif kind == K_GCOUNTER:
                w = max(w, int(batch.clocks.shape[1]))
            elif kind == K_PNCOUNTER:
                w = max(w, int(batch.planes.shape[1] * batch.planes.shape[2]))
            elif kind == K_MVREG:
                w = max(w, int(batch.clocks.shape[2]))
        return w

    def gather(self, obj, kind=None, member=None) -> ResultFrame:
        obj = np.asarray(obj, np.int64).reshape(-1)
        b = obj.shape[0]
        if kind is None:
            if len(self.batches) != 1:
                raise ValueError(
                    "a mixed-kind engine needs an explicit kind column")
            kind = next(iter(self.batches))
        kind = np.broadcast_to(np.asarray(kind, np.uint8), obj.shape).copy()
        member = np.full(obj.shape, NO_MEMBER, np.int32) if member is None \
            else np.broadcast_to(np.asarray(member, np.int32),
                                 obj.shape).copy()
        present = np.unique(kind)
        missing = [int(k) for k in present if int(k) not in self.batches]
        if missing:
            raise ValueError(
                f"read batch names unserved kinds {missing} "
                f"(served: {sorted(self.batches)})"
            )
        w = self.width()
        val = np.zeros(b, np.uint64)
        add = np.zeros((b, w), np.uint64)
        rm = np.zeros((b, w), np.uint64)
        extras: Dict[str, Any] = {}
        for k in present:
            idx = np.nonzero(kind == k)[0]
            sub = gather(self.batches[int(k)], obj[idx],
                         member=member[idx], kind=int(k), views=self.views)
            val[idx] = sub.val
            wk = sub.add_clock.shape[1]
            add[idx, :wk] = sub.add_clock
            rm[idx, :wk] = sub.rm_clock
            for name, col in sub.extras.items():
                extras.setdefault(name, {})[int(k)] = (idx, col)
        return ResultFrame(
            obj=obj, kind=kind, member=member,
            status=np.zeros(b, np.uint8), val=val,
            add_clock=add, rm_clock=rm,
            token=np.zeros(0, np.uint64), extras=extras,
        )
