"""OrswotBatch — N add-wins OR-sets on device (the flagship type).

Dense form of `/root/reference/src/orswot.rs:26-30`: set clock, member-slot
tables (interned ids + per-member dot clocks) and a deferred-remove table.
``merge`` runs the vectorized dot-algebra kernel
(:func:`crdt_tpu.ops.orswot_ops.merge`); the op path (`apply_add` /
`apply_remove`) applies one op per object across the batch.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from flax import struct

from ..config import counter_dtype
from ..error import CapacityOverflowError, raise_for_overflow
from ..ops import orswot_ops
from ..scalar.orswot import Orswot
from ..scalar.vclock import VClock
from ..utils.interning import Universe
from ..utils.hostmem import gc_paused
from ..obs.kernels import observed_kernel
from .vclock_batch import VClockBatch


def _np_planes(n, cfg):
    """Empty dense planes ``(clock, ids, dots, d_ids, d_clocks)`` as numpy
    arrays — the one place the shape/dtype/fill scheme lives (``zeros``
    and both bulk-ingest paths build on it)."""
    import numpy as np

    a, m, d = cfg.num_actors, cfg.member_capacity, cfg.deferred_capacity
    dt = counter_dtype(cfg)
    return (
        np.zeros((n, a), dtype=dt),
        np.full((n, m), orswot_ops.EMPTY, dtype=np.int32),
        np.zeros((n, m, a), dtype=dt),
        np.full((n, d), orswot_ops.EMPTY, dtype=np.int32),
        np.zeros((n, d, a), dtype=dt),
    )


def _next_pow2(c: int) -> int:
    return 1 if c <= 0 else 1 << (c - 1).bit_length()


# host-path egress slice size: per-call conversion cost grows superlinearly
# past a few hundred thousand objects (measured 2.5x at 1M vs 4x250k with
# identical final heap — INGEST_PROFILE.md), so to_scalar converts fleets
# in slices of this many objects
_EGRESS_SLICE = 250_000


def _resolve_members(universe, id_array):
    """Member-name resolution for a cell column: one registry lookup per
    UNIQUE id present, plus the inverse index per cell.  Shared by the
    Python egress loop and the native extension (same parity reason as
    ``OrswotBatch._actor_names``)."""
    import numpy as np

    uniq, inv = np.unique(id_array, return_inverse=True)
    member_of = universe.members.lookup
    return [member_of(int(m)) for m in uniq], inv


def _on_accelerator(x) -> bool:
    try:
        return any(dev.platform != "cpu" for dev in x.devices())
    except Exception:
        return False


@observed_kernel("batch.orswot.device_nnz")
@jax.jit
def _device_nnz(clock, ids, dots, d_ids, d_clocks):
    """Populated-cell counts for the five planes, as one tiny fetch."""
    return jnp.stack(
        [
            jnp.count_nonzero(clock),
            jnp.sum(ids != orswot_ops.EMPTY),
            jnp.count_nonzero(dots),
            jnp.sum(d_ids != orswot_ops.EMPTY),
            jnp.count_nonzero(d_clocks),
        ]
    ).astype(jnp.int64)


@observed_kernel("batch.orswot.device_compact")
@functools.partial(jax.jit, static_argnames=("sizes", "with_entries"))
def _device_compact(clock, ids, dots, d_ids, d_clocks, sizes,
                    with_entries=True):
    """Size-bounded sparsification ON DEVICE: only compact coordinate
    columns ever cross the host boundary (dense planes are ~20x the
    column bytes — `reports/INGEST_PROFILE.md`).  ``jnp.nonzero(size=k)``
    keeps numpy's row-major cell order (objects ascending, slots within),
    which the scalar reconstruction relies on; padding rows land at the
    END of each column and the caller trims them with the exact counts
    from :func:`_device_nnz`.  Indices are narrowed to int32 (N ≤ 2^31)
    to halve transfer bytes."""
    kc, ke, kd, kq, kh = sizes
    i32 = lambda *xs: tuple(x.astype(jnp.int32) for x in xs)  # noqa: E731
    co, ca = jnp.nonzero(clock, size=kc, fill_value=0)
    if with_entries:
        eo, es = jnp.nonzero(ids != orswot_ops.EMPTY, size=ke, fill_value=0)
        entries = i32(eo, es) + (ids[eo, es],)
    else:
        # `to_coo` reconstructs member ids from the dot bundle; skipping
        # the entry pass saves both the device nonzero and its transfer
        z = jnp.zeros((0,), jnp.int32)
        entries = (z, z, jnp.zeros((0,), ids.dtype))
    do, ds, da = jnp.nonzero(dots, size=kd, fill_value=0)
    qo, qr = jnp.nonzero(d_ids != orswot_ops.EMPTY, size=kq, fill_value=0)
    ho, hr, ha = jnp.nonzero(d_clocks, size=kh, fill_value=0)
    return (
        i32(co, ca) + (clock[co, ca],),
        entries,
        i32(do, ds) + (ids[do, ds], da.astype(jnp.int32), dots[do, ds, da]),
        i32(qo, qr) + (d_ids[qo, qr],),
        i32(ho, hr, ha) + (d_clocks[ho, hr, ha],),
    )


def _pad_cols(cols, k, id_fill=False):
    """Right-pad coordinate columns to length ``k`` with scatter-neutral
    rows: coordinate 0 everywhere, value 0 (counters) or EMPTY (id
    planes) — both are identities for the ``max`` scatter the expander
    uses, so padding never perturbs the planes while keeping the jit
    cache keyed on power-of-two sizes only."""
    import numpy as np

    out = []
    for j, c in enumerate(cols):
        is_val = j == len(cols) - 1
        # coordinate columns must be integer indexers on device; callers
        # may pass Python lists or empty arrays (np.asarray([]) is
        # float64).  Value columns arrive pre-cast to their plane dtype.
        c = np.asarray(c) if is_val else np.asarray(c, dtype=np.int32)
        fill = orswot_ops.EMPTY if (is_val and id_fill) else 0
        pad = np.full(k - c.shape[0], fill, dtype=c.dtype)
        out.append(np.concatenate([c, pad]) if k > c.shape[0] else c)
    return tuple(out)


@observed_kernel("batch.orswot.device_expand")
@functools.partial(jax.jit, static_argnames=("n", "a", "m", "d"))
def _device_expand(cells, n, a, m, d):
    """Inverse of :func:`_device_compact`: max-scatter compact columns
    into dense planes ON DEVICE, so ingest ships columns (~200× smaller
    than dense state at reference-shaped sparsity) instead of dense
    planes over the host link.  ``max`` is the right scatter everywhere:
    counter cells join by the lattice rule, and id planes start at
    EMPTY = -1 with real ids ≥ 0 written at most once per slot (host-side
    validation), so ``max`` equals assignment while padding rows
    (value EMPTY) are no-ops."""
    (co, ca, cc), (eo, es, em), (do, ds, da, dc), (qo, qr, qm), (ho, hr, ha, hc) = cells
    dt = cc.dtype
    return (
        jnp.zeros((n, a), dt).at[co, ca].max(cc),
        jnp.full((n, m), orswot_ops.EMPTY, jnp.int32).at[eo, es].max(em.astype(jnp.int32)),
        jnp.zeros((n, m, a), dt).at[do, ds, da].max(dc),
        jnp.full((n, d), orswot_ops.EMPTY, jnp.int32).at[qo, qr].max(qm.astype(jnp.int32)),
        jnp.zeros((n, d, a), dt).at[ho, hr, ha].max(hc),
    )


@observed_kernel("batch.orswot.densify_cells")
@functools.partial(jax.jit, static_argnames=("a", "m", "d"))
def _densify_cells(ids, d_ids, cell_idx, cell_val, a, m, d):
    """Dense planes from one fleet's compact cells
    (:class:`~crdt_tpu.batch.wirebulk.OrswotCells`) ON DEVICE, so the
    wire loop ships a fleet as what it holds instead of dense planes:
    the id rows pass through, and the counters max-scatter into the
    plane-major flat space ``[clock n*a | dots n*m*a | d_clocks n*d*a]``
    that is then cut into the three counter planes.  Cells are unique,
    so ``max`` is assignment, and padding cells (index 0, value 0) are
    no-ops."""
    n = ids.shape[0]
    nc, nd = n * a, n * m * a
    flat = jnp.zeros((n * (1 + m + d) * a,), cell_val.dtype)
    flat = flat.at[cell_idx].max(cell_val)
    return (flat[:nc].reshape(n, a), ids, flat[nc:nc + nd].reshape(n, m, a),
            d_ids, flat[nc + nd:].reshape(n, d, a))


def _build_planes(n, cfg, clock_cells, entry_cells, dot_cells, dref_cells,
                  dclk_cells, via_device=None, join_counters=False):
    """Shared ingest tail: scatter validated coordinate groups into the
    five dense planes.  ``via_device=True`` pads the columns to
    power-of-two lengths and max-scatters ON DEVICE
    (:func:`_device_expand`) so only compact columns cross the host link;
    the host path is the original vectorized numpy scatter —
    plain assignment when the caller guarantees unique coordinates
    (``join_counters=False``; ``np.ufunc.at`` is far slower), lattice
    ``np.maximum.at`` when duplicates must join by max.  Callers must
    pass value columns already cast to their plane dtype (counter dtype
    / int32 ids) — padding derives its dtype from the column."""
    import numpy as np

    if via_device is None:
        via_device = jax.default_backend() != "cpu"
    a, m, d = cfg.num_actors, cfg.member_capacity, cfg.deferred_capacity

    if via_device:
        # device scatter-max joins duplicates either way, matching both
        # callers (unique coords are a special case of max-join)
        padded = tuple(
            _pad_cols(
                tuple(np.ascontiguousarray(np.asarray(c)) for c in cols),
                _next_pow2(np.asarray(cols[0]).shape[0]),
                id_fill=id_fill,
            )
            for cols, id_fill in (
                (clock_cells, False),
                (entry_cells, True),
                (dot_cells, False),
                (dref_cells, True),
                (dclk_cells, False),
            )
        )
        return _device_expand(padded, n=n, a=a, m=m, d=d)

    clock, ids, dots, d_ids, d_clocks = _np_planes(n, cfg)

    def scatter(plane, idx, vals):
        if join_counters:
            np.maximum.at(plane, idx, vals)
        else:
            plane[idx] = vals

    co, ca, cc = (np.asarray(x) for x in clock_cells)
    if co.size:
        scatter(clock, (co, ca), cc)
    eo, es, em = (np.asarray(x) for x in entry_cells)
    if eo.size:
        ids[eo, es] = em
    do, ds, da, dc = (np.asarray(x) for x in dot_cells)
    if do.size:
        scatter(dots, (do, ds, da), dc)
    qo, qr, qm = (np.asarray(x) for x in dref_cells)
    if qo.size:
        d_ids[qo, qr] = qm
    ho, hr, ha, hc = (np.asarray(x) for x in dclk_cells)
    if ho.size:
        scatter(d_clocks, (ho, hr, ha), hc)
    return tuple(jnp.asarray(x) for x in (clock, ids, dots, d_ids, d_clocks))


@struct.dataclass
class OrswotBatch:
    clock: jax.Array  # u64[N, A]
    ids: jax.Array  # int32[N, M]  (-1 = empty)
    dots: jax.Array  # u64[N, M, A]
    d_ids: jax.Array  # int32[N, D] (-1 = empty)
    d_clocks: jax.Array  # u64[N, D, A]

    @classmethod
    def zeros(cls, n: int, universe: Universe) -> "OrswotBatch":
        return cls(*(jnp.asarray(x) for x in _np_planes(n, universe.config)))

    @classmethod
    @gc_paused
    def from_scalar(
        cls, states: Sequence[Orswot], universe: Universe,
        via_device: bool | None = None,
    ) -> "OrswotBatch":
        """Bulk ingest: one Python pass per object collects the flat COO
        value columns with C-level ``list.extend(map(...))`` loops — never
        a per-dot Python append — plus per-object/per-entry *counts*; the
        (object, slot) coordinate columns are then synthesized in bulk
        with ``np.repeat``/``np.arange`` and the scatters build the dense
        tables — on device when the backend is an accelerator, so only
        compact columns cross the host link (:func:`_build_planes`).  The
        per-dot Python bytecode of the append-based walk is what bounded
        ingest at 1M scale on the CPU (``reports/INGEST_PROFILE.md``);
        this path keeps the unavoidable O(total dots) work in C."""
        import numpy as np

        cfg = universe.config
        n = len(states)
        m, d = cfg.member_capacity, cfg.deferred_capacity
        dt = counter_dtype(cfg)
        aidx = universe.actors.intern
        midx = universe.members.intern

        ca, cc = [], []  # set-clock columns (actor, counter)
        c_counts = np.empty(n, dtype=np.int64)  # clock dots per object
        em = []  # entry member ids, object-major / insertion order
        e_counts = np.empty(n, dtype=np.int64)  # entries per object
        ga, gc = [], []  # entry-dot columns (actor, counter)
        g_counts = []  # dots per entry, aligned with em
        qm = []  # deferred member ids
        q_counts = np.empty(n, dtype=np.int64)  # deferred rows per object
        ha, hc = [], []  # deferred-clock columns
        h_counts = []  # clock dots per deferred row, aligned with qm

        for i, s in enumerate(states):
          try:
            cd = s.clock.dots
            c_counts[i] = len(cd)
            ca.extend(map(aidx, cd))
            cc.extend(cd.values())

            ents = s.entries
            if len(ents) > m:
                raise ValueError(
                    f"object {i}: {len(ents)} members > member_capacity {m}"
                )
            e_counts[i] = len(ents)
            em.extend(map(midx, ents))
            for vc in ents.values():
                vd = vc.dots
                g_counts.append(len(vd))
                ga.extend(map(aidx, vd))
                gc.extend(vd.values())

            nrows = sum(len(members) for members in s.deferred.values())
            if nrows > d:
                raise ValueError(
                    f"object {i}: {nrows} deferred rows > deferred_capacity {d}"
                )
            q_counts[i] = nrows
            for ck, members in s.deferred.items():
                # one interned column pair per witnessing clock, shared by
                # every member row buffered under it
                pa = [aidx(actor) for actor, _ in ck]
                pc = [counter for _, counter in ck]
                for member in members:
                    qm.append(midx(member))
                    h_counts.append(len(pa))
                    ha.extend(pa)
                    hc.extend(pc)
          except AttributeError as e:
            # a decodable-but-wrong-typed object graph (e.g. a corrupted
            # from_binary payload whose tag flip decoded a GCounter where
            # a VClock belongs, or a ctx type where an Orswot belongs)
            # surfaces as the documented contract exception, not a raw
            # AttributeError (found by the wire mutation fuzz)
            raise TypeError(
                f"object {i}: malformed scalar state "
                f"({type(s).__name__}: {e})"
            ) from None

        def _obj_slot(counts):
            """(object, within-object slot) coordinate columns for rows
            laid out object-major with ``counts`` rows per object."""
            obj = np.repeat(np.arange(counts.shape[0]), counts)
            starts = np.repeat(np.cumsum(counts) - counts, counts)
            return obj, np.arange(obj.shape[0]) - starts

        ei = np.zeros(0, dtype=np.int64)
        ev = np.zeros(0, dtype=dt)
        em32 = np.zeros(0, dtype=np.int32)
        clock_cells = (ei, ei, ev)
        entry_cells = (ei, ei, em32)
        dot_cells = (ei, ei, ei, ev)
        dref_cells = (ei, ei, em32)
        dclk_cells = (ei, ei, ei, ev)
        if ca:
            co = np.repeat(np.arange(n), c_counts)
            clock_cells = (co, np.asarray(ca), np.asarray(cc, dtype=dt))
        if em:
            eo, es = _obj_slot(e_counts)
            entry_cells = (eo, es, np.asarray(em, dtype=np.int32))
            if ga:
                g_counts_arr = np.asarray(g_counts)
                go = np.repeat(eo, g_counts_arr)
                gs = np.repeat(es, g_counts_arr)
                dot_cells = (go, gs, np.asarray(ga), np.asarray(gc, dtype=dt))
        if qm:
            qo, qs = _obj_slot(q_counts)
            dref_cells = (qo, qs, np.asarray(qm, dtype=np.int32))
            if ha:
                h_counts_arr = np.asarray(h_counts)
                ho = np.repeat(qo, h_counts_arr)
                hs = np.repeat(qs, h_counts_arr)
                dclk_cells = (ho, hs, np.asarray(ha), np.asarray(hc, dtype=dt))

        return cls(
            *_build_planes(
                n, cfg, clock_cells, entry_cells, dot_cells, dref_cells,
                dclk_cells, via_device=via_device,
            )
        )

    @classmethod
    @gc_paused
    def from_wire(
        cls, blobs: Sequence[bytes], universe: Universe,
        via_device: bool | None = None,
    ) -> "OrswotBatch":
        """Bulk ingest straight from wire blobs (``to_binary(orswot)``
        payloads — the replication format, replacing the reference's host
        serde `lib.rs:62-83` as the bulk path).

        Fast path: with an **identity universe** (``Universe.identity`` —
        int actors < ``num_actors``, int32 members) and the native engine
        available, the blobs are parsed IN PARALLEL by the C++ decoder
        (`crdt_tpu/native/wire_ingest.cpp`) directly into dense planes —
        no Python objects, no per-value interning; measured ≥10× the
        ``from_binary``+``from_scalar`` walk at 1M objects.  A universe
        whose actors and members are ``str`` / ``bytes`` names takes the
        same parser with a native name table per registry: names are
        looked up in parallel, and names never seen before are interned
        in blob order, with the ids ``Registry.intern`` would hand out
        (save that unseen members buffered under one deferred clock take
        ids in wire order, where ``from_scalar`` takes them in set
        order).  Blobs outside the native grammar (keys of another type,
        big-int counters, overlong varints) fall back to the Python
        decoder per blob, so the fast path never changes semantics —
        ``from_wire(blobs, uni)`` equals ``from_scalar([from_binary(b)
        for b in blobs], uni)``, for named universes up to that id
        order.

        With keys of any other type (ints in a named registry, tuples)
        or without the native engine, the whole batch takes the Python
        path.

        ``via_device`` (default: True on accelerator backends) routes the
        parsed state through compact COO columns and a device-side dense
        expand (:meth:`from_coo`) instead of shipping dense planes — a
        1M-object fleet's ~325 MB of planes against ~16 MB of columns.  The device route canonicalizes member-slot order
        (ascending id), which is semantically identical."""
        from ..utils.serde import from_binary
        from .wirebulk import orswot_planes_from_wire

        n = len(blobs)
        if n == 0:
            return cls.zeros(0, universe)
        planes = orswot_planes_from_wire(blobs, universe)
        if planes is None:
            # no native fast path (engine missing / keys neither
            # identity ints nor names): the whole batch decodes in Python
            return cls.from_scalar(
                [from_binary(b) for b in blobs], universe
            )
        clock, ids, dots, d_ids, d_clocks = planes
        if via_device is None:
            via_device = jax.default_backend() != "cpu"
        if via_device:
            # compact columns + device-side expand: dense planes never
            # cross the host link (they are ~20x the column bytes).  The
            # extraction reuses to_coo's host path — one sparsification
            # implementation to maintain — and from_coo's slot assignment
            # canonicalizes member order (ascending id), which is
            # semantically identical to the wire order the host route
            # preserves.
            tmp = cls(clock=clock, ids=ids, dots=dots, d_ids=d_ids,
                      d_clocks=d_clocks)
            clock_coords, dot_coords, q, h = tmp.to_coo(via_device=False)
            kwargs = {}
            if q[0].size:
                kwargs = {"deferred_members": q, "deferred_coords": h}
            return cls.from_coo(
                n, universe, clock_coords=clock_coords,
                dot_coords=dot_coords, via_device=True, **kwargs,
            )
        return cls(
            clock=jnp.asarray(clock), ids=jnp.asarray(ids),
            dots=jnp.asarray(dots), d_ids=jnp.asarray(d_ids),
            d_clocks=jnp.asarray(d_clocks),
        )

    @gc_paused
    def to_wire(self, universe: Universe) -> list[bytes]:
        """Bulk egress to wire blobs — the inverse of :meth:`from_wire`,
        byte-identical to ``[to_binary(s) for s in self.to_scalar(uni)]``.

        Fast path (identity or named universe + native engine): the
        parallel C++ encoder (`crdt_tpu/native/wire_ingest.cpp`)
        serializes the dense planes directly — no scalar objects; the
        deterministic orderings of the serde codec (encoded-bytes pair
        sort, repr-sorted clock keys) are reproduced exactly.  Counters
        at or above 2^63 (u64 planes only) and keys of other types take
        the Python path."""
        import numpy as np

        from ..utils.serde import to_binary
        from .wirebulk import orswot_planes_to_wire

        n = self.clock.shape[0]
        if n == 0:
            return []
        blobs = orswot_planes_to_wire(
            np.asarray(self.clock), np.asarray(self.ids),
            np.asarray(self.dots), np.asarray(self.d_ids),
            np.asarray(self.d_clocks), universe,
        )
        if blobs is None:
            return [to_binary(s) for s in self.to_scalar(universe)]
        return blobs

    @classmethod
    def from_coo(
        cls, n: int, universe: Universe, *,
        clock_coords, dot_coords, deferred_members=None, deferred_coords=None,
        via_device: bool | None = None,
    ) -> "OrswotBatch":
        """Columnar bulk ingest — build ``n`` dense states straight from
        COO coordinate arrays, without materializing any scalar objects
        (the per-object Python walk is what bounds :meth:`from_scalar` at
        ~130k obj/s — ``reports/INGEST_PROFILE.md``).  Validation and
        slot assignment stay host-side on the compact columns; the dense
        scatter runs on device on accelerator backends
        (:func:`_build_planes`), so dense planes never cross the host
        link.

        * ``clock_coords`` — ``(obj, actor_idx, counter)`` arrays for the
          set clocks.
        * ``dot_coords`` — ``(obj, member_id, actor_idx, counter)`` arrays
          for the member dot clocks; member slots are assigned per object
          in ascending member-id order (the engine's canonical order).
        * ``deferred_members`` — optional ``(obj, row, member_id)`` arrays;
          ``deferred_coords`` — ``(obj, row, actor_idx, counter)`` arrays
          giving each deferred row's witnessing clock.  Rows index the
          deferred table directly (a row is one buffered
          (member, clock) remove, `orswot.rs:29`).

        Duplicate *counter* coordinates (clock, dot, deferred-clock cells)
        join by ``max`` — the lattice's own rule, so re-ingesting
        overlapping exports is idempotent.  ``deferred_members`` rows are
        assignments, not lattice cells: two entries naming the same
        ``(obj, row)`` with different member ids are a conflict and raise.
        Actor indices must already be dense (``universe.actor_idx``);
        member ids are the interned int32 ids (``universe.member_id``).
        Raises ``ValueError`` on a negative member id (the ``EMPTY``
        sentinel leaking from an upstream export) in either ``dot_coords``
        or ``deferred_members``, when an object's distinct members exceed
        ``member_capacity``, when a deferred row index falls outside
        ``[0, deferred_capacity)``, or when only one of the two deferred
        argument pairs is supplied."""
        import numpy as np

        cfg = universe.config
        m, d = cfg.member_capacity, cfg.deferred_capacity
        dt = counter_dtype(cfg)
        ei = np.zeros(0, dtype=np.int64)
        ev = np.zeros(0, dtype=dt)
        em32 = np.zeros(0, dtype=np.int32)
        entry_cells = (ei, ei, em32)
        dot_cells = (ei, ei, ei, ev)
        dref_cells = (ei, ei, em32)
        dclk_cells = (ei, ei, ei, ev)

        co, ca, cc = (np.asarray(x) for x in clock_coords)
        clock_cells = (co, ca, cc.astype(dt))

        do, dm, da, dc = (np.asarray(x) for x in dot_coords)
        if do.size:
            if dm.min(initial=0) < 0:
                raise ValueError(
                    f"negative member id {int(dm.min())} in dot_coords "
                    "(EMPTY sentinel leaking from an export?)"
                )
            # slot assignment: unique (obj, member) pairs, ascending member
            # id within each object — np.unique's lexicographic sort gives
            # exactly that, and searchsorted ranks each pair within its
            # object's group
            pair_key = do.astype(np.int64) * (1 << 32) + dm.astype(np.int64)
            uniq, inv = np.unique(pair_key, return_inverse=True)
            uo = (uniq >> 32).astype(np.int64)
            um = (uniq & ((1 << 32) - 1)).astype(np.int32)
            slot = np.arange(uniq.size) - np.searchsorted(uo, uo)
            counts = np.bincount(uo, minlength=n)
            if counts.max(initial=0) > m:
                bad = int(np.argmax(counts))
                raise ValueError(
                    f"object {bad}: {int(counts[bad])} members > member_capacity {m}"
                )
            entry_cells = (uo, slot, um)
            dot_cells = (do, slot[inv], da, dc.astype(dt))

        if (deferred_members is None) != (deferred_coords is None):
            raise ValueError(
                "deferred_members and deferred_coords must be supplied together "
                "(a deferred row is a (member, clock) pair)"
            )
        if deferred_members is not None:
            def _check_rows(rows, label):
                if rows.size and (rows.min() < 0 or rows.max() >= d):
                    raise ValueError(
                        f"{label} row indices must lie in [0, "
                        f"deferred_capacity={d}); got "
                        f"[{int(rows.min())}, {int(rows.max())}]"
                    )

            qo, qr, qm = (np.asarray(x) for x in deferred_members)
            _check_rows(qr, "deferred_members")
            if qo.size:
                if qm.min(initial=0) < 0:
                    raise ValueError(
                        f"negative member id {int(qm.min())} in "
                        "deferred_members (EMPTY sentinel leaking from an "
                        "export?) — the row would be invisible to kernels "
                        "while its clock still scatters into d_clocks"
                    )
                # duplicate (obj, row) keys are assignments, not lattice
                # cells: silently last-write-winning would drop a remove
                key = qo.astype(np.int64) * d + qr.astype(np.int64)
                order = np.argsort(key, kind="stable")
                sk, sm = key[order], qm[order]
                dup = sk[1:] == sk[:-1]
                if np.any(dup & (sm[1:] != sm[:-1])):
                    i = int(np.nonzero(dup & (sm[1:] != sm[:-1]))[0][0])
                    raise ValueError(
                        f"conflicting deferred_members assignments for "
                        f"(obj={int(sk[i]) // d}, row={int(sk[i]) % d}): "
                        f"member ids {int(sm[i])} and {int(sm[i + 1])}"
                    )
                dref_cells = (qo, qr, qm.astype(np.int32))
            ho, hr, ha, hc = (np.asarray(x) for x in deferred_coords)
            _check_rows(hr, "deferred_coords")
            if ho.size:
                dclk_cells = (ho, hr, ha, hc.astype(dt))

        return cls(
            *_build_planes(
                n, cfg, clock_cells, entry_cells, dot_cells, dref_cells,
                dclk_cells, via_device=via_device, join_counters=True,
            )
        )

    def _cells(self, via_device: bool | None = None, want_entries: bool = True):
        """The five populated-cell coordinate bundles — clock, entry ids,
        entry dots (slot AND member id), deferred ids, deferred clocks —
        as host numpy columns.  When the planes live on an accelerator
        (auto-detected), sparsification runs ON DEVICE
        (:func:`_device_compact`) and only compact columns cross the
        host link; on CPU the same bundles come from ``np.nonzero``
        directly.  Both paths emit cells in row-major order.
        ``want_entries=False`` returns an empty entry bundle without
        computing or transferring it (``to_coo`` derives member ids from
        the dot bundle instead)."""
        import numpy as np

        if via_device is None:
            via_device = _on_accelerator(self.clock)
        planes = (self.clock, self.ids, self.dots, self.d_ids, self.d_clocks)
        if via_device:
            counts = [int(c) for c in np.asarray(_device_nnz(*planes))]  # crdtlint: disable=SC03 — snapshot sparsify sizes become statics, host fetch is the point
            if not want_entries:
                counts[1] = 0
            sizes = tuple(_next_pow2(c) for c in counts)
            bundles = jax.device_get(
                _device_compact(*planes, sizes=sizes, with_entries=want_entries)
            )
            return tuple(
                tuple(col[:c] for col in b) for b, c in zip(bundles, counts)
            )
        clock, ids, dots, d_ids, d_clocks = (np.asarray(x) for x in planes)
        co, ca = np.nonzero(clock)
        if want_entries:
            eo, es = np.nonzero(ids != orswot_ops.EMPTY)
            entries = (eo, es, ids[eo, es])
        else:
            z = np.zeros(0, dtype=np.int64)
            entries = (z, z, np.zeros(0, dtype=ids.dtype))
        do, ds, da = np.nonzero(dots)
        qo, qr = np.nonzero(d_ids != orswot_ops.EMPTY)
        ho, hr, ha = np.nonzero(d_clocks)
        return (
            (co, ca, clock[co, ca]),
            entries,
            (do, ds, ids[do, ds], da, dots[do, ds, da]),
            (qo, qr, d_ids[qo, qr]),
            (ho, hr, ha, d_clocks[ho, hr, ha]),
        )

    def to_coo(self, via_device: bool | None = None):
        """Columnar bulk egress — the inverse of :meth:`from_coo`: four
        coordinate-array tuples of populated cells (no Python objects;
        pair with :meth:`from_coo` for checkpoint-scale export of live
        fleets).  Returns ``(clock_coords, dot_coords, deferred_members,
        deferred_coords)``.  On an accelerator backend the
        sparsification runs on device and only compact columns transfer
        (see :meth:`_cells`)."""
        (co, ca, cv), _e, (do, _ds, dm, da, dv), q, h = self._cells(
            via_device, want_entries=False
        )
        return ((co, ca, cv), (do, dm, da, dv), q, h)

    @gc_paused
    def _actor_names(self, universe: Universe) -> list:
        """Per-actor-column names, hoisted out of the per-cell loops: the
        actor universe is dense (one list index per cell instead of a
        method call; only interned columns can carry data, the rest stay
        None).  Shared by the Python egress loop and the native
        extension so the two resolutions can never diverge."""
        n_interned = len(universe.actors)
        return [
            universe.actors.lookup(i) if i < n_interned else None
            for i in range(self.clock.shape[1])
        ]

    def to_scalar(
        self, universe: Universe, via_device: bool | None = None
    ) -> list[Orswot]:
        """Bulk egress: :meth:`_cells` extracts every populated cell in
        five vectorized passes (on device when the planes live on an
        accelerator — dense planes never cross the host link); the Python
        loop only walks actual dots (sparse), never the dense
        ``[N, M, A]`` volume.

        Host-path fleets convert in bounded slices: one monolithic pass
        measured 2.5× SLOWER at 1M than the same work in 250k slices
        (51k vs 128k obj/s, outputs all kept live either way — the cost
        grows superlinearly with per-call size, not with the resulting
        heap; `reports/INGEST_PROFILE.md` reproduction section)."""
        import numpy as np

        from ..scalar.vclock import VClock

        if via_device is None:
            via_device = _on_accelerator(self.clock)
        n_total = self.clock.shape[0]

        if not via_device and n_total > _EGRESS_SLICE * 3 // 2:
            # numpy views, not jnp slicing: one zero-copy np.asarray per
            # plane, then each slice is a view — no XLA slice dispatch or
            # per-slice plane copies
            planes = tuple(
                np.asarray(x)
                for x in (self.clock, self.ids, self.dots,
                          self.d_ids, self.d_clocks)
            )
            out: list = []
            s0 = 0
            while s0 < n_total:
                # a short final remainder (< slice/2) merges into this
                # slice instead of becoming a tiny ragged call
                end = s0 + _EGRESS_SLICE
                if n_total - end < _EGRESS_SLICE // 2:
                    end = n_total
                sub = OrswotBatch(*(p[s0:end] for p in planes))
                out.extend(sub.to_scalar(universe, via_device=False))
                s0 = end
            return out

        # native fast path: hand the cell bundles to the C extension,
        # which constructs the Orswot/VClock objects through the C API
        # (no interpreter frames per object).  Names are resolved
        # host-side — one registry lookup per actor column / unique
        # member id — so interned and identity universes both apply.
        # Measured >=3x the Python loop (VERDICT r4 item 6).
        if n_total > 0:
            try:
                from ..native import scalarize

                ext = scalarize.load()
            except (RuntimeError, OSError):
                ext = None
            if ext is not None:
                from ..scalar.orswot import Orswot as _Ors

                cells = self._cells(via_device)
                (co, ca, cv), (eo, es, em), (do, ds, _dm, da, dv), (
                    qo, qr, qm,
                ), (ho, hr, ha, hv) = cells
                actor_name = self._actor_names(universe)
                uniq_names, inv = _resolve_members(universe, em)
                q_names, q_inv = _resolve_members(universe, qm)
                i64 = lambda x: np.ascontiguousarray(x, dtype=np.int64)
                u64 = lambda x: np.ascontiguousarray(x, dtype=np.uint64)
                return ext.orswot_from_cells(
                    _Ors, VClock, n_total, actor_name,
                    i64(co), i64(ca), u64(cv),
                    i64(eo), i64(es), uniq_names, i64(inv),
                    i64(do), i64(ds), i64(da), u64(dv),
                    i64(qo), i64(qr), q_names, i64(q_inv),
                    i64(ho), i64(hr), i64(ha), u64(hv),
                )

        cells = self._cells(via_device)
        (co, ca, cv), (eo, es, em), (do, ds, _dm, da, dv), (qo, qr, qm), (
            ho, hr, ha, hv,
        ) = cells

        n = self.clock.shape[0]
        # registry lookups hoisted out of the per-cell loops (shared with
        # the native fast path above so the two can never diverge)
        actor_name = self._actor_names(universe)
        out = [Orswot() for _ in range(n)]

        for i, aix, v in zip(co.tolist(), ca.tolist(), cv.tolist()):
            out[i].clock.dots[actor_name[aix]] = v

        # entries in slot order (both cell paths emit row-major order),
        # matching the insertion order the naive path produced
        uniq_names, inv = _resolve_members(universe, em)
        entry_clocks = {}
        for i, j, u in zip(eo.tolist(), es.tolist(), inv.tolist()):
            vc = VClock()
            out[i].entries[uniq_names[u]] = vc
            entry_clocks[(i, j)] = vc
        for i, j, aix, v in zip(
            do.tolist(), ds.tolist(), da.tolist(), dv.tolist()
        ):
            entry_clocks[(i, j)].dots[actor_name[aix]] = v

        if qo.size:
            deferred_clocks = {}
            deferred_members = {}
            d_names, d_inv = _resolve_members(universe, qm)
            for i, j, u in zip(qo.tolist(), qr.tolist(), d_inv.tolist()):
                deferred_clocks[(i, j)] = VClock()
                deferred_members[(i, j)] = d_names[u]
            for i, j, aix, v in zip(
                ho.tolist(), hr.tolist(), ha.tolist(), hv.tolist()
            ):
                if (i, j) in deferred_clocks:
                    deferred_clocks[(i, j)].dots[actor_name[aix]] = v
            for (i, _j), vc in deferred_clocks.items():
                out[i].deferred.setdefault(vc.key(), set()).add(
                    deferred_members[(i, _j)]
                )
        return out

    @property
    def member_capacity(self) -> int:
        return self.ids.shape[-1]

    @property
    def deferred_capacity(self) -> int:
        return self.d_ids.shape[-1]

    def with_capacity(
        self, member_capacity: int | None = None, deferred_capacity: int | None = None
    ) -> "OrswotBatch":
        """Regrow the padded slot axes (elastic recovery from overflow).

        Capacities are this framework's static-shape concession (SURVEY.md
        §7.3); growing them pads with empty slots, which is semantically a
        no-op — empty slots are 'absent' (`orswot.rs` stores no entry at
        all), so the regrown batch is the same CRDT state."""
        m_new = self.member_capacity if member_capacity is None else member_capacity
        d_new = self.deferred_capacity if deferred_capacity is None else deferred_capacity
        if m_new < self.member_capacity or d_new < self.deferred_capacity:
            raise ValueError("with_capacity cannot shrink (would drop live slots)")
        pad_m = m_new - self.member_capacity
        pad_d = d_new - self.deferred_capacity
        if pad_m == 0 and pad_d == 0:
            return self

        def pad_slots(x, pad, tail_axes, fill=0):
            # slot axis is ndim-1-tail_axes; arbitrary leading batch axes
            # (replica-stacked batches are rank 3+, tests/test_sharding.py)
            widths = [(0, 0)] * x.ndim
            widths[x.ndim - 1 - tail_axes] = (0, pad)
            return jnp.pad(x, widths, constant_values=fill)

        return OrswotBatch(
            clock=self.clock,
            ids=pad_slots(self.ids, pad_m, 0, orswot_ops.EMPTY),
            dots=pad_slots(self.dots, pad_m, 1),
            d_ids=pad_slots(self.d_ids, pad_d, 0, orswot_ops.EMPTY),
            d_clocks=pad_slots(self.d_clocks, pad_d, 1),
        )

    # -- state path -------------------------------------------------------

    def merge(
        self, other: "OrswotBatch", check: bool = True,
        impl: str | None = None,
    ) -> "OrswotBatch":
        """Pairwise ORSWOT merge (`orswot.rs:89-156`).

        ``impl`` selects the kernel implementation; pass
        ``universe.config.merge_impl`` to apply a config's selection
        (batches are pure pytrees and do not carry the config), or leave
        ``None`` for the env/backend default — see
        :func:`crdt_tpu.ops.orswot_ops.resolve_merge_impl`.  The
        Map/value-kernel path (``OrswotKernel.from_config``) and the
        collectives thread it automatically."""
        m_cap = self.ids.shape[-1]
        d_cap = self.d_ids.shape[-1]
        clock, ids, dots, d_ids, d_clocks, overflow = _merge(
            self.clock, self.ids, self.dots, self.d_ids, self.d_clocks,
            other.clock, other.ids, other.dots, other.d_ids, other.d_clocks,
            m_cap, d_cap, impl,
        )
        if check:
            raise_for_overflow(overflow, "merge")
        return OrswotBatch(clock=clock, ids=ids, dots=dots, d_ids=d_ids, d_clocks=d_clocks)

    @classmethod
    def join_fleet(
        cls, fleets: Sequence["OrswotBatch"], check: bool = True,
        plunger: bool = True, impl: str | None = None,
    ) -> "OrswotBatch":
        """N-way anti-entropy join of replica fleets holding the same
        objects — the device-shaped form of the reference's merge-all
        loop (`/root/reference/test/orswot.rs:45-62`).

        Reduces the fleets as a pairwise tree
        (:func:`crdt_tpu.ops.orswot_ops.fold_merge_fleets`) in one jitted
        program: log-depth dependency chain, never stacking the fleets,
        so the device holds the inputs plus one pair's merge
        temporaries.  The optional defer plunger flushes buffered
        removes at the end."""
        if len(fleets) == 0:
            raise ValueError("join_fleet needs at least one fleet")
        if len(fleets) == 1:
            # still run the plunger self-merge so the output is canonical
            # (ascending-id slot order, deferred flushed) regardless of
            # fleet count
            f = fleets[0]
            if not plunger:
                return f
            return f.merge(f, check=check, impl=impl)
        m_cap = fleets[0].ids.shape[-1]
        d_cap = fleets[0].d_ids.shape[-1]
        planes = tuple(
            (f.clock, f.ids, f.dots, f.d_ids, f.d_clocks) for f in fleets
        )
        clock, ids, dots, d_ids, d_clocks, overflow = _fold_tree(
            planes, m_cap, d_cap, plunger, impl
        )
        if check:
            raise_for_overflow(overflow, "join_fleet")
        return cls(clock=clock, ids=ids, dots=dots, d_ids=d_ids, d_clocks=d_clocks)

    def truncate(self, clock, check: bool = True) -> "OrswotBatch":
        """``Causal::truncate`` (`orswot.rs:159-172`): forget causal history
        dominated by ``clock`` — the reference's merge-with-an-empty-set
        trick followed by subtracting ``clock`` from the set clock and
        every member clock.  ``clock``: ``[N, A]`` counter array, one
        truncation clock per object.  Same semantics as
        :meth:`~crdt_tpu.batch.val_kernels.OrswotKernel.truncate`, which
        serves the nested (Map) protocol."""
        m_cap = self.ids.shape[-1]
        d_cap = self.d_ids.shape[-1]
        (c, ids, dots, d_ids, d_clocks), overflow = _truncate(
            self.clock, self.ids, self.dots, self.d_ids, self.d_clocks,
            jnp.asarray(clock, dtype=self.clock.dtype), m_cap, d_cap,
        )
        if check:
            raise_for_overflow(overflow, "truncate")
        return OrswotBatch(
            clock=c, ids=ids, dots=dots, d_ids=d_ids, d_clocks=d_clocks
        )

    # -- op path ----------------------------------------------------------

    def apply_add(self, actor_idx, counter, member_id, check: bool = True) -> "OrswotBatch":
        """One ``Op::Add`` per object (`orswot.rs:66-79`)."""
        clock, ids, dots, d_ids, d_clocks, overflow = _apply_add(
            self.clock, self.ids, self.dots, self.d_ids, self.d_clocks,
            jnp.asarray(actor_idx), jnp.asarray(counter), jnp.asarray(member_id),
        )
        if check and bool(jnp.any(overflow)):
            raise CapacityOverflowError(
                "Orswot capacity overflow in apply_add: raise member_capacity",
                member=True,
                deferred=False,
            )
        return OrswotBatch(clock=clock, ids=ids, dots=dots, d_ids=d_ids, d_clocks=d_clocks)

    def apply_remove(self, rm_clock, member_id, check: bool = True) -> "OrswotBatch":
        """One ``Op::Rm`` per object (`orswot.rs:80-83,195-211`)."""
        clock, ids, dots, d_ids, d_clocks, overflow = _apply_remove(
            self.clock, self.ids, self.dots, self.d_ids, self.d_clocks,
            jnp.asarray(rm_clock), jnp.asarray(member_id),
        )
        if check and bool(jnp.any(overflow)):
            raise CapacityOverflowError(
                "Orswot capacity overflow in apply_remove: raise deferred_capacity",
                member=False,
                deferred=True,
            )
        return OrswotBatch(clock=clock, ids=ids, dots=dots, d_ids=d_ids, d_clocks=d_clocks)

    # -- reads ------------------------------------------------------------

    def contains(self, member_id):
        """Membership bitmap (`orswot.rs:214-224`)."""
        return orswot_ops.contains(self.ids, jnp.asarray(member_id))

    def member_count(self):
        return jnp.sum(self.ids != orswot_ops.EMPTY, axis=-1)

    def value_sets(self, universe: Universe) -> list[set]:
        """``value()`` per object (`orswot.rs:227-233`)."""
        import numpy as np

        ids = np.asarray(self.ids)
        return [
            {universe.members.lookup(int(x)) for x in row if x != orswot_ops.EMPTY}
            for row in ids
        ]


@observed_kernel("batch.orswot.merge")
@functools.partial(jax.jit, static_argnums=(10, 11, 12))
def _merge(ca, ia, da, dia, dca, cb, ib, db, dib, dcb, m_cap, d_cap, impl):
    return orswot_ops.merge(
        ca, ia, da, dia, dca, cb, ib, db, dib, dcb, m_cap, d_cap, impl=impl
    )


@observed_kernel("batch.orswot.fold_tree")
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _fold_tree(fleets, m_cap, d_cap, plunger, impl):
    return orswot_ops.fold_merge_fleets(
        fleets, m_cap, d_cap, plunger=plunger, impl=impl,
    )


@observed_kernel("batch.orswot.apply_add")
@jax.jit
def _apply_add(clock, ids, dots, d_ids, d_clocks, actor_idx, counter, member_id):
    return orswot_ops.apply_add(clock, ids, dots, d_ids, d_clocks, actor_idx, counter, member_id)


@observed_kernel("batch.orswot.apply_remove")
@jax.jit
def _apply_remove(clock, ids, dots, d_ids, d_clocks, rm_clock, member_id):
    return orswot_ops.apply_remove(clock, ids, dots, d_ids, d_clocks, rm_clock, member_id)


@observed_kernel("batch.orswot.truncate")
@functools.partial(jax.jit, static_argnums=(6, 7))
def _truncate(clock, ids, dots, d_ids, d_clocks, t_clock, m_cap, d_cap):
    """One semantics, one home: delegates to the nested-protocol kernel
    (`val_kernels.OrswotKernel.truncate_full`), keeping the per-axis
    overflow pair for raise_for_overflow."""
    from .val_kernels import OrswotKernel

    kern = OrswotKernel(
        member_capacity=m_cap,
        deferred_capacity=d_cap,
        num_actors=clock.shape[-1],
        counter_bits=clock.dtype.itemsize * 8,
    )
    return kern.truncate_full((clock, ids, dots, d_ids, d_clocks), t_clock)
