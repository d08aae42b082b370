"""Collective lattice joins — anti-entropy as an all-reduce (SURVEY.md §5).

Because ``CvRDT::merge`` is associative, commutative, and idempotent
(`/root/reference/src/traits.rs:9-12`), the global join of N replicas is a
reduction with merge as the combiner:

* **clock-shaped state** (VClock / GCounter / PNCounter): merge is pointwise
  max (`vclock.rs:131-137`), so the cross-device join is literally
  ``lax.pmax`` — one XLA collective riding ICI.
* **ORSWOT state**: merge is the dot-algebra kernel; the cross-device join
  is an **all-gather + canonical-order fold** with merge as the combiner —
  see :func:`allgather_join_orswot` for why a ppermute ring is *unsafe*
  for this type (the reference merge is merge-order-sensitive).
* **replica-axis stacks on one device**: a binary tree of pairwise merges
  (log2 R kernel launches, all fused under one jit).

Anti-entropy-to-fixpoint (`BASELINE.md` config ★) = fold/collective join +
one extra self-merge pass to flush deferred removes (the reference's
"defer plunger", `test/orswot.rs:61-62`), iterated until stable.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..error import CapacityOverflowError, raise_for_overflow
from ..obs.kernels import observed_kernel
from ..ops import orswot_ops


# -- clock-shaped types ------------------------------------------------------


def _check_replica_axis(leading: int, mesh: Mesh, axis: str) -> None:
    """Every collective join shards one replica per device over ``axis``;
    a mismatched leading axis means the caller stacked the fleet wrong."""
    if leading != mesh.shape[axis]:
        raise ValueError(
            f"leading replica axis {leading} != mesh axis "
            f"{axis}={mesh.shape[axis]} (one replica shard per device)"
        )


def all_reduce_clock_join(clocks, mesh: Mesh, axis: str = "replicas"):
    """Global VClock/GCounter/PNCounter join across a mesh axis.

    ``clocks``: an array whose leading axis is the replica axis, sharded
    one replica per device over ``axis`` (leading size must equal the mesh
    axis size); the join is an all-reduce-max — the direct ICI collective
    form of N-way ``VClock::merge``.  Every replica row of the output holds
    the global join."""
    _check_replica_axis(clocks.shape[0], mesh, axis)
    return _clock_join_fn(mesh, axis, clocks.ndim)(clocks)


@functools.lru_cache(maxsize=64)
def _clock_join_fn(mesh: Mesh, axis: str, ndim: int):
    """Cached jitted clock all-reduce (jax.jit caches by function identity;
    a per-call closure would retrace+recompile every call)."""
    spec = P(axis, *([None] * (ndim - 1)))

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )
    def _join(local):
        # reduce the local replicas, then all-reduce across devices
        local_join = jnp.max(local, axis=0, keepdims=True)
        return jax.lax.pmax(local_join, axis_name=axis)

    return observed_kernel("parallel.clock_join")(_join)


# -- generic tree reduction over a replica axis ------------------------------


def tree_reduce_merge(stack, merge_fn: Callable):
    """Reduce a replica-stacked pytree (leading axis R on every leaf) to a
    single state with a binary merge tree — log2(R) pairwise batch merges,
    all inside one jit trace.

    ``merge_fn(a, b) -> merged`` takes and returns the pytree without the
    replica axis.

    CAVEAT: safe for types whose merge is truly commutative (clocks,
    counters, LWW, MVReg).  For ORSWOT, merge order leaves different stale
    dots in entry clocks (`orswot.rs:94-103` asymmetry), so use the
    sequential left fold (:func:`fold_reduce_merge`) when bit-parity with
    the scalar N-way join matters."""
    leaves = jax.tree_util.tree_leaves(stack)
    r = leaves[0].shape[0]

    def take(i):
        return jax.tree_util.tree_map(lambda x: x[i], stack)

    # tree via repeated halving over python ints (static under jit)
    parts = [take(i) for i in range(r)]
    while len(parts) > 1:
        nxt = []
        for i in range(0, len(parts) - 1, 2):
            nxt.append(merge_fn(parts[i], parts[i + 1]))
        if len(parts) % 2 == 1:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def fold_reduce_merge(stack, merge_fn: Callable):
    """Sequential left fold over the replica axis — replica order 0..R-1,
    bit-matching the scalar idiom ``for w in witnesses: merged.merge(w)``
    (`test/orswot.rs:53-56`).  R-1 batch merges, each fully parallel over
    the object axis."""
    leaves = jax.tree_util.tree_leaves(stack)
    r = leaves[0].shape[0]

    def take(i):
        return jax.tree_util.tree_map(lambda x: x[i], stack)

    acc = take(0)
    for i in range(1, r):
        acc = merge_fn(acc, take(i))
    return acc


# -- ORSWOT collective join --------------------------------------------------


def _orswot_pair_merge(a, b, m_cap: int, d_cap: int, impl: str | None = None):
    """Pairwise merge over state tuples; returns (state5, overflow)."""
    *state, overflow = orswot_ops.merge(
        a[0], a[1], a[2], a[3], a[4], b[0], b[1], b[2], b[3], b[4],
        m_cap, d_cap, impl=impl,
    )
    return tuple(state), overflow


@functools.lru_cache(maxsize=64)
def shard_local_merge_fn(mesh: Mesh, axis: str, m_cap: int, d_cap: int,
                         impl: str | None = None):
    """Cached jitted shard-local pairwise merge over state 5-tuples —
    cache keyed on (mesh, axis, capacities, merge impl) so loop-heavy
    callers compile once, not per call."""
    spec = P(axis)

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=((spec,) * 5, (spec,) * 5),
        out_specs=((spec,) * 5, spec),
        check_vma=False,
    )
    def _local(sa, sb):
        return _orswot_pair_merge(sa, sb, m_cap, d_cap, impl)

    return observed_kernel("parallel.shard_local_merge")(_local)


def shard_local_pairwise_merge(a, b, mesh: Mesh, axis: str = "objects",
                               impl: str | None = None):
    """Pairwise ORSWOT merge of two object-sharded batches with a
    **zero-collective guarantee**: each device merges only its own object
    shard under ``shard_map``, so the compiled program provably moves no
    data across devices — and the merge kernel's deferred/deferred-free
    dispatch (`orswot_ops.merge`) is decided *per shard*, so shards whose
    objects carry no deferred rows stay on the fast path even when other
    shards don't.

    ``a``/``b``: OrswotBatch-shaped pytrees sharded over ``axis``.
    Returns ``(merged_state5, overflow)`` with the same sharding."""
    m_cap, d_cap = a.ids.shape[-1], a.d_ids.shape[-1]
    state_a = (a.clock, a.ids, a.dots, a.d_ids, a.d_clocks)
    state_b = (b.clock, b.ids, b.dots, b.d_ids, b.d_clocks)
    return shard_local_merge_fn(mesh, axis, m_cap, d_cap, impl)(state_a, state_b)


def _fold_orswot_stack(stack5, m_cap: int, d_cap: int,
                       impl: str | None = None):
    """Canonical left fold over a replica-stacked ORSWOT state 5-tuple
    (leading axis R on every array), ORing capacity overflow across every
    pairwise merge.  Delegates to ``orswot_ops.fold_merge_sequential``
    (the one home of the canonical-order + overflow invariant) — always
    the PAIRWISE loop here: this runs inside ``shard_map``, where the
    fused-fold dispatch of ``orswot_ops.fold_merge`` would put a
    ``pallas_call`` under a collective trace."""
    out = orswot_ops.fold_merge_sequential(
        *stack5, m_cap, d_cap, plunger=False, impl=impl
    )
    return out[:5], out[5]


def gather_fold_orswot(local, axis: str, m_cap: int, d_cap: int,
                       impl: str | None = None):
    """The ORSWOT cross-device join body, for use INSIDE shard_map: all-gather
    each state array over ``axis`` and fold in canonical device order 0..D-1
    (D is the all-gather's leading axis — derived, not caller-supplied, so a
    wrong device count can't silently truncate the fold).

    ``local``: 5-tuple of per-device state arrays (no leading replica axis).
    Returns ``(state5, overflow)`` where overflow is the OR of every pairwise
    merge's capacity-overflow flags.  The canonical order keeps the result
    identical on every device AND bit-equal to the scalar left-fold oracle —
    a ppermute ring (different fold origin per device) breaks both, because
    the reference merge is order-sensitive (`orswot.rs:94-103` asymmetry)."""
    gathered = tuple(jax.lax.all_gather(x, axis) for x in local)  # [D, ...]
    return _fold_orswot_stack(gathered, m_cap, d_cap, impl)


def allgather_join_orswot(batch, mesh: Mesh, axis: str = "replicas",
                          check: bool = True, impl: str | None = None,
                          object_axis: str | None = None):
    """All-reduce ORSWOT state across a mesh axis with merge as the
    combiner; result is identical on every device and bit-equal to the
    scalar left-fold join in device order 0..D-1 (see
    :func:`gather_fold_orswot` for why the fold order is canonical and a
    ppermute ring is not used).

    ``batch``: an :class:`OrswotBatch` whose leading axis is the replica
    axis, sharded one replica per device over ``axis``.  Raises on
    capacity overflow when ``check`` (pass ``check=False`` to skip the
    host sync).

    ``object_axis``: optionally shard the OBJECT dimension over a second
    mesh axis — the multi-host layout (``parallel.multihost``): objects
    partition over the slow tier (DCN) with zero cross-partition join
    traffic (each object's merge is independent,
    `/root/reference/src/orswot.rs:89-156` is per-object), while the
    replica collective stays on the fast tier."""
    from ..batch.orswot_batch import OrswotBatch

    m_cap = batch.ids.shape[-1]
    d_cap = batch.d_ids.shape[-1]
    _check_replica_axis(batch.clock.shape[0], mesh, axis)
    arrays = (batch.clock, batch.ids, batch.dots, batch.d_ids, batch.d_clocks)
    join = _orswot_join_fn(
        mesh, axis, m_cap, d_cap, tuple(a.ndim for a in arrays), impl,
        object_axis,
    )
    (clock, ids, dots, d_ids, d_clocks), overflow = join(arrays)
    if check:
        raise_for_overflow(overflow, "collective join")
    return OrswotBatch(clock=clock, ids=ids, dots=dots, d_ids=d_ids, d_clocks=d_clocks)


@functools.lru_cache(maxsize=64)
def _orswot_join_fn(mesh: Mesh, axis: str, m_cap: int, d_cap: int,
                    ndims: tuple, impl: str | None = None,
                    object_axis: str | None = None):
    """Cached jitted ORSWOT collective join (see :func:`_clock_join_fn`)."""
    specs = tuple(
        P(axis, object_axis, *([None] * (nd - 2))) for nd in ndims
    )
    over_spec = P(axis, object_axis)

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=(specs, over_spec),
        check_vma=False,
    )
    def _join(local):
        acc, overflow = gather_fold_orswot(
            tuple(x[0] for x in local), axis, m_cap, d_cap, impl
        )
        over = jnp.any(overflow, axis=0)[None]
        if object_axis is not None:
            # SPMD control-flow consistency: with objects sharded over a
            # second (possibly multi-process) axis, a shard-local raise
            # would diverge — the overflowed process raises while its
            # peers proceed and then hang at the next collective.  OR
            # the flags across the object axis so EVERY process takes
            # the same raise/no-raise branch; regrowth is global anyway
            # (with_capacity recompiles every process's program).
            flags = jax.lax.pmax(
                jnp.any(over, axis=(0, 1)).astype(jnp.int32), object_axis
            )
            over = jnp.broadcast_to(flags.astype(jnp.bool_), over.shape)
        return tuple(x[None] for x in acc), over

    return observed_kernel("parallel.orswot_join")(_join)


def _fold_map_stack(stack_state, kernel):
    """Canonical left fold over a replica-stacked Map state pytree (leading
    axis R on every leaf), ORing overflow across every pairwise merge —
    the Map analogue of :func:`_fold_orswot_stack`, recursing through the
    nested value state via the (static) value kernel."""
    leaves, treedef = jax.tree_util.tree_flatten(stack_state)
    r = leaves[0].shape[0]

    def take(i):
        return jax.tree_util.tree_unflatten(treedef, [x[i] for x in leaves])

    acc = take(0)
    overflow = None
    for i in range(1, r):
        acc, over = kernel.merge(acc, take(i))
        overflow = over if overflow is None else overflow | over
    if overflow is None:
        overflow = jnp.zeros((), dtype=bool)
    return acc, overflow


@functools.lru_cache(maxsize=64)
def _map_join_fn(mesh: Mesh, axis: str, kernel, flat_specs, spec_tree):
    """Cached jitted Map collective join — bounded like the sibling
    compiled-fn caches so long-lived drivers creating fresh meshes or
    kernels don't pin executables forever."""
    specs = jax.tree_util.tree_unflatten(spec_tree, list(flat_specs))

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=(specs, P(axis)),
        check_vma=False,
    )
    def _join(local_state):
        local = jax.tree_util.tree_map(lambda x: x[0], local_state)
        gathered = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, axis), local
        )
        acc, overflow = _fold_map_stack(gathered, kernel)
        return (
            jax.tree_util.tree_map(lambda x: x[None], acc),
            jnp.any(overflow)[None],
        )

    return observed_kernel("parallel.map_join")(_join)


def allgather_join_map(batch, mesh: Mesh, axis: str = "replicas", check: bool = True):
    """All-reduce Map state across a mesh axis with the recursive
    reset-remove merge (`/root/reference/src/map.rs:192-269`) as the
    combiner — same canonical-fold contract as
    :func:`allgather_join_orswot`: all-gather every state leaf (including
    the nested value state) over ``axis``, fold in device order 0..D-1,
    result identical on every device and bit-equal to the scalar N-way
    left fold.

    ``batch``: a :class:`~crdt_tpu.batch.map_batch.MapBatch` whose leading
    axis is the replica axis, one replica shard per device over ``axis``."""
    from ..batch.map_batch import MapBatch

    kernel = batch.kernel
    _check_replica_axis(batch.clock.shape[0], mesh, axis)
    state = batch.state
    specs = jax.tree_util.tree_map(
        lambda x: P(axis, *([None] * (x.ndim - 1))), state
    )
    flat_specs, spec_tree = jax.tree_util.tree_flatten(specs)
    join = _map_join_fn(mesh, axis, kernel, tuple(flat_specs), spec_tree)
    joined, overflow = join(state)
    if check and bool(jnp.any(overflow)):
        raise ValueError(
            "Map collective join overflow: raise key/deferred/value capacities"
        )
    return MapBatch.from_state(joined, kernel)


# -- LWWReg / MVReg / GSet collective joins ----------------------------------


def _fold_lww_stack(vals, markers):
    """Canonical left fold of a replica-stacked LWW state ``(vals[R, N],
    markers[R, N])`` with the pairwise rule (`lwwreg.rs:43-67`), ORing the
    equal-marker/different-value conflict bitmap across every step.

    The fold — not a one-shot argmax over the stack — is deliberate: the
    scalar N-way join errors on *any* pairwise equal-marker conflict it
    encounters en route (e.g. markers ``[5, 5, 9]`` with different values
    conflicts at step 1 even though the global max is unique), so bit- and
    error-parity require replaying the same prefix-max walk."""
    from ..ops import lww_ops

    r = vals.shape[0]
    acc_v, acc_m = vals[0], markers[0]
    conflict = jnp.zeros(vals.shape[1:], dtype=bool)
    for i in range(1, r):
        acc_v, acc_m, c = lww_ops.merge(acc_v, acc_m, vals[i], markers[i])
        conflict |= c
    return acc_v, acc_m, conflict


@functools.lru_cache(maxsize=64)
def _lww_join_fn(mesh: Mesh, axis: str, ndim: int):
    """Cached jitted LWW collective join (jax.jit caches by function
    identity — a per-call closure would retrace+recompile every call)."""
    spec = P(axis, *([None] * (ndim - 1)))

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )
    def _join(vals, markers):
        vg = jax.lax.all_gather(vals[0], axis)  # [D, N]
        mg = jax.lax.all_gather(markers[0], axis)
        v, m, conflict = _fold_lww_stack(vg, mg)
        return v[None], m[None], conflict[None]

    return observed_kernel("parallel.lww_join")(_join)


def allgather_join_lww(batch, mesh: Mesh, axis: str = "replicas", check: bool = True):
    """All-reduce LWW register state across a mesh axis: all-gather the
    ``(vals, markers)`` columns over ``axis`` and left-fold in canonical
    device order 0..D-1 with the marker-max select (`lwwreg.rs:43-67`) —
    BASELINE config 5's 10M-register fleet joined in one collective.

    ``batch``: an :class:`~crdt_tpu.batch.lwwreg_batch.LWWRegBatch` whose
    leading axis is the replica axis, one replica shard per device.
    Returns ``(joined, conflict_bitmap)``; when ``check``, raises
    :class:`~crdt_tpu.error.ConflictingMarker` if any element hit an
    equal-marker/different-value pair mid-fold (batched kernels cannot
    raise per-element — SURVEY.md §7.3 — so the bitmap surfaces
    host-side).  The joined rows are identical on every device."""
    from ..batch.lwwreg_batch import LWWRegBatch
    from ..error import ConflictingMarker

    _check_replica_axis(batch.vals.shape[0], mesh, axis)
    join = _lww_join_fn(mesh, axis, batch.vals.ndim)
    vals, markers, conflict = join(batch.vals, batch.markers)
    if check and bool(jnp.any(conflict)):
        idx = jnp.nonzero(conflict[0])[0]
        raise ConflictingMarker(
            f"{idx.shape[0]} conflicting marker(s) in collective join, "
            f"first at {int(idx[0])}"
        )
    return LWWRegBatch(vals=vals, markers=markers), conflict


def _fold_mvreg_stack(clocks, vals, k_cap: int):
    """Canonical left fold of a replica-stacked MVReg antichain
    ``(clocks[R, N, K, A], vals[R, N, K])``: pairwise keep-undominated
    merge + re-pack each step (`mvreg.rs:121-153`), ORing antichain
    overflow across steps."""
    from ..ops import mvreg_ops

    r = clocks.shape[0]
    acc_c, acc_v = clocks[0], vals[0]
    overflow = jnp.zeros(clocks.shape[1:2], dtype=bool)
    for i in range(1, r):
        c2, v2, keep = mvreg_ops.merge(acc_c, acc_v, clocks[i], vals[i])
        acc_c, acc_v, over = mvreg_ops.compact(c2, v2, keep, k_cap)
        overflow |= over
    return acc_c, acc_v, overflow


@functools.lru_cache(maxsize=64)
def _mvreg_join_fn(mesh: Mesh, axis: str, k_cap: int, c_ndim: int, v_ndim: int):
    """Cached jitted MVReg collective join (see :func:`_lww_join_fn`)."""
    c_spec = P(axis, *([None] * (c_ndim - 1)))
    v_spec = P(axis, *([None] * (v_ndim - 1)))
    o_spec = P(axis, None)

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(c_spec, v_spec),
        out_specs=(c_spec, v_spec, o_spec),
        check_vma=False,
    )
    def _join(clocks, vals):
        cg = jax.lax.all_gather(clocks[0], axis)  # [D, N, K, A]
        vg = jax.lax.all_gather(vals[0], axis)
        c, v, overflow = _fold_mvreg_stack(cg, vg, k_cap)
        return c[None], v[None], overflow[None]

    return observed_kernel("parallel.mvreg_join")(_join)


def allgather_join_mvreg(batch, mesh: Mesh, axis: str = "replicas", check: bool = True):
    """All-reduce MVReg antichain state across a mesh axis: all-gather the
    ``(clocks, vals)`` planes over ``axis`` and left-fold in canonical
    device order 0..D-1 with the keep-mutually-undominated merge
    (`mvreg.rs:121-153`), re-packing to K slots per step.

    ``batch``: an :class:`~crdt_tpu.batch.mvreg_batch.MVRegBatch` whose
    leading axis is the replica axis, one replica shard per device.
    Raises on antichain overflow past ``mv_capacity`` when ``check``.
    The joined rows are identical on every device; set-equality (not slot
    order) is the reference's own equality (`mvreg.rs:74-96`), but the
    canonical fold keeps even slot order bit-equal to the scalar N-way
    left fold."""
    from ..batch.mvreg_batch import MVRegBatch

    k_cap = batch.clocks.shape[-2]
    _check_replica_axis(batch.clocks.shape[0], mesh, axis)
    join = _mvreg_join_fn(mesh, axis, k_cap, batch.clocks.ndim, batch.vals.ndim)
    clocks, vals, overflow = join(batch.clocks, batch.vals)
    if check and bool(jnp.any(overflow)):
        raise CapacityOverflowError(
            "MVReg collective-join antichain overflow: raise CrdtConfig.mv_capacity",
            member=True, deferred=False,
        )
    return MVRegBatch(clocks=clocks, vals=vals)


def allgather_join_gset(batch, mesh: Mesh, axis: str = "replicas"):
    """Global GSet join across a mesh axis.  Union is commutative and
    idempotent with no order sensitivity (`gset.rs:30-34`), so unlike the
    ORSWOT/LWW/MVReg folds this is a direct all-reduce: one ``pmax`` over
    the membership bitmap (bool max ≡ OR) riding ICI.

    ``batch``: a :class:`~crdt_tpu.batch.gset_batch.GSetBatch` whose
    leading axis is the replica axis, one replica shard per device.
    Every replica row of the output holds the global union."""
    from ..batch.gset_batch import GSetBatch

    # bool max ≡ OR, so the bitmap union IS the clock join over u8
    # (collectives don't take bool); one shard_map body to maintain
    joined = all_reduce_clock_join(batch.bits.astype(jnp.uint8), mesh, axis)
    return GSetBatch(bits=joined.astype(bool))


# -- fleet-observability all-gather -------------------------------------------


def allgather_fleet_snapshots(observatory):
    """Aggregate fleet telemetry across the processes of a jax mesh —
    the scraper-free path for pjit deployments with NO network peers to
    gossip with: every process encodes its observatory's merged
    snapshot frame (:meth:`crdt_tpu.obs.fleet.FleetObservatory.encode`
    — versioned + CRC-guarded, so a skewed process fails loudly at
    decode), the frames ride one ``process_allgather`` over DCN (byte
    payloads padded to the fleet max, lengths gathered first), and
    every process folds every frame into its observatory.  Because the
    snapshot merge is commutative/associative/idempotent, all processes
    converge to the SAME fleet view — including each process's own
    echoed frame, which the G-Counter semantics absorb as a no-op.

    Returns the merged :class:`~crdt_tpu.obs.fleet.FleetSnapshot`.
    Single-process meshes degrade to a local capture+merge, so the
    call is safe unconditionally."""
    import numpy as np

    frame = observatory.encode()
    if jax.process_count() == 1:
        # nothing to gather; the encode above already refreshed the
        # local slice into the merged state
        return observatory.merged(refresh=False)

    from jax.experimental import multihost_utils

    data = np.frombuffer(frame, dtype=np.uint8)
    sizes = np.atleast_1d(np.asarray(
        multihost_utils.process_allgather(np.int64(data.size))
    )).reshape(-1)
    pad = int(sizes.max())
    buf = np.zeros(pad, dtype=np.uint8)
    buf[:data.size] = data
    gathered = np.atleast_2d(np.asarray(
        multihost_utils.process_allgather(buf)
    ))
    for row, size in zip(gathered, sizes):
        observatory.merge_frame(bytes(row[:int(size)]))
    return observatory.merged(refresh=False)


# -- anti-entropy to fixpoint ------------------------------------------------


@functools.lru_cache(maxsize=None)
def _anti_entropy_kernels(m_cap: int, d_cap: int, impl: str | None = None):
    """Jitted fold/plunge kernels, cached per capacity (and merge impl) so
    repeated anti_entropy calls hit the XLA compile cache instead of
    retracing (jax.jit caches by function identity; a per-call closure
    defeats it).  Shapes (R, N, A) still key the underlying jit cache as
    usual."""

    @jax.jit
    def _fold(arrays):
        acc, overflow = _fold_orswot_stack(arrays, m_cap, d_cap, impl)
        # the scalar overflow bit folds all objects by design: it is the
        # kernel's host-raise diagnostic, and the mesh lowering is a
        # shard-local any + one-bit OR on the host, never a data gather
        return acc, jnp.any(overflow, axis=0)  # crdtlint: disable=SC01 — scalar overflow diagnostic, shard-local any + host OR

    @jax.jit
    def _plunge(acc):
        nxt, over = _orswot_pair_merge(acc, acc, m_cap, d_cap, impl)
        same = jnp.array(True)
        for x, y in zip(nxt, acc):
            # the fixpoint predicate folds all objects by design: it is a
            # one-bit convergence flag, and the mesh lowering is a
            # shard-local all + one-bit AND on the host
            same &= jnp.array_equal(x, y)  # crdtlint: disable=SC01 — scalar fixpoint flag, shard-local all + host AND
        return nxt, same, jnp.any(over, axis=0)  # crdtlint: disable=SC01 — scalar overflow diagnostic, shard-local any + host OR

    return (observed_kernel("parallel.anti_entropy_fold")(_fold),
            observed_kernel("parallel.anti_entropy_plunge")(_plunge))


def anti_entropy(stack, max_rounds: int = 3, check: bool = True,
                 impl: str | None = None):
    """Converge a replica-stacked :class:`OrswotBatch` (leading axis R) to
    its fixpoint on one device/shard: left-fold-join the replicas in order
    0..R-1 (bit-parity with the scalar N-way join — see
    :func:`fold_reduce_merge`), then keep self-merging (the "defer
    plunger") until the state stops changing or ``max_rounds`` is hit.
    Returns ``(merged, rounds_used)``.

    Deferred removes make a single pass insufficient in general: a remove
    buffered under a future clock applies only once the joined clock covers
    it (`orswot.rs:195-211`).

    Capacity overflow across every merge is accumulated in-graph and raised
    once at the end when ``check`` — one host sync per round (the
    changed/overflow scalars), not one per merge."""
    from ..batch.orswot_batch import OrswotBatch

    m_cap = stack.ids.shape[-1]
    d_cap = stack.d_ids.shape[-1]
    arrays = (stack.clock, stack.ids, stack.dots, stack.d_ids, stack.d_clocks)

    import numpy as np

    _fold, _plunge = _anti_entropy_kernels(m_cap, d_cap, impl)
    acc, over_dev = _fold(arrays)
    overflow = np.array(jax.device_get(over_dev), dtype=bool)  # writable copy
    rounds = 1
    for _ in range(max_rounds - 1):
        acc, same_dev, over_dev = _plunge(acc)
        rounds += 1
        same, over = jax.device_get((same_dev, over_dev))
        overflow |= np.asarray(over, dtype=bool)
        if same:
            break
    if check:
        raise_for_overflow(overflow, "anti-entropy")
    merged = OrswotBatch(
        clock=acc[0], ids=acc[1], dots=acc[2], d_ids=acc[3], d_clocks=acc[4]
    )
    return merged, rounds
