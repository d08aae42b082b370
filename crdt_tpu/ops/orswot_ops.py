"""Batched ORSWOT kernels — the flagship merge (SURVEY.md §3.2, §7.3).

Dense per-object state (leading axes are free batch axes):

* ``clock   u64[..., A]``       — the set clock
* ``ids     int32[..., M]``     — interned member ids, ``-1`` = empty slot
* ``dots    u64[..., M, A]``    — per-member dot clocks (add-witnesses)
* ``d_ids   int32[..., D]``     — deferred-remove member ids, ``-1`` = empty
* ``d_clocks u64[..., D, A]``   — deferred-remove witnessing clocks

A member slot is live iff its id != -1; live members always carry non-empty
dot clocks (the reference never stores an entry with an empty clock —
`/root/reference/src/orswot.rs:132-138,205-210`).

``merge`` reproduces `/root/reference/src/orswot.rs:89-156` bit-exactly,
including the asymmetry: members only in *self* keep their **full** clock
when any dot is novel (`orswot.rs:94-103`), members only in *other* keep the
**subtracted** clock (`orswot.rs:132-138`).  The HashMap alignment of the
reference becomes a boolean O(M²) member-id match (the actor axis never
enters the quadratic term) for padded capacities M ≤ 64, and sort+gather
alignment above that.

Narrow-table merges dispatch on ``lax.cond(any deferred row exists)``:
the deferred-free fast path decides each slot's survival with
OR-reductions over the actor axis, rank-selects the winning ``m_cap``
member ids with a counting-rank sort (``_stable_order`` — O(S²) bool
compares + a one-hot-sum inversion, far cheaper than a comparison sort at
slot counts ≤ 128), and computes the dot algebra only for the selected
slots; the
2M-wide merged table of the classic pipeline is never materialized.
Deferred-bearing batches take the full-width pipeline with dedup + replay.
The effect on the BASELINE.md config-4 shapes was measured before PR 1
on a capture path that no longer exists; it is not measured on the chip
yet.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import clock_ops
from ..config import MERGE_IMPLS

EMPTY = -1
_SORT_MAX = jnp.iinfo(jnp.int32).max


# above this member capacity the O(M²) boolean match matrix costs more
# than sort+gather alignment (elastic regrowth can push M to 2^16, where
# the quadratic term would dominate even without the actor axis)
_ALIGN_MATCH_MAX_M = 64


def _align_sorted(ids_a, dots_a, ids_b, dots_b):
    """Sort+gather alignment — O(M log M), used above
    ``_ALIGN_MATCH_MAX_M`` where the quadratic match matrix would
    dominate.  Concatenate both tables, sort by member id, and match
    adjacent duplicates (runs have length ≤ 2 since ids are unique within
    each side).  Returns ``(ids, e1, e2, valid)`` over the 2M slots in
    sorted order, which ``compact_by_id`` canonicalizes anyway."""
    ids_cat = jnp.concatenate([ids_a, ids_b], axis=-1)  # [..., 2M]
    dots_cat = jnp.concatenate([dots_a, dots_b], axis=-2)  # [..., 2M, A]
    side = jnp.concatenate(
        [jnp.zeros_like(ids_a), jnp.ones_like(ids_b)], axis=-1
    )  # 0 = self, 1 = other

    key = jnp.where(ids_cat == EMPTY, _SORT_MAX, ids_cat)
    order = jnp.argsort(key, axis=-1, stable=True)
    s_ids = jnp.take_along_axis(ids_cat, order, axis=-1)
    s_dots = jnp.take_along_axis(dots_cat, order[..., None], axis=-2)
    s_side = jnp.take_along_axis(side, order, axis=-1)

    valid = s_ids != EMPTY
    nxt_same = jnp.concatenate(
        [(s_ids[..., 1:] == s_ids[..., :-1]) & valid[..., 1:],
         jnp.zeros_like(valid[..., :1])],
        axis=-1,
    )
    prv_same = jnp.concatenate(
        [jnp.zeros_like(valid[..., :1]),
         (s_ids[..., 1:] == s_ids[..., :-1]) & valid[..., :-1]],
        axis=-1,
    )
    first = valid & ~prv_same

    from_a = jnp.where((s_side == 0)[..., None], s_dots, 0)
    from_b = jnp.where((s_side == 1)[..., None], s_dots, 0)
    nxt = lambda x: jnp.concatenate([x[..., 1:, :], jnp.zeros_like(x[..., :1, :])], axis=-2)
    take_nxt = nxt_same[..., None]
    e1 = jnp.maximum(from_a, jnp.where(take_nxt, nxt(from_a), 0))
    e2 = jnp.maximum(from_b, jnp.where(take_nxt, nxt(from_b), 0))
    out_ids = jnp.where(first, s_ids, EMPTY)
    return out_ids, e1, e2, first


def _merge_aligned(e1, e2, present1, present2, self_clock, other_clock):
    """The per-member dot-algebra rule (`orswot.rs:92-138`), elementwise
    over the actor axis.  ``e1``/``e2``: ``[..., S, A]``; clocks ``[..., A]``."""
    sc = self_clock[..., None, :]
    oc = other_clock[..., None, :]

    # present in both (`orswot.rs:105-129`)
    common = clock_ops.intersection(e1, e2)
    c1 = clock_ops.subtract(clock_ops.subtract(e1, common), oc)
    c2 = clock_ops.subtract(clock_ops.subtract(e2, common), sc)
    out_both = jnp.maximum(common, jnp.maximum(c1, c2))

    # only in self (`orswot.rs:94-103`): keep FULL clock iff not dominated
    keep1 = ~clock_ops.leq(e1, oc)  # [..., S]
    out_only1 = jnp.where(keep1[..., None], e1, 0)

    # only in other (`orswot.rs:132-138`): keep the SUBTRACTED clock
    out_only2 = clock_ops.subtract(e2, sc)

    both = (present1 & present2)[..., None]
    only1 = (present1 & ~present2)[..., None]
    out = jnp.where(both, out_both, jnp.where(only1, out_only1, out_only2))
    return jnp.where((present1 | present2)[..., None], out, 0)


def _dedup_deferred(d_ids, d_clocks):
    """Drop exact (member, clock) duplicate rows, keeping the first.

    The reference's deferred map is ``{clock: {members}}``
    (`orswot.rs:29`) — pairs are unique by construction; after
    concatenating two tables we restore that invariant.  O(D²) pairwise
    compare — D is small."""
    same_member = d_ids[..., :, None] == d_ids[..., None, :]  # [..., D, D]
    same_clock = clock_ops.eq(d_clocks[..., :, None, :], d_clocks[..., None, :, :])
    valid = d_ids != EMPTY
    dup_pair = same_member & same_clock & valid[..., :, None] & valid[..., None, :]
    d = d_ids.shape[-1]
    earlier = jnp.tril(jnp.ones((d, d), dtype=bool), k=-1)
    is_dup = jnp.any(dup_pair & earlier, axis=-1)
    keep = valid & ~is_dup
    return jnp.where(keep, d_ids, EMPTY), jnp.where(keep[..., None], d_clocks, 0)


def _apply_deferred(clock, ids, dots, d_ids, d_clocks):
    """Replay buffered removes (`orswot.rs:195-243`), single pass.

    For each member, subtract the join of all matching deferred clocks
    (sequential subtracts compose into subtract-by-max); drop emptied
    members; retain only deferred rows still ahead of the set clock.

    The member×deferred cross product makes this the most bandwidth-heavy
    stage, which is why ``merge`` only enters it when a deferred row
    exists in the batch at all."""
    d_valid = d_ids != EMPTY
    match = ids[..., :, None] == jnp.where(d_valid, d_ids, EMPTY - 1)[..., None, :]
    # [..., M, A]: per-member join of matching deferred clocks
    rm = jnp.max(
        jnp.where(match[..., None], d_clocks[..., None, :, :], 0), axis=-2
    ) if d_ids.shape[-1] > 0 else jnp.zeros_like(dots)
    new_dots = clock_ops.subtract(dots, rm)
    live = ~clock_ops.is_empty(new_dots) & (ids != EMPTY)
    new_ids = jnp.where(live, ids, EMPTY)
    new_dots = jnp.where(live[..., None], new_dots, 0)

    # keep deferred rows whose clock is still not covered (`orswot.rs:197`)
    still_ahead = ~clock_ops.leq(d_clocks, clock[..., None, :]) & d_valid
    out_d_ids = jnp.where(still_ahead, d_ids, EMPTY)
    out_d_clocks = jnp.where(still_ahead[..., None], d_clocks, 0)
    return new_ids, new_dots, out_d_ids, out_d_clocks


# counting-rank sort is O(S²) bools per object; above this slot count the
# quadratic term loses to XLA's comparison sort
_RANK_SORT_MAX_S = 128


def _scatterless_default():
    """Whether to invert the rank permutation without a scatter.

    ``put_along_axis`` lowers to an XLA scatter; the dense one-hot-sum
    inversion reuses the ``[..., S, S]`` bool the counting rank already
    materialized and measured faster on BOTH backends with the r2
    rank-select kernel — CPU: 1.21x at config-4 (87 vs 105 ms), 1.26x at
    north-star fold shapes (4.50 vs 5.69 s/chunk-fold); TPU: scatters
    are served by XLA:TPU's generic scatter path, far slower than dense
    reductions at these tiny slot counts.  (The original CPU-prefers-
    scatter finding predated the rank-select rewrite.)
    ``CRDT_SCATTERLESS=0/1`` forces a path for A/B measurements."""
    import os

    force = os.environ.get("CRDT_SCATTERLESS")
    if force is not None:
        return force == "1"
    return True


def _stable_order(key):
    """Permutation that stably sorts ``key`` ascending along the last axis.

    For the small static slot counts of the member/deferred tables this is
    a counting rank (``rank[i]`` = number of slots ordered before slot i,
    ties broken by slot index) — a handful of fused elementwise passes
    over an ``[..., S, S]`` bool, which beats XLA's generic comparison
    sort by a wide margin at S ≤ ~128.  The rank is inverted with a
    one-hot masked sum by default on every backend (a scatter under
    ``CRDT_SCATTERLESS=0`` — see :func:`_scatterless_default` for the
    measurements).  Larger S falls back to ``argsort``."""
    s = key.shape[-1]
    if s > _RANK_SORT_MAX_S:
        return jnp.argsort(key, axis=-1, stable=True)
    idx = jnp.arange(s, dtype=jnp.int32)
    ki = key[..., :, None]
    kj = key[..., None, :]
    before = (kj < ki) | ((kj == ki) & (idx[None, :] < idx[:, None]))
    rank = jnp.sum(before, axis=-1).astype(jnp.int32)  # position of slot i
    if _scatterless_default():
        # out[k] = i with rank[i] == k, as a one-hot masked sum — reuses
        # the [..., S, S] shape already materialized for `before`, and
        # avoids an XLA scatter entirely
        onehot = rank[..., None, :] == idx[:, None]  # [..., k, i]
        return jnp.sum(jnp.where(onehot, idx, 0), axis=-1, dtype=jnp.int32)
    return jnp.put_along_axis(
        jnp.zeros(rank.shape, jnp.int32),
        rank,
        jnp.broadcast_to(idx, rank.shape),
        axis=-1,
        inplace=False,
    )


def compact(ids, payload, cap):
    """Pack live slots first (original slot order) and truncate to ``cap``.

    ``payload`` has one extra trailing axis (the actor axis).  Returns
    ``(ids, payload, overflow)``."""
    live = ids != EMPTY
    order = _stable_order((~live).astype(jnp.int32))
    ids = jnp.take_along_axis(ids, order, axis=-1)[..., :cap]
    payload = jnp.take_along_axis(payload, order[..., None], axis=-2)[..., :cap, :]
    overflow = jnp.sum(live, axis=-1) > cap
    return ids, payload, overflow


def compact_by_id(ids, payload, cap):
    """Pack live slots in ascending member-id order and truncate to ``cap``
    — the canonical member-table order every engine emits (C++ mirrors it,
    `crdt_core.cpp` ORSWOT merge; Pallas restores it by rank selection)."""
    live = ids != EMPTY
    key = jnp.where(live, ids, _SORT_MAX)
    order = _stable_order(key)
    ids = jnp.take_along_axis(ids, order, axis=-1)[..., :cap]
    payload = jnp.take_along_axis(payload, order[..., None], axis=-2)[..., :cap, :]
    overflow = jnp.sum(live, axis=-1) > cap
    return ids, payload, overflow


def resolve_merge_impl(impl: str | None = None) -> str:
    """Resolve which pairwise-merge implementation ``merge`` dispatches to.

    Implementations: ``rank`` (the rank-select pipeline below, CPU
    default), ``unrolled`` (gather/sort-free tile math,
    :mod:`crdt_tpu.ops.orswot_unrolled`; exact for uint32 counters only —
    bit-equal outside the conservative-overflow objects, see
    ``tests/test_orswot_unrolled.py``), or ``pallas``.

    ``pallas`` — for PAIRWISE merges an alias of ``unrolled``: a fused
    pairwise kernel cannot beat jnp on traffic (both read 2 states and
    write 1), and :mod:`crdt_tpu.ops.orswot_pallas` stays importable for
    benches/tests only.  Where ``pallas`` can pay is the R-way FOLD —
    each replica state read once instead of the sequential fold's
    3-states-per-merge — which :func:`fold_merge` dispatches to the
    union-aligned fused kernel (:mod:`crdt_tpu.ops.orswot_fold_aligned`).

    Precedence: an explicit non-``"auto"`` choice (the ``impl=`` argument
    to :func:`merge`, usually fed from ``CrdtConfig.merge_impl``) wins;
    otherwise the ``CRDT_MERGE_IMPL`` env var (a process-level override —
    set it before the first compile; jit caches key on shapes only, so
    flipping it later does not retrace already-compiled shapes); otherwise
    the backend default: ``unrolled`` on TPU, ``rank`` elsewhere (the
    unrolled tile math trades extra dot-table reads for regularity —
    measured 17% slower on the memory-bound CPU backend).  The TPU
    default rests on a layout A/B taken before PR 1 on a capture path
    that no longer exists; it waits for a chip A/B (ROADMAP D5).  A/B
    harnesses should pass ``impl=`` explicitly — each choice is a
    distinct Python call graph, so no cache clearing is needed."""
    import os

    import jax

    if impl is not None and impl != "auto":
        if impl not in MERGE_IMPLS:
            raise ValueError(
                f"merge impl {impl!r} (CrdtConfig.merge_impl / "
                f"CRDT_MERGE_IMPL) is not one of rank/unrolled/pallas"
            )
        return impl
    env = os.environ.get("CRDT_MERGE_IMPL")
    if env is not None:
        if env not in MERGE_IMPLS:
            raise ValueError(
                f"CRDT_MERGE_IMPL={env!r} is not one of rank/unrolled/pallas"
            )
        return env
    return "unrolled" if jax.default_backend() == "tpu" else "rank"


def merge(
    clock_a, ids_a, dots_a, dids_a, dclocks_a,
    clock_b, ids_b, dots_b, dids_b, dclocks_b,
    m_cap: int, d_cap: int, impl: str | None = None,
):
    """Full pairwise ORSWOT merge (`orswot.rs:89-156`).

    Returns ``(clock, ids, dots, d_ids, d_clocks, overflow)``; overflow is
    ``bool[..., 2]`` — ``[..., 0]`` set where survivors exceed ``m_cap``,
    ``[..., 1]`` where deferred rows exceed ``d_cap`` (host raises a
    :class:`~crdt_tpu.error.CapacityOverflowError` naming the axis —
    capacity is the static-shape concession, and elastic recovery grows
    only the overflowed axis).

    Narrow member tables dispatch on "any deferred row in the batch"
    (``lax.cond``): the deferred-free fast path — the common case — never
    materializes the 2M-wide merged table at all.  It decides survival
    with cheap reductions, rank-selects the ``m_cap`` winning slots, and
    computes the dot algebra only for those; deferred-bearing batches take
    the full-width pipeline.

    ``impl`` selects the implementation (see :func:`resolve_merge_impl`
    for choices and precedence); ``None``/``"auto"`` resolves the
    env-var/backend default.
    """
    impl = resolve_merge_impl(impl)
    if impl in ("unrolled", "pallas") and clock_a.dtype.itemsize > 4:
        # the TPU fast paths are exact for <=32-bit counters only; wider
        # batches silently taking the rank path cost default-config users
        # the measured speedup (VERDICT r3 weak #6) — say so, once per trace
        import warnings

        warnings.warn(
            f"orswot merge impl {impl!r} requires <=32-bit counters; this "
            f"{clock_a.dtype.name} batch falls back to the 'rank' path. "
            "Build the universe with CrdtConfig(counter_bits=32) (see "
            "CrdtConfig.tpu_default()) to stay on the TPU fast paths.",
            stacklevel=2,
        )
    if (
        impl in ("unrolled", "pallas")
        and clock_a.dtype.itemsize <= 4
        and ids_a.shape[-1] <= _ALIGN_MATCH_MAX_M
    ):
        # the tile math unrolls Python loops over the slot axes, so wide
        # member tables (elastic regrowth) stay on the rank path's
        # sort-aligned _merge_wide below; rank-polymorphic
        # (ellipsis-based tile math), so any batch shape dispatches.
        # impl == "pallas" is an alias of unrolled for PAIRWISE merges
        # (round-5 keep-or-kill: the fused pairwise kernel lost 5x
        # on-chip and is bench-only — see resolve_merge_impl); the fused
        # Pallas product arm is the R-way fold_merge below
        from . import orswot_unrolled

        return orswot_unrolled.merge_unrolled(
            clock_a, ids_a, dots_a, dids_a, dclocks_a,
            clock_b, ids_b, dots_b, dids_b, dclocks_b,
            m_cap, d_cap,
        )
    if ids_a.shape[-1] > _ALIGN_MATCH_MAX_M:
        return _merge_wide(
            clock_a, ids_a, dots_a, dids_a, dclocks_a,
            clock_b, ids_b, dots_b, dids_b, dclocks_b,
            m_cap, d_cap,
        )
    from jax import lax

    clock = clock_ops.merge(clock_a, clock_b)
    # the whole-batch cond dispatch reads every object, but both branches
    # compute the same lattice join — per-shard the predicate just picks
    # the shard's own fast path, so the fold is a dispatch hint, not data
    any_deferred = jnp.any(dids_a != EMPTY) | jnp.any(dids_b != EMPTY)  # crdtlint: disable=SC01 — fast-path dispatch, branches agree
    operands = (
        clock, clock_a, ids_a, dots_a, dids_a, dclocks_a,
        clock_b, ids_b, dots_b, dids_b, dclocks_b,
    )
    ids, out_dots, d_ids, d_clocks, over = lax.cond(
        any_deferred,
        lambda args: _merge_narrow_deferred(*args, m_cap, d_cap),
        lambda args: _merge_narrow_fast(*args, m_cap, d_cap),
        operands,
    )
    return clock, ids, out_dots, d_ids, d_clocks, over


def _member_match(ids_a, ids_b):
    """Boolean member alignment: match matrix reductions only (no clock
    data enters the quadratic term)."""
    valid_a = ids_a != EMPTY
    valid_b = ids_b != EMPTY
    match = valid_a[..., :, None] & (ids_a[..., :, None] == ids_b[..., None, :])
    a_matched = jnp.any(match, axis=-1)
    j_idx = jnp.argmax(match, axis=-1).astype(jnp.int32)
    b_only = valid_b & ~jnp.any(match, axis=-2)
    return valid_a, a_matched, j_idx, b_only


def _rank_select_merge(
    clock_a, ids_a, dots_a, clock_b, ids_b, dots_b, m_cap: int,
):
    """Shared merge core: survival reduces → rank-select → compute.

    Survival of every slot is decidable from OR-reductions over the actor
    axis (no merged clock is ever written), so the only ``[..., *, A]``
    arrays materialized are the gathers feeding the final ``m_cap``-slot
    algebra.  Returns ``(out_ids, out_dots, n_survivors)`` — the member
    table in canonical ascending-id order, pre-deferred-replay."""
    ma = ids_a.shape[-1]
    valid_a, a_matched, j_idx, b_only = _member_match(ids_a, ids_b)
    sc = clock_a[..., None, :]
    oc = clock_b[..., None, :]

    # per-(slot, actor) survival predicates, OR-reduced over actors:
    # matched  — the dot-algebra output has a non-zero lane
    #            (`orswot.rs:105-129`)
    # a-only   — some dot is novel wrt other's set clock (`orswot.rs:94-103`)
    # b-only   — some dot is novel wrt self's set clock  (`orswot.rs:132-138`)
    e2 = jnp.take_along_axis(dots_b, j_idx[..., None], axis=-2)
    same = dots_a == e2
    both_lane = (same & (dots_a > 0)) | (~same & ((dots_a > oc) | (e2 > sc)))
    a_novel = jnp.any(dots_a > oc, axis=-1)
    a_surv = valid_a & jnp.where(a_matched, jnp.any(both_lane, axis=-1), a_novel)
    b_surv = b_only & jnp.any(dots_b > sc, axis=-1)

    n_surv = jnp.sum(a_surv, axis=-1) + jnp.sum(b_surv, axis=-1)

    # rank-select the m_cap smallest surviving member ids (canonical
    # ascending-id order, same as compact_by_id)
    keys = jnp.concatenate(
        [jnp.where(a_surv, ids_a, _SORT_MAX), jnp.where(b_surv, ids_b, _SORT_MAX)],
        axis=-1,
    )
    sel = _stable_order(keys)[..., :m_cap]  # concat-space source slot
    out_ids_key = jnp.take_along_axis(keys, sel, axis=-1)
    live = out_ids_key != _SORT_MAX
    out_ids = jnp.where(live, out_ids_key, EMPTY)

    # gather algebra inputs for the selected slots only; the "other side"
    # clock is one combined gather from dots_b — the b-only slot's own
    # dots and the matched a-slot's counterpart live in the same table
    is_b = sel >= ma
    sel_a = jnp.where(is_b, 0, sel)
    src_a = jnp.take_along_axis(dots_a, sel_a[..., None], axis=-2)
    sel_matched = jnp.take_along_axis(a_matched, sel_a, axis=-1) & ~is_b
    j_sel = jnp.take_along_axis(j_idx, sel_a, axis=-1)
    j_comb = jnp.where(is_b, sel - ma, j_sel)
    src_other = jnp.take_along_axis(dots_b, j_comb[..., None], axis=-2)

    # dot algebra on [..., m_cap, A] (`orswot.rs:105-138`)
    common = clock_ops.intersection(src_a, src_other)
    c1 = clock_ops.subtract(clock_ops.subtract(src_a, common), oc)
    c2 = clock_ops.subtract(clock_ops.subtract(src_other, common), sc)
    out_both = jnp.maximum(common, jnp.maximum(c1, c2))
    out_a = jnp.where(sel_matched[..., None], out_both, src_a)
    out_dots = jnp.where(is_b[..., None], clock_ops.subtract(src_other, sc), out_a)
    out_dots = jnp.where(live[..., None], out_dots, 0)
    return out_ids, out_dots, n_surv


def _merge_narrow_fast(
    clock, clock_a, ids_a, dots_a, dids_a, dclocks_a,
    clock_b, ids_b, dots_b, dids_b, dclocks_b,
    m_cap: int, d_cap: int,
):
    """Deferred-free merge — the rank-select core alone.  Bit-exact with
    the deferred pipeline because replay over empty deferred tables is the
    identity; the output deferred tables are empty by construction of the
    dispatch."""
    out_ids, out_dots, n_surv = _rank_select_merge(
        clock_a, ids_a, dots_a, clock_b, ids_b, dots_b, m_cap
    )
    m_over = n_surv > m_cap
    d_shape = dids_a.shape[:-1] + (d_cap,)
    d_ids = jnp.full(d_shape, EMPTY, dids_a.dtype)
    d_clocks = jnp.zeros(d_shape + dclocks_a.shape[-1:], dclocks_a.dtype)
    d_over = jnp.zeros(m_over.shape, bool)
    return out_ids, out_dots, d_ids, d_clocks, jnp.stack([m_over, d_over], axis=-1)


def _merge_narrow_deferred(
    clock, clock_a, ids_a, dots_a, dids_a, dclocks_a,
    clock_b, ids_b, dots_b, dids_b, dclocks_b,
    m_cap: int, d_cap: int,
):
    """Merge for batches carrying deferred rows: the rank-select core,
    then union + dedup + replay of the deferred tables
    (`orswot.rs:141-155`) at ``m_cap`` width, then a repack of whatever
    the replay emptied.

    Replaying after compaction is exact whenever the survivor set fits
    ``m_cap``; when it does not, the member-overflow flag is already set
    (from the pre-replay survivor count — marginally more conservative
    than counting post-replay, in the rare case a replay would have freed
    enough slots) and the host discards the state and regrows, so the
    truncated replay is never observed."""
    out_ids, out_dots, n_surv = _rank_select_merge(
        clock_a, ids_a, dots_a, clock_b, ids_b, dots_b, m_cap
    )
    m_over = n_surv > m_cap

    # union + dedup the deferred tables (`orswot.rs:141-148`), replay
    # after the clock join (`orswot.rs:153-155`)
    d_ids = jnp.concatenate([dids_a, dids_b], axis=-1)
    d_clocks = jnp.concatenate([dclocks_a, dclocks_b], axis=-2)
    d_ids, d_clocks = _dedup_deferred(d_ids, d_clocks)
    out_ids, out_dots, d_ids, d_clocks = _apply_deferred(
        clock, out_ids, out_dots, d_ids, d_clocks
    )

    # repack slots the replay emptied (canonical ascending-id order is
    # preserved — subtraction never changes ids)
    out_ids, out_dots, _ = compact_by_id(out_ids, out_dots, m_cap)
    d_ids, d_clocks, d_over = compact(d_ids, d_clocks, d_cap)
    return out_ids, out_dots, d_ids, d_clocks, jnp.stack([m_over, d_over], axis=-1)


def _merge_wide(
    clock_a, ids_a, dots_a, dids_a, dclocks_a,
    clock_b, ids_b, dots_b, dids_b, dclocks_b,
    m_cap: int, d_cap: int,
):
    """Sort-aligned merge pipeline for member tables wider than
    ``_ALIGN_MATCH_MAX_M`` (same semantics, O(M log M) alignment)."""
    ids, e1, e2, valid = _align_sorted(ids_a, dots_a, ids_b, dots_b)
    p1 = ~clock_ops.is_empty(e1) & valid
    p2 = ~clock_ops.is_empty(e2) & valid
    out_dots = _merge_aligned(e1, e2, p1, p2, clock_a, clock_b)
    survive = ~clock_ops.is_empty(out_dots)
    ids = jnp.where(survive, ids, EMPTY)
    out_dots = jnp.where(survive[..., None], out_dots, 0)

    d_ids = jnp.concatenate([dids_a, dids_b], axis=-1)
    d_clocks = jnp.concatenate([dclocks_a, dclocks_b], axis=-2)
    d_ids, d_clocks = _dedup_deferred(d_ids, d_clocks)

    clock = clock_ops.merge(clock_a, clock_b)
    ids, out_dots, d_ids, d_clocks = _apply_deferred(clock, ids, out_dots, d_ids, d_clocks)

    ids, out_dots, m_over = compact_by_id(ids, out_dots, m_cap)
    d_ids, d_clocks, d_over = compact(d_ids, d_clocks, d_cap)
    return clock, ids, out_dots, d_ids, d_clocks, jnp.stack([m_over, d_over], axis=-1)


def fold_merge(
    clock, ids, dots, dids, dclocks, m_cap: int, d_cap: int,
    plunger: bool = True, impl: str | None = None, u_cap: int | None = None,
):
    """Left-fold ``R`` stacked replica fleets (arrays ``[R, N, ...]``)
    into one ``[N, ...]`` state, with the defer-plunger self-merge
    (`/root/reference/test/orswot.rs:45-62`) — the anti-entropy join.

    This is the level where the fused Pallas arm lives (round-5
    keep-or-kill decision, `docs/GUIDE.md`): with ``impl="pallas"`` and
    eligible shapes (uint32 counters, ``[R, N, ...]`` rank-3 planes) the
    whole fold runs in one union-aligned kernel
    (:mod:`~crdt_tpu.ops.orswot_fold_aligned`) that reads each replica
    state exactly once — ``(R+1)/R`` states of HBM traffic per merge
    instead of the sequential fold's 3.  Overflow flagged by the kernel
    is conservative (see its module docstring); callers discard and
    regrow exactly as with the pairwise flags.  Other ``impl`` choices
    (or ineligible shapes) run the sequential pairwise fold.

    Returns ``(clock, ids, dots, d_ids, d_clocks, overflow)``."""
    resolved = resolve_merge_impl(impl)
    if (
        resolved == "pallas"
        and clock.dtype.itemsize <= 4
        and clock.ndim == 3
        and ids.shape[-1] <= _ALIGN_MATCH_MAX_M
    ):
        from . import orswot_fold_aligned

        return orswot_fold_aligned.fold_merge(
            clock, ids, dots, dids, dclocks, m_cap, d_cap,
            u_cap=u_cap, plunger=plunger,
        )
    return fold_merge_sequential(
        clock, ids, dots, dids, dclocks, m_cap, d_cap,
        plunger=plunger, impl=impl,
    )


def fold_merge_sequential(
    clock, ids, dots, dids, dclocks, m_cap: int, d_cap: int,
    plunger: bool = True, impl: str | None = None,
):
    """The canonical sequential left fold over stacked ``[R, N, ...]``
    planes, ORing capacity overflow across every pairwise merge — THE
    one place the canonical-order + overflow invariant lives: the fused
    :func:`fold_merge` dispatch, the collective join
    (`parallel/collective.py`), and the on-device anti-entropy fold all
    route through here."""
    state = (clock, ids, dots, dids, dclocks)
    acc = tuple(x[0] for x in state)
    over_acc = jnp.zeros(clock.shape[1:-1] + (2,), bool)
    for i in range(1, clock.shape[0]):
        out = merge(*acc, *(x[i] for x in state), m_cap, d_cap, impl=impl)
        acc, over_acc = out[:5], over_acc | out[5]
    if plunger:
        out = merge(*acc, *acc, m_cap, d_cap, impl=impl)
        acc, over_acc = out[:5], over_acc | out[5]
    return acc + (over_acc,)


def fold_merge_fleets(fleets, m_cap: int, d_cap: int,
                      plunger: bool = True, impl: str | None = None):
    """Join ``R`` replica fleets (a sequence of ``(clock, ids, dots,
    d_ids, d_clocks)`` tuples over the same ``N`` objects) into one
    state by pairwise tree reduction.

    Same R-1 merges (plus an optional defer-plunger self-merge,
    `/root/reference/test/orswot.rs:61-62`) as the sequential left fold,
    in a log-depth dependency chain: level ``l`` merges neighbours
    ``(0,1), (2,3), ...`` of level ``l-1``, an odd fleet out carrying
    through to the next level.  Each pair is its own merge — the fleets
    are never stacked — so the working set is the inputs plus ONE
    pair's merge temporaries.  A batched merge over all pairs of a level
    would hold every pair's 2M-wide merged table at once, which at the
    north-star width (A=64, M=16, R=8 × 125k objects) does not fit a
    16 GB chip.

    Equivalence to the left fold: for deferred-free states the merge is
    a pure lattice join (`orswot.rs:89-156`) over a canonical encoding
    (ascending-id member order, pointwise-max clocks), so tree and left
    fold are **bit-identical**.  When causally-future removes are in
    flight, the reference's own semantics are fold-order-sensitive in
    the *dot tables*: ``apply_deferred`` (`orswot.rs:195-211,235-243`)
    subtracts the remove clock during every intermediate merge, so which
    dots it erases depends on which partner states have already been
    joined — the scalar engine reproduces exactly this (verified in
    ``tests/test_orswot.py::TestFoldMergeTree``).  ``value()``, the set
    clock, and the member table remain order-independent, which is the
    CRDT convergence guarantee; this function is bit-faithful to the
    scalar engine folding in the same tree order.

    Returns ``(clock, ids, dots, d_ids, d_clocks, overflow)`` with
    ``overflow`` OR-reduced over every merge in the tree.
    """
    level = [tuple(f) for f in fleets]
    over_acc = jnp.zeros(level[0][0].shape[:-1] + (2,), bool)
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            out = merge(*level[i], *level[i + 1], m_cap, d_cap, impl=impl)
            nxt.append(out[:5])
            over_acc = over_acc | out[5]
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    state = level[0]
    if plunger:
        out = merge(*state, *state, m_cap, d_cap, impl=impl)
        state, over = out[:5], out[5]
        over_acc = over_acc | over
    return tuple(state) + (over_acc,)


def apply_add(clock, ids, dots, dids, dclocks, actor_idx, counter, member_id):
    """Batched ``Op::Add`` (`orswot.rs:66-79`): one add per object.

    Returns updated state + overflow flag (no free member slot)."""
    seen = jnp.take_along_axis(clock, actor_idx[..., None], axis=-1)[..., 0] >= counter

    existing = ids == member_id[..., None]  # [..., M]
    has_slot = jnp.any(existing, axis=-1)
    free = ids == EMPTY
    has_free = jnp.any(free, axis=-1)
    slot = jnp.where(
        has_slot, jnp.argmax(existing, axis=-1), jnp.argmax(free, axis=-1)
    )
    overflow = ~seen & ~has_slot & ~has_free

    do = (~seen & (has_slot | has_free))[..., None]
    onehot = jnp.arange(ids.shape[-1]) == slot[..., None]
    new_ids = jnp.where(do & onehot, member_id[..., None], ids)
    # witness the dot on the member clock and the set clock
    dot_update = (do & onehot)[..., None] & (
        jnp.arange(dots.shape[-1]) == actor_idx[..., None, None]
    )
    new_dots = jnp.where(dot_update, jnp.maximum(dots, counter[..., None, None]), dots)
    new_clock = jnp.where(
        do & (jnp.arange(clock.shape[-1]) == actor_idx[..., None]),
        jnp.maximum(clock, counter[..., None]),
        clock,
    )
    new_ids2, new_dots2, d_ids, d_clocks = _apply_deferred(
        new_clock, new_ids, new_dots, dids, dclocks
    )
    return new_clock, new_ids2, new_dots2, d_ids, d_clocks, overflow


def apply_remove(clock, ids, dots, dids, dclocks, rm_clock, member_id):
    """Batched ``Op::Rm`` → ``apply_remove`` (`orswot.rs:195-211`).

    Defers when the remove clock is ahead of the set clock, and always
    subtracts the remove clock from the member's dots.  Returns updated
    state + overflow flag (deferred table full)."""
    ahead = ~clock_ops.leq(rm_clock, clock)  # [...]

    # dedup: an identical (member, clock) row may already be buffered
    d_valid = dids != EMPTY
    same = (dids == member_id[..., None]) & clock_ops.eq(
        dclocks, rm_clock[..., None, :]
    ) & d_valid
    already = jnp.any(same, axis=-1)
    want_defer = ahead & ~already
    free = ~d_valid
    has_free = jnp.any(free, axis=-1)
    slot = jnp.argmax(free, axis=-1)
    overflow = want_defer & ~has_free
    do = (want_defer & has_free)[..., None]
    onehot = jnp.arange(dids.shape[-1]) == slot[..., None]
    new_dids = jnp.where(do & onehot, member_id[..., None], dids)
    new_dclocks = jnp.where((do & onehot)[..., None], rm_clock[..., None, :], dclocks)

    # subtract the remove clock from the member's dots (`orswot.rs:205-210`)
    target = ids == member_id[..., None]
    sub = clock_ops.subtract(dots, rm_clock[..., None, :])
    new_dots = jnp.where(target[..., None], sub, dots)
    live = ~clock_ops.is_empty(new_dots) & (ids != EMPTY)
    new_ids = jnp.where(live, ids, EMPTY)
    new_dots = jnp.where(live[..., None], new_dots, 0)
    return clock, new_ids, new_dots, new_dids, new_dclocks, overflow


def contains(ids, member_id):
    """Membership bitmap (`orswot.rs:214-224`)."""
    return jnp.any(ids == member_id[..., None], axis=-1)


def member_mask(ids):
    """Live-member mask — ``value()`` as a bitmap over slots."""
    return ids != EMPTY
