"""Union-aligned fused Pallas fold — the bandwidth-bound ORSWOT join.

The first fused fold (:mod:`crdt_tpu.ops.orswot_pallas`) iterates the full
pairwise tile merge — O(M²) alignment, per-slot rank-select compaction —
once per replica, and Mosaic stack-allocates ~1.4 MB of temporaries per
object for it, forcing 8-object tiles; measured on-chip it is
VPU-compute-bound at 0.60M merges/s while moving only ~3.4 GB/s
(`docs/GUIDE.md`, 2026-08-01 window).  This kernel restructures the fold around
one observation: **the expensive work in the pairwise pipeline is
alignment and compaction, and neither needs to happen per step.**

Algorithm, per object tile:

1. **Union table, once** — the distinct member ids across all ``R``
   replica tables, built incrementally in first-occurrence order with
   id-plane ops only (``[T, U]`` compares; no ``[A]``-axis data moves).
2. **Align, once per replica** — replica ``r``'s dot rows gathered onto
   union slots by masked max (``U×M`` compares, ``[T, U, A]`` selects).
3. **Fold steps, pure elementwise** — with every side on the same slot
   table the pairwise dot-algebra (`/root/reference/src/orswot.rs:89-156`)
   is elementwise over ``[T, U, A]``: no sorting, no gathers, no
   compaction.  Each step replays the (narrow) deferred pipeline exactly
   like the pairwise merge — union+dedup, clock join, subtract, compact
   to ``d_cap`` — so step ``k`` is bit-identical to the jnp fold's step
   ``k`` whenever no capacity overflow occurs.
4. **Canonical compaction, once** — ascending-member-id rank selection of
   the final survivors into ``m_cap`` slots.

Contract vs the sequential jnp fold (``orswot_ops.merge`` left fold +
defer plunger, `/root/reference/test/orswot.rs:45-62`):

* **No overflow flagged ⇒ bit-identical outputs** (clock, member table,
  deferred table).  Asserted in ``tests/test_orswot_fold_aligned.py``.
* **Overflow flagged ⇒ outputs unspecified** (the host discards and
  regrows — `parallel/executor.py` — so truncated states are never
  observed).  The flag is conservative: it covers the jnp fold's
  per-step survivor overflow AND the union table itself outgrowing
  ``u_cap`` (a case the stepwise fold never sees because it truncates as
  it goes).  The kernel may therefore flag inputs the jnp fold would
  not; it never stays silent where the jnp fold would flag.

Traffic: each replica state is read exactly once and the joined state
written once — ``(R+1)/R`` states per merge instead of the sequential
fold's 3 (read acc + read replica + write acc).  At the north-star
shapes (A=64, M=16, D=2, u32, R=8) that is ~5.5 KB/merge vs the jnp
fold's measured 14.8 KB/merge (`docs/GUIDE.md`).

Counters ride the same biased-int32 kernel domain as
:mod:`~crdt_tpu.ops.orswot_pallas` (``x ^ 0x8000_0000``; compare/max/
select only, exact over the full uint32 range), and the module reuses
its hard-won Mosaic idioms (`_emask`/`_bstack` i1 handling, int32
index-map constants, 32-bit trace mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..config import x64_disabled
from ..obs.kernels import observed_kernel
from .orswot_pallas import (
    EMPTY,
    ZERO,
    _VMEM_LIMIT_BYTES,
    _all,
    _any,
    _bstack,
    _check_dtypes,
    _emask,
    _from_kernel_dtype,
    _interpret_default,
    _nonempty,
    _pad_to,
    _rank_select,
    _rank_select_slots,
    _state_specs,
    _sub,
    _to_kernel_dtype,
    _ZERO,
)

_SORT_MAX = np.int32(np.iinfo(np.int32).max)


# ---------------------------------------------------------------------------
# tile math
# ---------------------------------------------------------------------------


def _build_union(id_planes, u_cap: int):
    """Distinct member ids across the replica tables, first-occurrence
    order, into ``u_cap`` slots.

    ``id_planes`` is a list of ``[T, M]`` int32 planes.  Returns
    ``(union_ids [T, u_cap], n_union [T])`` — slots past the distinct
    count hold ``EMPTY``; ids past ``u_cap`` are dropped (the caller
    flags ``n_union > u_cap`` as overflow).  Id-plane ops only: per
    candidate, one ``[T, u_cap]`` membership test and a one-hot place at
    the running count."""
    t = id_planes[0].shape[0]
    union_ids = jnp.full((t, u_cap), EMPTY, jnp.int32)
    n_union = jnp.zeros((t,), jnp.int32)
    slot_iota = jnp.arange(u_cap, dtype=jnp.int32)
    for ids in id_planes:
        for m in range(ids.shape[-1]):
            cand = ids[..., m : m + 1]  # [T, 1]
            is_new = (cand[..., 0] != EMPTY) & ~_any(
                (union_ids != EMPTY) & (union_ids == cand)
            )
            place = _emask(is_new) & (
                slot_iota[None, :] == n_union[..., None]
            )
            union_ids = jnp.where(place, cand, union_ids)
            n_union = n_union + is_new.astype(jnp.int32)
    return union_ids, n_union


def _align_to_union(union_ids, ids, dots):
    """Replica dot rows gathered onto union slots (``ZERO`` rows where
    the member is absent).  ``ids``/``dots``: ``[T, M]`` / ``[T, M, A]``;
    returns ``[T, U, A]``."""
    out = jnp.full(union_ids.shape + dots.shape[-1:], ZERO, jnp.int32)
    for m in range(ids.shape[-1]):
        cand = ids[..., m : m + 1]
        match = (union_ids != EMPTY) & (union_ids == cand)  # [T, U]
        out = jnp.maximum(
            out, jnp.where(_emask(match), dots[..., m : m + 1, :], ZERO)
        )
    return out


def _step_members(acc, e2, c_prev, c_rep, union_valid, m_cap: int):
    """One fold step's member dot-algebra on union slots — the exact
    pairwise rule (`orswot.rs:92-138`) with self = accumulator (clock
    ``c_prev``), other = replica (clock ``c_rep``).  Returns
    ``(out [T, U, A], m_over [T])`` where ``m_over`` reproduces the jnp
    fold's pre-replay survivor count check."""
    sc = c_prev[..., None, :]
    oc = c_rep[..., None, :]
    p1 = _nonempty(acc)  # [T, U]
    p2 = _nonempty(e2)

    common = jnp.where(acc == e2, acc, ZERO)
    c1 = _sub(_sub(acc, common), oc)
    c2 = _sub(_sub(e2, common), sc)
    out_both = jnp.maximum(common, jnp.maximum(c1, c2))
    keep1 = ~_all(acc <= oc)  # keep FULL clock (`orswot.rs:94-103`)
    out_only1 = jnp.where(_emask(keep1), acc, ZERO)
    out_only2 = _sub(e2, sc)  # subtracted clock (`orswot.rs:132-138`)

    both = _emask(p1 & p2)
    only1 = _emask(p1 & ~p2)
    out = jnp.where(both, out_both, jnp.where(only1, out_only1, out_only2))
    out = jnp.where(_emask(union_valid), out, ZERO)

    n_surv = jnp.sum(
        (_nonempty(out) & union_valid).astype(jnp.int32), axis=-1
    )
    return out, n_surv > m_cap


def _step_deferred(union_ids, acc, c_new, d1_ids, d1_clocks, d2_ids, d2_clocks,
                   d_cap: int):
    """One fold step's deferred pipeline: union + dedup-keep-first
    (`orswot.rs:141-148`), replay against the member rows (`:155` →
    `:195-211`), retain still-ahead rows, compact to ``d_cap`` in
    first-occurrence slot order — bit-matching the pairwise merge's
    ``_dedup_deferred`` → ``_apply_deferred`` → ``compact`` chain."""
    d_ids = jnp.concatenate([d1_ids, d2_ids], axis=-1)  # [T, 2D]
    d_clocks = jnp.concatenate([d1_clocks, d2_clocks], axis=-2)
    dn = d_ids.shape[-1]
    d_valid = d_ids != EMPTY
    dup_cols = [jnp.zeros(d_ids.shape[:-1], dtype=bool)]
    for j in range(1, dn):
        dup_j = jnp.zeros(d_ids.shape[:-1], dtype=bool)
        for i in range(j):
            same = (
                d_valid[..., i]
                & d_valid[..., j]
                & (d_ids[..., i] == d_ids[..., j])
                & _all(d_clocks[..., i, :] == d_clocks[..., j, :])
            )
            dup_j = dup_j | same
        dup_cols.append(dup_j)
    d_live = d_valid & ~_bstack(dup_cols, axis=-1)

    # replay: subtract the join of matching deferred clocks per member
    rm = jnp.full_like(acc, ZERO)
    for k in range(dn):
        match = (
            (union_ids != EMPTY)
            & (union_ids == d_ids[..., k : k + 1])
            & d_live[..., k : k + 1]
        )
        rm = jnp.maximum(
            rm, jnp.where(_emask(match), d_clocks[..., k : k + 1, :], ZERO)
        )
    new_acc = _sub(acc, rm)

    still_ahead = d_live & ~_all(d_clocks <= c_new[..., None, :])
    d_ids_out, d_clocks_out, d_over = _rank_select_slots(
        still_ahead, d_ids, d_clocks, d_cap
    )
    return new_acc, d_ids_out, d_clocks_out, d_over


# ---------------------------------------------------------------------------
# pallas_call wrapper
# ---------------------------------------------------------------------------


def _tile_size(a, m, d, r, u_cap, vmem_budget=40 * 1024 * 1024):
    """Largest power-of-two object tile fitting the VMEM budget.

    Working set per object: the R input states + output, the aligned
    accumulator/replica planes (~4 live ``[U, A]`` temporaries — the
    elementwise steps keep at most the rule's select chain alive), and
    the final rank-select's per-slot selects.  Calibrate against the
    compiled memory plan (``compiled.memory_analysis()`` for a described
    v5e, as ``tests/test_chip_compile.py`` compiles it)."""
    import os

    forced = os.environ.get("CRDT_PALLAS_TILE")
    if forced:
        t = int(forced)
        if t < 8 or t & (t - 1):
            raise ValueError(
                f"CRDT_PALLAS_TILE={forced!r} must be a power of two >= 8"
            )
        return t
    state_bytes = 4 * (a + m + m * a + d + d * a)
    work_bytes = 4 * (6 * u_cap * a + 8 * d * a + 2 * r * m + 4 * u_cap)
    bytes_per_obj = (r + 1) * state_bytes + work_bytes
    # capped at 64, not the VMEM ceiling: Mosaic splits every wide op
    # into ~tile native registers, so compile time scales ~linearly with
    # the tile (measured: the r=4 kernel at tile 512 took 33 min to
    # compile); tile 64 keeps the instruction count ~8x smaller while
    # the grid pipeline still overlaps HBM at 977 tiles/chunk
    t = 64
    while t > 8 and t * bytes_per_obj > vmem_budget:
        t //= 2
    if t * bytes_per_obj > vmem_budget:
        raise ValueError(
            f"aligned-fold working set ({t * bytes_per_obj} bytes at the "
            f"minimum tile of {t} objects, r={r}, u_cap={u_cap}) exceeds "
            f"the {vmem_budget}-byte VMEM budget; use the jnp fold "
            "(orswot_ops.merge left fold) or a smaller fold width R"
        )
    return t


def pad_to_tile(state, m_cap: int, d_cap: int, n_states: int, u_cap: int | None = None):
    """Pad ``[R, N, ...]`` stacked planes on the object axis to this
    kernel's tile size (fill: ``EMPTY`` for id planes, 0 for counters) so
    callers pay the padding copy once outside a timed loop."""
    a = state[0].shape[-1]
    m = state[1].shape[-1]
    d = state[3].shape[-1]
    r = n_states - 1
    t = _tile_size(a, m, d, r, u_cap if u_cap is not None else 2 * m_cap)
    return tuple(
        _pad_to(x, t, axis=1, fill=EMPTY if x.dtype == jnp.int32 else 0)
        for x in state
    )


@observed_kernel("ops.fold_aligned.fold_merge")
@functools.partial(jax.jit, static_argnames=(
    "m_cap", "d_cap", "u_cap", "interpret", "plunger", "prebiased"))
def fold_merge(
    clock, ids, dots, dids, dclocks,
    m_cap: int, d_cap: int, u_cap: int | None = None,
    interpret: bool | None = None, plunger: bool = True,
    prebiased: bool = False,
):
    """Anti-entropy fold of ``R`` stacked replica fleets (``[R, N, ...]``
    planes) into one ``[N, ...]`` state — drop-in for
    ``orswot_pallas.fold_merge`` (same signature plus ``u_cap``), built
    on the union-aligned tile math above.

    ``u_cap`` bounds the per-object distinct-member union across all
    replicas (default ``2 * m_cap``); a wider union flags member
    overflow.  See the module docstring for the overflow contract."""
    if interpret is None:
        interpret = _interpret_default()
    r, n, a = clock.shape
    m, d = ids.shape[-1], dids.shape[-1]
    if u_cap is None:
        u_cap = 2 * m_cap
    t = _tile_size(a, m, d, r, u_cap)
    state = (clock, ids, dots, dids, dclocks)
    if prebiased:
        if clock.dtype != jnp.int32:
            raise TypeError(
                f"prebiased fold expects int32 kernel-domain planes, got "
                f"{clock.dtype}; use orswot_pallas.to_kernel_domain() first"
            )
        cdt = None
        state = tuple(
            _pad_to(x, t, axis=1, fill=EMPTY if i in (1, 3) else ZERO)
            for i, x in enumerate(state)
        )
    else:
        _check_dtypes(clock)
        cdt = clock.dtype
        state = tuple(
            _pad_to(x, t, axis=1, fill=EMPTY if x.dtype == jnp.int32 else 0)
            for x in state
        )
        state = _to_kernel_dtype(state)
    n_pad = state[0].shape[1]

    def kernel(ca, ia, da, dia, dca, oc, oi, od, odi, odc, oover):
        # --- union + first alignment -----------------------------------
        union_ids, n_union = _build_union([ia[rr] for rr in range(r)], u_cap)
        union_valid = union_ids != EMPTY
        acc = _align_to_union(union_ids, ia[0], da[0])
        c_acc = ca[0]
        d_ids_acc, d_clocks_acc = dia[0], dca[0]
        m_over = n_union > u_cap
        d_over = jnp.zeros_like(m_over)

        def step(acc, c_acc, d_ids_acc, d_clocks_acc, e2, c_rep, d2i, d2c):
            out, over_m = _step_members(
                acc, e2, c_acc, c_rep, union_valid, m_cap
            )
            c_new = jnp.maximum(c_acc, c_rep)
            out, d_ids_o, d_clocks_o, over_d = _step_deferred(
                union_ids, out, c_new, d_ids_acc, d_clocks_acc, d2i, d2c,
                d_cap,
            )
            return out, c_new, d_ids_o, d_clocks_o, over_m, over_d

        for rr in range(1, r):
            e2 = _align_to_union(union_ids, ia[rr], da[rr])
            acc, c_acc, d_ids_acc, d_clocks_acc, om, od_ = step(
                acc, c_acc, d_ids_acc, d_clocks_acc, e2, ca[rr], dia[rr], dca[rr]
            )
            m_over, d_over = m_over | om, d_over | od_
        if plunger:
            acc, c_acc, d_ids_acc, d_clocks_acc, om, od_ = step(
                acc, c_acc, d_ids_acc, d_clocks_acc,
                acc, c_acc, d_ids_acc, d_clocks_acc,
            )
            m_over, d_over = m_over | om, d_over | od_

        # --- canonical compaction (ascending member id) ----------------
        live = _nonempty(acc) & union_valid
        keys = jnp.where(live, union_ids, _SORT_MAX)
        ids_out, dots_out, _ = _rank_select(keys, live, union_ids, acc, m_cap)

        for ref, val in zip(
            (oc, oi, od, odi, odc),
            (c_acc, ids_out, dots_out, d_ids_acc, d_clocks_acc),
        ):
            ref[...] = val
        oover[...] = _bstack([m_over, d_over], axis=-1).astype(jnp.int32)

    in_specs = []
    for x in state:
        rest = x.ndim - 2
        in_specs.append(
            pl.BlockSpec(
                (r, t) + x.shape[2:],
                lambda i, _r=rest: (_ZERO, i) + (_ZERO,) * _r,
            )
        )
    out_shape = (
        jax.ShapeDtypeStruct((n_pad, a), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, m_cap), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, m_cap, a), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, d_cap), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, d_cap, a), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, 2), jnp.int32),
    )
    # 32-bit trace mode — see orswot_pallas.merge
    with x64_disabled():
        out = pl.pallas_call(
            kernel,
            grid=(n_pad // t,),
            in_specs=in_specs,
            out_specs=_state_specs(t, [s.shape for s in out_shape]),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES
            ),
            interpret=interpret,
        )(*state)
    c, i, dts, di, dc, over = (x[:n] for x in out)
    if prebiased:
        return c, i, dts, di, dc, over.astype(bool)
    return (
        _from_kernel_dtype(c, cdt), i, _from_kernel_dtype(dts, cdt), di,
        _from_kernel_dtype(dc, cdt), over.astype(bool),
    )
