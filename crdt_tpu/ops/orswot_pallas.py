"""Fused Pallas TPU kernels for the ORSWOT merge hot path.

The jnp path (:mod:`crdt_tpu.ops.orswot_ops`) expresses the merge as
concat → argsort → gather → dot-algebra → compact; under XLA that is
several HBM round-trips over the ``[N, 2M, A]`` tables per merge.  These
kernels run the **entire** pairwise merge — alignment, dot algebra,
deferred union/dedup/replay, canonical compaction — for a tile of objects
inside VMEM, with exactly one HBM read of the inputs and one HBM write of
the outputs per object:

* :func:`merge` — fused pairwise merge, drop-in for
  ``orswot_ops.merge`` (bit-identical outputs, same signature).
* :func:`fold_merge` — the anti-entropy fold: joins ``R`` stacked replica
  fleets to fixpoint (left fold + defer-plunger self-merge,
  `/root/reference/test/orswot.rs:45-62`) while the accumulator lives in
  registers/VMEM across all ``R`` steps — the jnp fold re-reads the
  accumulator from HBM every step, so this saves ``~R×`` accumulator
  bandwidth, which dominates the north-star benchmark.

Design notes (vs the jnp path):

* Member alignment is O(M²) masked compares instead of a 2M argsort —
  there is no sort primitive in Mosaic, and for the padded member
  capacities this framework targets (M ≤ 64) the quadratic match is a
  handful of VPU passes over data already in VMEM.
* Canonical output order (ascending member id, then free slots — what the
  argsort path produces) is restored by *rank selection*: each survivor's
  output slot is the count of live members with a smaller id, and output
  slot ``k`` gathers its row with a one-hot masked reduction.  Deferred
  rows keep first-occurrence order (the jnp path's stable pack), via the
  same rank trick with slot index as the key.
* Counters are ``uint32`` on the Pallas path (Mosaic has no 64-bit
  vectors); the scalar/u64 path remains the parity oracle for u64.
  Inside the kernel counters are held as **bias-mapped int32** —
  ``x ^ 0x8000_0000`` bitcast to int32 — because Mosaic has no
  unsigned-integer reductions.  The XOR bias is an order-preserving
  bijection uint32→int32, and this kernel only ever *compares, maxes
  and selects* counters (never adds them), so signed-domain arithmetic
  is exact over the full uint32 range; counter ``0`` becomes the
  sentinel :data:`ZERO` (= INT32_MIN) inside the kernel.  The
  entry/exit bias is one fused XOR outside the kernel.

Deployment note: the kernels compile for v5e with the real Mosaic
compiler against a described (not attached) chip —
``tests/test_chip_compile.py`` keeps that true (the x64 pitfalls are
handled: 32-bit trace mode, signed-domain reductions, int32 index-map
constants).  They have not yet executed on a chip, and the per-backend
default fold waits for a chip A/B against the jnp path (ROADMAP D5).
The jnp path is the portable default and the two are bit-identical
(``tests/test_orswot_pallas.py``).

Semantics follow `/root/reference/src/orswot.rs:89-156` exactly — the
asymmetric keep rules (`orswot.rs:94-103` vs `:132-138`), deferred-map
union (`:141-148`), clock join (`:153`) and deferred replay (`:155`) — see
``orswot_ops`` for the rule-by-rule citations; parity with that path (and
transitively with the scalar engine) is asserted in
``tests/test_orswot_pallas.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.kernels import observed_kernel

from ..config import x64_disabled


EMPTY = -1
# biased-int32 representation of counter 0 (see module docstring): the
# kernel-internal "absent / empty clock lane" sentinel
ZERO = np.int32(-(2**31))
_BIAS = np.uint32(0x8000_0000)


# ---------------------------------------------------------------------------
# tile math (plain jnp on VMEM-resident values; shared by both kernels)
# ---------------------------------------------------------------------------


def _emask(b):
    """Rank-expand a boolean mask by one trailing axis, in the i32 domain.

    Mosaic's vector layout inference rejects shape casts on ``i1``
    vectors (``tpu.reshape vector<...xi1> -> vector<...x1xi1>``, found by
    local AOT compile against a v5e topology) — so the reshape runs on an
    int32 widening and the ``i1`` is re-derived by an elementwise compare
    in the target shape."""
    return b.astype(jnp.int32)[..., None] > 0


def _bstack(cols, axis=-1):
    """Stack boolean columns along a new axis via int32 (see :func:`_emask`:
    ``jnp.stack`` reshapes each ``i1`` column, which Mosaic cannot lower)."""
    return jnp.stack([c.astype(jnp.int32) for c in cols], axis=axis) > 0


def _align_against(ids_a, dots_a, ids_b, dots_b):
    """For each a-slot, the matching b dot clock (``ZERO`` — the biased
    empty lane — if unmatched), plus the mask of b-slots consumed by a
    match.  O(M_a · M_b) masked compares."""
    m_b = ids_b.shape[-1]
    valid_a = ids_a != EMPTY
    e2 = jnp.full_like(dots_a, ZERO)
    # columns are collected and stacked rather than written with
    # ``.at[..., j].set`` — under jax_enable_x64 the scatter's literal
    # start indices trace as int64 scalars, which Mosaic cannot lower
    b_cols = []
    for j in range(m_b):
        mj = valid_a & (ids_a == ids_b[..., j : j + 1])  # [T, M_a]
        e2 = jnp.maximum(e2, jnp.where(_emask(mj), dots_b[..., j : j + 1, :], ZERO))
        b_cols.append(_any(mj))
    return e2, _bstack(b_cols, axis=-1)


def _merge_rule(e1, e2, p1, p2, valid, self_clock, other_clock):
    """The three-way per-member dot-algebra (`orswot.rs:92-138`)."""
    sc = self_clock[..., None, :]
    oc = other_clock[..., None, :]
    common = jnp.where(e1 == e2, e1, ZERO)
    c1 = _sub(_sub(e1, common), oc)
    c2 = _sub(_sub(e2, common), sc)
    out_both = jnp.maximum(common, jnp.maximum(c1, c2))
    keep1 = ~_all(e1 <= oc)
    out_only1 = jnp.where(_emask(keep1), e1, ZERO)
    out_only2 = _sub(e2, sc)
    both = _emask(p1 & p2)
    only1 = _emask(p1 & ~p2)
    out = jnp.where(both, out_both, jnp.where(only1, out_only1, out_only2))
    return jnp.where(_emask(valid), out, ZERO)


def _sub(a, b):
    return jnp.where(a > b, a, ZERO)


def _any(x, axis=-1):
    """Bool any-reduce in the int32 domain.  JAX's Mosaic lowering proxies
    ``reduce_or`` through float literals (``jnp.where(b, 1.0, 0.0)`` +
    ``maximumf``), which become unsupported f64 under jax_enable_x64; an
    int32 max-reduce lowers natively (MAXSI)."""
    return jnp.max(x.astype(jnp.int32), axis=axis) > 0


def _all(x, axis=-1):
    """Bool all-reduce in the int32 domain (see :func:`_any`)."""
    return jnp.min(x.astype(jnp.int32), axis=axis) > 0


def _nonempty(clock):
    return _any(clock != ZERO)


def _rank_select(keys, live, payload_ids, payload_clocks, cap):
    """Pack live slots in ascending-``keys`` order into ``cap`` output slots.

    ``keys`` must be unique among live slots.  Returns
    ``(ids[T, cap], clocks[T, cap, A], overflow[T])``."""
    s = keys.shape[-1]
    rank = jnp.zeros(keys.shape, dtype=jnp.int32)
    for j in range(s):
        smaller = live & live[..., j : j + 1] & (keys[..., j : j + 1] < keys)
        rank = rank + smaller.astype(jnp.int32)
    # rank[j] = #live slots with key < key[j]  (only meaningful where live)
    out_ids = []
    out_clocks = []
    for k in range(cap):
        sel = live & (rank == k)  # [T, S], at most one hot
        out_ids.append(
            jnp.sum(jnp.where(sel, payload_ids + 1, 0), axis=-1, dtype=jnp.int32) - 1
        )
        out_clocks.append(
            jnp.max(jnp.where(_emask(sel), payload_clocks, ZERO), axis=-2)
        )
    ids = jnp.stack(out_ids, axis=-1)
    clocks = jnp.stack(out_clocks, axis=-2)
    overflow = jnp.sum(live, axis=-1, dtype=jnp.int32) > cap
    return ids, clocks, overflow


def _rank_select_slots(live, payload_ids, payload_clocks, cap):
    """Deferred-table pack: keep live slots in slot (first-occurrence)
    order — the specialization of :func:`_rank_select` for ``keys`` = the
    slot index, which is what the deferred compaction always uses.

    Everything is python-unrolled into 1-D ``[T]`` / 2-D ``[T, A]`` ops:
    the deferred concat axis is tiny (``2·d_cap``, typically 4), and
    Mosaic's vector layout inference CHECK-crashes
    (``array.h: limits[i] <= dim(i)``) on any ``[T, 1] → [T, s]``
    broadcast or ``axis=-2`` reduction over a minor axis smaller than the
    native tile — found by local AOT compile against a v5e topology (the
    member-table call is fine: its ``2·m_cap`` axis is tile-sized).  With
    slot-order keys the rank of slot ``j`` is just the running count of
    live slots before it, so no pairwise compare is needed at all."""
    s = live.shape[-1]
    run = jnp.zeros(live.shape[:-1], dtype=jnp.int32)
    rank = []
    for j in range(s):
        rank.append(run)
        run = run + live[..., j].astype(jnp.int32)
    out_ids = []
    out_clocks = []
    for k in range(cap):
        oid = jnp.full(live.shape[:-1], -1, dtype=jnp.int32)
        clk = jnp.full_like(payload_clocks[..., 0, :], ZERO)  # [T, A]
        for j in range(s):
            sel_j = live[..., j] & (rank[j] == k)  # [T], at most one hot over j
            oid = oid + jnp.where(sel_j, payload_ids[..., j] + 1, 0)
            clk = jnp.maximum(
                clk, jnp.where(_emask(sel_j), payload_clocks[..., j, :], ZERO)
            )
        out_ids.append(oid)
        out_clocks.append(clk)
    ids = jnp.stack(out_ids, axis=-1)
    clocks = jnp.stack(out_clocks, axis=-2)
    overflow = run > cap
    return ids, clocks, overflow


def _merge_tile(sa, sb, m_cap: int, d_cap: int):
    """Full pairwise merge of two tile states.

    A state is ``(clock[T,A], ids[T,M], dots[T,M,A], d_ids[T,D],
    d_clocks[T,D,A])``; output uses ``m_cap``/``d_cap`` slots."""
    ca, ids_a, dots_a, dida, dca = sa
    cb, ids_b, dots_b, didb, dcb = sb

    # --- member alignment + dot algebra (`orswot.rs:92-138`) ---
    e2_for_a, b_matched = _align_against(ids_a, dots_a, ids_b, dots_b)
    valid_a = ids_a != EMPTY
    valid_b = ids_b != EMPTY
    out_a = _merge_rule(
        dots_a, e2_for_a, valid_a & _nonempty(dots_a), valid_a & _nonempty(e2_for_a),
        valid_a, ca, cb,
    )
    # unmatched b members: the only-in-other rule (`orswot.rs:132-138`)
    b_only = valid_b & ~b_matched
    out_b = jnp.where(_emask(b_only), _sub(dots_b, ca[..., None, :]), ZERO)

    ids_cat = jnp.concatenate(
        [jnp.where(valid_a, ids_a, EMPTY), jnp.where(b_only, ids_b, EMPTY)], axis=-1
    )
    dots_cat = jnp.concatenate([out_a, out_b], axis=-2)  # [T, Ma+Mb, A]

    # --- deferred union + dedup, keep first (`orswot.rs:141-148`) ---
    d_ids = jnp.concatenate([dida, didb], axis=-1)  # [T, Da+Db]
    d_clocks = jnp.concatenate([dca, dcb], axis=-2)
    dn = d_ids.shape[-1]
    d_valid = d_ids != EMPTY
    # column-stack instead of .at[].set — see _align_against
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
    is_dup = _bstack(dup_cols, axis=-1)
    d_live = d_valid & ~is_dup
    d_ids = jnp.where(d_live, d_ids, EMPTY)
    d_clocks = jnp.where(_emask(d_live), d_clocks, ZERO)

    # --- clock join (`orswot.rs:153`) then deferred replay (`:155`) ---
    clock = jnp.maximum(ca, cb)
    rm = jnp.full_like(dots_cat, ZERO)
    for k in range(dn):
        match = (ids_cat == d_ids[..., k : k + 1]) & d_live[..., k : k + 1]
        rm = jnp.maximum(
            rm, jnp.where(_emask(match), d_clocks[..., k : k + 1, :], ZERO)
        )
    new_dots = _sub(dots_cat, rm)
    live = _nonempty(new_dots) & (ids_cat != EMPTY)
    still_ahead = d_live & ~_all(d_clocks <= clock[..., None, :])

    # --- canonical compaction ---
    big = jnp.iinfo(jnp.int32).max
    m_keys = jnp.where(live, ids_cat, big)
    ids_out, dots_out, m_over = _rank_select(m_keys, live, ids_cat, new_dots, m_cap)
    dids_out, dclk_out, d_over = _rank_select_slots(
        still_ahead, d_ids, d_clocks, d_cap
    )
    return (clock, ids_out, dots_out, dids_out, dclk_out), _bstack(
        [m_over, d_over], axis=-1
    )


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _check_dtypes(clock):
    if clock.dtype.itemsize > 4:
        raise TypeError(
            f"Pallas ORSWOT kernels need <=32-bit counters, got {clock.dtype}; "
            "use the jnp path (orswot_ops) for u64"
        )


def _to_kernel_dtype(state):
    """Bias-map the clock-valued planes to int32 for the kernel.

    ``state`` is the canonical 5-tuple ``(clock, ids, dots, d_ids,
    d_clocks)``; planes 0/2/4 carry counters and get the order-preserving
    ``x ^ 0x8000_0000`` bitcast (exact over the full uint32 range — the
    kernel only compares/maxes/selects counters), planes 1/3 are already
    int32 member ids."""
    clock, ids, dots, d_ids, d_clocks = state
    bias = lambda x: jax.lax.bitcast_convert_type(
        x.astype(jnp.uint32) ^ _BIAS, jnp.int32
    )
    return bias(clock), ids, bias(dots), d_ids, bias(d_clocks)


def _from_kernel_dtype(x, cdt):
    """Invert :func:`_to_kernel_dtype`'s bias on one counter plane."""
    return (jax.lax.bitcast_convert_type(x, jnp.uint32) ^ _BIAS).astype(cdt)


# Mosaic scoped-VMEM ceiling requested from the compiler.  v5e has 128 MiB
# of VMEM per core; leave headroom for the compiler's own buffers and the
# double-buffered HBM⇄VMEM pipeline of the input/output blocks.
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def _tile_size(a, m, d, n_states=2, vmem_budget=48 * 1024 * 1024):
    """Largest power-of-two tile whose working set fits the VMEM budget.

    ``n_states`` is how many full states are live per tile object: 2 for a
    pairwise merge, R+1 for the fold (all R replica blocks plus the
    accumulator).  The temporaries term is calibrated against Mosaic's own
    scoped-stack accounting (local v5e AOT compile of the pairwise merge
    at a=16/m=8/d=2 reported 22.47 MiB for a 256-object tile ⇒ ~88 KiB
    per object ⇒ ~11 live ``[2m, a]`` planes per *survivor slot*): the
    unrolled rank-select keeps roughly one masked ``[2m, a]`` select live
    per output slot, and Mosaic stack-allocates them without reuse."""
    import os

    forced = os.environ.get("CRDT_PALLAS_TILE")
    if forced:
        # Read at TRACE time (like CRDT_MERGE_IMPL — jit caches are keyed
        # on shapes/dtypes only, so changing it after a first compile
        # keeps the old tile for same-shaped inputs; clear jit caches to
        # re-dispatch).  Bypasses the VMEM-budget model: the knob exists
        # for on-chip tile experiments where Mosaic's own scoped-vmem
        # error is the ground truth the model is calibrated against.
        try:
            t = int(forced)
        except ValueError:
            raise ValueError(
                f"CRDT_PALLAS_TILE={forced!r} is not an integer"
            ) from None
        if t < 8 or t & (t - 1):
            raise ValueError(
                f"CRDT_PALLAS_TILE={forced!r} must be a power of two >= 8"
            )
        return t
    state_bytes = 4 * (a + m + m * a + d + d * a)
    tmp_bytes = 4 * 11 * (2 * m) * m * a + 4 * 8 * d * a
    # the fold kernel unrolls n_states-1 sequential _merge_tile calls;
    # Mosaic reuses *some* dead stack slots across them, so the
    # temporaries term scales with the merge count but is capped
    # (calibration: pairwise merge, n_states=2, factor 1; local AOT
    # compiles of the fold bound the effective reuse)
    bytes_per_obj = n_states * state_bytes + min(max(1, n_states - 1), 4) * tmp_bytes
    t = 256
    while t > 8 and t * bytes_per_obj > vmem_budget:
        t //= 2
    if t * bytes_per_obj > vmem_budget:
        raise ValueError(
            f"ORSWOT working set ({t * bytes_per_obj} bytes at the minimum "
            f"tile of {t} objects, n_states={n_states}) exceeds the "
            f"{vmem_budget}-byte VMEM budget; use the jnp path "
            "(orswot_ops.merge) or a smaller fold width R"
        )
    return t


def _pad_to(x, t, axis=0, fill=0):
    n = x.shape[axis]
    pad = (-n) % t
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    # tile-alignment tail pad: the phantom rows are EMPTY-filled, sliced
    # back off after the pallas_call, and under a mesh each shard pads
    # its own slice — no real object ever crosses a shard boundary here
    return jnp.pad(x, widths, constant_values=fill)  # crdtlint: disable=SC01 — per-shard tile-alignment pad, sliced off after


_ZERO = np.int32(0)  # index-map constants must be 32-bit: under
# jax_enable_x64 a literal ``0`` traces as an int64 scalar, and Mosaic has
# no 64-bit support (the int64→int32 truncation recurses forever in its
# convert helper)


def _state_specs(t, shapes, batch_axes=1):
    """BlockSpecs blocking the leading object axis into tiles of ``t``."""
    specs = []
    for shp in shapes:
        block = (t,) + shp[batch_axes:]
        rest = len(shp) - batch_axes
        specs.append(pl.BlockSpec(block, lambda i, _r=rest: (i,) + (_ZERO,) * _r))
    return specs


def _interpret_default():
    return jax.default_backend() != "tpu"


@observed_kernel("ops.pallas.merge")
@functools.partial(jax.jit, static_argnames=("m_cap", "d_cap", "interpret"))
def merge(
    clock_a, ids_a, dots_a, dids_a, dclocks_a,
    clock_b, ids_b, dots_b, dids_b, dclocks_b,
    m_cap: int, d_cap: int, interpret: bool | None = None,
):
    """Fused pairwise merge — drop-in for ``orswot_ops.merge`` (2-D batch
    ``[N, ...]`` states, uint32 counters).  Returns
    ``(clock, ids, dots, d_ids, d_clocks, overflow)``."""
    _check_dtypes(clock_a)
    _check_dtypes(clock_b)
    if interpret is None:
        interpret = _interpret_default()
    n, a = clock_a.shape
    m, d = ids_a.shape[-1], dids_a.shape[-1]
    t = _tile_size(a, m, d)
    sa = (clock_a, ids_a, dots_a, dids_a, dclocks_a)
    sb = (clock_b, ids_b, dots_b, dids_b, dclocks_b)
    sa = tuple(_pad_to(x, t, fill=EMPTY if x.dtype == jnp.int32 else 0) for x in sa)
    sb = tuple(_pad_to(x, t, fill=EMPTY if x.dtype == jnp.int32 else 0) for x in sb)
    sa, sb = _to_kernel_dtype(sa), _to_kernel_dtype(sb)
    n_pad = sa[0].shape[0]
    cdt = clock_a.dtype

    def kernel(ca, ia, da, dia, dca, cb, ib, db, dib, dcb, oc, oi, od, odi, odc, oover):
        out, over = _merge_tile(
            tuple(r[...] for r in (ca, ia, da, dia, dca)),
            tuple(r[...] for r in (cb, ib, db, dib, dcb)),
            m_cap, d_cap,
        )
        for ref, val in zip((oc, oi, od, odi, odc), out):
            ref[...] = val
        oover[...] = over.astype(jnp.int32)

    in_shapes = [x.shape for x in sa] * 2
    out_shape = (
        jax.ShapeDtypeStruct((n_pad, a), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, m_cap), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, m_cap, a), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, d_cap), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, d_cap, a), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, 2), jnp.int32),
    )
    # the kernel must trace in 32-bit mode: under jax_enable_x64 every
    # Python-int literal (the `0`s in jnp.where etc.) becomes an i64[]
    # scalar operand, and Mosaic has no 64-bit support — its convert
    # helper recurses forever on the i64→i32 truncation
    with x64_disabled():
        out = pl.pallas_call(
            kernel,
            grid=(n_pad // t,),
            in_specs=_state_specs(t, in_shapes),
            out_specs=_state_specs(t, [s.shape for s in out_shape]),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES
            ),
            interpret=interpret,
        )(*sa, *sb)
    clock, ids, dots, dids, dclk, over = (x[:n] for x in out)
    return (
        _from_kernel_dtype(clock, cdt), ids, _from_kernel_dtype(dots, cdt),
        dids, _from_kernel_dtype(dclk, cdt), over.astype(bool),
    )


def pad_to_tile(state, m_cap: int, d_cap: int, n_states: int):
    """Pad ``[R, N, ...]`` stacked planes on the object axis to the fold's
    tile size, with the module's own fill policy (``EMPTY`` for id planes,
    0 for counter planes) — so callers can pay the padding copy ONCE
    outside a timed loop and :func:`fold_merge`'s internal `_pad_to`
    becomes a no-op.  Returns the padded 5-tuple."""
    a = state[0].shape[-1]
    m = state[1].shape[-1]
    d = state[3].shape[-1]
    t = _tile_size(a, m, d, n_states=n_states)
    return tuple(
        _pad_to(x, t, axis=1, fill=EMPTY if x.dtype == jnp.int32 else 0)
        for x in state
    )


def to_kernel_domain(state):
    """Public: map a canonical 5-tuple of ``[R, N, ...]`` planes into the
    kernel's biased-int32 domain (see :func:`_to_kernel_dtype`).  Pair
    with ``fold_merge(..., prebiased=True)`` to hoist the uint32↔int32
    conversion copies (~a full working set per call) out of a timed loop;
    XOR salting commutes with the bias, so salt chains work unchanged in
    this domain.  Rejects >32-bit counters like the in-band path (the
    bias cast would silently truncate them)."""
    _check_dtypes(state[0])
    return _to_kernel_dtype(state)


def from_kernel_domain(x, dtype):
    """Public inverse of :func:`to_kernel_domain` for one counter plane."""
    return _from_kernel_dtype(x, dtype)


@observed_kernel("ops.pallas.fold_merge")
@functools.partial(jax.jit, static_argnames=(
    "m_cap", "d_cap", "interpret", "plunger", "prebiased"))
def fold_merge(
    clock, ids, dots, dids, dclocks,
    m_cap: int, d_cap: int, interpret: bool | None = None, plunger: bool = True,
    prebiased: bool = False,
):
    """Anti-entropy fold: join ``R`` stacked replica fleets (arrays are
    ``[R, N, ...]``) into one ``[N, ...]`` state, entirely in VMEM.

    Left-folds replica ``r`` into the accumulator for ``r = 1..R-1`` and
    finishes with a defer-plunger self-merge
    (`/root/reference/test/orswot.rs:61-62`) so buffered removes flush —
    matching ``r`` sequential ``orswot_ops.merge`` calls bit-exactly, but
    with the accumulator never leaving the chip.

    ``prebiased=True``: the counter planes are already in the kernel's
    biased-int32 domain (:func:`to_kernel_domain`) and the outputs stay
    there — the entry/exit conversion copies drop out entirely (callers
    invert with :func:`from_kernel_domain` once, outside their loop)."""
    if interpret is None:
        interpret = _interpret_default()
    r, n, a = clock.shape
    m, d = ids.shape[-1], dids.shape[-1]
    # all R replica blocks plus the accumulator are live in VMEM per tile
    t = _tile_size(a, m, d, n_states=r + 1)
    state = (clock, ids, dots, dids, dclocks)
    if prebiased:
        if clock.dtype != jnp.int32:
            raise TypeError(
                f"prebiased fold expects int32 kernel-domain planes, got "
                f"{clock.dtype}; use to_kernel_domain() first"
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
        refs = (ca, ia, da, dia, dca)
        acc = tuple(ref[0] for ref in refs)
        over_any = jnp.zeros((acc[0].shape[0], 2), dtype=bool)
        for rr in range(1, r):
            acc, over = _merge_tile(acc, tuple(ref[rr] for ref in refs), m_cap, d_cap)
            over_any = over_any | over
        if plunger:
            acc, over = _merge_tile(acc, acc, m_cap, d_cap)
            over_any = over_any | over
        for ref, val in zip((oc, oi, od, odi, odc), acc):
            ref[...] = val
        oover[...] = over_any.astype(jnp.int32)

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
    # 32-bit trace mode — see the matching comment in merge()
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
