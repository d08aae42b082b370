"""Numpy-facing wrappers over the native C++ kernels.

Same dense SoA layouts and bit-exact outputs (including slot order) as the
JAX batch kernels in :mod:`crdt_tpu.ops` — the three engines (scalar Python,
JAX/XLA, native C++) are interchangeable behind the same array contracts,
and the parity suite compares them byte-for-byte.

Counter dtype may be uint32 or uint64 (reference: u64, `vclock.rs:23`); the
two instantiations are separate C symbols picked by dtype.  LWWReg values
and MVReg payloads cross the ABI as int64 (interned ids / opaque payloads).
"""

from __future__ import annotations

import ctypes

import numpy as np

from . import loader

_SUFFIX = {np.dtype(np.uint32): "u32", np.dtype(np.uint64): "u64"}


def _fn(name: str, dtype) -> "ctypes._CFuncPtr":
    suf = _SUFFIX.get(np.dtype(dtype))
    if suf is None:
        raise TypeError(f"unsupported counter dtype {dtype!r} (uint32/uint64)")
    return getattr(loader.load(), f"{name}_{suf}")


def _ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.c_void_p)


def _contig(*arrays):
    return tuple(np.ascontiguousarray(x) for x in arrays)


def _check_counters(*arrays):
    dt = np.dtype(arrays[0].dtype)
    for x in arrays[1:]:
        if np.dtype(x.dtype) != dt:
            raise TypeError(f"counter dtype mismatch: {dt} vs {x.dtype}")
    return dt


def _count_native(name: str, objects: int) -> None:
    """Always-on call/object counters for the hot native entry points
    (``native.engine.<name>.{calls,objects}``) — one dict increment per
    BULK call, same discipline as the wire codec counters.  A counter
    family that vanishes between two snapshots is the silent-fallback
    smell: the native path stopped being exercised without anything
    failing loudly."""
    from ..utils import tracing

    tracing.count(f"native.engine.{name}.calls")
    tracing.count(f"native.engine.{name}.objects", objects)


# -- VClock ------------------------------------------------------------------


def _elementwise(name: str, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a, b = _contig(a, b)
    dt = _check_counters(a, b)
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch: {a.shape} vs {b.shape}")
    out = np.empty_like(a)
    _fn(name, dt)(_ptr(a), _ptr(b), _ptr(out), ctypes.c_int64(a.size))
    return out


def vclock_merge(a, b):
    """Pointwise max (`vclock.rs:131-137`)."""
    return _elementwise("vclock_merge", a, b)


def vclock_intersection(a, b):
    """Common dots (`vclock.rs:219-228`)."""
    return _elementwise("vclock_intersect", a, b)


def vclock_subtract(a, b):
    """Keep a's dots ahead of b's (`vclock.rs:236-242`)."""
    return _elementwise("vclock_subtract", a, b)


def vclock_truncate(a, b):
    """GLB, pointwise min (`vclock.rs:103-120`)."""
    return _elementwise("vclock_truncate", a, b)


def vclock_compare(a, b):
    """Per-row lattice partial order over ``[n, A]``: ``(leq, geq)`` bool
    arrays (`vclock.rs:59-71`)."""
    a, b = _contig(a, b)
    dt = _check_counters(a, b)
    if a.shape != b.shape or a.ndim < 1:
        raise ValueError(f"shape mismatch: {a.shape} vs {b.shape}")
    n = int(np.prod(a.shape[:-1], dtype=np.int64)) if a.ndim > 1 else 1
    actors = a.shape[-1]
    leq = np.empty(n, dtype=np.uint8)
    geq = np.empty(n, dtype=np.uint8)
    _fn("vclock_compare", dt)(
        _ptr(a), _ptr(b), ctypes.c_int64(n), ctypes.c_int64(actors),
        _ptr(leq), _ptr(geq),
    )
    shape = a.shape[:-1]
    return leq.astype(bool).reshape(shape), geq.astype(bool).reshape(shape)


# -- LWWReg ------------------------------------------------------------------


def lww_merge(val_a, marker_a, val_b, marker_b):
    """Batched LWW merge; returns ``(val, marker, conflict)``
    (`lwwreg.rs:43-67`; conflict surfaced as a bitmap, SURVEY.md §7.3)."""
    val_a, val_b = _contig(
        np.asarray(val_a, dtype=np.int64), np.asarray(val_b, dtype=np.int64)
    )
    marker_a, marker_b = _contig(marker_a, marker_b)
    dt = _check_counters(marker_a, marker_b)
    if not (val_a.shape == val_b.shape == marker_a.shape == marker_b.shape):
        raise ValueError(
            f"lww_merge: shape mismatch {val_a.shape}/{marker_a.shape}/"
            f"{val_b.shape}/{marker_b.shape}"
        )
    n = marker_a.size
    val = np.empty_like(val_a)
    marker = np.empty_like(marker_a)
    conflict = np.empty(n, dtype=np.uint8)
    _fn("lww_merge", dt)(
        _ptr(val_a), _ptr(marker_a), _ptr(val_b), _ptr(marker_b),
        _ptr(val), _ptr(marker), _ptr(conflict), ctypes.c_int64(n),
    )
    return val, marker, conflict.astype(bool).reshape(marker_a.shape)


# -- MVReg -------------------------------------------------------------------


def mvreg_merge(clocks_a, vals_a, clocks_b, vals_b, k_cap: int | None = None):
    """Batched antichain merge (`mvreg.rs:121-153`); returns
    ``(clocks, vals, overflow)`` packed to ``k_cap`` slots, self's survivors
    first — the same order as the JAX ``merge`` + ``compact``."""
    clocks_a, clocks_b = _contig(clocks_a, clocks_b)
    vals_a, vals_b = _contig(
        np.asarray(vals_a, dtype=np.int64), np.asarray(vals_b, dtype=np.int64)
    )
    dt = _check_counters(clocks_a, clocks_b)
    if clocks_a.shape != clocks_b.shape or clocks_a.ndim < 2:
        raise ValueError(f"shape mismatch: {clocks_a.shape} vs {clocks_b.shape}")
    if vals_a.shape != clocks_a.shape[:-1] or vals_b.shape != clocks_b.shape[:-1]:
        raise ValueError(
            f"mvreg_merge: vals shapes {vals_a.shape}/{vals_b.shape} don't "
            f"match clocks {clocks_a.shape[:-1]}"
        )
    *lead, k, a = clocks_a.shape
    n = int(np.prod(lead, dtype=np.int64)) if lead else 1
    k_cap = k if k_cap is None else k_cap
    clocks = np.zeros((*lead, k_cap, a), dtype=dt)
    vals = np.zeros((*lead, k_cap), dtype=np.int64)
    overflow = np.empty(n, dtype=np.uint8)
    _fn("mvreg_merge", dt)(
        _ptr(clocks_a), _ptr(vals_a), _ptr(clocks_b), _ptr(vals_b),
        ctypes.c_int64(n), ctypes.c_int64(k), ctypes.c_int64(a),
        ctypes.c_int64(k_cap), _ptr(clocks), _ptr(vals), _ptr(overflow),
    )
    return clocks, vals, overflow.astype(bool).reshape(lead)


# -- ORSWOT ------------------------------------------------------------------


def _orswot_state(clock, ids, dots, d_ids, d_clocks):
    clock, dots, d_clocks = _contig(clock, dots, d_clocks)
    ids, d_ids = _contig(
        np.asarray(ids, dtype=np.int32), np.asarray(d_ids, dtype=np.int32)
    )
    # full cross-field shape check: the C kernels index with raw pointer
    # arithmetic, so any inconsistency here is an out-of-bounds read there
    *lead, a = clock.shape
    m = ids.shape[-1]
    d = d_ids.shape[-1]
    expect = {
        "ids": (*lead, m),
        "dots": (*lead, m, a),
        "d_ids": (*lead, d),
        "d_clocks": (*lead, d, a),
    }
    got = {"ids": ids.shape, "dots": dots.shape,
           "d_ids": d_ids.shape, "d_clocks": d_clocks.shape}
    if got != expect:
        raise ValueError(f"inconsistent ORSWOT state shapes: {got} != {expect}")
    return clock, ids, dots, d_ids, d_clocks


def orswot_merge(
    clock_a, ids_a, dots_a, dids_a, dclocks_a,
    clock_b, ids_b, dots_b, dids_b, dclocks_b,
    m_cap: int | None = None, d_cap: int | None = None,
    out=None,
):
    """Full pairwise ORSWOT merge (`orswot.rs:89-156`), bit-exact with
    :func:`crdt_tpu.ops.orswot_ops.merge` including output slot order
    (members ascending by id, deferred rows in self-then-other order).

    Returns ``(clock, ids, dots, d_ids, d_clocks, overflow)`` with
    ``overflow`` = ``bool[..., 2]`` (member / deferred axis flags, matching
    the jnp kernel).

    ``out``: optional preallocated 5-tuple of output planes to write into
    (same shapes/dtypes the call would otherwise allocate).  The C kernel
    fully overwrites every output cell, so reuse is safe; fold loops
    ping-pong two buffer sets to avoid an mmap page-zeroing pass per
    merge (~working-set bytes of pure overhead each call at fleet
    scale).  Outputs MUST NOT alias either input."""
    A = _orswot_state(clock_a, ids_a, dots_a, dids_a, dclocks_a)
    B = _orswot_state(clock_b, ids_b, dots_b, dids_b, dclocks_b)
    dt = _check_counters(A[0], B[0])
    if any(x.shape != y.shape for x, y in zip(A, B)):
        raise ValueError(
            f"orswot_merge: side shapes differ: "
            f"{[x.shape for x in A]} vs {[y.shape for y in B]}"
        )
    *lead, a = A[0].shape
    n = int(np.prod(lead, dtype=np.int64)) if lead else 1
    m = A[1].shape[-1]
    d = A[3].shape[-1]
    m_cap = m if m_cap is None else m_cap
    d_cap = d if d_cap is None else d_cap

    if out is None:
        clock = np.empty((*lead, a), dtype=dt)
        ids = np.empty((*lead, m_cap), dtype=np.int32)
        dots = np.empty((*lead, m_cap, a), dtype=dt)
        d_ids = np.empty((*lead, d_cap), dtype=np.int32)
        d_clocks = np.empty((*lead, d_cap, a), dtype=dt)
    else:
        clock, ids, dots, d_ids, d_clocks = out
        expect = (
            ((*lead, a), dt), ((*lead, m_cap), np.int32),
            ((*lead, m_cap, a), dt), ((*lead, d_cap), np.int32),
            ((*lead, d_cap, a), dt),
        )
        for name, buf, (shape, dtype) in zip(
            ("clock", "ids", "dots", "d_ids", "d_clocks"),
            (clock, ids, dots, d_ids, d_clocks), expect,
        ):
            if (not isinstance(buf, np.ndarray) or buf.shape != shape
                    or buf.dtype != np.dtype(dtype)
                    or not buf.flags.c_contiguous):
                raise ValueError(
                    f"out[{name}]: need C-contiguous {np.dtype(dtype)}"
                    f"{shape}, got "
                    f"{getattr(buf, 'dtype', type(buf))}"
                    f"{getattr(buf, 'shape', '')}"
                )
            for src in (*A, *B):
                if np.shares_memory(buf, src):
                    raise ValueError(f"out[{name}] aliases an input plane")
        # outputs must also be distinct from each other (same-shaped int32
        # planes like ids/d_ids would otherwise pass every check above)
        outs = (clock, ids, dots, d_ids, d_clocks)
        for i in range(len(outs)):
            for j in range(i + 1, len(outs)):
                if np.shares_memory(outs[i], outs[j]):
                    raise ValueError(
                        "out planes must not alias each other "
                        f"(planes {i} and {j} share memory)"
                    )
    overflow = np.empty(n * 2, dtype=np.uint8)
    _count_native("orswot_merge", n)
    _fn("orswot_merge", dt)(
        _ptr(A[0]), _ptr(A[1]), _ptr(A[2]), _ptr(A[3]), _ptr(A[4]),
        _ptr(B[0]), _ptr(B[1]), _ptr(B[2]), _ptr(B[3]), _ptr(B[4]),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(m),
        ctypes.c_int64(d), ctypes.c_int64(m_cap), ctypes.c_int64(d_cap),
        _ptr(clock), _ptr(ids), _ptr(dots), _ptr(d_ids), _ptr(d_clocks),
        _ptr(overflow),
    )
    return (
        clock, ids, dots, d_ids, d_clocks,
        overflow.astype(bool).reshape(*lead, 2),
    )


def orswot_apply_add(clock, ids, dots, dids, dclocks, actor_idx, counter, member_id):
    """Batched ``Op::Add`` (`orswot.rs:66-79`), in-place semantics returned
    as fresh arrays; bit-exact with the JAX ``apply_add`` (slot positions
    untouched).  Returns the 5 state arrays + overflow."""
    state = _orswot_state(clock, ids, dots, dids, dclocks)
    state = tuple(x.copy() for x in state)
    dt = _check_counters(state[0])
    *lead, a = state[0].shape
    n = int(np.prod(lead, dtype=np.int64)) if lead else 1
    m = state[1].shape[-1]
    d = state[3].shape[-1]
    actor_idx = np.ascontiguousarray(np.asarray(actor_idx, dtype=np.int32))
    counter = np.ascontiguousarray(np.asarray(counter, dtype=dt))
    member_id = np.ascontiguousarray(np.asarray(member_id, dtype=np.int32))
    for name, arr in (("actor_idx", actor_idx), ("counter", counter),
                      ("member_id", member_id)):
        if arr.shape != tuple(lead):
            raise ValueError(f"apply_add: {name} shape {arr.shape} != {tuple(lead)}")
    if np.any(actor_idx < 0) or np.any(actor_idx >= a):
        raise ValueError(f"apply_add: actor_idx out of range [0, {a})")
    overflow = np.empty(n, dtype=np.uint8)
    _fn("orswot_apply_add", dt)(
        _ptr(state[0]), _ptr(state[1]), _ptr(state[2]), _ptr(state[3]),
        _ptr(state[4]), _ptr(actor_idx), _ptr(counter), _ptr(member_id),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(m),
        ctypes.c_int64(d), _ptr(overflow),
    )
    return (*state, overflow.astype(bool).reshape(lead))


def orswot_apply_remove(clock, ids, dots, dids, dclocks, rm_clock, member_id):
    """Batched ``Op::Rm`` (`orswot.rs:195-211`); returns the 5 state arrays
    + overflow (deferred table full), bit-exact with the JAX
    ``apply_remove``."""
    state = _orswot_state(clock, ids, dots, dids, dclocks)
    state = tuple(x.copy() for x in state)
    dt = _check_counters(state[0])
    *lead, a = state[0].shape
    n = int(np.prod(lead, dtype=np.int64)) if lead else 1
    m = state[1].shape[-1]
    d = state[3].shape[-1]
    rm_clock = np.ascontiguousarray(np.asarray(rm_clock, dtype=dt))
    member_id = np.ascontiguousarray(np.asarray(member_id, dtype=np.int32))
    if rm_clock.shape != (*lead, a):
        raise ValueError(f"apply_remove: rm_clock shape {rm_clock.shape} != {(*lead, a)}")
    if member_id.shape != tuple(lead):
        raise ValueError(f"apply_remove: member_id shape {member_id.shape} != {tuple(lead)}")
    overflow = np.empty(n, dtype=np.uint8)
    _fn("orswot_apply_remove", dt)(
        _ptr(state[0]), _ptr(state[1]), _ptr(state[2]), _ptr(state[3]),
        _ptr(state[4]), _ptr(rm_clock), _ptr(member_id),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(m),
        ctypes.c_int64(d), _ptr(overflow),
    )
    return (*state, overflow.astype(bool).reshape(lead))


# -- Map<K, Orswot> ----------------------------------------------------------


def map_orswot_merge(
    state_a, state_b, k_cap: int | None = None, d_cap: int | None = None
):
    """Full pairwise ``Map<K, Orswot>`` merge (`map.rs:192-269` with
    `orswot.rs:89-156` nested) — the hardest composition path, bit-exact
    with :func:`crdt_tpu.ops.map_ops.merge` under an ``OrswotKernel``
    including output slot order (keys ascending; nested member tables in
    the nested merge's compact order, truncate holes preserved).

    ``state`` = ``(clock[N,A], keys i32[N,K], eclocks[N,K,A],
    (o_clock[N,K,A], o_ids i32[N,K,M], o_dots[N,K,M,A],
    o_dids i32[N,K,D2], o_dclocks[N,K,D2,A]), d_keys i32[N,D],
    d_clocks[N,D,A])`` — the nested 5-tuple is the OrswotKernel value
    state.  Returns ``(state, overflow)`` with one flag per object."""
    def unpack(state):
        clock, keys, eclocks, vals, d_keys, d_clocks = state
        ovc, oid, odot, odid, odclk = vals
        clock, eclocks, ovc, odot, odclk, d_clocks = _contig(
            clock, eclocks, ovc, odot, odclk, d_clocks
        )
        keys, oid, odid, d_keys = _contig(
            np.asarray(keys, dtype=np.int32), np.asarray(oid, dtype=np.int32),
            np.asarray(odid, dtype=np.int32), np.asarray(d_keys, dtype=np.int32),
        )
        return clock, keys, eclocks, ovc, oid, odot, odid, odclk, d_keys, d_clocks

    A = unpack(state_a)
    B = unpack(state_b)
    dt = _check_counters(A[0], B[0], A[2], B[2], A[3], B[3], A[5], B[5],
                         A[7], B[7], A[9], B[9])
    if any(x.shape != y.shape for x, y in zip(A, B)):
        raise ValueError(
            f"map_orswot_merge: side shapes differ: "
            f"{[x.shape for x in A]} vs {[y.shape for y in B]}"
        )
    clk, keys_, ec, ovc_, oid_, odot_, odid_, odclk_, dk_, dc_ = A
    *lead, a = clk.shape
    k = keys_.shape[-1]
    m = oid_.shape[-1]
    d2 = odid_.shape[-1]
    d = dk_.shape[-1]
    lead_t = tuple(lead)
    if (
        keys_.shape != (*lead_t, k)
        or ec.shape != (*lead_t, k, a)
        or ovc_.shape != (*lead_t, k, a)
        or oid_.shape != (*lead_t, k, m)
        or odot_.shape != (*lead_t, k, m, a)
        or odid_.shape != (*lead_t, k, d2)
        or odclk_.shape != (*lead_t, k, d2, a)
        or dk_.shape != (*lead_t, d)
        or dc_.shape != (*lead_t, d, a)
    ):
        raise ValueError(
            f"map_orswot_merge: inconsistent state shapes: {[x.shape for x in A]}"
        )
    n = int(np.prod(lead, dtype=np.int64)) if lead else 1
    k_cap = k if k_cap is None else k_cap
    d_cap = d if d_cap is None else d_cap

    clock = np.empty((*lead, a), dtype=dt)
    keys = np.empty((*lead, k_cap), dtype=np.int32)
    eclocks = np.empty((*lead, k_cap, a), dtype=dt)
    ovc = np.empty((*lead, k_cap, a), dtype=dt)
    oid = np.empty((*lead, k_cap, m), dtype=np.int32)
    odot = np.empty((*lead, k_cap, m, a), dtype=dt)
    odid = np.empty((*lead, k_cap, d2), dtype=np.int32)
    odclk = np.empty((*lead, k_cap, d2, a), dtype=dt)
    d_keys = np.empty((*lead, d_cap), dtype=np.int32)
    d_clocks = np.empty((*lead, d_cap, a), dtype=dt)
    overflow = np.empty(n, dtype=np.uint8)
    _fn("map_orswot_merge", dt)(
        *(_ptr(x) for x in A), *(_ptr(x) for x in B),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(k),
        ctypes.c_int64(m), ctypes.c_int64(d2), ctypes.c_int64(d),
        ctypes.c_int64(k_cap), ctypes.c_int64(d_cap),
        _ptr(clock), _ptr(keys), _ptr(eclocks), _ptr(ovc), _ptr(oid),
        _ptr(odot), _ptr(odid), _ptr(odclk), _ptr(d_keys), _ptr(d_clocks),
        _ptr(overflow),
    )
    return (
        (clock, keys, eclocks, (ovc, oid, odot, odid, odclk), d_keys, d_clocks),
        overflow.astype(bool).reshape(lead),
    )


# -- Map<K, Map<K2, MVReg>> --------------------------------------------------


def map_map_mvreg_merge(
    state_a, state_b, k_cap: int | None = None, d_cap: int | None = None
):
    """Full pairwise ``Map<K, Map<K2, MVReg>>`` merge — nested reset-remove
    composition (`map.rs:192-269` recursing into itself at `:229`, the
    `test/map.rs:8` shape), bit-exact with :func:`crdt_tpu.ops.map_ops.merge`
    under a ``MapKernel(val_kernel=MVRegKernel)``.

    ``state`` = ``(clock[N,A], keys i32[N,K], eclocks[N,K,A],
    (i_clock[N,K,A], i_keys i32[N,K,K2], i_eclocks[N,K,K2,A],
    (mv_clocks[N,K,K2,V,A], mv_vals[N,K,K2,V]), i_dkeys i32[N,K,D3],
    i_dclocks[N,K,D3,A]), d_keys i32[N,D], d_clocks[N,D,A])`` — the nested
    6-tuple is the inner MapKernel value state.  Returns
    ``(state, overflow)`` with one flag per object."""
    def unpack(state):
        clock, keys, eclocks, vals, d_keys, d_clocks = state
        iclk, ikeys, iec, (imvc, imvv), idk, idc = vals
        clock, eclocks, iclk, iec, imvc, imvv, idc, d_clocks = _contig(
            clock, eclocks, iclk, iec, imvc, imvv, idc, d_clocks
        )
        keys, ikeys, idk, d_keys = _contig(
            np.asarray(keys, dtype=np.int32), np.asarray(ikeys, dtype=np.int32),
            np.asarray(idk, dtype=np.int32), np.asarray(d_keys, dtype=np.int32),
        )
        return (clock, keys, eclocks, iclk, ikeys, iec, imvc, imvv, idk, idc,
                d_keys, d_clocks)

    A = unpack(state_a)
    B = unpack(state_b)
    dt = _check_counters(A[0], B[0], A[2], B[2], A[3], B[3], A[5], B[5],
                         A[6], B[6], A[7], B[7], A[9], B[9], A[11], B[11])
    if any(x.shape != y.shape for x, y in zip(A, B)):
        raise ValueError(
            f"map_map_mvreg_merge: side shapes differ: "
            f"{[x.shape for x in A]} vs {[y.shape for y in B]}"
        )
    (clk, keys_, ec, iclk_, ikeys_, iec_, imvc_, imvv_, idk_, idc_,
     dk_, dc_) = A
    *lead, a = clk.shape
    lead_t = tuple(lead)
    k = keys_.shape[-1]
    k2 = ikeys_.shape[-1]
    v_cap = imvc_.shape[-2]
    d3 = idk_.shape[-1]
    d = dk_.shape[-1]
    if (
        keys_.shape != (*lead_t, k)
        or ec.shape != (*lead_t, k, a)
        or iclk_.shape != (*lead_t, k, a)
        or ikeys_.shape != (*lead_t, k, k2)
        or iec_.shape != (*lead_t, k, k2, a)
        or imvc_.shape != (*lead_t, k, k2, v_cap, a)
        or imvv_.shape != (*lead_t, k, k2, v_cap)
        or idk_.shape != (*lead_t, k, d3)
        or idc_.shape != (*lead_t, k, d3, a)
        or dk_.shape != (*lead_t, d)
        or dc_.shape != (*lead_t, d, a)
    ):
        raise ValueError(
            f"map_map_mvreg_merge: inconsistent state shapes: "
            f"{[x.shape for x in A]}"
        )
    n = int(np.prod(lead, dtype=np.int64)) if lead else 1
    k_cap = k if k_cap is None else k_cap
    d_cap = d if d_cap is None else d_cap

    clock = np.empty((*lead, a), dtype=dt)
    keys = np.empty((*lead, k_cap), dtype=np.int32)
    eclocks = np.empty((*lead, k_cap, a), dtype=dt)
    iclk = np.empty((*lead, k_cap, a), dtype=dt)
    ikeys = np.empty((*lead, k_cap, k2), dtype=np.int32)
    iec = np.empty((*lead, k_cap, k2, a), dtype=dt)
    imvc = np.empty((*lead, k_cap, k2, v_cap, a), dtype=dt)
    imvv = np.empty((*lead, k_cap, k2, v_cap), dtype=dt)
    idk = np.empty((*lead, k_cap, d3), dtype=np.int32)
    idc = np.empty((*lead, k_cap, d3, a), dtype=dt)
    d_keys = np.empty((*lead, d_cap), dtype=np.int32)
    d_clocks = np.empty((*lead, d_cap, a), dtype=dt)
    overflow = np.empty(n, dtype=np.uint8)
    _fn("map_map_mvreg_merge", dt)(
        *(_ptr(x) for x in A), *(_ptr(x) for x in B),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(k),
        ctypes.c_int64(k2), ctypes.c_int64(v_cap), ctypes.c_int64(d3),
        ctypes.c_int64(d), ctypes.c_int64(k_cap), ctypes.c_int64(d_cap),
        _ptr(clock), _ptr(keys), _ptr(eclocks), _ptr(iclk), _ptr(ikeys),
        _ptr(iec), _ptr(imvc), _ptr(imvv), _ptr(idk), _ptr(idc),
        _ptr(d_keys), _ptr(d_clocks), _ptr(overflow),
    )
    return (
        (clock, keys, eclocks,
         (iclk, ikeys, iec, (imvc, imvv), idk, idc), d_keys, d_clocks),
        overflow.astype(bool).reshape(lead),
    )


# -- Map<K, MVReg> -----------------------------------------------------------


def _map_state(clock, keys, eclocks, mv_clocks, mv_vals, d_keys, d_clocks):
    clock, eclocks, mv_clocks, mv_vals, d_clocks = _contig(
        clock, eclocks, mv_clocks, mv_vals, d_clocks
    )
    keys, d_keys = _contig(
        np.asarray(keys, dtype=np.int32), np.asarray(d_keys, dtype=np.int32)
    )
    return clock, keys, eclocks, mv_clocks, mv_vals, d_keys, d_clocks


def map_mvreg_merge(
    state_a, state_b, k_cap: int | None = None, d_cap: int | None = None
):
    """Full pairwise ``Map<K, MVReg>`` merge (`map.rs:192-269`) — the
    recursive reset-remove composition path, bit-exact with
    :func:`crdt_tpu.ops.map_ops.merge` under an ``MVRegKernel`` including
    output slot order (keys ascending, value antichain self-then-other).

    ``state`` = ``(clock[N,A], keys i32[N,K], eclocks[N,K,A],
    mv_clocks[N,K,V,A], mv_vals[N,K,V], d_keys i32[N,D], d_clocks[N,D,A])``.
    Returns ``(state, overflow)`` with one overflow flag per object (key /
    deferred / value-capacity, matching the jnp kernel's single flag)."""
    A = _map_state(*state_a)
    B = _map_state(*state_b)
    dt = _check_counters(A[0], B[0], A[2], B[2], A[3], B[3], A[4], B[4], A[6], B[6])
    if any(x.shape != y.shape for x, y in zip(A, B)):
        raise ValueError(
            f"map_mvreg_merge: side shapes differ: "
            f"{[x.shape for x in A]} vs {[y.shape for y in B]}"
        )
    # intra-state shape relations — the C kernel indexes with raw pointer
    # arithmetic, so a K/V/D mismatch between arrays would read out of
    # bounds rather than fail
    clk, keys_, ec, mvc, mvv, dk_, dc_ = A
    lead_, a_ = clk.shape[:-1], clk.shape[-1]
    k_ = keys_.shape[-1]
    if (
        keys_.shape != (*lead_, k_)
        or ec.shape != (*lead_, k_, a_)
        or mvc.shape[:-2] != (*lead_, k_)
        or mvc.shape[-1] != a_
        or mvv.shape != mvc.shape[:-1]
        or dk_.shape[:-1] != lead_
        or dc_.shape != (*dk_.shape, a_)
    ):
        raise ValueError(
            "map_mvreg_merge: inconsistent state shapes: "
            f"{[x.shape for x in A]}"
        )
    *lead, a = A[0].shape
    n = int(np.prod(lead, dtype=np.int64)) if lead else 1
    k = A[1].shape[-1]
    v_cap = A[3].shape[-2]
    d = A[5].shape[-1]
    k_cap = k if k_cap is None else k_cap
    d_cap = d if d_cap is None else d_cap

    clock = np.empty((*lead, a), dtype=dt)
    keys = np.empty((*lead, k_cap), dtype=np.int32)
    eclocks = np.empty((*lead, k_cap, a), dtype=dt)
    mv_clocks = np.empty((*lead, k_cap, v_cap, a), dtype=dt)
    mv_vals = np.empty((*lead, k_cap, v_cap), dtype=dt)
    d_keys = np.empty((*lead, d_cap), dtype=np.int32)
    d_clocks = np.empty((*lead, d_cap, a), dtype=dt)
    overflow = np.empty(n, dtype=np.uint8)
    _fn("map_mvreg_merge", dt)(
        _ptr(A[0]), _ptr(A[1]), _ptr(A[2]), _ptr(A[3]), _ptr(A[4]),
        _ptr(A[5]), _ptr(A[6]),
        _ptr(B[0]), _ptr(B[1]), _ptr(B[2]), _ptr(B[3]), _ptr(B[4]),
        _ptr(B[5]), _ptr(B[6]),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(k),
        ctypes.c_int64(v_cap), ctypes.c_int64(d), ctypes.c_int64(k_cap),
        ctypes.c_int64(d_cap),
        _ptr(clock), _ptr(keys), _ptr(eclocks), _ptr(mv_clocks),
        _ptr(mv_vals), _ptr(d_keys), _ptr(d_clocks), _ptr(overflow),
    )
    return (
        (clock, keys, eclocks, mv_clocks, mv_vals, d_keys, d_clocks),
        overflow.astype(bool).reshape(lead),
    )


# -- bulk wire ingest --------------------------------------------------------


def orswot_ingest_wire(buf, offsets, a: int, m: int, d: int, dtype, out=None):
    """Parallel wire-format decode of ``n`` concatenated ORSWOT blobs
    (`crdt_tpu/native/wire_ingest.cpp`) straight into dense planes.

    ``buf``: uint8 array of the concatenated serde blobs; ``offsets``:
    int64[n+1] blob boundaries.  Identity interning is assumed (the
    caller — ``OrswotBatch.from_wire`` — guarantees an identity
    universe): actor index == actor value (< ``a``), member id == member
    value (int32).

    ``out``: optional preallocated ``(clock, ids, dots, d_ids,
    d_clocks)`` 5-tuple to decode into (same shapes/dtypes the call
    would otherwise allocate).  The C parser then clears each object's
    rows itself before writing, so buffers may be REUSED across calls —
    which is the point: a fresh ~plane-set allocation per call
    page-faults GBs of zeroed memory and measured a 27x ingest collapse
    at north-star chunk scale (the pipelined wire loop's staging buffers
    exist to amortize exactly this; see docs/GUIDE.md).

    Returns ``(clock, ids, dots, d_ids, d_clocks, status)`` where
    ``status`` is uint8[n]: 0 ok, 1 fast-path fallback (blob structure
    outside the integer-keyed grammar — decode it in Python), 2 member
    overflow, 3 deferred overflow, 4 actor out of range.  Rows with
    nonzero status are left empty."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(dtype)
    planes = _orswot_out(n, a, m, d, dt, out)
    status = np.zeros(n, dtype=np.uint8)
    _count_native("orswot_ingest_wire", n)
    fn = _fn("orswot_ingest_wire", dt)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n),
        ctypes.c_int64(a), ctypes.c_int64(m), ctypes.c_int64(d),
        *(_ptr(p) for p in planes), _ptr(status),
        ctypes.c_int64(0 if out is None else 1),
    )
    return (*planes, status)


def _orswot_out(n: int, a: int, m: int, d: int, dt, out) -> tuple:
    """The plane 5-tuple a parse writes into: fresh empty planes, or
    ``out`` checked for shape, dtype and C order."""
    if out is None:
        return (
            np.zeros((n, a), dtype=dt),
            np.full((n, m), -1, dtype=np.int32),
            np.zeros((n, m, a), dtype=dt),
            np.full((n, d), -1, dtype=np.int32),
            np.zeros((n, d, a), dtype=dt),
        )
    expect = (
        ((n, a), dt), ((n, m), np.dtype(np.int32)),
        ((n, m, a), dt), ((n, d), np.dtype(np.int32)),
        ((n, d, a), dt),
    )
    for name, buf_, (shape, dtype_) in zip(
        ("clock", "ids", "dots", "d_ids", "d_clocks"), out, expect,
    ):
        if (not isinstance(buf_, np.ndarray) or buf_.shape != shape
                or buf_.dtype != dtype_
                or not buf_.flags.c_contiguous):
            raise ValueError(
                f"out[{name}]: need C-contiguous {dtype_}{shape}, got "
                f"{getattr(buf_, 'dtype', type(buf_))}"
                f"{getattr(buf_, 'shape', '')}"
            )
    return tuple(out)


def orswot_encode_wire(clock, ids, dots, d_ids, d_clocks):
    """Parallel wire-format ENCODE of dense planes into serde blobs —
    the inverse of :func:`orswot_ingest_wire`, byte-identical to
    ``to_binary`` of the per-object scalar states (identity universes).

    Returns ``(buf, offsets)``: concatenated blobs + int64[n+1]
    boundaries (blob i is ``buf[offsets[i]:offsets[i+1]]``)."""
    clock, ids, dots, d_ids, d_clocks = _contig(
        clock, ids, dots, d_ids, d_clocks
    )
    dt = _check_counters(clock, dots, d_clocks)
    n, a = clock.shape
    m = ids.shape[-1]
    d = d_ids.shape[-1]
    offsets = np.zeros(n + 1, dtype=np.int64)
    _count_native("orswot_encode_wire", n)
    fn = _fn("orswot_encode_wire", dt)
    fn(
        _ptr(clock), _ptr(ids), _ptr(dots), _ptr(d_ids), _ptr(d_clocks),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(m),
        ctypes.c_int64(d), _ptr(offsets), None,
    )
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(
        _ptr(clock), _ptr(ids), _ptr(dots), _ptr(d_ids), _ptr(d_clocks),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(m),
        ctypes.c_int64(d), _ptr(offsets), _ptr(buf),
    )
    return buf, offsets


def orswot_encode_wire_rows(clock, ids, dots, d_ids, d_clocks, rows):
    """Indexed wire ENCODE (native ABI v10): serialize only the fleet
    rows named by ``rows`` (int64 indices), straight from the full dense
    planes — the delta anti-entropy gather path
    (:mod:`crdt_tpu.sync.delta`).  Byte-identical to gathering the rows
    into compact planes and calling :func:`orswot_encode_wire`, without
    the gather copy.

    Returns ``(buf, offsets)``: concatenated blobs + int64[k+1]
    boundaries, in ``rows`` order."""
    clock, ids, dots, d_ids, d_clocks = _contig(
        clock, ids, dots, d_ids, d_clocks
    )
    dt = _check_counters(clock, dots, d_clocks)
    n, a = clock.shape
    m = ids.shape[-1]
    d = d_ids.shape[-1]
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    if rows.size and (rows.min() < 0 or rows.max() >= n):
        raise ValueError(
            f"orswot_encode_wire_rows: row indices must lie in [0, {n}); "
            f"got [{int(rows.min())}, {int(rows.max())}]"
        )
    k = rows.shape[0]
    offsets = np.zeros(k + 1, dtype=np.int64)
    _count_native("orswot_encode_wire_rows", k)
    fn = _fn("orswot_encode_wire_rows", dt)
    args = (
        _ptr(clock), _ptr(ids), _ptr(dots), _ptr(d_ids), _ptr(d_clocks),
        _ptr(rows), ctypes.c_int64(k), ctypes.c_int64(a),
        ctypes.c_int64(m), ctypes.c_int64(d),
    )
    fn(*args, _ptr(offsets), None)
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(*args, _ptr(offsets), _ptr(buf))
    return buf, offsets


# -- named ORSWOT codec (str / bytes actors and members) ----------------------


class NameTable:
    """One registry's native name table (`wire_ingest.cpp` ``NameTable``):
    the encoded serde bytes of every interned name, in id order, with a
    hash index over them.  Append-only; parses and encodes read it under
    a shared lock while an append takes it alone.  ``capacity`` bounds
    the ids a parse may hand out."""

    def __init__(self, capacity: int):
        lib = loader.load()
        lib.names_new.restype = ctypes.c_void_p
        lib.names_new.argtypes = [ctypes.c_int64]
        lib.names_free.argtypes = [ctypes.c_void_p]
        for name in ("names_count", "names_append", "names_span",
                     "names_read"):
            getattr(lib, name).restype = ctypes.c_int64
        self._lib = lib
        self.handle = ctypes.c_void_p(lib.names_new(capacity))

    def __del__(self):
        handle = getattr(self, "handle", None)
        if handle:
            self._lib.names_free(handle)
            self.handle = None

    def __len__(self) -> int:
        return int(self._lib.names_count(self.handle))

    def append(self, encoded: list[bytes]) -> int:
        """Append names given as their encoded serde bytes, in order;
        returns the new count."""
        from ..batch.wirebulk import concat_blobs

        buf, offsets = concat_blobs(encoded)
        buf = np.frombuffer(buf, dtype=np.uint8)
        return int(self._lib.names_append(
            self.handle, _ptr(buf), _ptr(offsets),
            ctypes.c_int64(len(encoded))))

    def read(self, start: int, end: int) -> list[bytes]:
        """The encoded bytes of names ``[start, end)``."""
        from ..batch.wirebulk import slice_blobs

        size = self._lib.names_span(self.handle, ctypes.c_int64(start),
                                    ctypes.c_int64(end))
        buf = np.empty(size, dtype=np.uint8)
        offsets = np.empty(end - start + 1, dtype=np.int64)
        self._lib.names_read(self.handle, ctypes.c_int64(start),
                             ctypes.c_int64(end), _ptr(buf), _ptr(offsets))
        return slice_blobs(buf.tobytes(), offsets)


def orswot_ingest_named(buf, offsets, a: int, m: int, d: int, dtype,
                        actors: NameTable, members: NameTable, out=None):
    """The parallel pass of the named wire decode: like
    :func:`orswot_ingest_wire`, with every actor and member key a str or
    bytes name looked up in ``actors`` / ``members`` (the row's actor
    column and member id are the names' ids).  Rows are always cleared
    first, so ``out`` may be reused.  Status 5 marks a blob holding a
    name neither table has yet: :func:`orswot_intern_named` takes it."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(dtype)
    planes = _orswot_out(n, a, m, d, dt, out)
    status = np.zeros(n, dtype=np.uint8)
    _count_native("orswot_ingest_named", n)
    fn = _fn("orswot_ingest_named", dt)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n),
        ctypes.c_int64(a), ctypes.c_int64(m), ctypes.c_int64(d),
        actors.handle, members.handle,
        *(_ptr(p) for p in planes), _ptr(status),
    )
    return (*planes, status)


def orswot_intern_named(buf, offsets, idx, planes, status,
                        actors: NameTable, members: NameTable) -> int:
    """The serial pass of the named decode: re-parse blobs ``idx``
    (ascending) into their rows of ``planes``, appending each unseen name
    to its table where it is met, and update ``status``.  Stops after the
    first blob whose status comes out 1 (outside the grammar); returns
    how many of ``idx`` it took."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    clock, ids = planes[0], planes[1]
    dt = np.dtype(clock.dtype)
    fn = _fn("orswot_intern_named", dt)
    fn.restype = ctypes.c_int64
    return int(fn(
        _ptr(buf), _ptr(offsets), _ptr(idx), ctypes.c_int64(idx.shape[0]),
        ctypes.c_int64(clock.shape[1]), ctypes.c_int64(ids.shape[1]),
        ctypes.c_int64(planes[3].shape[1]), actors.handle, members.handle,
        *(_ptr(p) for p in planes), _ptr(status),
    ))


def orswot_ingest_cells(buf, offsets, a: int, m: int, d: int, ids, d_ids,
                        cell_idx, cell_val, status, tables=None) -> int:
    """Parallel wire decode of ``n`` concatenated ORSWOT blobs into
    compact cells: the member and deferred ids as dense int32 rows
    (``ids[n, m]``, ``d_ids[n, d]``, overwritten whole) and each nonzero
    counter as a ``(cell_idx, cell_val)`` pair, its flat index into the
    plane-major space ``[clock n*a | dots n*m*a | d_clocks n*d*a]``.
    The same parse, statuses and empty rows as
    :func:`orswot_ingest_wire` (``tables``: the ``(actors, members)``
    :class:`NameTable` pair of a named universe, as
    :func:`orswot_ingest_named`'s parallel pass; None for identity
    keys).

    Returns the number of cells.  When it exceeds ``cell_idx``'s length
    the cell columns are incomplete: grow them and call again."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(cell_val.dtype)
    for name, arr, shape, want in (
        ("ids", ids, (n, m), np.int32), ("d_ids", d_ids, (n, d), np.int32),
        ("cell_idx", cell_idx, cell_val.shape, np.int32),
        ("status", status, (n,), np.uint8),
    ):
        if (arr.shape != shape or arr.dtype != want
                or not arr.flags.c_contiguous):
            raise ValueError(f"{name}: need C-contiguous "
                             f"{np.dtype(want)}{shape}, got "
                             f"{arr.dtype}{arr.shape}")
    if not cell_val.flags.c_contiguous or cell_val.ndim != 1:
        raise ValueError("cell_val: need a C-contiguous vector")
    if n * (1 + m + d) * a > np.iinfo(np.int32).max:
        raise ValueError(f"{n} objects of {(1 + m + d) * a} cells exceed "
                         "the int32 flat index")
    actors, members = (None, None) if tables is None else \
        (tables[0].handle, tables[1].handle)
    _count_native("orswot_ingest_cells", n)
    fn = _fn("orswot_ingest_cells", dt)
    fn.restype = ctypes.c_int64
    return int(fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n),
        ctypes.c_int64(a), ctypes.c_int64(m), ctypes.c_int64(d),
        actors, members, _ptr(ids), _ptr(d_ids), _ptr(cell_idx),
        _ptr(cell_val), ctypes.c_int64(cell_val.shape[0]), _ptr(status),
    ))


def orswot_encode_named(clock, ids, dots, d_ids, d_clocks,
                        actors: NameTable, members: NameTable, repr_rank):
    """Parallel wire ENCODE of dense planes with names for keys —
    byte-identical to ``to_binary`` of the per-object scalar states of a
    universe whose names the two tables hold.  ``repr_rank``: int32[A],
    each actor column's rank by ``repr`` of its name (the ClockKey pair
    order of deferred removes).

    Returns ``(buf, offsets)``, or None when a row holds an actor column
    or member id without a name (nothing is encoded then)."""
    clock, ids, dots, d_ids, d_clocks = _contig(
        clock, ids, dots, d_ids, d_clocks
    )
    dt = _check_counters(clock, dots, d_clocks)
    n, a = clock.shape
    m = ids.shape[-1]
    d = d_ids.shape[-1]
    repr_rank = np.ascontiguousarray(repr_rank, dtype=np.int32)
    offsets = np.zeros(n + 1, dtype=np.int64)
    _count_native("orswot_encode_named", n)
    fn = _fn("orswot_encode_named", dt)
    fn.restype = ctypes.c_int64
    args = (
        _ptr(clock), _ptr(ids), _ptr(dots), _ptr(d_ids), _ptr(d_clocks),
        ctypes.c_int64(n), ctypes.c_int64(a), ctypes.c_int64(m),
        ctypes.c_int64(d), actors.handle, members.handle, _ptr(repr_rank),
    )
    if fn(*args, _ptr(offsets), None):
        return None
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(*args, _ptr(offsets), _ptr(buf))
    return buf, offsets


def mvreg_ingest_wire(buf, offsets, k: int, a: int, dtype):
    """Parallel MVReg wire decode (see :func:`orswot_ingest_wire` for the
    buffer/status conventions).  Returns ``(clocks, vals, status)``."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(dtype)
    clocks = np.zeros((n, k, a), dtype=dt)
    vals = np.zeros((n, k), dtype=dt)
    status = np.zeros(n, dtype=np.uint8)
    fn = _fn("mvreg_ingest_wire", dt)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n),
        ctypes.c_int64(k), ctypes.c_int64(a),
        _ptr(clocks), _ptr(vals), _ptr(status),
    )
    return clocks, vals, status


def mvreg_encode_wire(clocks, vals):
    """Parallel MVReg wire encode — byte-identical to ``to_binary`` of
    the scalars (identity universes).  Returns ``(buf, offsets)``."""
    clocks, vals = _contig(clocks, vals)
    dt = _check_counters(clocks, vals)
    n, k, a = clocks.shape
    offsets = np.zeros(n + 1, dtype=np.int64)
    fn = _fn("mvreg_encode_wire", dt)
    fn(
        _ptr(clocks), _ptr(vals), ctypes.c_int64(n),
        ctypes.c_int64(k), ctypes.c_int64(a), _ptr(offsets), None,
    )
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(
        _ptr(clocks), _ptr(vals), ctypes.c_int64(n),
        ctypes.c_int64(k), ctypes.c_int64(a), _ptr(offsets), _ptr(buf),
    )
    return buf, offsets


def lww_ingest_wire(buf, offsets):
    """Parallel LWWReg wire decode.  Returns ``(vals, markers, status)``
    (both u64 — markers are timestamps, `lwwreg.rs:16-24`; callers in a
    narrower counter mode must use the Python path, see
    LWWRegBatch.from_wire)."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    vals = np.zeros(n, dtype=np.uint64)
    markers = np.zeros(n, dtype=np.uint64)
    status = np.zeros(n, dtype=np.uint8)
    fn = _fn("lww_ingest_wire", np.uint64)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n),
        _ptr(vals), _ptr(markers), _ptr(status),
    )
    return vals, markers, status


def lww_encode_wire(vals, markers):
    """Parallel LWWReg wire encode.  Returns ``(buf, offsets)``.

    u64 planes only — the C symbol has no u32 instantiation (markers are
    timestamps); narrower planes must take the Python path."""
    vals, markers = _contig(vals, markers)
    dt = _check_counters(vals, markers)
    if dt != np.dtype(np.uint64):
        raise TypeError(f"lww_encode_wire requires uint64 planes, got {dt}")
    n = vals.shape[0]
    offsets = np.zeros(n + 1, dtype=np.int64)
    fn = _fn("lww_encode_wire", np.uint64)
    fn(
        _ptr(vals), _ptr(markers), ctypes.c_int64(n), _ptr(offsets), None,
    )
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(
        _ptr(vals), _ptr(markers), ctypes.c_int64(n), _ptr(offsets), _ptr(buf),
    )
    return buf, offsets


def _fn_raw(name: str) -> "ctypes._CFuncPtr":
    """A dtype-independent C symbol (no u32/u64 suffix — e.g. the GSet
    bitmap codec, whose planes are bool)."""
    lib = loader.load()
    fn = getattr(lib, name, None)
    if fn is None:
        raise AttributeError(f"native library lacks symbol {name}")
    return fn


def gset_ingest_wire(buf, offsets, u: int):
    """Parallel GSet wire decode into the bool membership bitmap.
    Returns ``(bits, status)``; status 2 = member id >= bitmap width."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    # bool_ shares uint8's layout; the C side writes 0/1 bytes, so no
    # post-hoc astype copy of the (n, U) plane is needed
    bits = np.zeros((n, u), dtype=np.bool_)
    status = np.zeros(n, dtype=np.uint8)
    fn = _fn_raw("gset_ingest_wire")
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n), ctypes.c_int64(u),
        _ptr(bits), _ptr(status),
    )
    return bits, status


def gset_encode_wire(bits):
    """Parallel GSet wire encode (sorted-items order reproduced).
    Returns ``(buf, offsets)``."""
    bits = np.ascontiguousarray(np.asarray(bits, dtype=np.uint8))
    n, u = bits.shape
    offsets = np.zeros(n + 1, dtype=np.int64)
    fn = _fn_raw("gset_encode_wire")
    fn(
        _ptr(bits), ctypes.c_int64(n), ctypes.c_int64(u), _ptr(offsets), None,
    )
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(
        _ptr(bits), ctypes.c_int64(n), ctypes.c_int64(u), _ptr(offsets),
        _ptr(buf),
    )
    return buf, offsets


# -- clock-shaped wire codecs (VClock / GCounter / PNCounter) ----------------
# (tag constants live in crdt_tpu/batch/wirebulk.py, the single Python
# source; callers pass them through)


def clockish_ingest_wire(buf, offsets, tag: int, a: int, dtype):
    """Parallel decode of pure-clock-body wire blobs (``0x20`` VClock /
    ``0x22`` GCounter — `gcounter.rs:26-28`: a GCounter IS a VClock) into
    dense ``[N, A]`` planes.  Returns ``(clocks, status)``; status codes
    as the other legs (1 fallback, 4 actor out of range)."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(dtype)
    clocks = np.zeros((n, a), dtype=dt)
    status = np.zeros(n, dtype=np.uint8)
    fn = _fn("clockish_ingest_wire", dt)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n), ctypes.c_int64(tag),
        ctypes.c_int64(a), _ptr(clocks), _ptr(status),
    )
    return clocks, status


def clockish_encode_wire(clocks, tag: int):
    """Parallel encode of dense ``[N, A]`` clock planes to wire blobs
    under the given tag — byte-identical to ``to_binary`` of the scalars
    (identity universes).  Returns ``(buf, offsets)``."""
    (clocks,) = _contig(clocks)
    dt = _check_counters(clocks)
    n, a = clocks.shape
    offsets = np.zeros(n + 1, dtype=np.int64)
    fn = _fn("clockish_encode_wire", dt)
    fn(
        _ptr(clocks), ctypes.c_int64(n), ctypes.c_int64(tag),
        ctypes.c_int64(a), _ptr(offsets), None,
    )
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(
        _ptr(clocks), ctypes.c_int64(n), ctypes.c_int64(tag),
        ctypes.c_int64(a), _ptr(offsets), _ptr(buf),
    )
    return buf, offsets


def pncounter_ingest_wire(buf, offsets, a: int, dtype):
    """Parallel PNCounter wire decode into stacked ``[N, 2, A]`` planes
    (P = plane 0, `pncounter.rs:33-36`).  Returns ``(planes, status)``."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(dtype)
    planes = np.zeros((n, 2, a), dtype=dt)
    status = np.zeros(n, dtype=np.uint8)
    fn = _fn("pncounter_ingest_wire", dt)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n), ctypes.c_int64(a),
        _ptr(planes), _ptr(status),
    )
    return planes, status


def pncounter_encode_wire(planes):
    """Parallel PNCounter wire encode from ``[N, 2, A]`` planes.
    Returns ``(buf, offsets)``."""
    (planes,) = _contig(planes)
    dt = _check_counters(planes)
    n, two, a = planes.shape
    if two != 2:
        raise ValueError(f"PNCounter planes must be [N, 2, A], got {planes.shape}")
    offsets = np.zeros(n + 1, dtype=np.int64)
    fn = _fn("pncounter_encode_wire", dt)
    fn(
        _ptr(planes), ctypes.c_int64(n), ctypes.c_int64(a), _ptr(offsets),
        None,
    )
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(
        _ptr(planes), ctypes.c_int64(n), ctypes.c_int64(a), _ptr(offsets),
        _ptr(buf),
    )
    return buf, offsets


# -- Map<K, MVReg> wire codec ------------------------------------------------


def map_mvreg_ingest_wire(buf, offsets, a: int, k: int, d: int, kv: int, dtype):
    """Parallel Map<K, MVReg> wire decode into the dense Map planes.
    Returns ``(clock, keys, eclocks, vclocks, vvals, d_keys, d_clocks,
    status)``; status 5 = value antichain wider than ``kv``."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(dtype)
    clock = np.zeros((n, a), dtype=dt)
    keys = np.full((n, k), -1, dtype=np.int32)
    eclocks = np.zeros((n, k, a), dtype=dt)
    vclocks = np.zeros((n, k, kv, a), dtype=dt)
    vvals = np.zeros((n, k, kv), dtype=dt)
    d_keys = np.full((n, d), -1, dtype=np.int32)
    d_clocks = np.zeros((n, d, a), dtype=dt)
    status = np.zeros(n, dtype=np.uint8)
    fn = _fn("map_mvreg_ingest_wire", dt)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n), ctypes.c_int64(a),
        ctypes.c_int64(k), ctypes.c_int64(d), ctypes.c_int64(kv),
        _ptr(clock), _ptr(keys), _ptr(eclocks), _ptr(vclocks), _ptr(vvals),
        _ptr(d_keys), _ptr(d_clocks), _ptr(status),
    )
    return clock, keys, eclocks, vclocks, vvals, d_keys, d_clocks, status


def map_mvreg_encode_wire(clock, keys, eclocks, vclocks, vvals, d_keys,
                          d_clocks):
    """Parallel Map<K, MVReg> wire encode — byte-identical to
    ``to_binary`` of the scalars (identity universes).
    Returns ``(buf, offsets)``."""
    clock, keys, eclocks, vclocks, vvals, d_keys, d_clocks = _contig(
        clock, keys, eclocks, vclocks, vvals, d_keys, d_clocks
    )
    dt = _check_counters(clock, eclocks, vclocks, vvals, d_clocks)
    n, a = clock.shape
    k = keys.shape[1]
    d = d_keys.shape[1]
    kv = vvals.shape[2]
    offsets = np.zeros(n + 1, dtype=np.int64)
    fn = _fn("map_mvreg_encode_wire", dt)
    args = (
        _ptr(clock), _ptr(keys), _ptr(eclocks), _ptr(vclocks), _ptr(vvals),
        _ptr(d_keys), _ptr(d_clocks), ctypes.c_int64(n), ctypes.c_int64(a),
        ctypes.c_int64(k), ctypes.c_int64(d), ctypes.c_int64(kv),
    )
    fn(*args, _ptr(offsets), None)
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(*args, _ptr(offsets), _ptr(buf))
    return buf, offsets


def map_orswot_ingest_wire(buf, offsets, a: int, k: int, d: int, mv: int,
                           dv: int, dtype):
    """Parallel Map<K, Orswot> wire decode.  Returns ``(clock, keys,
    eclocks, vclock, vids, vdots, vdids, vdclocks, d_keys, d_clocks,
    status)``; status 5 = a value's member/deferred table overflow."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(dtype)
    clock = np.zeros((n, a), dtype=dt)
    keys = np.full((n, k), -1, dtype=np.int32)
    eclocks = np.zeros((n, k, a), dtype=dt)
    vclock = np.zeros((n, k, a), dtype=dt)
    vids = np.full((n, k, mv), -1, dtype=np.int32)
    vdots = np.zeros((n, k, mv, a), dtype=dt)
    vdids = np.full((n, k, dv), -1, dtype=np.int32)
    vdclocks = np.zeros((n, k, dv, a), dtype=dt)
    d_keys = np.full((n, d), -1, dtype=np.int32)
    d_clocks = np.zeros((n, d, a), dtype=dt)
    status = np.zeros(n, dtype=np.uint8)
    fn = _fn("map_orswot_ingest_wire", dt)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n), ctypes.c_int64(a),
        ctypes.c_int64(k), ctypes.c_int64(d), ctypes.c_int64(mv),
        ctypes.c_int64(dv), _ptr(clock), _ptr(keys), _ptr(eclocks),
        _ptr(vclock), _ptr(vids), _ptr(vdots), _ptr(vdids), _ptr(vdclocks),
        _ptr(d_keys), _ptr(d_clocks), _ptr(status),
    )
    return (clock, keys, eclocks, vclock, vids, vdots, vdids, vdclocks,
            d_keys, d_clocks, status)


def map_orswot_encode_wire(clock, keys, eclocks, vclock, vids, vdots, vdids,
                           vdclocks, d_keys, d_clocks):
    """Parallel Map<K, Orswot> wire encode — byte-identical to
    ``to_binary`` of the scalars (identity universes).
    Returns ``(buf, offsets)``."""
    planes = _contig(clock, keys, eclocks, vclock, vids, vdots, vdids,
                     vdclocks, d_keys, d_clocks)
    (clock, keys, eclocks, vclock, vids, vdots, vdids, vdclocks, d_keys,
     d_clocks) = planes
    dt = _check_counters(clock, eclocks, vclock, vdots, vdclocks, d_clocks)
    n, a = clock.shape
    k = keys.shape[1]
    d = d_keys.shape[1]
    mv = vids.shape[2]
    dv = vdids.shape[2]
    offsets = np.zeros(n + 1, dtype=np.int64)
    fn = _fn("map_orswot_encode_wire", dt)
    args = (
        _ptr(clock), _ptr(keys), _ptr(eclocks), _ptr(vclock), _ptr(vids),
        _ptr(vdots), _ptr(vdids), _ptr(vdclocks), _ptr(d_keys),
        _ptr(d_clocks), ctypes.c_int64(n), ctypes.c_int64(a),
        ctypes.c_int64(k), ctypes.c_int64(d), ctypes.c_int64(mv),
        ctypes.c_int64(dv),
    )
    fn(*args, _ptr(offsets), None)
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(*args, _ptr(offsets), _ptr(buf))
    return buf, offsets


# -- Map<K, Map<K2, MVReg>> wire codec (the reference's canonical
# nesting, `/root/reference/test/map.rs:8`) ---------------------------------


def map_map_mvreg_ingest_wire(buf, offsets, a: int, k: int, d: int, k2: int,
                              d2: int, kv: int, dtype):
    """Parallel nested-Map wire decode into the dense nested planes.
    Returns ``(clock, keys, eclocks, iclock, ikeys, ieclocks, vclocks,
    vvals, id_keys, id_clocks, d_keys, d_clocks, status)``; status 5 =
    any inner overflow (keys > k2, deferred > d2, antichain > kv)."""
    buf = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = offsets.shape[0] - 1
    dt = np.dtype(dtype)
    clock = np.zeros((n, a), dtype=dt)
    keys = np.full((n, k), -1, dtype=np.int32)
    eclocks = np.zeros((n, k, a), dtype=dt)
    iclock = np.zeros((n, k, a), dtype=dt)
    ikeys = np.full((n, k, k2), -1, dtype=np.int32)
    ieclocks = np.zeros((n, k, k2, a), dtype=dt)
    vclocks = np.zeros((n, k, k2, kv, a), dtype=dt)
    vvals = np.zeros((n, k, k2, kv), dtype=dt)
    id_keys = np.full((n, k, d2), -1, dtype=np.int32)
    id_clocks = np.zeros((n, k, d2, a), dtype=dt)
    d_keys = np.full((n, d), -1, dtype=np.int32)
    d_clocks = np.zeros((n, d, a), dtype=dt)
    status = np.zeros(n, dtype=np.uint8)
    fn = _fn("map_map_mvreg_ingest_wire", dt)
    fn.restype = ctypes.c_int64
    fn(
        _ptr(buf), _ptr(offsets), ctypes.c_int64(n), ctypes.c_int64(a),
        ctypes.c_int64(k), ctypes.c_int64(d), ctypes.c_int64(k2),
        ctypes.c_int64(d2), ctypes.c_int64(kv),
        _ptr(clock), _ptr(keys), _ptr(eclocks), _ptr(iclock), _ptr(ikeys),
        _ptr(ieclocks), _ptr(vclocks), _ptr(vvals), _ptr(id_keys),
        _ptr(id_clocks), _ptr(d_keys), _ptr(d_clocks), _ptr(status),
    )
    return (clock, keys, eclocks, iclock, ikeys, ieclocks, vclocks, vvals,
            id_keys, id_clocks, d_keys, d_clocks, status)


def map_map_mvreg_encode_wire(clock, keys, eclocks, iclock, ikeys, ieclocks,
                              vclocks, vvals, id_keys, id_clocks, d_keys,
                              d_clocks):
    """Parallel nested-Map wire encode — byte-identical to ``to_binary``
    of the scalars (identity universes).  Returns ``(buf, offsets)``."""
    planes = _contig(clock, keys, eclocks, iclock, ikeys, ieclocks, vclocks,
                     vvals, id_keys, id_clocks, d_keys, d_clocks)
    (clock, keys, eclocks, iclock, ikeys, ieclocks, vclocks, vvals, id_keys,
     id_clocks, d_keys, d_clocks) = planes
    dt = _check_counters(clock, eclocks, iclock, ieclocks, vclocks, vvals,
                         id_clocks, d_clocks)
    n, a = clock.shape
    k = keys.shape[1]
    d = d_keys.shape[1]
    k2 = ikeys.shape[2]
    d2 = id_keys.shape[2]
    kv = vvals.shape[3]
    offsets = np.zeros(n + 1, dtype=np.int64)
    fn = _fn("map_map_mvreg_encode_wire", dt)
    args = (
        _ptr(clock), _ptr(keys), _ptr(eclocks), _ptr(iclock), _ptr(ikeys),
        _ptr(ieclocks), _ptr(vclocks), _ptr(vvals), _ptr(id_keys),
        _ptr(id_clocks), _ptr(d_keys), _ptr(d_clocks), ctypes.c_int64(n),
        ctypes.c_int64(a), ctypes.c_int64(k), ctypes.c_int64(d),
        ctypes.c_int64(k2), ctypes.c_int64(d2), ctypes.c_int64(kv),
    )
    fn(*args, _ptr(offsets), None)
    np.cumsum(offsets, out=offsets)
    buf = np.empty(int(offsets[-1]), dtype=np.uint8)
    fn(*args, _ptr(offsets), _ptr(buf))
    return buf, offsets
