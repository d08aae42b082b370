"""Parity: the union-aligned fused fold vs the sequential jnp fold.

The jnp pairwise path (``orswot_ops``) is bit-exact against the scalar
engine (``tests/test_parity.py``), so equality here gives transitive
parity with the reference semantics
(`/root/reference/src/orswot.rs:89-156`).

Contract under test (module docstring of ``orswot_fold_aligned``): when
no overflow is flagged the outputs are bit-identical to the sequential
left fold + defer plunger; when the union outgrows ``u_cap`` the member
overflow flag must be set.  Fleets come from ``anti_entropy_fleets`` —
the bounded-union anti-entropy shape the fold is for — plus adversarial
deferred-heavy and degenerate cases.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from crdt_tpu.ops import orswot_fold_aligned, orswot_ops, orswot_pallas
from crdt_tpu.utils.testdata import anti_entropy_fleets


def _stack(reps):
    return tuple(jnp.stack([rep[i] for rep in reps]) for i in range(5))


def _jnp_fold(stacked, m_cap, d_cap, plunger=True):
    acc = tuple(x[0] for x in stacked)
    over = jnp.zeros(stacked[0].shape[1:-1] + (2,), bool)
    for i in range(1, stacked[0].shape[0]):
        out = orswot_ops.merge(*acc, *(x[i] for x in stacked), m_cap, d_cap)
        acc, over = out[:5], over | out[5]
    if plunger:
        out = orswot_ops.merge(*acc, *acc, m_cap, d_cap)
        acc, over = out[:5], over | out[5]
    return acc + (over,)


def _assert_same(ref, got):
    names = ("clock", "ids", "dots", "d_ids", "d_clocks", "overflow")
    for name, r, g in zip(names, ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g), err_msg=name)


def _fleet_stack(seed, n, a, m, d, r, **kw):
    rng = np.random.RandomState(seed)
    return _stack(anti_entropy_fleets(rng, n, a, m, d, r, **kw))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize(
    "shape",
    [
        # (n, a, m, d, r, base, novel) — union base + r*novel <= m
        (33, 8, 8, 2, 4, 3, 1),
        (17, 4, 12, 2, 5, 6, 1),
        (21, 16, 6, 2, 3, 3, 1),
    ],
)
def test_fold_parity_no_deferred(seed, shape):
    n, a, m, d, r, base, novel = shape
    stacked = _fleet_stack(seed, n, a, m, d, r, base=base, novel=novel)
    ref = _jnp_fold(stacked, m, d)
    got = orswot_fold_aligned.fold_merge(*stacked, m, d, interpret=True)
    assert not np.asarray(ref[5]).any()
    _assert_same(ref, got)


@pytest.mark.parametrize("seed", [3, 4])
@pytest.mark.parametrize("deferred_frac", [0.3, 1.0])
def test_fold_parity_with_deferred(seed, deferred_frac):
    n, a, m, d, r = 29, 8, 10, 2, 4
    stacked = _fleet_stack(
        seed, n, a, m, d, r, base=4, novel=1, deferred_frac=deferred_frac
    )
    ref = _jnp_fold(stacked, m, d)
    got = orswot_fold_aligned.fold_merge(*stacked, m, d, interpret=True)
    assert not np.asarray(ref[5]).any()
    _assert_same(ref, got)


def test_fold_parity_north_star_shape():
    """The exact BASELINE.md north-star config at miniature n."""
    stacked = _fleet_stack(
        5, 64, 64, 16, 2, 8, base=6, novel=1, deferred_frac=0.25
    )
    ref = _jnp_fold(stacked, 16, 2)
    got = orswot_fold_aligned.fold_merge(*stacked, 16, 2, u_cap=16, interpret=True)
    assert not np.asarray(ref[5]).any()
    _assert_same(ref, got)


def test_fold_no_plunger():
    stacked = _fleet_stack(6, 19, 8, 8, 2, 4, base=3, novel=1, deferred_frac=0.5)
    ref = _jnp_fold(stacked, 8, 2, plunger=False)
    got = orswot_fold_aligned.fold_merge(
        *stacked, 8, 2, interpret=True, plunger=False
    )
    _assert_same(ref, got)


def test_fold_not_multiple_of_tile():
    # n deliberately prime so the object axis needs padding
    stacked = _fleet_stack(7, 13, 4, 6, 2, 3, base=3, novel=1)
    ref = _jnp_fold(stacked, 6, 2)
    got = orswot_fold_aligned.fold_merge(*stacked, 6, 2, interpret=True)
    _assert_same(ref, got)


def test_union_overflow_flagged():
    """Disjoint member sets per replica: union = r * m members > u_cap
    must set the member-overflow flag (conservative contract)."""
    from crdt_tpu.utils.testdata import random_orswot_arrays

    rng = np.random.RandomState(8)
    n, a, m, d, r = 9, 4, 4, 2, 6
    reps = []
    for rep in range(r):
        clock, ids, dots, dids, dclocks = random_orswot_arrays(
            rng, n, a, m, d, np.uint32, min_live=m
        )
        # force disjoint id spaces per replica so the union is r*m
        ids = np.where(ids != -1, ids + (rep << 25), -1).astype(np.int32)
        reps.append((clock, ids, dots, dids, dclocks))
    stacked = _stack(reps)
    got = orswot_fold_aligned.fold_merge(
        *stacked, m, d, u_cap=8, interpret=True
    )
    # union is 24 distinct ids per object > u_cap=8
    assert np.asarray(got[5])[:, 0].all()


def test_r1_fold_is_plunger_only():
    stacked = _fleet_stack(9, 11, 4, 6, 2, 1, base=3, novel=1, deferred_frac=1.0)
    ref = _jnp_fold(stacked, 6, 2)
    got = orswot_fold_aligned.fold_merge(*stacked, 6, 2, interpret=True)
    _assert_same(ref, got)


def test_prebiased_roundtrip_and_salt_commute():
    """The bench hot path: pad + bias outside, fold in the kernel domain;
    XOR clock salting commutes with the bias."""
    m, d, r = 10, 2, 4
    stacked = _fleet_stack(10, 23, 8, m, d, r, base=4, novel=1, deferred_frac=0.3)
    ref = _jnp_fold(stacked, m, d)

    padded = orswot_fold_aligned.pad_to_tile(stacked, m, d, n_states=r + 1)
    biased = orswot_pallas.to_kernel_domain(padded)
    got = orswot_fold_aligned.fold_merge(
        *biased, m, d, interpret=True, prebiased=True
    )
    n = stacked[0].shape[1]
    unb = (
        orswot_pallas.from_kernel_domain(got[0], jnp.uint32)[:n],
        got[1][:n],
        orswot_pallas.from_kernel_domain(got[2], jnp.uint32)[:n],
        got[3][:n],
        orswot_pallas.from_kernel_domain(got[4], jnp.uint32)[:n],
        got[5][:n],
    )
    _assert_same(ref, unb)

    # salt the clock planes in both domains; outputs must agree
    salt = jnp.uint32(5)
    salted_ref = orswot_fold_aligned.fold_merge(
        *((stacked[0] ^ salt,) + stacked[1:]), m, d, interpret=True
    )
    biased_salted = (biased[0] ^ jnp.int32(5),) + biased[1:]
    salted_got = orswot_fold_aligned.fold_merge(
        *biased_salted, m, d, interpret=True, prebiased=True
    )
    unb_s = (
        orswot_pallas.from_kernel_domain(salted_got[0], jnp.uint32)[:n],
        salted_got[1][:n],
        orswot_pallas.from_kernel_domain(salted_got[2], jnp.uint32)[:n],
        salted_got[3][:n],
        orswot_pallas.from_kernel_domain(salted_got[4], jnp.uint32)[:n],
        salted_got[5][:n],
    )
    _assert_same(salted_ref, unb_s)


def test_u64_counters_rejected():
    stacked = _fleet_stack(11, 5, 4, 6, 2, 2, base=3, novel=1)
    as_u64 = (stacked[0].astype(jnp.uint64), stacked[1],
              stacked[2].astype(jnp.uint64), stacked[3],
              stacked[4].astype(jnp.uint64))
    with pytest.raises(TypeError):
        orswot_fold_aligned.fold_merge(*as_u64, 6, 2, interpret=True)


def test_full_uint32_counter_range_parity():
    """Counters spanning the sign boundary of the biased domain."""
    rng = np.random.RandomState(12)
    n, a, m, d, r = 17, 4, 8, 2, 4
    reps = anti_entropy_fleets(rng, n, a, m, d, r, base=4, novel=1)
    bumped = []
    for clock, ids, dots, dids, dclocks in reps:
        hi = dots.astype(np.uint64) * np.uint64(42949672)  # spread to 2^32
        dots = np.minimum(hi, np.uint64(0xFFFF_FFFF)).astype(np.uint32)
        clock = dots.max(axis=1)
        bumped.append((clock, ids, dots, dids, dclocks))
    stacked = _stack(bumped)
    ref = _jnp_fold(stacked, m, d)
    got = orswot_fold_aligned.fold_merge(*stacked, m, d, interpret=True)
    assert not np.asarray(ref[5]).any()
    _assert_same(ref, got)


@pytest.mark.parametrize("impl", ["rank", "pallas"])
def test_ops_fold_merge_dispatch_parity(impl):
    """The first-class ``orswot_ops.fold_merge`` API: every impl choice
    produces the sequential left fold + plunger bit-exactly (the pallas
    choice dispatches the union-aligned fused kernel)."""
    stacked = _fleet_stack(20, 23, 8, 8, 2, 4, base=3, novel=1,
                           deferred_frac=0.4)
    ref = _jnp_fold(stacked, 8, 2)
    got = orswot_ops.fold_merge(*stacked, 8, 2, impl=impl)
    _assert_same(ref, got)


def test_ops_fold_merge_pallas_u64_degrades_to_sequential():
    """u64 planes are ineligible for the fused kernel: a pallas request
    must still produce the fold (via the sequential pairwise path)."""
    stacked = _fleet_stack(21, 9, 4, 6, 2, 3, base=3, novel=1)
    as_u64 = (stacked[0].astype(jnp.uint64), stacked[1],
              stacked[2].astype(jnp.uint64), stacked[3],
              stacked[4].astype(jnp.uint64))
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the documented u64 fast-path warning
        ref = _jnp_fold(as_u64, 6, 2)
        got = orswot_ops.fold_merge(*as_u64, 6, 2, impl="pallas")
    _assert_same(ref, got)


def test_north_star_traffic_budget():
    """VERDICT r4 item 1's traffic model, pinned: <= 8 KB of HBM bytes
    per merge at the north-star shapes, computed from the kernel's
    ACTUAL padded argument/output arrays (what the pallas_call's
    BlockSpecs stream — the kernel holds the whole tile working set in
    VMEM, so arguments + outputs ARE the HBM traffic; an intermediate
    spill would surface in the AOT memory plan, which the fold_aligned_ns
    target reports).  Also pins the documented
    pallas_aligned_fold bytes/merge constant against drift."""
    # union-aligned fold: each replica state read once + one output
    # write per object — (r+1)/r states/merge at the north-star shapes
    # (A=64, M=16, D=2, u32: 4936 B/state, r=8) ≈ 5.55 KB/merge
    bytes_per_merge = 5550.0

    n, a, m, d, r = 512, 64, 16, 2, 8  # north-star shapes at reduced n
    stacked = _fleet_stack(30, n, a, m, d, r, base=6, novel=1)
    padded = orswot_fold_aligned.pad_to_tile(
        stacked, 16, 2, n_states=r + 1, u_cap=16
    )
    n_pad = padded[0].shape[1]
    in_bytes = sum(np.asarray(x).nbytes for x in padded)
    out = orswot_fold_aligned.fold_merge(
        *padded, 16, 2, u_cap=16, interpret=True
    )
    # overflow plane is int32 on-kernel; count the kernel-side widths
    out_bytes = sum(np.asarray(x).nbytes for x in out[:5]) + n_pad * 2 * 4
    per_merge = (in_bytes + out_bytes) / (n_pad * r)
    assert per_merge <= 8_192, per_merge
    assert abs(per_merge - bytes_per_merge) / bytes_per_merge < 0.02, \
        per_merge
