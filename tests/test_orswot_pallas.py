"""Parity: fused Pallas ORSWOT kernels vs the jnp path.

The jnp path (``orswot_ops``) is itself bit-exact against the scalar engine
(``tests/test_parity.py``), so equality here gives transitive parity with
the reference semantics (`/root/reference/src/orswot.rs:89-156`).

Kernels run in Pallas interpret mode on the CPU test mesh.  Compiles for
a described v5e are pinned by ``tests/test_chip_compile.py``; execution
on the chip is not covered yet.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from crdt_tpu.ops import orswot_ops, orswot_pallas
from crdt_tpu.utils.testdata import random_orswot_arrays


def _pair(rng, n, a, m, d):
    lhs = tuple(jnp.asarray(x) for x in random_orswot_arrays(rng, n, a, m, d, np.uint32))
    rhs = tuple(jnp.asarray(x) for x in random_orswot_arrays(rng, n, a, m, d, np.uint32))
    return lhs, rhs


def _assert_same(ref, got):
    names = ("clock", "ids", "dots", "d_ids", "d_clocks", "overflow")
    for name, r, g in zip(names, ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g), err_msg=name)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", [(17, 4, 3, 2), (33, 8, 4, 2)])
def test_pairwise_merge_parity(seed, shape):
    n, a, m, d = shape
    rng = np.random.RandomState(seed)
    lhs, rhs = _pair(rng, n, a, m, d)
    _assert_same(
        orswot_ops.merge(*lhs, *rhs, m, d),
        orswot_pallas.merge(*lhs, *rhs, m, d, interpret=True),
    )


def test_pairwise_merge_not_multiple_of_tile():
    # n deliberately prime so the object axis needs padding
    rng = np.random.RandomState(7)
    lhs, rhs = _pair(rng, 13, 4, 3, 2)
    _assert_same(
        orswot_ops.merge(*lhs, *rhs, 3, 2),
        orswot_pallas.merge(*lhs, *rhs, 3, 2, interpret=True),
    )


def test_fold_merge_matches_sequential_fold():
    rng = np.random.RandomState(3)
    n, a, m, d, r = 21, 8, 4, 2, 5
    reps = [
        tuple(jnp.asarray(x) for x in random_orswot_arrays(rng, n, a, m, d, np.uint32))
        for _ in range(r)
    ]
    stacked = tuple(jnp.stack([rep[i] for rep in reps]) for i in range(5))
    acc = tuple(x[0] for x in stacked)
    over = jnp.zeros((n, 2), bool)
    for i in range(1, r):
        out = orswot_ops.merge(*acc, *(x[i] for x in stacked), m, d)
        acc, over = out[:5], over | out[5]
    out = orswot_ops.merge(*acc, *acc, m, d)  # defer plunger
    acc, over = out[:5], over | out[5]
    got = orswot_pallas.fold_merge(*stacked, m, d, interpret=True)
    _assert_same(acc + (over,), got)

    # the pre-biased entry point: pad+bias once outside,
    # fold in the kernel domain, unbias once after — bit-equal
    padded = orswot_pallas.pad_to_tile(stacked, m, d, n_states=r + 1)
    biased = orswot_pallas.to_kernel_domain(padded)
    gb = orswot_pallas.fold_merge(
        *biased, m, d, interpret=True, prebiased=True
    )
    unb = (
        orswot_pallas.from_kernel_domain(gb[0], jnp.uint32)[:n],
        gb[1][:n],
        orswot_pallas.from_kernel_domain(gb[2], jnp.uint32)[:n],
        gb[3][:n],
        orswot_pallas.from_kernel_domain(gb[4], jnp.uint32)[:n],
        gb[5][:n],
    )
    _assert_same(acc + (over,), unb)


def test_overflow_flag_parity():
    # force member-capacity overflow: disjoint member sets, tiny m_cap
    rng = np.random.RandomState(4)
    n, a, m, d = 9, 4, 4, 2
    lhs, rhs = _pair(rng, n, a, m, d)
    ref = orswot_ops.merge(*lhs, *rhs, 2, d)
    got = orswot_pallas.merge(*lhs, *rhs, 2, d, interpret=True)
    _assert_same(ref, got)
    assert bool(np.asarray(ref[5]).any()), "fixture should overflow somewhere"


def test_u64_counters_rejected():
    rng = np.random.RandomState(5)
    lhs = tuple(
        jnp.asarray(x) for x in random_orswot_arrays(rng, 4, 4, 3, 2, np.uint64)
    )
    with pytest.raises(TypeError, match="32-bit"):
        orswot_pallas.merge(*lhs, *lhs, 3, 2, interpret=True)


def test_full_uint32_counter_range_parity():
    """Counters at and above 2**31 must merge bit-identically — the kernel
    works in a bias-mapped signed domain (x ^ 0x8000_0000) precisely so
    the full uint32 range stays exact (a plain int32 cast would wrap and
    silently corrupt the merge)."""
    rng = np.random.RandomState(6)
    n, a, m, d = 16, 4, 4, 2
    lhs, rhs = _pair(rng, n, a, m, d)

    def inflate(state):
        clock, ids, dots, dids, dclocks = state
        big = jnp.uint32(1 << 31)
        # preserve the 0 = absent-lane invariant while pushing every live
        # counter into the high half of the uint32 range
        up = lambda x: jnp.where(x > 0, x + big, x)
        return up(clock), ids, up(dots), dids, up(dclocks)

    lhs, rhs = inflate(lhs), inflate(rhs)
    ref = orswot_ops.merge(*lhs, *rhs, m, d)
    got = orswot_pallas.merge(*lhs, *rhs, m, d, interpret=True)
    _assert_same(ref, got)
    assert int(np.asarray(got[0]).max()) >= 1 << 31, "fixture must exercise the high half"


def test_salt_chain_commutes_with_bias():
    """A salt applied in the kernel's biased domain (the prebiased
    entry point): XOR commutes with the x^0x80000000 bias, so salting-then-biasing equals biasing-then-salting, and the
    biased-domain next_salt (max & 7 | 1) picks the same salt values."""
    rng = np.random.RandomState(7)
    n, a, m, d, r = 17, 8, 4, 2, 4
    reps = [
        tuple(jnp.asarray(x) for x in random_orswot_arrays(rng, n, a, m, d, np.uint32))
        for _ in range(r)
    ]
    stacked = tuple(jnp.stack([rep[i] for rep in reps]) for i in range(5))
    padded = orswot_pallas.pad_to_tile(stacked, m, d, n_states=r + 1)
    biased = orswot_pallas.to_kernel_domain(padded)

    salt = 5
    # unbiased domain: salt the clock plane, fold, read next_salt bits
    u_salted = (padded[0] ^ jnp.uint32(salt),) + padded[1:]
    u_out = orswot_pallas.fold_merge(*u_salted, m, d, interpret=True)[:5]
    u_next = int(jnp.max(u_out[2]) & jnp.uint32(7)) | 1

    # biased domain: same salt applied to the biased plane
    b_salted = (biased[0] ^ jnp.int32(salt),) + biased[1:]
    b_out = orswot_pallas.fold_merge(
        *b_salted, m, d, interpret=True, prebiased=True
    )[:5]
    b_next = int(jnp.max(b_out[2]).astype(jnp.int32) & jnp.int32(7)) | 1

    assert u_next == b_next, "next_salt must agree across domains"
    for k, (u, b) in enumerate(zip(u_out, b_out)):
        if k in (1, 3):  # id planes are unbiased in both
            assert jnp.array_equal(u, b), f"plane {k}"
        else:
            unb = orswot_pallas.from_kernel_domain(b, jnp.uint32)
            assert jnp.array_equal(u, unb), f"plane {k}"
