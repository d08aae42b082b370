"""Gather/sort-free ORSWOT merge — the TPU-default implementation.

The rank-select pipeline (:func:`crdt_tpu.ops.orswot_ops.merge`) leans on
``take_along_axis`` gathers and a counting-rank permutation.  This module
runs the same algebra as unrolled one-hot selects and max-reductions over
the small static slot axes — the style of the Pallas tile math
(:mod:`crdt_tpu.ops.orswot_pallas`), which XLA fuses into dense
elementwise passes.  It trades O(M) extra reads of the dot tables for
regularity: measured 17% slower on the memory-bound CPU backend.  It
is the **TPU default** on the strength of a layout A/B taken before
PR 1 on a capture path that no longer exists; the per-backend default
waits for a chip A/B (ROADMAP D5).

Semantics are `/root/reference/src/orswot.rs:89-156` throughout — the
rule-by-rule citations live in ``orswot_ops``/``orswot_pallas``; this
variant only changes execution layout, never the algebra.  Counters are
uint32 (the bias-to-int32 trick of the Pallas path — order-preserving
``x ^ 0x8000_0000``; exact, since the merge only compares/maxes/selects).
"""

from __future__ import annotations

from . import orswot_pallas as _op

EMPTY = _op.EMPTY
ZERO = _op.ZERO


def merge_unrolled(
    clock_a, ids_a, dots_a, dids_a, dclocks_a,
    clock_b, ids_b, dots_b, dids_b, dclocks_b,
    m_cap: int, d_cap: int,
):
    """Pairwise merge via the unrolled (gather/sort-free) tile math in the
    standard ``[N, ...]`` layout.  Drop-in for ``orswot_ops.merge``: it IS
    ``orswot_pallas._merge_tile`` run as plain jnp, so parity with the
    production merge is inherited from ``tests/test_orswot_pallas.py`` and
    re-asserted in ``tests/test_orswot_unrolled.py``."""
    _op._check_dtypes(clock_a)
    _op._check_dtypes(clock_b)
    cdt = clock_a.dtype
    sa = _op._to_kernel_dtype((clock_a, ids_a, dots_a, dids_a, dclocks_a))
    sb = _op._to_kernel_dtype((clock_b, ids_b, dots_b, dids_b, dclocks_b))
    (clock, ids, dots, dids, dclk), over = _op._merge_tile(sa, sb, m_cap, d_cap)
    return (
        _op._from_kernel_dtype(clock, cdt), ids,
        _op._from_kernel_dtype(dots, cdt), dids,
        _op._from_kernel_dtype(dclk, cdt), over,
    )
