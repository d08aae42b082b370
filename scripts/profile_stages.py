"""Stage-level profile of the ORSWOT merge at north-star shapes.

Times each kernel stage as a device-side chain (one dispatch, host sync
paid once), plus a raw `jnp.maximum` bandwidth probe over the same
footprint, so "optimize the merge" has a concrete target on the platform
that matters.  Works on any backend (`JAX_PLATFORMS=cpu` for a local
run); on the chip:

    python scripts/profile_stages.py            # north-star chunk shapes
    python scripts/profile_stages.py --config4  # BASELINE config-4 shapes
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from crdt_tpu.ops import clock_ops, orswot_ops
    from crdt_tpu.utils.testdata import random_orswot_arrays

    if "--config4" in sys.argv:
        n, a, m, d = 100_000, 16, 8, 4
        iters = 20
    else:  # one north-star chunk
        n, a, m, d = 62_500, 64, 16, 2
        iters = 20

    rng = np.random.RandomState(0)
    lhs = tuple(jnp.asarray(x) for x in random_orswot_arrays(
        rng, n, a, m, d, min_live=m, deferred_frac=0.25))
    rhs = tuple(jnp.asarray(x) for x in random_orswot_arrays(
        rng, n, a, m, d, min_live=m))
    clock_a, ids_a, dots_a, dids_a, dclocks_a = lhs
    clock_b, ids_b, dots_b, dids_b, dclocks_b = rhs
    state_bytes = sum(x.nbytes for x in lhs)
    print(f"backend={jax.default_backend()} n={n} a={a} m={m} d={d} "
          f"state={state_bytes/1e6:.0f} MB/side")

    from crdt_tpu.utils.benchtime import sync_overhead

    sync = sync_overhead()
    print(f"sync overhead: {sync*1e3:.1f} ms")

    def chain_time(step, init, label, bytes_moved=None, consts=()):
        """step: (state, *consts) -> state, chained iters times.

        Thin wrapper over crdt_tpu.utils.benchtime.chain_timer (one
        jitted lax.scan; sync constant subtracted; device arrays flow in
        as jit parameters via ``consts``, never closures).
        """
        from crdt_tpu.utils.benchtime import chain_timer

        t, _ = chain_timer(step, init, iters, consts=consts,
                           sync_overhead_s=sync)
        bw = f"  {bytes_moved/t/1e9:6.1f} GB/s" if bytes_moved else ""
        print(f"{label:34s} {t*1e3:9.2f} ms{bw}")
        return t

    # raw bandwidth floor: elementwise max over the dots footprint
    chain_time(lambda s, db: (jnp.maximum(s[0], db),),
               (dots_a,), "bandwidth: maximum(dots,dots)",
               bytes_moved=3 * dots_a.nbytes, consts=(dots_b,))

    # full pairwise merge (the real thing, deferred rows present)
    chain_time(
        lambda s, *r: orswot_ops.merge(*s, *r, m, d)[:5], lhs,
        "full merge (deferred present)",
        bytes_moved=3 * state_bytes, consts=rhs)

    # deferred-free merge → rank-select fast path via the cond
    lhs_nd = (clock_a, ids_a, dots_a,
              jnp.full_like(dids_a, -1), jnp.zeros_like(dclocks_a))
    chain_time(
        lambda s, *r: orswot_ops.merge(*s, *r, m, d)[:5],
        lhs_nd, "merge fast path (no deferred)",
        bytes_moved=3 * state_bytes, consts=lhs_nd)

    # stage: member match (quadratic bool)
    def step_match(s, idb):
        va, am, j_idx, bo = orswot_ops._member_match(s[0], idb)
        # consume every output so nothing is DCE'd out of the chain
        return (jnp.where(am & va & ~bo, s[0], j_idx),)
    chain_time(step_match, (ids_a,), "_member_match [N,M,M] bool",
               consts=(ids_b,))

    # stage: rank-select core alone (survival reduces + rank + gathers)
    def step_core(s, cb, idb, db):
        clock, ids, dots = s
        out_ids, out_dots, n_surv = orswot_ops._rank_select_merge(
            clock, ids, dots, cb, idb, db, m)
        clock2 = clock_ops.merge(clock, jnp.max(out_dots, axis=-2))
        return (clock2, out_ids, out_dots)
    chain_time(step_core, (clock_a, ids_a, dots_a), "_rank_select_merge core",
               consts=(clock_b, ids_b, dots_b))

    # stage: counting-rank order over 2M keys, vs XLA argsort
    keys = jnp.concatenate([ids_a, ids_b], axis=-1)
    def step_order(s):
        o = orswot_ops._stable_order(s[0])
        return (jnp.take_along_axis(s[0], o, axis=-1),)
    chain_time(step_order, (keys,), "_stable_order [N,2M] + gather")

    def step_sort(s):
        o = jnp.argsort(s[0], axis=-1, stable=True)
        return (jnp.take_along_axis(s[0], o, axis=-1),)
    chain_time(step_sort, (keys,), "jnp.argsort [N,2M] + gather")

    # stage: deferred pipeline (dedup + replay)
    def step_deferred(s, ca, ia, da):
        d_ids, d_clocks = orswot_ops._dedup_deferred(s[0], s[1])
        ids2, dots2, d_ids2, d_clocks2 = orswot_ops._apply_deferred(
            ca, ia, da, d_ids, d_clocks)
        # keep the member-side replay (dots2) live in the carry
        return (d_ids2, jnp.maximum(d_clocks2, dots2[..., :d, :]))
    chain_time(step_deferred, (dids_a, dclocks_a), "deferred dedup+replay",
               consts=(clock_a, ids_a, dots_a))

    # the unrolled tile math (crdt_tpu/ops/orswot_unrolled.py, the TPU
    # default).  TPU-only: on CPU it is memory-bound by design (O(M)
    # extra passes) and costs minutes for a number we already know.
    if jax.default_backend() == "tpu" or "--all-stages" in sys.argv:
        from crdt_tpu.ops import orswot_pallas, orswot_unrolled

        chain_time(
            lambda s, *r: orswot_unrolled.merge_unrolled(*s, *r, m, d)[:5],
            lhs, "merge_unrolled (std layout)",
            bytes_moved=3 * state_bytes, consts=rhs)

        # unrolled-path internal stages (the shared tile math of
        # crdt_tpu/ops/orswot_pallas.py, biased-int32 domain) — the TPU
        # default dispatches here since the round-3 A/B, so the stage
        # attribution that matters on-chip is THIS path's
        op = orswot_pallas
        u32 = [tuple(x.astype(jnp.uint32) if x.dtype != jnp.int32 else x
                     for x in side) for side in (lhs, rhs)]
        ka = op._to_kernel_dtype(u32[0])
        kb = op._to_kernel_dtype(u32[1])

        def step_align(s, kb1, kb2):
            e2, bm = op._align_against(s[1], s[0], kb1, kb2)
            return (jnp.maximum(s[0], jnp.where(op._emask(bm), e2, op.ZERO)),
                    s[1])
        chain_time(step_align, (ka[2], ka[1]), "unrolled: align (M^2 select)",
                   consts=(kb[1], kb[2]))

        e2_0, bm_0 = op._align_against(ka[1], ka[2], kb[1], kb[2])

        def step_rule(s, ka1, ka0, kb0):
            dots, e2 = s
            valid_a = ka1 != op.EMPTY
            out = op._merge_rule(
                dots, e2, valid_a & op._nonempty(dots),
                valid_a & op._nonempty(e2), valid_a, ka0, kb0)
            # both carries data-depend on the output so XLA can neither
            # hoist the rule nor constant-fold e2 into the loop body
            return (jnp.maximum(dots, out), jnp.maximum(e2, out))
        chain_time(step_rule, (ka[2], e2_0), "unrolled: dot-algebra rule",
                   consts=(ka[1], ka[0], kb[0]))

        ids_cat0 = jnp.concatenate([ka[1], kb[1]], axis=-1)

        def step_rank(s, idc):
            big = jnp.iinfo(jnp.int32).max
            live = idc != op.EMPTY
            m_keys = jnp.where(live, idc, big)
            out_ids, out_dots, n_surv = op._rank_select(
                m_keys, live, idc, s[0], m)
            # consume ids and the survivor count too, or XLA DCEs the
            # id-pack sums and overflow reduce out of the timed stage
            salt = (out_ids[..., :1] + n_surv[..., None])[..., None]
            return (jnp.concatenate(
                [jnp.maximum(out_dots, s[0][..., :m, :] ^ salt),
                 s[0][..., m:, :]], axis=-2),)
        chain_time(step_rank, (jnp.concatenate([ka[2], kb[2]], axis=-2),),
                   "unrolled: member rank-select", consts=(ids_cat0,))
    else:
        print("unrolled variant + stages skipped (non-TPU backend; "
              "--all-stages to force)")


if __name__ == "__main__":
    main()
