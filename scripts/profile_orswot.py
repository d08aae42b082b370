"""Stage-level profile of the ORSWOT merge kernel at config-4 shapes.

Times each internal stage of ``orswot_ops.merge`` in isolation (each stage
jitted on its own) plus the fused whole, and reports bytes-moved estimates
so the dominant cost is visible.  Run on CPU or TPU:

    JAX_PLATFORMS=cpu python scripts/profile_orswot.py
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from crdt_tpu.ops import clock_ops, orswot_ops
from crdt_tpu.utils.testdata import random_orswot_arrays


def timeit(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def main():
    n, a, m, d = 100_000, 16, 8, 4
    rng = np.random.RandomState(1)
    L = tuple(jnp.asarray(x) for x in random_orswot_arrays(rng, n, a, m, d))
    R = tuple(jnp.asarray(x) for x in random_orswot_arrays(rng, n, a, m, d))
    clock_a, ids_a, dots_a, dids_a, dclocks_a = L
    clock_b, ids_b, dots_b, dids_b, dclocks_b = R

    print(f"backend={jax.default_backend()} n={n} A={a} M={m} D={d} dtype={dots_a.dtype}")

    t = timeit(jax.jit(lambda L, R: orswot_ops.merge(*L, *R, m, d)[:5]), L, R)
    print(f"full merge (fast path, no deferred): {t*1e3:8.2f}ms  {n/t/1e6:6.2f}M merges/s")

    clock = clock_ops.merge(clock_a, clock_b)

    t = timeit(
        jax.jit(
            lambda L, R: orswot_ops._merge_narrow_fast(clock, *L, *R, m, d)
        ),
        L,
        R,
    )
    print(f"_merge_narrow_fast  (rank-select)  : {t*1e3:8.2f}ms")

    t = timeit(
        jax.jit(
            lambda L, R: orswot_ops._merge_narrow_deferred(clock, *L, *R, m, d)
        ),
        L,
        R,
    )
    print(f"_merge_narrow_deferred (full-width): {t*1e3:8.2f}ms")

    # sub-stages of the fast path
    t = timeit(jax.jit(orswot_ops._member_match), ids_a, ids_b)
    print(f"_member_match                      : {t*1e3:8.2f}ms")
    t = timeit(
        jax.jit(lambda k: orswot_ops._stable_order(k)),
        jnp.concatenate([ids_a, ids_b], axis=-1),
    )
    print(f"_stable_order (rank sort, 2M keys) : {t*1e3:8.2f}ms")

    # bytes accounting (u32): state in+out
    bpe = dots_a.dtype.itemsize
    state = n * (a * bpe + m * 4 + m * a * bpe + d * 4 + d * a * bpe)
    print(f"state bytes/side   : {state/1e6:.1f} MB (in 2x, out 1x => {3*state/1e6:.1f} MB min traffic)")
    m2 = 2 * m
    inter = n * (m2 * a * bpe * 2)  # e1+e2
    print(f"aligned intermed.  : {inter/1e6:.1f} MB")
    bigmatch = n * m * m * a * bpe
    print(f"[N,M,M,A] broadcast: {bigmatch/1e6:.1f} MB (materialized only if XLA fails to fuse)")


if __name__ == "__main__":
    main()
