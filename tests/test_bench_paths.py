"""The bench harness's timing-path invariants, at test scale.

bench.py's north star streams chunk folds through a salted ``lax.scan``
(one dispatch, host sync paid once).  The work-elision check — replay
the exact salt chain as per-step dispatches XLA cannot hoist across and
demand bit-equality — used to live in the timed bench; it cost 113s per
run at full scale and contributed to a lost round artifact (VERDICT r3),
so the bench now runs it opt-in (``CRDT_RUN_ELISION_CHECK=1``) and the
invariant lives HERE at small shapes: if the scan's while-loop were
invariant-hoisted or partially DCE'd into computing fewer folds, the
data-dependent salts would diverge and the replay would not match.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from crdt_tpu.ops import orswot_ops
from crdt_tpu.utils.testdata import anti_entropy_fleets


@pytest.mark.parametrize("n_chunks", [4, 6])
def test_salted_scan_matches_stepped_replay(n_chunks):
    rng = np.random.RandomState(2)
    chunk, a, m, d, r = 64, 8, 8, 2, 4

    templates = []
    for _ in range(2):
        reps = anti_entropy_fleets(
            rng, chunk, a, m, d, r, base=3, novel=1, deferred_frac=0.25,
        )
        templates.append(
            tuple(jnp.stack([rep[k] for rep in reps]) for k in range(5))
        )
    t0_, t1_ = templates

    def fold_join(stack):
        acc = tuple(x[0] for x in stack)
        for i in range(1, r):
            acc = orswot_ops.merge(*acc, *(x[i] for x in stack), m, d)[:5]
        return orswot_ops.merge(*acc, *acc, m, d)[:5]  # defer plunger

    def salted_fold(tpl, salt):
        return fold_join((tpl[0] ^ salt,) + tpl[1:])

    def next_salt(acc):
        # max-reduce the DOTS plane: keeps the expensive member pipeline
        # live under DCE (see bench.py bench_north_star)
        return (jnp.max(acc[2]) & jnp.uint32(7)) | jnp.uint32(1)

    @jax.jit
    def run_chunks(t0_, t1_):
        def body(carry, _):
            salt, _prev = carry
            o0 = salted_fold(t0_, salt)
            o1 = salted_fold(t1_, next_salt(o0))
            return (next_salt(o1), o1), None

        init = (jnp.uint32(1), tuple(x[0] for x in t0_))
        (_salt, out), _ = lax.scan(body, init, None, length=n_chunks // 2)
        return out

    scan_out = run_chunks(t0_, t1_)

    # per-step replay: separately compiled programs, same salt chain
    sf = jax.jit(salted_fold)
    ns = jax.jit(next_salt)
    salt = jnp.uint32(1)
    out = None
    for _ in range(n_chunks // 2):
        o0 = sf(t0_, salt)
        o1 = sf(t1_, ns(o0))
        salt = ns(o1)
        out = o1

    for i, (g, w) in enumerate(zip(scan_out, out)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"plane {i}: scan diverged from per-step replay",
        )


@pytest.mark.parametrize("fold_path", ["native", "jnp"])
def test_bench_e2e_wire_runs_both_fold_paths(fold_path, monkeypatch):
    """``bench_e2e_wire`` at its small size, on the fold each backend
    takes (the native CPU fold; the device fold, forced on the CPU by
    ``CRDT_SKIP_NATIVE_HEADLINE``): a failure here would silently drop
    ``e2e_wire_s`` and every overhead gate that reads it."""
    import bench
    from crdt_tpu.batch.wireloop import _native_fold_engine

    if fold_path == "native" and _native_fold_engine() is None:
        pytest.skip("native fold unavailable")
    monkeypatch.setattr(bench, "SMALL", True)
    monkeypatch.setenv("CRDT_SKIP_NATIVE_HEADLINE",
                       "1" if fold_path == "jnp" else "0")
    out = bench.bench_e2e_wire()
    assert out["e2e_wire_fold_path"] == fold_path
    assert out["e2e_wire_s"] > 0
    assert out["e2e_wire_replica_objects"] == 2 * 4 * 1_000
    assert out["e2e_shape_ingest_obj_per_sec"] > 0
