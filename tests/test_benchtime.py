"""The shared chained timer (crdt_tpu.utils.benchtime).

Every capture path (bench.py, profile_stages) times through this
helper; what matters for correctness is that the chain really executes
its iterations data-dependently and that consts arrive as jit
parameters (a closure would inline the arrays into the lowered module
as constants, so here we pin the calling convention).
"""
import jax.numpy as jnp
import numpy as np

from crdt_tpu.utils.benchtime import chain_timer, sync_overhead


def test_chain_executes_every_iteration():
    y = jnp.arange(256, dtype=jnp.uint32)
    t, out = chain_timer(
        lambda c, yy: (jnp.maximum(c[0], yy) + 1,),
        (jnp.zeros(256, jnp.uint32),),
        iters=10,
        consts=(y,),
        sync_overhead_s=0.0,
    )
    assert t > 0
    # 10 data-dependent iterations: the running max gains +1 each step
    assert int(np.asarray(out[0]).max()) == 255 + 10


def test_consts_are_positional_varargs():
    a = jnp.full((8,), 3, jnp.uint32)
    b = jnp.full((8,), 5, jnp.uint32)
    _, out = chain_timer(
        lambda c, x, y: (c[0] + x + y,),
        (jnp.zeros(8, jnp.uint32),),
        iters=4,
        consts=(a, b),
        sync_overhead_s=0.0,
    )
    assert np.asarray(out[0]).tolist() == [32] * 8  # 4 * (3 + 5)


def test_sync_overhead_nonnegative():
    s = sync_overhead(reps=2)
    assert 0 <= s < 60
