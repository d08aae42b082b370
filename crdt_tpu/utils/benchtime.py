"""Chained device-side timing.

Every host↔device sync round-trip costs a fixed constant, so
per-dispatch timing measures the sync, not the kernel.  The timer
therefore runs ``iters`` iterations of ``state -> step(state, *consts)``
inside ONE jitted ``lax.scan`` — the carry makes every iteration
data-dependent on the previous one, so XLA's while-loop executes each
one — pays the sync once, subtracts the same-run sync constant, and
divides by ``iters``.

``jax.jit`` inlines closed-over concrete arrays into the lowered module
as dense constants, so every device array the step needs besides the
carry flows in through ``consts`` — a jit parameter — never a closure.
Completion is forced by fetching one scalar from the output.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Sequence


def sync_overhead(reps: int = 3) -> float:
    """The fixed dispatch+fetch round-trip, measured NOW, in the same
    run as the timing it corrects.  Median of ``reps`` samples.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    tiny = jax.jit(lambda x: x + 1)
    tone = jnp.zeros((8,), jnp.uint32)
    np.asarray(tiny(tone))  # compile + warm
    samples = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        np.asarray(tiny(tone))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def chain_timer(
    step: Callable[..., Any],
    init: Any,
    iters: int,
    consts: Sequence[Any] = (),
    sync_overhead_s: float | None = None,
    reps: int = 1,
):
    """Time ``step`` chained ``iters`` times on device.

    ``step(state, *consts) -> state`` (same pytree shape).  Returns
    ``(seconds_per_iter, final_state)``; with ``reps > 1`` the median
    of ``reps`` timed runs is used.
    """
    import jax
    import numpy as np
    from jax import lax

    @jax.jit
    def run(s0, cs):
        return lax.scan(lambda c, _: (step(c, *cs), None), s0, None,
                        length=iters)[0]

    consts = tuple(consts)
    out = run(init, consts)
    jax.block_until_ready(out)  # compile + warmup
    if sync_overhead_s is None:
        sync_overhead_s = sync_overhead()
    times = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        out = run(init, consts)
        np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[0])
        times.append(time.perf_counter() - t0)
    per_iter = max(float(np.median(times)) - sync_overhead_s, 1e-9) / iters
    return per_iter, out
