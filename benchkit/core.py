"""JSON-line state, wall-clock budget, stage isolation.

Three mechanisms: a wall-clock budget (CRDT_BENCH_BUDGET_S, default
540s) with per-stage estimates; the incremental ``emit`` (consumers
take the LAST {"metric"...} line, so the artifact gets monotonically
better); and per-stage isolation — a stage that raises is logged and
recorded, the later stages still run, and the run exits non-zero
(:func:`failed_stages`).
"""

from __future__ import annotations

import json
import os
import sys
import time


SMALL = os.environ.get("CRDT_BENCH_SMALL") == "1"


def log(*args):
    print(*args, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- budget
#
# Stages are skipped once the remaining wall budget
# (CRDT_BENCH_BUDGET_S, default 540s) is below their estimated cost;
# the headline JSON line is (re)printed after every completed stage, so
# consumers take the LAST line starting {"metric"; CPU backends
# downshift north-star/resident chunk counts (rates stay comparable;
# totals are recorded in the JSON).

_T0 = time.monotonic()
_BUDGET_S = float(os.environ.get("CRDT_BENCH_BUDGET_S", "540"))


def remaining_budget() -> float:
    return _BUDGET_S - (time.monotonic() - _T0)


_JSON_STATE: dict = {
    "metric": "orswot_merges_per_sec_to_fixpoint",
    "value": None,
    "unit": "merges/s",
    "vs_baseline": None,
}


def emit(**fields):
    """Merge ``fields`` into the headline record and print it (again).

    Consumers parse the LAST {"metric"...} line, so re-printing after
    every stage makes the artifact monotonically better instead of
    all-or-nothing."""
    _JSON_STATE.update(fields)
    if _JSON_STATE.get("value") is not None:
        _JSON_STATE["vs_baseline"] = round(_JSON_STATE["value"] / 1e7, 4)
        print(json.dumps(_JSON_STATE), flush=True)


_FAILED: list = []


def failed_stages() -> list:
    """Names of the stages that raised in this run, in order."""
    return list(_FAILED)


def run_stage(name: str, est_s: float, fn, *args, required: bool = False,
              **kwargs):
    """Run one bench stage, isolating failures and budget exhaustion.

    Returns the stage result or None (skipped/errored).  A stage that
    raises is logged, recorded in the artifact (``<name>_error``) and in
    :func:`failed_stages` — the later stages still run, and the caller
    exits non-zero.

    ``required=True`` marks a VALIDATION stage (parity gates, TPU
    validation): it is never budget-skipped — an artifact whose numbers
    were never validated is worse than a late artifact (VERDICT r5 weak
    #3: budget starvation ate four validation stages while contender
    stages ran)."""
    rem = remaining_budget()
    if rem < est_s:
        if required:
            log(
                f"stage {name}: budget low (remaining {rem:.0f}s < est "
                f"{est_s:.0f}s) but stage is REQUIRED validation — running"
            )
        else:
            log(f"stage {name}: SKIPPED (remaining budget {rem:.0f}s < est {est_s:.0f}s)")
            emit(**{f"{name}_skipped": "budget"})
            return None
    try:
        return fn(*args, **kwargs)
    except Exception as e:  # noqa: BLE001 — stage isolation is the point
        import traceback

        log(f"stage {name}: FAILED ({type(e).__name__}: {str(e)[:300]})")
        log(traceback.format_exc(limit=8))
        emit(**{f"{name}_error": f"{type(e).__name__}: {str(e)[:120]}"})
        _FAILED.append(name)
        return None


def _downshift() -> bool:
    """True when full-scale shapes would risk the budget: CPU backends
    downshift chunk counts unless the caller
    insists (CRDT_BENCH_FULL=1).  Rates stay comparable — only the number
    of timed repetitions shrinks."""
    if os.environ.get("CRDT_BENCH_FULL") == "1":
        return False
    import jax

    return jax.default_backend() == "cpu"


def _sync_overhead():
    """Same-run host sync constant (crdt_tpu.utils.benchtime)."""
    from crdt_tpu.utils.benchtime import sync_overhead

    return sync_overhead()


def timeit_chained(step, init, iters=None, sync_overhead_s=None, consts=()):
    """Per-iteration wall time of ``step`` chained on-device.

    Thin wrapper over ``crdt_tpu.utils.benchtime.chain_timer`` (see its
    docstring: one jitted lax.scan, sync constant subtracted,
    consts-as-jit-parameters).  Median of 3 runs.
    """
    from crdt_tpu.utils.benchtime import chain_timer

    if iters is None:
        iters = 10 if SMALL else 100
    return chain_timer(step, init, iters, consts=consts,
                       sync_overhead_s=sync_overhead_s, reps=3)


