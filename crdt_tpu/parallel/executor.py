"""Host-level join executor — elastic recovery for device batches.

SURVEY.md §5: the reference's fault-tolerance story is purely algebraic —
idempotent merge makes redelivery safe (`/root/reference/src/traits.rs:36`),
deferred removes buffer causally-future ops (`orswot.rs:195-203`) — and the
TPU build adds "a host-level retry/requeue for failed device batches" on
top.  This module is that component.

On TPU the two batch failure modes are:

* **capacity overflow** — the static-shape concession (SURVEY.md §7.3):
  a join's survivor set outgrows the padded member/deferred slot axes.
  The kernels report this as a per-object overflow bitmap; recovery is to
  regrow the slot axes (``with_capacity``) and re-run the join.  Because
  merge is idempotent and the regrown batch is the same CRDT state, the
  retry is always algebraically safe.
* **transient device failure** — a dispatch raising ``RuntimeError``
  (a lost device or connection, preemption).  Recovery is to
  requeue the same join up to ``max_retries`` times.

The executor joins a queue of batches into one state — as a left fold
(one recoverable pair merge per step) or, on TPU backends by default, as
the type's pairwise-tree reduction with recovery at whole-tree
granularity (``strategy`` field) — finishing with a defer-plunger
self-merge (`/root/reference/test/orswot.rs:61-62`) so buffered removes
flush.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional, Sequence

from ..error import CapacityOverflowError
from ..obs import events as obs_events
from ..obs import kernels as obs_kernels
from ..utils import tracing


def _record_recovery(kind: str, **fields) -> None:
    """Executor recoveries (regrows, transient requeues) are rare and
    diagnostic-grade: count them always-on AND leave a flight-recorder
    event, so a fleet that silently regrew mid-join shows up on
    ``/events`` with the capacities it regrew to.

    The counter lives under ``executor.recovery.*`` — a namespace
    disjoint from the ``executor.regrow`` SPAN below, because the obs
    registry claims one metric type per name and the span forwards into
    a histogram of the same name.
    """
    tracing.count(f"executor.recovery.{kind}")
    if kind == "regrow":
        # stamp the capacity-ladder transition for the kernel
        # observatory: the next compile each kernel pays on the regrown
        # shapes is ladder-attributed, not shape churn
        # (crdt_tpu/obs/kernels.py storm_report)
        obs_kernels.note_ladder_transition(kind)
    obs_events.record(f"executor.{kind}", **fields)


@dataclasses.dataclass
class JoinStats:
    """What happened during a ``join_all`` run."""

    joins: int = 0
    overflow_regrows: int = 0
    transient_retries: int = 0
    final_member_capacity: Optional[int] = None
    final_deferred_capacity: Optional[int] = None


class JoinError(RuntimeError):
    """A join could not be completed within the executor's limits."""


# substrings that mark a RuntimeError as plausibly transient (device-side,
# worth requeueing); anything else is treated as deterministic and raised
# without burning the retry budget on backoff sleeps.  An HBM OOM is not
# transient: the same join at the same shape fails the same way again.
_TRANSIENT_MARKERS = (
    "unavailable",
    "deadline",
    "aborted",
    "cancelled",
    "preempt",
    "connection",
    "socket",
    "device gone",
    "device lost",
)


def _is_transient(err: BaseException) -> bool:
    msg = str(err).lower()
    return any(marker in msg for marker in _TRANSIENT_MARKERS)


@dataclasses.dataclass
class JoinExecutor:
    """Join driver with overflow regrowth and transient retry.

    The schedule is the ``strategy`` field: a left fold (recovery per
    pair merge) or the batch type's pairwise-tree reduction (recovery
    re-runs the whole tree — safe because merge is idempotent).

    Works with any batch type exposing ``merge(other, check=True)`` that
    raises :class:`~crdt_tpu.error.CapacityOverflowError` on capacity
    overflow; elastic regrowth additionally needs ``with_capacity``/
    ``member_capacity``/``deferred_capacity`` (``OrswotBatch`` has all
    three; types without capacities — counters, registers — simply never
    overflow).  Only the axis the error names is regrown.

    ``max_capacity`` bounds geometric regrowth (×2 per overflow);
    ``max_retries`` bounds requeues of a join whose dispatch raised
    ``RuntimeError``.
    """

    max_capacity: int = 1 << 16
    max_retries: int = 2
    grow_factor: int = 2
    retry_backoff_s: float = 0.5  # doubles per retry; 0 disables sleeping
    # join schedule: "sequential" = left fold, one recoverable pair merge
    # at a time; "tree" = the type's pairwise-tree reduction
    # (``join_fleet``) — log-depth, each level one batched call, recovery
    # at whole-tree granularity; "auto" = tree on TPU backends (the
    # launch shape accelerators want), sequential elsewhere (measured
    # faster on a single CPU core — docs/GUIDE.md)
    strategy: str = "auto"

    def join_all(
        self,
        batches: Sequence[Any],
        plunger: bool = True,
        stats: Optional[JoinStats] = None,
    ) -> Any:
        """Fold ``batches`` into one joined batch (anti-entropy)."""
        if not batches:
            raise ValueError("join_all needs at least one batch")
        stats = stats if stats is not None else JoinStats()
        if self._use_tree(batches):
            return self._join_tree(list(batches), plunger, stats)
        acc = batches[0]
        with tracing.span("executor.join_all"):
            for nxt in batches[1:]:
                acc, nxt = self._equalize(acc, nxt)
                acc = self._merge_recovering(acc, nxt, stats)
            if plunger:
                acc = self._merge_recovering(acc, acc, stats)
        stats.final_member_capacity = getattr(acc, "member_capacity", None)
        stats.final_deferred_capacity = getattr(acc, "deferred_capacity", None)
        return acc

    def _use_tree(self, batches: Sequence[Any]) -> bool:
        if self.strategy not in ("sequential", "tree", "auto"):
            raise ValueError(
                f"unknown strategy {self.strategy!r}; use 'sequential', "
                "'tree' or 'auto'"
            )
        if self.strategy == "sequential" or len(batches) < 2:
            return False
        if not hasattr(type(batches[0]), "join_fleet"):
            if self.strategy == "tree":
                raise ValueError(
                    f"strategy='tree' requires {type(batches[0]).__name__} to "
                    "implement join_fleet; use 'sequential' or 'auto'"
                )
            return False
        if self.strategy == "tree":
            return True
        import jax

        return jax.default_backend() == "tpu"

    def _join_tree(self, batches: list, plunger: bool, stats: JoinStats) -> Any:
        """Whole-tree join with the same two recoveries as the fold:
        capacity overflow regrows every fleet and re-runs the tree
        (idempotent merge makes the re-run algebraically safe), transient
        RuntimeErrors requeue up to ``max_retries``."""
        # equalize all fleets to the max capacities up front
        if hasattr(batches[0], "with_capacity"):
            m = max(b.member_capacity for b in batches)
            d = max(b.deferred_capacity for b in batches)
            batches = [
                b if (b.member_capacity, b.deferred_capacity) == (m, d)
                else b.with_capacity(m, d)
                for b in batches
            ]
        retries = 0
        with tracing.span("executor.join_all_tree"):
            while True:
                try:
                    out = type(batches[0]).join_fleet(
                        batches, check=True, plunger=plunger
                    )
                    stats.joins += len(batches) - 1 + (1 if plunger else 0)
                    stats.final_member_capacity = getattr(
                        out, "member_capacity", None
                    )
                    stats.final_deferred_capacity = getattr(
                        out, "deferred_capacity", None
                    )
                    return out
                except CapacityOverflowError as overflow:
                    if not hasattr(batches[0], "with_capacity"):
                        raise
                    m = batches[0].member_capacity
                    d = batches[0].deferred_capacity
                    new_m = self._grown(m, overflow.member)
                    new_d = self._grown(d, overflow.deferred)
                    if new_m == m and new_d == d:
                        raise JoinError(
                            f"tree join overflowed at max_capacity="
                            f"{self.max_capacity} (member_capacity={m}, "
                            f"deferred_capacity={d})"
                        ) from overflow
                    stats.overflow_regrows += 1
                    # before/after capacity stamps: the capacity
                    # observatory's regrow_timeline correlates these
                    # events with the occupancy curve that forced them
                    _record_recovery("regrow", schedule="tree",
                                     member_capacity_before=m,
                                     deferred_capacity_before=d,
                                     member_capacity=new_m,
                                     deferred_capacity=new_d)
                    with tracing.span("executor.regrow"):
                        batches = [b.with_capacity(new_m, new_d) for b in batches]
                except RuntimeError as transient:
                    if isinstance(transient, JoinError) or not _is_transient(
                        transient
                    ):
                        raise
                    retries += 1
                    if retries > self.max_retries:
                        raise JoinError(
                            f"tree join failed after {self.max_retries} retries"
                        ) from transient
                    stats.transient_retries += 1
                    _record_recovery("transient_retry", schedule="tree",
                                     attempt=retries,
                                     error=str(transient)[:200])
                    if self.retry_backoff_s > 0:
                        time.sleep(self.retry_backoff_s * (2 ** (retries - 1)))

    def _grown(self, cur: int, hit: bool) -> int:
        if not hit:
            return cur
        # never shrink: a batch may already exceed max_capacity
        return max(cur, min(max(1, cur) * self.grow_factor, self.max_capacity))

    # -- internals ---------------------------------------------------------

    def _equalize(self, a: Any, b: Any):
        """Bring two batches to a common capacity before merging."""
        if not hasattr(a, "with_capacity") or not hasattr(b, "with_capacity"):
            return a, b
        m = max(a.member_capacity, b.member_capacity)
        d = max(a.deferred_capacity, b.deferred_capacity)
        if (a.member_capacity, a.deferred_capacity) == (m, d) == (
            b.member_capacity,
            b.deferred_capacity,
        ):
            return a, b
        return a.with_capacity(m, d), b.with_capacity(m, d)

    def _merge_recovering(self, acc: Any, nxt: Any, stats: JoinStats) -> Any:
        retries = 0
        while True:
            try:
                with tracing.span("executor.merge"):
                    out = acc.merge(nxt, check=True)
                stats.joins += 1
                return out
            except CapacityOverflowError as overflow:
                # capacity overflow: regrow the overflowed axes and requeue
                if not hasattr(acc, "with_capacity"):
                    raise
                m = getattr(acc, "member_capacity", 0)
                d = getattr(acc, "deferred_capacity", 0)
                new_m = self._grown(m, overflow.member)
                new_d = self._grown(d, overflow.deferred)
                if new_m == m and new_d == d:
                    raise JoinError(
                        f"join overflowed at max_capacity={self.max_capacity} "
                        f"(member_capacity={m}, deferred_capacity={d})"
                    ) from overflow
                stats.overflow_regrows += 1
                _record_recovery("regrow", schedule="sequential",
                                 member_capacity_before=m,
                                 deferred_capacity_before=d,
                                 member_capacity=new_m,
                                 deferred_capacity=new_d)
                with tracing.span("executor.regrow"):
                    acc = acc.with_capacity(new_m, new_d)
                    nxt = nxt.with_capacity(new_m, new_d)
            except RuntimeError as transient:
                # XLA surfaces lost devices, preemption AND deterministic
                # failures (shape/compile errors) as RuntimeError subclasses;
                # only messages carrying transient markers are requeued —
                # deterministic failures surface immediately
                if isinstance(transient, JoinError) or not _is_transient(transient):
                    raise
                retries += 1
                if retries > self.max_retries:
                    raise JoinError(
                        f"join failed after {self.max_retries} retries"
                    ) from transient
                stats.transient_retries += 1
                _record_recovery("transient_retry", schedule="sequential",
                                 attempt=retries,
                                 error=str(transient)[:200])
                if self.retry_backoff_s > 0:
                    time.sleep(self.retry_backoff_s * (2 ** (retries - 1)))


def join_all(batches: Sequence[Any], **kwargs: Any) -> Any:
    """One-shot convenience: ``JoinExecutor().join_all(batches)``."""
    executor_kwargs = {
        k: kwargs.pop(k)
        for k in (
            "max_capacity", "max_retries", "grow_factor", "retry_backoff_s",
            "strategy",
        )
        if k in kwargs
    }
    return JoinExecutor(**executor_kwargs).join_all(batches, **kwargs)
