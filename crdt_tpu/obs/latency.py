"""Latency observatory — where the time goes, and how stale a read is.

The stack can survive kill -9 (durable/) and sync in O(log N) bytes
(sync/tree), but until this module it could not answer the first two
questions a serving fleet gets asked: *how stale is a read from this
replica*, and *which leg of a sync session actually costs the wall
time*.  Three measurement planes, all host-side and stdlib/numpy-free
unless noted:

* :class:`SessionProfile` — the critical path of ONE sync session,
  accounted in integer nanoseconds.  :class:`~crdt_tpu.sync.session.
  SyncSession` stamps a monotonic clock around every frame send/recv
  (``network``), every encode/decode (``serialize``), every digest/
  tree/delta-apply kernel call (``kernel``) and the piggyback
  bookkeeping (``other``); the residual the stamps missed is
  ``unaccounted`` — which is itself published (if the profiler loses
  track of time, that is a finding, not a rounding error).  The
  identity ``serialize + network + kernel + other + unaccounted ==
  wall`` holds to the nanosecond by construction and is pinned in
  ``tests/test_latency.py``.

* :class:`RttEstimator` — Jacobson/Karels SRTT/RTTVAR (SIGCOMM '88)
  over the ack round-trips :class:`~crdt_tpu.cluster.transport.
  ResilientTransport` already performs (it round-trips every DATA
  frame; before this module it threw the timing away).  Karn's rule:
  retransmitted frames never contribute samples.  The estimator feeds
  the transport's adaptive retransmit timer (``srtt + 4·rttvar``,
  clamped to the RetryPolicy bounds) and the per-link
  ``cluster.transport.<link>.rtt_*`` gauges.

* :class:`LagTracker` — write-to-visible replication lag per
  ``(origin, observer)`` pair.  The origin node stamps every ingested
  op dot ``(actor, counter)`` with a monotonic nanosecond clock
  (:meth:`LagTracker.record_ingest_batch` — bounded: newest
  :data:`STAMPS_PER_ACTOR` dots per actor, :data:`MAX_ACTORS` actors);
  the stamps ride sync sessions as a hello-negotiated LAG sidecar
  frame (:data:`crdt_tpu.sync.delta.FRAME_LAG` — the 23 B/op op-frame
  wire format is untouched).  The observer measures an entry the
  moment its dot becomes visible in the local clock plane — at the
  session's digest-convergence check, and again after every op-log
  fold (:meth:`observe_visibility`) — and publishes
  ``sync.peer.<peer>.lag_{p50_s,p99_s,outstanding,current_s}``.  Monotonic
  clocks are only comparable within one clock domain, so the sidecar
  carries the origin's process tag: a cross-process entry degrades
  loudly (``sync.lag.fallback.clock_domain``) instead of publishing a
  garbage number, exactly like every other capability mismatch.

The convergence SLO rides along: :meth:`LagTracker.observe_round`
keeps a bounded window of gossip-round outcomes and publishes
``sync.slo.converged_frac`` — the fraction of recent rounds that
converged within the target budget.

docs/GUIDE.md "Latency & lag" documents the metric table and how to read a
:class:`SessionProfile`.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from . import metrics as metrics_mod

#: newest ingest stamps retained per origin actor (the sidecar is
#: bounded by construction: MAX_ACTORS * STAMPS_PER_ACTOR entries)
STAMPS_PER_ACTOR = 8
#: distinct origin actors the stamp table tracks
MAX_ACTORS = 512
#: measured write-to-visible samples retained per peer
LAG_WINDOW = 512
#: gossip-round outcomes the SLO window retains
SLO_WINDOW = 128
#: default convergence-SLO budget: a round "meets SLO" when it
#: converged and finished within this many seconds
SLO_BUDGET_S = 1.0


# ---- session critical-path profile ------------------------------------------

#: the accounted categories, in report order
PROFILE_CATEGORIES = ("serialize", "network", "kernel", "other")


class SessionProfile:
    """Integer-nanosecond accounting of one sync session's wall time.

    Used single-threaded by the session that owns it (the lock-step
    protocol drives one leg at a time), so there is no lock.  Stamping
    is leaf-only by convention — :meth:`clock` regions must not nest
    (nesting would double-charge the overlap and break the accounting
    identity; the session instruments leaf call sites only).
    """

    __slots__ = ("wall_ns", "serialize_ns", "network_ns", "kernel_ns",
                 "other_ns", "frames_sent", "frames_received", "_t0",
                 "_depth")

    def __init__(self):
        self.wall_ns = 0
        self.serialize_ns = 0
        self.network_ns = 0
        self.kernel_ns = 0
        self.other_ns = 0
        self.frames_sent = 0
        self.frames_received = 0
        self._t0: Optional[int] = None
        self._depth = 0

    # -- stamping ------------------------------------------------------------

    def start(self) -> None:
        self._t0 = time.monotonic_ns()

    def add(self, category: str, ns: int) -> None:
        setattr(self, f"{category}_ns",
                getattr(self, f"{category}_ns") + int(ns))

    @contextlib.contextmanager
    def clock(self, category: str) -> Iterator[None]:
        """Charge the region's wall time to ``category``.  Nested
        regions charge only the innermost category for the overlap
        (the outer region's stamp still covers its exclusive tail), so
        a mis-nested call site degrades to slight over-counting of the
        inner category — never to time counted twice."""
        t0 = time.monotonic_ns()
        self._depth += 1
        try:
            yield
        finally:
            self._depth -= 1
            if self._depth == 0:
                self.add(category, time.monotonic_ns() - t0)

    def finish(self) -> None:
        """Close the profile: the wall clock stops here.  Idempotent —
        the last call wins (the session finalizes once, in ``sync``)."""
        if self._t0 is not None:
            self.wall_ns = time.monotonic_ns() - self._t0

    # -- derived views -------------------------------------------------------

    @property
    def accounted_ns(self) -> int:
        return (self.serialize_ns + self.network_ns + self.kernel_ns
                + self.other_ns)

    @property
    def unaccounted_ns(self) -> int:
        """The residual the stamps missed — by construction the
        accounting identity ``accounted + unaccounted == wall`` holds
        to the nanosecond.  Large values mean the profiler lost track
        of a phase; the session publishes this as its own histogram so
        that is alertable."""
        return self.wall_ns - self.accounted_ns

    @property
    def network_wait_frac(self) -> float:
        """Fraction of the session wall spent blocked on the wire —
        the number the gossip scheduler and the windowed-ARQ bench
        read: ~1.0 means the protocol is RTT-bound (pipelining wins),
        ~0.0 means it is compute/serialize-bound (pipelining won't)."""
        return self.network_ns / self.wall_ns if self.wall_ns else 0.0

    def to_dict(self) -> dict:
        return {
            "wall_ns": self.wall_ns,
            "serialize_ns": self.serialize_ns,
            "network_ns": self.network_ns,
            "kernel_ns": self.kernel_ns,
            "other_ns": self.other_ns,
            "unaccounted_ns": self.unaccounted_ns,
            "network_wait_frac": round(self.network_wait_frac, 6),
            "frames_sent": self.frames_sent,
            "frames_received": self.frames_received,
        }

    def __repr__(self) -> str:  # the demo prints these
        ms = 1e6
        return (
            f"SessionProfile(wall={self.wall_ns / ms:.2f}ms "
            f"serialize={self.serialize_ns / ms:.2f} "
            f"network={self.network_ns / ms:.2f} "
            f"kernel={self.kernel_ns / ms:.2f} "
            f"other={self.other_ns / ms:.2f} "
            f"unaccounted={self.unaccounted_ns / ms:.2f})"
        )


# ---- Jacobson/Karels RTT estimation -----------------------------------------


class RttEstimator:
    """SRTT/RTTVAR per Jacobson/Karels (SIGCOMM '88, RFC 6298 shape).

    First sample seeds ``srtt = s``, ``rttvar = s/2``; thereafter
    ``rttvar = (1-β)·rttvar + β·|srtt - s|`` then
    ``srtt = (1-α)·srtt + α·s`` with the classic gains α=1/8, β=1/4.
    :meth:`rto` is the retransmit timer ``srtt + 4·rttvar`` clamped
    into the caller's bounds — the caller supplies them so the policy
    (RetryPolicy) stays the single source of truth for limits.

    Thread-safe via one small lock: the transport's send path and a
    scraper may race.
    """

    __slots__ = ("alpha", "beta", "srtt_s", "rttvar_s", "samples",
                 "last_sample_s", "_lock")

    def __init__(self, alpha: float = 1.0 / 8, beta: float = 1.0 / 4):
        self.alpha = float(alpha)
        self.beta = float(beta)
        self.srtt_s: Optional[float] = None
        self.rttvar_s: Optional[float] = None
        self.samples = 0
        self.last_sample_s: Optional[float] = None
        self._lock = threading.Lock()

    def observe(self, sample_s: float) -> None:
        """Fold one round-trip sample in.  Callers apply Karn's rule
        (never sample a retransmitted frame) — the estimator cannot
        tell a first ack from a late one."""
        s = float(sample_s)
        if s < 0.0:
            return  # a clock that stepped backwards is not a sample
        with self._lock:
            if self.srtt_s is None:
                self.srtt_s = s
                self.rttvar_s = s / 2.0
            else:
                self.rttvar_s = ((1.0 - self.beta) * self.rttvar_s
                                 + self.beta * abs(self.srtt_s - s))
                self.srtt_s = (1.0 - self.alpha) * self.srtt_s + self.alpha * s
            self.samples += 1
            self.last_sample_s = s

    def rto(self, floor_s: float, cap_s: float,
            default_s: Optional[float] = None) -> Optional[float]:
        """The adaptive retransmit timer ``srtt + 4·rttvar`` clamped to
        ``[floor_s, cap_s]``; ``default_s`` (clamped too) before the
        first sample, or None when no default is given."""
        with self._lock:
            raw = (None if self.srtt_s is None
                   else self.srtt_s + 4.0 * self.rttvar_s)
        if raw is None:
            if default_s is None:
                return None
            raw = default_s
        return min(max(raw, float(floor_s)), float(cap_s))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "srtt_s": self.srtt_s,
                "rttvar_s": self.rttvar_s,
                "samples": self.samples,
                "last_sample_s": self.last_sample_s,
            }


# ---- write-to-visible lag ---------------------------------------------------


def _percentile(sorted_samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted sequence."""
    if not sorted_samples:
        return 0.0
    idx = min(len(sorted_samples) - 1,
              max(0, int(round(q * (len(sorted_samples) - 1)))))
    return float(sorted_samples[idx])


class _PeerLag:
    """One origin peer's lag state at this observer."""

    __slots__ = ("samples", "pending", "measured_frontier")

    def __init__(self):
        # measured write-to-visible seconds, bounded window
        self.samples: deque = deque(maxlen=LAG_WINDOW)
        # not-yet-visible sidecar entries: {actor: [(counter, mono_ns)]}
        self.pending: Dict[int, List[Tuple[int, int]]] = {}
        # highest counter already measured (or discarded) per actor —
        # re-delivered sidecar entries must not re-measure
        self.measured_frontier: Dict[int, int] = {}


class LagTracker:
    """Origin-timestamp table + per-peer write-to-visible lag gauges.

    One instance per replica (``ClusterNode`` owns one); the registry
    defaults to the process-global one so in-process fleets share a
    scrape surface, with peer labels keeping the pairs apart.
    ``proc_tag`` names this node's monotonic clock domain — entries
    from another domain are counted and dropped, never compared.
    """

    def __init__(self, registry: Optional[metrics_mod.MetricsRegistry]
                 = None, *,
                 proc_tag: Optional[str] = None,
                 slo_budget_s: float = SLO_BUDGET_S,
                 per_actor: int = STAMPS_PER_ACTOR,
                 max_actors: int = MAX_ACTORS):
        from . import events as events_mod

        self._registry = registry
        self.proc_tag = proc_tag if proc_tag is not None \
            else events_mod._PROC_TAG
        self.slo_budget_s = float(slo_budget_s)
        self.per_actor = int(per_actor)
        self.max_actors = int(max_actors)
        self._lock = threading.Lock()
        # origin side: {actor: deque[(counter, mono_ns)]}
        self._stamps: Dict[int, deque] = {}
        # observer side
        self._peers: Dict[str, _PeerLag] = {}
        self._slo: deque = deque(maxlen=SLO_WINDOW)

    def _reg(self) -> metrics_mod.MetricsRegistry:
        return self._registry if self._registry is not None \
            else metrics_mod.registry()

    # -- origin side: stamp ingested writes ----------------------------------

    def record_ingest(self, actor: int, counter: int,
                      mono_ns: Optional[int] = None) -> None:
        """Stamp one ingested dot ``(actor, counter)`` with the origin
        monotonic clock.  Bounded: newest ``per_actor`` dots per actor,
        ``max_actors`` actors (beyond that, new actors are dropped —
        lag measurement degrades, ingest never blocks)."""
        now = time.monotonic_ns() if mono_ns is None else int(mono_ns)
        with self._lock:
            dq = self._stamps.get(int(actor))
            if dq is None:
                if len(self._stamps) >= self.max_actors:
                    return
                dq = self._stamps[int(actor)] = deque(maxlen=self.per_actor)
            dq.append((int(counter), now))

    def record_ingest_batch(self, ops) -> None:
        """Stamp the dot frontier of one :class:`~crdt_tpu.oplog.
        records.OpBatch`: per dotted actor, the batch's highest counter
        (one stamp per actor per batch keeps the table — and the
        sidecar — bounded by actors, not by write rate)."""
        if ops is None or len(ops) == 0:
            return
        now = time.monotonic_ns()
        frontier: Dict[int, int] = {}
        for actor, counter in zip(ops.actor.tolist(), ops.counter.tolist()):
            a, c = int(actor), int(counter)
            if frontier.get(a, -1) < c:
                frontier[a] = c
        for a, c in frontier.items():
            self.record_ingest(a, c, mono_ns=now)

    def export_entries(self) -> List[Tuple[int, int, int]]:
        """The sidecar payload: every retained ``(actor, counter,
        origin_mono_ns)`` stamp, actor-major, counter-ascending."""
        with self._lock:
            out = []
            for actor in sorted(self._stamps):
                out.extend((actor, c, t) for c, t in self._stamps[actor])
        return out

    # -- observer side: sidecar in, visibility measured ----------------------

    def ingest_sidecar(self, peer: str,
                       entries: Sequence[Tuple[int, int, int]],
                       origin_proc: str) -> int:
        """Fold a peer's sidecar entries into the pending set; returns
        how many were accepted.  Entries from another monotonic clock
        domain are dropped loudly (``sync.lag.fallback.clock_domain``)
        — a cross-process monotonic diff is not a latency, and a
        degraded gauge beats a lying one.  Own echoes (the peer
        re-shipping OUR stamps once transitive sidecars exist) and
        already-measured counters are skipped silently."""
        from ..utils import tracing

        if origin_proc != self.proc_tag:
            tracing.count("sync.lag.fallback.clock_domain")
            return 0
        accepted = 0
        with self._lock:
            st = self._peers.get(peer)
            if st is None:
                st = self._peers[peer] = _PeerLag()
            for actor, counter, mono_ns in entries:
                actor, counter = int(actor), int(counter)
                if counter <= st.measured_frontier.get(actor, -1):
                    continue
                bucket = st.pending.setdefault(actor, [])
                if any(c == counter for c, _ in bucket):
                    continue
                bucket.append((counter, int(mono_ns)))
                accepted += 1
        return accepted

    def observe_visibility(self, visible, peer: Optional[str] = None
                           ) -> int:
        """Measure every pending entry whose dot the local planes now
        witness: ``visible`` maps actor → highest visible counter (any
        indexable — the per-actor max of the batch clock plane).  Runs
        at the session's converged check and after every op-log fold
        (the two moments visibility advances).  Returns the number of
        new samples; refreshes the per-peer gauges either way."""
        from ..utils import tracing

        measured = 0
        now = time.monotonic_ns()
        with self._lock:
            peers = ([peer] if peer is not None else list(self._peers))
            for name in peers:
                st = self._peers.get(name)
                if st is None:
                    continue
                for actor in list(st.pending):
                    try:
                        vis = int(visible[actor])
                    except (IndexError, KeyError, TypeError):
                        continue
                    keep = []
                    for counter, mono_ns in st.pending[actor]:
                        if counter <= vis:
                            st.samples.append(
                                max(0, now - mono_ns) / 1e9)
                            st.measured_frontier[actor] = max(
                                st.measured_frontier.get(actor, -1),
                                counter)
                            measured += 1
                        else:
                            keep.append((counter, mono_ns))
                    if keep:
                        st.pending[actor] = keep
                    else:
                        del st.pending[actor]
        if measured:
            tracing.count("sync.lag.samples", measured)
        self.refresh()
        return measured

    # -- gauges ---------------------------------------------------------------

    def refresh(self) -> None:
        """Recompute the per-peer lag gauges: p50/p99 over the sample
        window, the outstanding (shipped-but-not-yet-visible) entry
        count, and ``current_s`` — the age of the OLDEST outstanding
        entry (0 when everything shipped is visible: the quiescent
        fleet reads zero, which is the acceptance pin)."""
        now = time.monotonic_ns()
        with self._lock:
            views = []
            for name, st in self._peers.items():
                samples = sorted(st.samples)
                outstanding = sum(len(v) for v in st.pending.values())
                oldest = min(
                    (t for v in st.pending.values() for _, t in v),
                    default=None)
                views.append((name, samples, outstanding, oldest))
        reg = self._reg()
        for name, samples, outstanding, oldest in views:
            reg.gauge_set(f"sync.peer.{name}.lag_p50_s",
                          _percentile(samples, 0.50))
            reg.gauge_set(f"sync.peer.{name}.lag_p99_s",
                          _percentile(samples, 0.99))
            reg.gauge_set(f"sync.peer.{name}.lag_outstanding", outstanding)
            reg.gauge_set(
                f"sync.peer.{name}.lag_current_s",
                0.0 if oldest is None else max(0, now - oldest) / 1e9)

    # -- the convergence SLO ---------------------------------------------------

    def observe_round(self, converged: bool, wall_s: float) -> float:
        """Record one gossip round's outcome; returns (and publishes as
        ``sync.slo.converged_frac``) the fraction of the recent window
        that converged within the SLO budget."""
        ok = bool(converged) and float(wall_s) <= self.slo_budget_s
        with self._lock:
            self._slo.append(ok)
            frac = sum(self._slo) / len(self._slo)
        self._reg().gauge_set("sync.slo.converged_frac", frac)
        return frac

    # -- snapshots -------------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-ready per-peer lag state (what the demo prints)."""
        with self._lock:
            out = {}
            for name, st in self._peers.items():
                samples = sorted(st.samples)
                out[name] = {
                    "samples": len(st.samples),
                    "p50_s": _percentile(samples, 0.50),
                    "p99_s": _percentile(samples, 0.99),
                    "outstanding": sum(
                        len(v) for v in st.pending.values()),
                }
            return {
                "peers": out,
                "stamped_actors": len(self._stamps),
                "slo_window": len(self._slo),
                "slo_converged_frac": (
                    sum(self._slo) / len(self._slo) if self._slo else None),
            }

    def reset(self) -> None:
        with self._lock:
            self._stamps.clear()
            self._peers.clear()
            self._slo.clear()


# -- the default (process-global) tracker -------------------------------------

_DEFAULT: Optional[LagTracker] = None
_DEFAULT_LOCK = threading.Lock()


def lag_tracker() -> LagTracker:
    """The process-global lag tracker — what scheduler-less deployments
    and the examples stamp into by default (cluster nodes own private
    ones so multi-node in-process fleets keep their pairs apart)."""
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = LagTracker()
    return _DEFAULT
