"""Typed metric registry — counters, gauges, log2-bucketed histograms.

The flat span/counter dicts in :mod:`crdt_tpu.utils.tracing` only become
legible when a caller diffs snapshots after the fact; a live export
surface (:mod:`crdt_tpu.obs.export`) needs metrics with *types*, because
a Prometheus scrape renders a counter, a gauge, and a histogram
differently and a consumer alerts on them differently:

* :class:`Counter` — monotonically increasing event counts (the
  always-on ``wire.*`` native-vs-fallback accounting, sync frame
  bytes).  Resets only with the registry.
* :class:`Gauge` — a point-in-time level (wire-loop staging-pool
  occupancy, parse-queue depth, per-peer digest divergence).  Last
  write wins.
* :class:`Histogram` — log2-bucketed distributions (span latencies,
  sync frame sizes).  Power-of-two buckets make ``observe`` one
  ``frexp`` + dict increment — cheap enough to stay always-on — while
  still answering "how many syncs took >128 ms" from the export.

Everything here is dependency-free and import-light: no JAX, no numpy.
Thread-safety: the one-shot registry methods (``counter_inc`` /
``gauge_set`` / ``observe``) and ``snapshot`` run under the registry
lock; a :class:`Counter` handle locks itself so cached-handle ``inc``
never drops increments; :class:`Gauge` handle writes are last-write-
wins by contract; :class:`Histogram` handles should be fed through
``registry.observe`` (multi-field updates need the registry lock to
keep snapshots untorn).  The
existing :mod:`crdt_tpu.utils.tracing` API re-routes into the default
registry, so every current ``span``/``count``/``record_sync``/
``record_wire`` call site feeds this module with no churn at the call
sites (see ``Tracer.forward_metrics``).
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Iterator, Optional, Tuple


class Counter:
    """A monotonically increasing event count.

    ``inc`` takes the counter's own lock: handles are cached by hot
    paths and mutated outside the registry lock, and a read-modify-write
    without one can drop increments under concurrent writers — which the
    monotonic-counter contract forbids.
    """

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += int(n)


class Gauge:
    """A point-in-time level; last write wins.

    Handle mutation is deliberately unsynchronized: a gauge tolerates a
    lost write by contract (the racing ``set`` that wins *is* the
    current level).  ``inc`` is read-modify-write — only use it on
    gauges with a single writer.
    """

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def inc(self, n: float = 1.0) -> None:
        self.value += float(n)


def log2_bucket(v: float) -> int:
    """The log2 bucket exponent for one observation: ``frexp`` puts
    ``v = m * 2**e`` with ``0.5 <= m < 1`` in ``[2**(e-1), 2**e)``;
    pulling exact powers of two (``m == 0.5``) down one exponent makes
    bucket ``e`` hold ``(2**(e-1), 2**e]``, so 4.0 exports under
    ``le="4"``, not ``le="8"`` (Prometheus ``le`` bounds are
    inclusive).  Non-positive values land in the floor bucket.
    Exposed so always-on instruments (the kernel observatory's
    per-call path) can bucket locally and merge via
    :meth:`MetricsRegistry.observe_aggregate`."""
    if v > 0.0:
        m, e = math.frexp(v)
        return e - 1 if m == 0.5 else e
    return Histogram.ZERO_BUCKET


class Histogram:
    """Log2-bucketed distribution: bucket ``e`` counts observations in
    ``(2**(e-1), 2**e]``.  Non-positive observations land in a floor
    bucket (exponent :data:`ZERO_BUCKET`) so a zero-length span is
    counted, not lost.  Sum/count/min/max ride along so the export can
    emit Prometheus ``_sum``/``_count`` and the mean survives bucketing.

    Observe through ``registry.observe`` under concurrency: ``observe``
    updates several fields, and only the registry lock keeps a
    concurrent ``snapshot`` from seeing them torn.
    """

    ZERO_BUCKET = -1075  # below the smallest subnormal double's exponent

    __slots__ = ("name", "count", "sum", "min", "max", "buckets")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.buckets: Dict[int, int] = {}

    def observe(self, v: float) -> None:
        v = float(v)
        e = log2_bucket(v)
        self.buckets[e] = self.buckets.get(e, 0) + 1
        self.count += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v

    def cumulative(self) -> Iterator[Tuple[float, int]]:
        """``(upper_bound, cumulative_count)`` pairs in ascending bound
        order — the Prometheus ``le`` series (without the +Inf bucket,
        which equals :attr:`count`)."""
        running = 0
        for e in sorted(self.buckets):
            running += self.buckets[e]
            bound = 0.0 if e == self.ZERO_BUCKET else math.ldexp(1.0, e)
            yield bound, running


class MetricsRegistry:
    """One process's named metrics, behind one lock.

    Names are free-form dotted strings (``wire.sync.digest.bytes``);
    the Prometheus exporter sanitizes them at scrape time, so hot paths
    never pay for name mangling.  A name is permanently one type —
    re-registering ``x`` as a gauge after counting it raises, because a
    silent type flip would corrupt the export.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    def _claim(self, name: str, kind: str) -> None:
        for other_kind, table in (("counter", self._counters),
                                  ("gauge", self._gauges),
                                  ("histogram", self._histograms)):
            if other_kind != kind and name in table:
                raise ValueError(
                    f"metric {name!r} is already a {other_kind}, "
                    f"cannot re-register as a {kind}"
                )

    # -- typed handles (hot paths hold these to skip the dict lookup) --------

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                self._claim(name, "counter")
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                self._claim(name, "gauge")
                g = self._gauges[name] = Gauge(name)
            return g

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                self._claim(name, "histogram")
                h = self._histograms[name] = Histogram(name)
            return h

    # -- one-shot observations ------------------------------------------------

    def counter_inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                self._claim(name, "counter")
                c = self._counters[name] = Counter(name)
            c.inc(n)

    def gauge_set(self, name: str, v: float) -> None:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                self._claim(name, "gauge")
                g = self._gauges[name] = Gauge(name)
            g.set(v)

    def observe(self, name: str, v: float) -> None:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                self._claim(name, "histogram")
                h = self._histograms[name] = Histogram(name)
            h.observe(v)

    def observe_aggregate(self, name: str, buckets: Dict[int, int],
                          count: int, total: float,
                          vmin: float, vmax: float) -> None:
        """Merge a locally-aggregated log2 distribution (buckets keyed
        by :func:`log2_bucket` exponent) into ``name`` in one lock
        acquisition — how deferred instruments (the kernel
        observatory's per-call wall accounting) publish without paying
        a registry round-trip per observation."""
        if count <= 0:
            return
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                self._claim(name, "histogram")
                h = self._histograms[name] = Histogram(name)
            for e, c in buckets.items():
                h.buckets[e] = h.buckets.get(e, 0) + c
            h.count += count
            h.sum += total
            if vmin < h.min:
                h.min = vmin
            if vmax > h.max:
                h.max = vmax

    # -- snapshots ------------------------------------------------------------

    def snapshot(self) -> dict:
        """A JSON-ready consistent copy: ``{"counters": {name: int},
        "gauges": {name: float}, "histograms": {name: {count, sum, min,
        max, buckets: {exponent: count}}}}`` — taken under the lock, so
        a scrape concurrent with writers never sees a torn histogram."""
        with self._lock:
            return {
                "counters": {k: c.value for k, c in self._counters.items()},
                "gauges": {k: g.value for k, g in self._gauges.items()},
                "histograms": {
                    k: {
                        "count": h.count,
                        "sum": h.sum,
                        "min": (None if h.count == 0 else h.min),
                        "max": (None if h.count == 0 else h.max),
                        "buckets": dict(h.buckets),
                    }
                    for k, h in self._histograms.items()
                },
            }

    def counters_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {k: c.value for k, c in self._counters.items()}

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()


# -- the default (process-global) registry -----------------------------------

_DEFAULT: Optional[MetricsRegistry] = None
_DEFAULT_LOCK = threading.Lock()


def registry() -> MetricsRegistry:
    """The process-global registry every always-on instrument feeds and
    the ``/metrics`` exporter scrapes."""
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = MetricsRegistry()
    return _DEFAULT
