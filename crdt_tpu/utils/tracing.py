"""Tracing & profiling — the observability subsystem (SURVEY.md §5).

The reference has no tracing at all (no logging crates in
`/root/reference/Cargo.toml:17-25`; its only observability is ``Display``
impls driven by `examples/pprint.rs`).  On TPU the equivalent first-class
needs are (a) wall-time accounting of the host legs around the device
work — merges are dispatched asynchronously, so a span names what the
host did while the device ran or waited — and (b) XLA profiler capture
for inspecting fusion/HBM behavior.  This module provides both,
dependency-free:

* :func:`span` / :class:`Tracer` — nestable wall-time spans aggregated
  into per-name statistics (count / total / mean / min / max).  When JAX
  is importable each span also emits a ``jax.profiler.TraceAnnotation``
  so spans line up with XLA ops in captured traces: per-kernel device
  time comes from that trace, not from blocking on kernel outputs.
* :func:`profile` — context manager around ``jax.profiler.trace`` writing
  a TensorBoard-loadable XLA trace directory; no-ops cleanly when the
  backend can't profile.

Everything is opt-in and zero-cost when unused; the global tracer is
disabled by default and enabled with :func:`enable` (or the
``CRDT_TRACE=1`` environment variable, read at import).

The global tracer also re-routes every observation into the typed
metric registry (:mod:`crdt_tpu.obs.metrics`): spans feed latency
histograms, counters feed registry counters — so each existing
``span``/``count``/``record_sync``/``record_wire`` call site shows up
on the live ``/metrics`` surface with no churn here.  Bare ``Tracer``
instances (tests, scoped measurements) do NOT forward unless
constructed with ``forward_metrics=True``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, Optional


@dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    min_s: float = float("inf")
    max_s: float = 0.0

    def add(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        self.min_s = min(self.min_s, dt)
        self.max_s = max(self.max_s, dt)

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


# metric names whose registry forwarding already warned about a
# name/type conflict — warn once per name, then drop silently
_CONFLICT_WARNED: set = set()


def _forward(observe: Callable[[str, float], None], name: str,
             value: float) -> None:
    """Forward one observation into the obs registry, never raising.

    The registry claims one metric type per name (a span and a counter
    sharing a name would conflict); instrumentation must degrade to a
    warning in that case, not raise ValueError through the code path it
    is instrumenting."""
    try:
        observe(name, value)
    except ValueError as e:
        if name not in _CONFLICT_WARNED:
            _CONFLICT_WARNED.add(name)
            warnings.warn(
                f"dropping metric forwarding for {name!r}: {e}",
                RuntimeWarning, stacklevel=3,
            )


@dataclass
class Tracer:
    """Aggregates named wall-time spans and event counters; thread-safe.

    Counters are the *path-taken* half of observability (SURVEY §5): the
    wire codecs count native-vs-fallback blobs per call so a silent
    fallback regression is visible in the counters, not just in wall
    time.  Unlike spans they are always on — one dict increment per
    *bulk call* (not per blob) is free — so ``enabled`` gates spans only.
    """

    enabled: bool = True
    stats: Dict[str, SpanStats] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)
    # re-route observations into the typed obs registry (the global
    # tracer sets this, so every legacy call site feeds /metrics)
    forward_metrics: bool = False
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)
    _reg: Any = field(default=None, repr=False)

    def _registry(self):
        # cached: count() is always-on, so the import-machinery lookup
        # must be paid once, not per increment
        if self._reg is None:
            from ..obs import metrics as obs_metrics

            self._reg = obs_metrics.registry()
        return self._reg

    def add(self, name: str, dt: float) -> None:
        """Record one observation for ``name`` (thread-safe)."""
        with self._lock:
            self.stats.setdefault(name, SpanStats()).add(dt)
        if self.forward_metrics:
            # span latency histogram (log2 buckets), seconds
            _forward(self._registry().observe, name, dt)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the event counter ``name`` (thread-safe).

        Zero increments are dropped so snapshots only carry counters
        that actually fired — a fallback counter that never appears is
        distinguishable from one that counted 0 this interval."""
        if n == 0:
            return
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + int(n)
        if self.forward_metrics:
            _forward(self._registry().counter_inc, name, int(n))

    def counters(self) -> Dict[str, int]:
        """A snapshot copy of all event counters."""
        with self._lock:
            return dict(self.counts)

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        if not self.enabled:
            yield
            return
        annot = _trace_annotation(name)
        t0 = time.perf_counter()
        try:
            with annot:
                yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def reset(self) -> None:
        with self._lock:
            self.stats.clear()
            self.counts.clear()

    def report(self) -> str:
        """Human-readable table, longest total first."""
        with self._lock:
            # snapshot under the lock so rows aren't torn by concurrent adds
            rows = sorted(
                ((name, dataclasses.replace(s)) for name, s in self.stats.items()),
                key=lambda kv: kv[1].total_s,
                reverse=True,
            )
            counter_rows = sorted(self.counts.items())
        if not rows and not counter_rows:
            return "(no spans recorded)"
        # the name column widens to the longest name so long span names
        # (wire.sync.*) never tear the table out of alignment
        cw = max(
            [48] + [len(name) for name, _ in counter_rows]
        ) if counter_rows else 48
        if not rows:
            return "\n".join(f"{name:<{cw}} {n:>12}" for name, n in counter_rows)
        w = max([32] + [len(name) for name, _ in rows])
        lines = [
            f"{'span':<{w}} {'count':>7} {'total':>10} {'mean':>10} "
            f"{'min':>10} {'max':>10}"
        ]
        for name, s in rows:
            lines.append(
                f"{name:<{w}} {s.count:>7} {s.total_s*1e3:>9.2f}ms "
                f"{s.mean_s*1e3:>9.3f}ms {s.min_s*1e3:>9.3f}ms "
                f"{s.max_s*1e3:>9.3f}ms"
            )
        cw = max(cw, w)
        lines.extend(f"{name:<{cw}} {n:>12}" for name, n in counter_rows)
        return "\n".join(lines)


def _trace_annotation(name: str):
    """A jax.profiler.TraceAnnotation when JAX is importable, else a no-op.

    Only attaches annotations if jax is ALREADY imported — tracing scalar
    code must not drag the device runtime in."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


# -- global tracer -----------------------------------------------------------

_GLOBAL = Tracer(enabled=os.environ.get("CRDT_TRACE") == "1",
                 forward_metrics=True)


def get_tracer() -> Tracer:
    return _GLOBAL


def enable(on: bool = True) -> None:
    _GLOBAL.enabled = on


def span(name: str):
    """``with tracing.span("orswot.merge"): ...`` on the global tracer."""
    return _GLOBAL.span(name)


def count(name: str, n: int = 1) -> None:
    """Increment an always-on event counter on the global tracer (the
    wire codecs' native-vs-fallback accounting; one increment per bulk
    call, so no ``enabled`` gate)."""
    _GLOBAL.count(name, n)


def counters() -> Dict[str, int]:
    """Snapshot of the global tracer's event counters."""
    return _GLOBAL.counters()


def counters_since(before: Dict[str, int]) -> Dict[str, int]:
    """Counter deltas vs an earlier :func:`counters` snapshot — the
    per-stage view the bench uses: snapshot, run a stage, diff."""
    now = _GLOBAL.counters()
    out = {k: v - before.get(k, 0) for k, v in now.items()}
    return {k: v for k, v in out.items() if v}


def native_fraction(deltas: Dict[str, int], prefix: str) -> Optional[float]:
    """The fraction of blobs that took the native path for one wire
    stage, from a :func:`counters_since` delta dict.

    ``prefix`` is the counter family (e.g. ``"wire.orswot.from_wire"``);
    the convention is ``<prefix>.native`` / ``<prefix>.fallback`` blob
    counts plus ``<prefix>.fallback_reason.<why>`` detail counters.
    Returns None when the stage moved no blobs."""
    native = deltas.get(f"{prefix}.native", 0)
    fallback = deltas.get(f"{prefix}.fallback", 0)
    total = native + fallback
    if total == 0:
        return None
    return native / total


def record_sync(leg: str, *, nbytes: int = 0, objects: int = 0) -> None:
    """Count one sync-protocol frame under the always-on
    ``wire.sync.<leg>.{bytes,objects}`` counters (legs: ``digest`` /
    ``delta`` / ``full``) — the per-phase bytes-on-wire accounting the
    bench publishes as ``delta_ratio`` next to ``native_fraction``.
    One increment pair per FRAME, not per object, so it is free at any
    fleet scale (same discipline as :func:`record_wire
    <crdt_tpu.batch.wirebulk.record_wire>`).  Each frame's size also
    lands in a log2-bucketed histogram so the export answers "how big
    are my delta frames" without a bench diff."""
    count(f"wire.sync.{leg}.bytes", nbytes)
    count(f"wire.sync.{leg}.objects", objects)
    if _GLOBAL.forward_metrics:
        _forward(_GLOBAL._registry().observe,
                 f"wire.sync.{leg}.frame_bytes", nbytes)


def delta_ratio(delta_bytes: int, full_state_bytes: int) -> Optional[float]:
    """Delta payload bytes over the full-state bytes the same exchange
    would have cost — the O(divergence) claim as one number (≤ ~0.01 +
    framing at 1% divergence; 1.0+ means the delta path degenerated).
    None when the full-state reference size is unknown or zero."""
    if not full_state_bytes:
        return None
    return delta_bytes / full_state_bytes


def report() -> str:
    return _GLOBAL.report()


def reset() -> None:
    _GLOBAL.reset()


# profiler-setup failures already flight-recorded, one event per
# exception class (the counter keeps counting every failure)
_PROFILER_UNAVAILABLE_SEEN: set = set()


def _profiler_unavailable(exc: BaseException, log_dir: str) -> None:
    """Profiler setup failed: count it always, flight-record it once
    per exception class — so "the trace directory is empty" is
    diagnosable from ``/events`` instead of silently shrugged off."""
    count("obs.profiler_unavailable")
    cls = type(exc).__name__
    if cls in _PROFILER_UNAVAILABLE_SEEN:
        return
    _PROFILER_UNAVAILABLE_SEEN.add(cls)
    try:
        from ..obs import events as obs_events

        obs_events.record(
            "obs.profiler_unavailable", error=cls,
            detail=str(exc)[:200], log_dir=log_dir,
        )
    except Exception:  # diagnostics must never fail the traced caller
        pass


@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[None]:
    """Capture an XLA profiler trace into ``log_dir`` (TensorBoard format).

    Swallows backend "profiling unsupported" errors (e.g. remote-TPU
    tunnels) so callers can leave this on unconditionally — caller
    exceptions still propagate.  A swallowed setup failure is no longer
    silent: it increments ``obs.profiler_unavailable`` and leaves a
    one-time-per-exception-class flight-recorder event naming the
    exception, so an empty trace directory is diagnosable from
    ``/events``."""
    import jax

    try:
        ctx = jax.profiler.trace(log_dir)
        ctx.__enter__()
    except Exception as e:
        _profiler_unavailable(e, log_dir)
        ctx = None
    try:
        yield
    finally:
        if ctx is not None:
            try:
                ctx.__exit__(None, None, None)
            except Exception:
                pass
