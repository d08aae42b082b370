"""The program's own spans on the trace's clock.

The store names the host legs of its loops with ``tracing.span``, and
each span is also a ``jax.profiler.TraceAnnotation``: a host event of
the trace, on the same clock as the device's operations.  The readers
here sum those events' durations, clipped to the ``bench.window``
annotation, over every host thread.  Where the program has no such span
(a build before the spans existed) they return None.
"""

from __future__ import annotations

from benchmark.tracefile import _clip


def named(*names):
    """A matcher for the spans called exactly one of ``names``."""
    wanted = frozenset(names)
    return lambda name: name in wanted


def legs(trace, match) -> list:
    """The host events whose name ``match`` accepts, clipped to the
    window."""
    return [e for e in _clip(trace.host, trace.window) if match(e.name)]


def ms_per(trace, match, per) -> float | None:
    """Milliseconds of the matched spans, summed, per ``per`` (rounds,
    frames); None without a trace, a span or a count."""
    if trace is None or not per:
        return None
    found = legs(trace, match)
    if not found:
        return None
    return sum(e.end - e.start for e in found) / 1e6 / per


def mean_ms(trace, match) -> float | None:
    """Milliseconds per matched span, for a leg that runs once per
    step; None without a trace or a span."""
    if trace is None:
        return None
    return ms_per(trace, match, len(legs(trace, match)))
