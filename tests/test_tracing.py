"""Tracing subsystem (SURVEY.md §5): spans, counters, profiler capture."""

import jax
import jax.numpy as jnp

from crdt_tpu.utils import tracing


def test_span_aggregation():
    tr = tracing.Tracer()
    for _ in range(3):
        with tr.span("work"):
            pass
    with tr.span("other"):
        pass
    assert tr.stats["work"].count == 3
    assert tr.stats["other"].count == 1
    assert tr.stats["work"].total_s >= tr.stats["work"].max_s
    rep = tr.report()
    assert "work" in rep and "other" in rep


def test_disabled_tracer_records_nothing():
    tr = tracing.Tracer(enabled=False)
    with tr.span("work"):
        pass
    assert tr.stats == {}


def test_span_records_on_exception():
    tr = tracing.Tracer()
    try:
        with tr.span("boom"):
            raise ValueError("x")
    except ValueError:
        pass
    assert tr.stats["boom"].count == 1


def test_profile_context_tolerates_unsupported_backend(tmp_path):
    from crdt_tpu.ops import clock_ops

    with tracing.profile(str(tmp_path / "trace")):
        out = jax.jit(clock_ops.merge)(jnp.zeros((4, 4), jnp.uint32),
                                       jnp.ones((4, 4), jnp.uint32))
        jax.block_until_ready(out)


def test_profile_propagates_caller_exceptions(tmp_path):
    try:
        with tracing.profile(str(tmp_path / "trace2")):
            raise RuntimeError("inner")
    except RuntimeError as e:
        assert str(e) == "inner"
    else:
        raise AssertionError("exception swallowed")


def test_empty_report():
    assert "no spans" in tracing.Tracer().report()


def test_report_widens_to_longest_span_name():
    """Span names longer than the old fixed 32-char column must not tear
    the table: the name column widens to the longest name, so the count
    field sits at the same offset on every row."""
    tr = tracing.Tracer()
    long = "wire.sync.full_state_exchange.with.an.absurdly.long.suffix"
    assert len(long) > 32
    tr.add(long, 0.001)
    tr.add("short", 0.002)
    lines = tr.report().splitlines()
    header, row_a, row_b = lines[0], lines[1], lines[2]
    w = len(long)  # the longest name defines the column width
    assert header[:w].rstrip() == "span"
    assert header[w:w + 8] == f" {'count':>7}"
    row_long, row_short = (row_a, row_b) if row_a.startswith(long) \
        else (row_b, row_a)
    assert row_long[:w].rstrip() == long
    assert row_short[:w].rstrip() == "short"
    # both spans ran once: identical, aligned count fields
    assert row_long[w:w + 8] == row_short[w:w + 8] == f" {1:>7}"


def test_global_tracer_forwards_into_obs_registry():
    """The legacy span/count API re-routes into the typed obs registry
    (the tentpole's no-churn contract): counters land as registry
    counters, spans as log2 latency histograms."""
    from crdt_tpu.obs import metrics as obs_metrics

    tracing.reset()
    reg = obs_metrics.registry()
    tracing.count("wire.trace_forward_probe.native", 7)
    snap = reg.snapshot()
    assert snap["counters"]["wire.trace_forward_probe.native"] >= 7

    tracing.enable(True)
    try:
        with tracing.span("trace_forward_probe.span"):
            pass
    finally:
        tracing.enable(False)
        tracing.reset()
    h = reg.snapshot()["histograms"]["trace_forward_probe.span"]
    assert h["count"] >= 1 and h["sum"] >= 0.0


def test_forwarding_name_conflict_warns_instead_of_raising():
    """A name already claimed as another metric type in the obs registry
    must not make instrumentation raise through the instrumented code
    path (the executor.regrow counter-vs-span collision): forwarding
    drops the observation with one RuntimeWarning per name, and the
    tracer's own span stats still record."""
    import warnings

    from crdt_tpu.obs import metrics as obs_metrics

    tracing.reset()
    name = "trace_conflict_probe.span"
    obs_metrics.registry().counter_inc(name)  # claim the name as a counter
    tracing.enable(True)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for _ in range(2):  # second conflict must stay silent
                with tracing.span(name):
                    pass
    finally:
        tracing.enable(False)
    conflicts = [w for w in caught
                 if issubclass(w.category, RuntimeWarning)
                 and name in str(w.message)]
    assert len(conflicts) == 1
    assert tracing.get_tracer().stats[name].count == 2
    assert name not in obs_metrics.registry().snapshot()["histograms"]
    tracing.reset()


def test_bare_tracer_does_not_forward():
    """Non-global Tracer instances stay self-contained — tests and
    scoped measurements must not pollute the process registry."""
    from crdt_tpu.obs import metrics as obs_metrics

    tr = tracing.Tracer()
    tr.count("bare_tracer_probe.counter", 3)
    with tr.span("bare_tracer_probe.span"):
        pass
    snap = obs_metrics.registry().snapshot()
    assert "bare_tracer_probe.counter" not in snap["counters"]
    assert "bare_tracer_probe.span" not in snap["histograms"]


def test_profile_setup_failure_is_counted_and_flight_recorded(
        tmp_path, monkeypatch):
    """A swallowed profiler-setup failure must leave a diagnosable
    trail: the obs.profiler_unavailable counter counts every failure,
    the flight-recorder event fires ONCE per exception class — so "the
    trace directory is empty" is answerable from /events."""
    import jax

    from crdt_tpu.obs import events as obs_events
    from crdt_tpu.obs import metrics as obs_metrics

    class ProfilerBroken(RuntimeError):
        pass

    def boom(log_dir):
        raise ProfilerBroken("no profiler on this backend")

    monkeypatch.setattr(jax.profiler, "trace", boom)
    tracing._PROFILER_UNAVAILABLE_SEEN.discard("ProfilerBroken")
    before = obs_metrics.registry().counters_snapshot()
    for _ in range(2):  # caller body still runs, failures still count
        ran = False
        with tracing.profile(str(tmp_path / "trace")):
            ran = True
        assert ran
    after = obs_metrics.registry().counters_snapshot()
    assert after.get("obs.profiler_unavailable", 0) - \
        before.get("obs.profiler_unavailable", 0) == 2
    evs = [e for e in obs_events.recorder().snapshot(
               kind="obs.profiler_unavailable")
           if e["fields"]["error"] == "ProfilerBroken"]
    assert len(evs) == 1  # one event per exception class, not per failure
    assert "no profiler" in evs[0]["fields"]["detail"]
