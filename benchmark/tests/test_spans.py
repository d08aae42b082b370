"""The span readers (``spans.py`` and the per-layer metrics that read the
program's host legs), on hand-made traces and on traced CPU runs of the
tiny cells."""

from __future__ import annotations

import os

import pytest

from benchmark import spans
from benchmark.run import LayerView, load_module
from benchmark.tests.conftest import BENCH
from benchmark.tracefile import Event, Trace

SEED = (1 << 33) + 5


def _ev(name, lo, hi):
    return Event(name, lo, hi)


def _view(host, **stats):
    trace = Trace(window=(100, 1100), devices=[],
                  host=[_ev("bench.window", 100, 1100)] + host)
    return LayerView(trace=trace, stats=stats, traffic={},
                     device_kind="TPU v5 lite", chips=1)


def _read(metric, view):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    return load_module(path, "bench_metric").read(view)


def test_legs_are_clipped_to_the_window_and_summed_by_name():
    view = _view([_ev("wireloop.put", 150, 250), _ev("wireloop.put", 300, 400),
                  # a leg the window cuts: only its 50 ns inside count
                  _ev("wireloop.put", 1050, 1200),
                  # before the window: out
                  _ev("wireloop.put", 0, 90),
                  _ev("wireloop.parse", 150, 400)])
    found = spans.legs(view.trace, spans.named("wireloop.put"))
    assert [(e.start, e.end) for e in found] == [(150, 250), (300, 400),
                                                 (1050, 1100)]
    assert spans.ms_per(view.trace, spans.named("wireloop.put"), 2) == \
        pytest.approx(250 / 1e6 / 2)
    assert spans.mean_ms(view.trace, spans.named("wireloop.parse")) == \
        pytest.approx(250 / 1e6)


def test_no_trace_no_span_or_no_count_reads_none():
    view = _view([_ev("wireloop.put", 150, 250)])
    put = spans.named("wireloop.put")
    assert spans.ms_per(None, put, 2) is None
    assert spans.ms_per(view.trace, put, 0) is None
    assert spans.ms_per(view.trace, spans.named("wireloop.fetch"), 2) is None
    assert spans.mean_ms(None, put) is None
    assert spans.mean_ms(view.trace, spans.named("mesh.step.fetch")) is None


def test_wire_legs_per_round_by_hand():
    view = _view([_ev("wireloop.put", 100, 300), _ev("wireloop.put", 400, 500),
                  _ev("wireloop.fetch", 500, 540),
                  _ev("wireloop.encode", 540, 700),
                  _ev("wireloop.parse", 100, 900)], rounds=2)
    assert _read("put_ms.wire", view) == pytest.approx(300 / 1e6 / 2)
    assert _read("fetch_ms.wire", view) == pytest.approx(40 / 1e6 / 2)
    assert _read("encode_ms.wire", view) == pytest.approx(160 / 1e6 / 2)


def test_mesh_fetch_per_step_by_hand():
    view = _view([_ev("mesh.step.dispatch", 100, 110),
                  _ev("mesh.step.wait", 110, 300),
                  _ev("mesh.step.fetch", 300, 380),
                  _ev("mesh.step.fetch", 600, 720),
                  _ev("bench.step", 100, 720)])
    assert _read("fetch_ms.mesh", view) == pytest.approx(100 / 1e6)


def test_read_host_legs_leave_out_the_wait_and_sum_both_clients():
    view = _view([
        # client 1
        _ev("serve.leg.decode", 100, 110), _ev("serve.leg.admit", 110, 115),
        _ev("serve.leg.dispatch", 115, 130), _ev("serve.leg.wait", 130, 400),
        _ev("serve.leg.fetch", 400, 450), _ev("serve.leg.heat", 450, 470),
        _ev("serve.leg.encode", 470, 480),
        # client 2, overlapping client 1's wait
        _ev("serve.leg.decode", 200, 220), _ev("serve.leg.wait", 220, 600),
        _ev("bench.frame", 100, 480)], frames=2)
    host = 10 + 5 + 15 + 50 + 20 + 10 + 20
    assert _read("host_ms.reads", view) == pytest.approx(host / 1e6 / 2)


@pytest.mark.parametrize("metric", ["put_ms.wire", "fetch_ms.wire",
                                    "encode_ms.wire", "fetch_ms.mesh",
                                    "host_ms.reads"])
def test_a_program_without_the_spans_reads_none(metric):
    """A build whose loops name no host legs (the program before the
    spans) leaves the metric out of the line instead of failing."""
    view = _view([_ev("bench.rounds", 100, 900), _ev("bench.frame", 100, 500),
                  _ev("bench.step", 100, 300)], rounds=3, frames=4)
    assert _read(metric, view) is None
    assert _read(metric, LayerView(None, {"rounds": 3, "frames": 4}, {},
                                   "TPU v5 lite", 1)) is None


@pytest.mark.parametrize("cell, metrics", [
    # on the CPU the wire loop folds with the native engine, which puts
    # nothing on a device: put_ms.wire is the chip's (jnp fold) alone
    ("wire.tiny", {"fetch_ms.wire", "encode_ms.wire"}),
    ("mesh.tiny", {"fetch_ms.mesh"}),
    ("reads.tiny", {"host_ms.reads"}),
])
def test_traced_run_reads_the_host_legs(run_tiny, cell, metrics):
    """The CPU trace holds the program's host spans (and no device
    plane), so each cell's span metrics read a positive value."""
    out = run_tiny(cell, SEED, trace=True)
    assert out["correct"], out["checks"]
    assert metrics <= set(out["metrics"])
    assert all(out["metrics"][m]["value"] > 0 for m in metrics)
    assert all(out["metrics"][m]["unit"] == "ms" for m in metrics)
