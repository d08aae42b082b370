"""The trace reduction, on a small trace recorded on a v5e chip
(``record_trace.py``: two joins of 2 x 2,048 objects in a
``bench.window`` annotation) and on hand-made events."""

from __future__ import annotations

import os

import pytest

from benchmark import tracefile, work
from benchmark.tracefile import Device, Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "fold_small.xplane.pb")
FOLD = r"^jit__fold_tree"


@pytest.fixture(scope="module")
def chip_trace():
    return tracefile.reduce_xplane(DATA, devices=1)


def test_recorded_trace_has_the_window_and_one_chip(chip_trace):
    t = chip_trace
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    assert 0 < t.window_s < 1
    busy = t.busy_s()[0]
    assert 0 < busy <= t.window_s


def test_programs_are_found_by_name(chip_trace):
    t = chip_trace
    assert t.program_calls(FOLD) == [2]
    fold_s = t.program_s(FOLD)[0]
    # the fold program's executions hold (nearly) all of the busy time
    assert 0.5 * t.busy_s()[0] < fold_s <= t.window_s


def test_ops_are_named_short_and_ranked(chip_trace):
    top = chip_trace.top_ops(10)
    assert len(top) == 10
    assert all(" = " not in name and not name.startswith("%")
               for name, _ in top)
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0


def test_idle_share_and_gaps(chip_trace):
    idle = tracefile.idle_pct(chip_trace)
    assert 0 <= idle < 100
    gaps = chip_trace.idle_gaps(5)
    assert gaps and all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                          reverse=True)


def test_roofline_of_the_recorded_joins(chip_trace):
    nbytes = work.join_bytes(2, 2048, 4936) * 2
    pct = work.roofline_pct(nbytes, chip_trace.program_s(FOLD)[0],
                            "TPU v5 lite")
    assert 0 < pct < 100


def _ev(name, lo, hi):
    return Event(name, lo, hi)


def test_union_clip_and_gaps_by_hand():
    dev = Device("/device:TPU:0",
                 ops=[_ev("a", 0, 30), _ev("b", 20, 40), _ev("c", 60, 70),
                      _ev("d", 90, 200)],
                 modules=[_ev("jit__fold_tree(1)", 0, 40),
                          _ev("jit_other(2)", 60, 70),
                          _ev("jit__fold_tree(1)", 90, 200)])
    host = [_ev("bench.window", 10, 100), _ev("fetch", 40, 60),
            _ev("round", 0, 100)]
    t = Trace(window=(10, 100), devices=[dev], host=host)
    assert t.window_s == pytest.approx(90e-9)
    # busy: [10,40] + [60,70] + [90,100] = 50 ns of 90
    assert t.busy_s() == [pytest.approx(50e-9)]
    assert tracefile.idle_pct(t) == pytest.approx(100 * 40 / 90)
    assert t.program_calls(FOLD) == [2]
    assert t.program_s(FOLD) == [pytest.approx(40e-9)]
    # gaps [40,60] and [70,90]; the first is named by the shortest host
    # event over its middle
    gaps = t.idle_gaps(2)
    assert [g[1] for g in gaps] == [pytest.approx(20e-9)] * 2
    assert gaps[0][0] == "fetch" and gaps[1][0] == "round"


def test_a_trace_without_the_window_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        tracefile.find_xplane(str(tmp_path))
