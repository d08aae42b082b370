"""Unit tests for the bench harness machinery extracted into
``benchkit`` (VERDICT r4 item 8) — the pieces whose failure loses round
artifacts, tested without running the full bench.

The end-to-end contract stays where it was: the SMALL-mode full run the
rounds exercise.
"""

import json

import pytest


def _fresh_core(monkeypatch, budget="540"):
    """Import a pristine benchkit.core with a controlled budget env."""
    import sys

    monkeypatch.setenv("CRDT_BENCH_BUDGET_S", budget)
    for name in [n for n in sys.modules if n.startswith("benchkit")]:
        sys.modules.pop(name)
    import benchkit.core as core

    return core


def test_emit_prints_only_with_value(monkeypatch, capsys):
    core = _fresh_core(monkeypatch)
    core.emit(config4_merges_per_sec=5.0)  # no headline value yet
    assert capsys.readouterr().out == ""
    core.emit(value=2e6)
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["value"] == 2e6
    assert rec["vs_baseline"] == 0.2  # value / 1e7
    assert rec["config4_merges_per_sec"] == 5.0  # earlier field retained


def test_run_stage_skips_on_budget_and_records_errors(monkeypatch, capsys):
    core = _fresh_core(monkeypatch, budget="0")
    assert core.run_stage("x", 10, lambda: 1) is None
    core.emit(value=1.0)  # make the state printable
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["x_skipped"] == "budget"

    core = _fresh_core(monkeypatch, budget="10000")

    def boom():
        raise RuntimeError("kaput")

    assert core.failed_stages() == []
    assert core.run_stage("y", 1, boom) is None
    core.emit(value=1.0)
    out = capsys.readouterr().out
    assert "RuntimeError: kaput" in json.loads(
        out.strip().splitlines()[-1]
    )["y_error"]
    # the failure is recorded: the bench exits non-zero at the end
    assert core.failed_stages() == ["y"]
    # and a healthy stage returns its value
    assert core.run_stage("z", 1, lambda: 42) == 42
    assert core.failed_stages() == ["y"]
