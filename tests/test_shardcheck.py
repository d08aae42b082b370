"""shardcheck self-tests: the sharding-contract tier's repo gate, the
SC01-SC05 fixture matrix, 100% contract coverage, the stale-sanction
re-flag, and the tier-1 regression pin on un-declared manifest rows.

Like tests/test_kernelcheck.py this module imports jax (tracing under
abstract meshes is the whole point) and runs under the `analysis`
marker.
"""

import json
import os
import subprocess
import sys

import pytest

from crdt_tpu.analysis.core import Baseline, ParsedFile, repo_root
from crdt_tpu.analysis.kernels import MANIFEST, SHARD_CLASSES

pytestmark = pytest.mark.analysis

REPO = repo_root()
FIXDIR = os.path.join(REPO, "tests", "analysis_fixtures")
sys.path.insert(0, FIXDIR)


def _run_specs(specs, baseline=None):
    from crdt_tpu.analysis.shard_rules import run_shardcheck

    return run_shardcheck(specs=specs, baseline=baseline)


# ---- the repo-wide gate -----------------------------------------------------


@pytest.fixture(scope="module")
def repo_gate():
    """One subprocess run of the real CLI gate, shared by the gate
    tests: `python -m crdt_tpu.analysis --shard --json` exactly as
    scripts/ci.sh invokes it — CPU backend, no TPU required."""
    proc = subprocess.run(
        [sys.executable, "-m", "crdt_tpu.analysis", "--shard", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    return proc


def test_repo_gate_exits_zero_with_empty_baseline(repo_gate):
    """The shipped tree is contract-clean: exit 0, zero live findings,
    zero trace errors, nothing parked for the SC rules in the
    baseline (pragmas with justifications are the only sanctions)."""
    assert repo_gate.returncode == 0, repo_gate.stdout + repo_gate.stderr
    out = json.loads(repo_gate.stdout)
    assert out["ok"] is True
    assert out["findings"] == []
    assert out["shardcheck"]["trace_errors"] == []
    with open(os.path.join(REPO, "crdt_tpu", "analysis",
                           "baseline.json")) as fh:
        entries = json.load(fh)
    assert [e for e in entries if e["rule"].startswith("SC")] == []


def test_repo_gate_covers_every_contract(repo_gate):
    """Every manifest row carries a contract; every
    buildable non-host_only row traced, with mesh-shaped cases; the
    provenance walker saw no unknown primitives (an unknown prim is a
    silently-unanalyzed data path)."""
    out = json.loads(repo_gate.stdout)
    sc = out["shardcheck"]
    assert sc["kernels"] == len(MANIFEST)
    assert sum(sc["contracts"].values()) == len(MANIFEST)
    assert set(sc["contracts"]) <= set(SHARD_CLASSES)
    n_traceable = sum(
        1 for s in MANIFEST
        if s.build is not None and s.sharding.sclass != "host_only")
    assert sc["traced"] == n_traceable
    assert sc["cases"] > sc["traced"]          # ladders, not single traces
    assert sc["mesh_cases"] > 0                # shard-shaped re-traces ran
    assert sc["unknown_prims"] == []
    # declared-no-trace rows are reported, never silent
    assert {s["kernel"] for s in sc["skipped"]} == {
        s.name for s in MANIFEST
        if s.build is None or s.sharding.sclass == "host_only"}
    # the SC03 lexical scan actually walked the hot-path packages
    assert sc["sc03_files"] > 10


def test_every_manifest_row_declares_a_contract():
    """100% coverage asserted directly: `sharding=None` rows cannot
    ship (the kernel-manifest tier-1 rule pins the same invariant)."""
    missing = [s.name for s in MANIFEST if s.sharding is None]
    assert missing == []
    for s in MANIFEST:
        assert s.sharding.sclass in SHARD_CLASSES, s.name


def test_reduction_collective_declarations_match_traces(repo_gate):
    """The report's per-kernel lowered-collective sets agree with the
    manifest declarations — SC02 holding on the real tree, visible in
    the artifact rather than only as absence-of-findings."""
    sc = json.loads(repo_gate.stdout)["shardcheck"]
    declared = {s.name: sorted(s.sharding.collectives) for s in MANIFEST}
    for kernel, lowered in sc["collectives"].items():
        assert sorted(lowered) == declared[kernel], kernel


# ---- fixture matrix: every rule fires with the right id + anchor -----------


@pytest.fixture(scope="module")
def bad_result():
    import shard_bad

    result, report = _run_specs(shard_bad.SPECS)
    assert report.trace_errors == [], report.trace_errors
    return result


@pytest.mark.parametrize("rule,kernel", [
    ("SC01", "fixture_shard.cross_object"),
    ("SC02", "fixture_shard.undeclared_psum"),
    ("SC02", "fixture_shard.phantom_pmax"),
    ("SC04", "fixture_shard.ragged_rung"),
    ("SC05", "fixture_shard.budget_blowout"),
])
def test_bad_fixture_fails_with_rule_and_kernel_name(bad_result, rule,
                                                     kernel):
    hits = [f for f in bad_result.findings if f.rule == rule]
    assert hits, f"{rule} produced no finding"
    assert any(kernel in f.message for f in hits), (
        rule, [f.message for f in hits])
    for f in hits:
        assert f.path and f.line >= 1


def test_bad_fixture_findings_anchor_in_the_fixture(bad_result):
    """SC01 and the extra-collective SC02 anchor at the offending
    equation's source line in the fixture — the 'equation user frame'
    acceptance: a pragma ON THAT LINE is what sanctions the idiom."""
    for rule in ("SC01", "SC02"):
        hits = [f for f in bad_result.findings if f.rule == rule]
        assert any(
            f.path == "tests/analysis_fixtures/shard_bad.py" and f.line > 1
            for f in hits), (rule, [(f.path, f.line) for f in hits])


def test_sc03_fires_on_mounted_hot_path_source():
    """The lexical SC03 scan flags an int() round-trip on a jitted
    kernel's output when the source sits at a mesh hot-path rel."""
    import shard_bad

    from crdt_tpu.analysis.shard_rules import check_host_roundtrips

    pf = ParsedFile("x", "crdt_tpu/batch/_fixture_sc03.py",
                    shard_bad.SC03_BAD_SRC)
    findings = check_host_roundtrips([pf], specs=())
    assert [f.rule for f in findings] == ["SC03"]
    assert "int()" in findings[0].message
    assert findings[0].line == shard_bad.SC03_BAD_SRC.splitlines().index(
        "    return int(total)") + 1


def test_sc03_ok_twin_clean_or_pragma_suppressed():
    import shard_ok

    from crdt_tpu.analysis.shard_rules import check_host_roundtrips

    pf = ParsedFile("x", "crdt_tpu/batch/_fixture_sc03.py",
                    shard_ok.SC03_OK_SRC)
    findings = check_host_roundtrips([pf], specs=())
    # the sample-point sin fires and its pragma suppresses it — the
    # twin is analyzed, not inert
    assert [f.rule for f in findings] == ["SC03"]
    assert pf.suppressed("SC03", findings[0].line)


def test_ok_twins_suppressed_or_clean():
    import shard_ok

    result, report = _run_specs(shard_ok.SPECS)
    assert report.trace_errors == [], report.trace_errors
    assert result.findings == [], [f.render() for f in result.findings]
    # the pragma'd SC01 sin really fired and was suppressed in the
    # fixture file — not inert
    fixture_sup = [f for f in result.suppressed
                   if f.path == "tests/analysis_fixtures/shard_ok.py"]
    assert {f.rule for f in fixture_sup} == {"SC01"}
    assert result.stale_baseline == []


def test_routed_gather_is_sanctioned_only_when_declared():
    """The same gather flips SC01 on/off with the `routed` declaration
    — the exemption is the contract, not walker blindness."""
    import dataclasses

    import shard_ok

    spec = next(s for s in shard_ok.SPECS
                if s.name == "fixture_shard.routed_gather")
    undeclared = dataclasses.replace(
        spec, sharding=dataclasses.replace(spec.sharding, routed=()))
    result, _ = _run_specs([undeclared])
    assert any(f.rule == "SC01" for f in result.findings), [
        f.render() for f in result.findings]


def test_baseline_parks_a_contract_finding():
    """The shared baseline machinery covers SC findings (justification
    required by the Baseline schema, same as the other tiers)."""
    import shard_bad

    spec = [s for s in shard_bad.SPECS
            if s.name == "fixture_shard.phantom_pmax"]
    baseline = Baseline([{
        "rule": "SC02",
        "path": "tests/analysis_fixtures/shard_bad.py",
        "message": "kernel fixture_shard.phantom_pmax: declares*",
        "justification": "fixture: demonstrates baseline parking for "
                         "site-anchored contract findings",
    }])
    result, _ = _run_specs(spec, baseline=baseline)
    assert result.findings == [], [f.render() for f in result.findings]
    assert [f.rule for f in result.baselined] == ["SC02"]


def test_stale_sc_sanction_reflagged_when_contract_traces_clean(
        monkeypatch):
    """A pragma sanctioning SC01 on a kernel that now traces clean is
    itself a live finding (the KC01 stale-sanction discipline): fix
    the sin in the pragma'd fixture kernel and the suppression re-arms
    as 'stale SC01 sanction'."""
    import shard_ok

    # keep the pragma'd file, but swap the kernel body for a clean one
    def _b_clean():
        import jax  # noqa: F401

        def center(x):
            return x * 2

        from crdt_tpu.analysis.kernels import TraceCase
        return [TraceCase("r0", center, shard_ok._b_pragma_sum()[0].args)]

    import dataclasses

    spec = next(s for s in shard_ok.SPECS
                if s.name == "fixture_shard.pragma_sum")
    clean = dataclasses.replace(spec, build=_b_clean)
    result, _ = _run_specs([clean])
    stale = [f for f in result.findings
             if f.rule == "SC01" and "stale SC01 sanction" in f.message
             and f.path == "tests/analysis_fixtures/shard_ok.py"]
    assert stale, [f.render() for f in result.findings]


# ---- the tier-1 regression pin ---------------------------------------------


def test_undeclared_manifest_row_fails_source_lint(monkeypatch):
    """Un-declaring any manifest row's sharding contract fails the
    tier-1 kernel-manifest rule — contract coverage can never silently
    regress below 100%."""
    import dataclasses

    import crdt_tpu.analysis.kernels as kernels
    from crdt_tpu.analysis import run_lint

    stripped = (dataclasses.replace(MANIFEST[0], sharding=None),
                ) + tuple(MANIFEST[1:])
    monkeypatch.setattr(kernels, "MANIFEST", stripped)
    pf = ParsedFile("x", "crdt_tpu/batch/_none.py", "import jax\n")
    result = run_lint([pf], only_rules=["kernel-manifest"])
    hits = [f for f in result.findings
            if "declares no sharding contract" in f.message]
    assert hits and MANIFEST[0].name in hits[0].message, [
        f.render() for f in result.findings]


def test_malformed_contract_fails_source_lint(monkeypatch):
    """A collective-carrying pointwise contract is malformed at the
    source tier (collectives belong to reduction rows only)."""
    import dataclasses

    import crdt_tpu.analysis.kernels as kernels
    from crdt_tpu.analysis import run_lint
    from crdt_tpu.analysis.kernels import pointwise

    bad_contract = dataclasses.replace(
        pointwise(), collectives=("psum",))
    bad = (dataclasses.replace(MANIFEST[0], sharding=bad_contract),
           ) + tuple(MANIFEST[1:])
    monkeypatch.setattr(kernels, "MANIFEST", bad)
    pf = ParsedFile("x", "crdt_tpu/batch/_none.py", "import jax\n")
    result = run_lint([pf], only_rules=["kernel-manifest"])
    assert any("malformed sharding contract" in f.message
               for f in result.findings), [
        f.render() for f in result.findings]
