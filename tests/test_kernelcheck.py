"""kernelcheck self-tests: the jaxpr tier's repo gate, the fixture
regression matrix, manifest coverage, and the KC01 stale-sanction
rule.

The AST tier's tests (tests/test_analysis.py) stay jax-free; this
module deliberately is NOT — tracing kernels is the whole point — and
runs under the same `analysis` marker.
"""

import json
import os
import subprocess
import sys

import pytest

from crdt_tpu.analysis.core import Baseline, ParsedFile, repo_root
from crdt_tpu.analysis.kernels import (
    MANIFEST, iter_jit_sites, manifest_keys,
)

pytestmark = pytest.mark.analysis

REPO = repo_root()
FIXDIR = os.path.join(REPO, "tests", "analysis_fixtures")
sys.path.insert(0, FIXDIR)


def _run_specs(specs, baseline=None):
    from crdt_tpu.analysis.jaxpr_rules import run_kernelcheck

    return run_kernelcheck(specs=specs, baseline=baseline)


# ---- the repo-wide gate -----------------------------------------------------


@pytest.fixture(scope="module")
def repo_gate():
    """One subprocess run of the real CLI gate, shared by the gate
    tests: `python -m crdt_tpu.analysis --kernels --json` exactly as
    scripts/ci.sh invokes it."""
    proc = subprocess.run(
        [sys.executable, "-m", "crdt_tpu.analysis", "--kernels", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    return proc


def test_repo_gate_exits_zero_with_empty_baseline(repo_gate):
    """The shipped tree is contract-clean: exit 0, zero live findings,
    zero trace errors, nothing parked for the KC rules in the
    baseline."""
    assert repo_gate.returncode == 0, repo_gate.stdout + repo_gate.stderr
    out = json.loads(repo_gate.stdout)
    assert out["ok"] is True
    assert out["findings"] == []
    assert out["kernelcheck"]["trace_errors"] == []
    with open(os.path.join(REPO, "crdt_tpu", "analysis",
                           "baseline.json")) as fh:
        entries = json.load(fh)
    assert [e for e in entries if e["rule"].startswith("KC")] == []


def test_repo_gate_covers_the_manifest(repo_gate):
    """Every buildable spec traced, every jit site under
    crdt_tpu/ accounted for."""
    out = json.loads(repo_gate.stdout)
    kc = out["kernelcheck"]
    n_build = sum(1 for s in MANIFEST if s.build is not None)
    assert kc["traced"] == n_build
    assert kc["cases"] >= 2 * kc["traced"]  # ladders, not single traces
    # declared-no-trace rows are reported, never silent
    assert {s["kernel"] for s in kc["skipped"]} == {
        s.name for s in MANIFEST if s.build is None}
    # the AST extractor saw every site the manifest claims (coverage
    # itself is enforced by the kernel-manifest rule in tier 1)
    assert kc["jit_sites"] > 0
    assert kc["jit_sites"] <= len(manifest_keys()) + len(MANIFEST)


def test_mosaic_specs_traced_real_pallas_regions(repo_gate):
    """Each mosaic spec traced >=1 pallas_call and is 64-bit-clean —
    the static KC01 pin on Mosaic's 32-bit limit."""
    mosaic = json.loads(repo_gate.stdout)["kernelcheck"]["mosaic"]
    assert set(mosaic) == {s.name for s in MANIFEST if s.mosaic}
    for name, stats in mosaic.items():
        assert stats["pallas_calls"] > 0, f"{name} traced no pallas_call"
        assert stats["wide_ops"] == 0, (
            f"{name} leaked {stats['wide_ops']} 64-bit ops into Mosaic")


# ---- fixture matrix: every rule fires with the right id + kernel name ------


@pytest.fixture(scope="module")
def bad_result():
    import kernels_bad

    result, report = _run_specs(kernels_bad.SPECS)
    assert report.trace_errors == [], report.trace_errors
    return result


@pytest.mark.parametrize("rule,kernel", [
    ("KC01", "fixture.i64_lowering"),
    ("KC02", "fixture.float_scatter"),
    ("KC03", "fixture.baked_const"),
    ("KC04", "fixture.shape_special"),
    ("KC05", "fixture.hidden_callback"),
])
def test_bad_fixture_fails_with_rule_and_kernel_name(bad_result, rule,
                                                     kernel):
    hits = [f for f in bad_result.findings if f.rule == rule]
    assert hits, f"{rule} produced no finding"
    assert any(kernel in f.message for f in hits), (
        rule, [f.message for f in hits])
    # findings carry a real location (jaxpr source frame or jit site)
    for f in hits:
        assert f.path and f.line >= 1


def test_bad_fixture_findings_anchor_in_the_fixture(bad_result):
    """KC01/KC02/KC05 anchor at the offending equation's source line in
    the fixture file — the 'jaxpr location' acceptance: a pragma ON
    THAT LINE is what sanctions the idiom."""
    for rule in ("KC01", "KC02", "KC05"):
        hits = [f for f in bad_result.findings if f.rule == rule]
        assert any(
            f.path == "tests/analysis_fixtures/kernels_bad.py" and f.line > 1
            for f in hits), (rule, [(f.path, f.line) for f in hits])


def test_ok_twins_suppressed_or_clean():
    import kernels_ok

    baseline = Baseline([{
        "rule": "KC03",
        "path": "tests/analysis_fixtures/kernels_ok.py",
        "message": "kernel fixture_ok.baselined_const*",
        "justification": "fixture: demonstrates baseline parking for "
                         "const findings (no per-equation source frame "
                         "to hang a pragma on)",
    }])
    result, report = _run_specs(kernels_ok.SPECS, baseline=baseline)
    assert report.trace_errors == [], report.trace_errors
    assert result.findings == [], [f.render() for f in result.findings]
    # the pragma'd sin really fired and was suppressed — not inert
    assert {f.rule for f in result.suppressed} == {"KC02"}
    assert [f.rule for f in result.baselined] == ["KC03"]
    assert result.stale_baseline == []


def test_kc01_sanction_reflagged_as_stale(monkeypatch):
    """A pragma sanctioning KC01 never silences it: Mosaic has no
    64-bit support on any supported jax, so the suppression re-arms as
    a live 'stale sanction' finding."""
    import kernels_bad

    spec = [s for s in kernels_bad.SPECS
            if s.name == "fixture.i64_lowering"]
    result, _ = _run_specs(spec)
    line = next(f.line for f in result.findings if f.rule == "KC01")

    # sanction it: pragma on the offending line, via a patched pragma
    # map (the fixture file on disk stays sin-without-pragma)
    real_suppressed = ParsedFile.suppressed

    def fake_suppressed(self, rule, ln):
        if (self.rel.endswith("kernels_bad.py") and rule == "KC01"
                and ln == line):
            return True
        return real_suppressed(self, rule, ln)

    monkeypatch.setattr(ParsedFile, "suppressed", fake_suppressed)
    result2, _ = _run_specs(spec)
    assert any(f.rule == "KC01" for f in result2.suppressed)
    stale = [f for f in result2.findings
             if f.rule == "KC01" and "stale KC01 sanction" in f.message]
    assert stale, [f.render() for f in result2.findings]


# ---- the tier-1 AST rule: kernel-manifest ----------------------------------


def test_unmanifested_jit_entry_point_fails_source_lint():
    """A new @jax.jit under crdt_tpu/ without a KernelSpec row fails
    crdtlint BEFORE kernelcheck ever runs (the single-source
    discipline, same as obs/namespace.py for metric names)."""
    from crdt_tpu.analysis import run_lint

    src = (
        "import jax\n"
        "@jax.jit\n"
        "def rogue_kernel(x):\n"
        "    return x + 1\n"
    )
    pf = ParsedFile("x", "crdt_tpu/batch/rogue.py", src)
    result = run_lint([pf], only_rules=["kernel-manifest"])
    assert [f.rule for f in result.findings] == ["kernel-manifest"]
    assert "rogue_kernel" in result.findings[0].message
    assert result.findings[0].line == 3


def test_every_jit_call_form_is_extracted():
    """The extractor names every jit application form the tree uses:
    decorator, partial-decorator, direct call, lambda, computed."""
    src = (
        "import functools, jax\n"
        "@jax.jit\n"
        "def plain(x): return x\n"
        "@functools.partial(jax.jit, static_argnums=(1,))\n"
        "def with_static(x, k): return x\n"
        "def factory():\n"
        "    def kernel(x): return x\n"
        "    return jax.jit(kernel)\n"
        "class Loop:\n"
        "    def warm(self):\n"
        "        self._f = jax.jit(functools.partial(plain))\n"
        "probe = jax.jit(lambda x: x + 1)\n"
    )
    names = {s.name for s in iter_jit_sites(
        ParsedFile("x", "crdt_tpu/batch/forms.py", src).tree)}
    assert names == {
        "plain", "with_static", "factory.kernel", "Loop.warm.<jit>",
        "<lambda>",
    }


def test_stale_manifest_row_fails_source_lint():
    """A manifest row pointing at a deleted/moved jit site is flagged
    when the row's target file is in the scanned set."""
    from crdt_tpu.analysis import run_lint

    spec = MANIFEST[0]
    pf = ParsedFile("x", spec.path, "import jax\n")  # site gone
    result = run_lint([pf], only_rules=["kernel-manifest"])
    assert any(
        f.rule == "kernel-manifest" and "stale manifest row" in f.message
        and spec.name in f.message
        for f in result.findings), [f.render() for f in result.findings]


def test_manifest_covers_every_site_on_the_real_tree():
    """100% coverage, asserted directly against the source tree (the
    CLI gate asserts it too, via the kernel-manifest rule)."""
    from crdt_tpu.analysis.core import default_targets, load_files

    files, errors = load_files(default_targets(), root=REPO)
    assert not errors
    covered = manifest_keys()
    missing = []
    for pf in files:
        if (not pf.rel.startswith("crdt_tpu/")
                or pf.rel.startswith("crdt_tpu/analysis/")):
            continue
        for site in iter_jit_sites(pf.tree):
            if (pf.rel, site.name) not in covered:
                missing.append((pf.rel, site.name))
    assert missing == []


def test_manifest_rows_are_unique_and_well_formed():
    names = [s.name for s in MANIFEST]
    assert len(names) == len(set(names))
    for s in MANIFEST:
        assert s.path.startswith("crdt_tpu/")
        assert s.determinism in (
            "bitwise", "integer-lattice", "float-accum")
        assert s.compile_budget >= 1
        assert (s.build is None) == bool(s.notrace_reason)
