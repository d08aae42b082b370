"""kernelcheck — jaxpr-level contract analysis over the kernel manifest.

The second analysis tier (``python -m crdt_tpu.analysis --kernels``):
where crdtlint reads source text, kernelcheck traces every manifested
kernel abstractly (``jax.make_jaxpr`` over ``ShapeDtypeStruct`` args —
no device, no compile, runs under ``JAX_PLATFORMS=cpu``) across the
canonical capacity ladder and walks the resulting ``ClosedJaxpr``\\s:

* **KC01 dtype-lowering** — 64-bit values inside a ``pallas_call``
  region.  Mosaic has no 64-bit support; an i64 scalar that slips into
  a Pallas kernel fails (or hangs) in its compiler — this pins it
  statically.  A spec
  declared ``mosaic=True`` that traces no ``pallas_call`` at all is
  also flagged (a stale declaration hides the whole check).
* **KC02 scatter-determinism** — ``scatter-add``/``scatter-mul`` on
  inexact (float) dtypes without ``unique_indices``: the accumulation
  order is unspecified, so two replicas folding the same delta can
  produce different bytes and break the digest-equality convergence
  oracle.  Integer scatter folds (the scatter-``max`` witness rule) are
  associative-commutative and sanctioned.
* **KC03 baked-constant** — closure-captured arrays surfacing as jaxpr
  consts above the spec's byte budget: they re-upload with EVERY
  lowering of the regrow ladder and duplicate in HBM per compile.
* **KC04 recompile-budget** — distinct lowerings across the declared
  ladder (jit cache keys: static fingerprint + arg avals) beyond the
  spec's ``compile_budget``: the regrow path legitimately recompiles
  once per capacity rung; anything more is a retrace leak.
* **KC05 hidden host callback** — ``pure_callback``/``io_callback``/
  ``debug_callback`` primitives in hot-path kernels: a host round-trip
  serializes the device pipeline where the whole design is async
  dispatch.

Findings anchor at real source coordinates (the offending equation's
user frame when jax kept one, else the kernel's jit site), so the
standard ``# crdtlint: disable=KCxx`` pragmas and the shared
``baseline.json`` park/stale machinery apply unchanged, with one
exception: a pragma sanctioning KC01 is itself re-flagged.  Mosaic has
no 64-bit support on any supported jax, so a 64-bit op inside a
``pallas_call`` is never a valid sanction.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional, Sequence

from .core import (
    Baseline, Finding, LintResult, load_files, repo_root,
)
from .kernels import MANIFEST, KernelSpec, TraceCase, iter_jit_sites

KERNEL_RULES = ("KC01", "KC02", "KC03", "KC04", "KC05")

#: scatter primitives whose combiner accumulates (order-sensitive on
#: inexact dtypes); scatter-max/min and plain scatter are order-free
_ACCUM_SCATTERS = {"scatter-add", "scatter-mul", "scatter-sub"}

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback"}


@dataclasses.dataclass
class KernelReport:
    """Everything one kernelcheck run learned beyond the findings."""

    kernels: int = 0
    traced: int = 0
    cases: int = 0
    skipped: List[dict] = dataclasses.field(default_factory=list)
    trace_errors: List[str] = dataclasses.field(default_factory=list)
    mosaic: dict = dataclasses.field(default_factory=dict)
    jit_sites: int = 0
    elapsed_s: float = 0.0

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    """Inner jaxprs carried in an equation's params (pjit, scan, cond,
    while, pallas_call, custom_* ...), normalized to objects with
    ``.eqns``."""
    out = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for x in vals:
            if hasattr(x, "eqns"):
                out.append(x)
            elif hasattr(x, "jaxpr") and hasattr(
                    getattr(x, "jaxpr"), "eqns"):
                out.append(x.jaxpr)
    return out


def _walk(jaxpr, inside_pallas: bool = False):
    """Yield ``(eqn, inside_pallas)`` for every equation, recursing
    through sub-jaxprs; ``inside_pallas`` is sticky below any
    ``pallas_call``."""
    for eqn in jaxpr.eqns:
        now = inside_pallas or "pallas" in eqn.primitive.name
        yield eqn, now
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub, now)


def _eqn_loc(eqn, root: str):
    """Best-effort repo-relative ``(path, line)`` of an equation's user
    frame, else ``None`` — jax keeps source info through tracing and it
    is exactly the 'jaxpr location' a finding should name."""
    try:
        from jax._src import source_info_util

        for frame in source_info_util.user_frames(eqn.source_info.traceback):
            fname = getattr(frame, "file_name", "") or ""
            if fname.startswith(root):
                rel = os.path.relpath(fname, root).replace(os.sep, "/")
                if rel.startswith("crdt_tpu/analysis/"):
                    continue  # the harness frame is never the finding's home
                return rel, int(getattr(frame, "start_line", 0) or 0)
    except Exception:
        pass
    return None


def _aval_bits(var) -> int:
    aval = getattr(var, "aval", None)
    dt = getattr(aval, "dtype", None)
    return getattr(dt, "itemsize", 0) * 8


def _flat_avals(args):
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    return tuple((tuple(x.shape), str(x.dtype)) for x in leaves)


# ---------------------------------------------------------------------------
# per-spec checking
# ---------------------------------------------------------------------------


def _site_line(spec: KernelSpec, files_by_rel: dict) -> int:
    pf = files_by_rel.get(spec.path)
    if pf is None:
        return 1
    for site in iter_jit_sites(pf.tree):
        if site.name == spec.jit_name:
            return site.line
    return 1


def _loc_for(spec, eqn, files_by_rel, root):
    loc = _eqn_loc(eqn, root)
    if loc is not None:
        return loc
    return spec.path, _site_line(spec, files_by_rel)


def _check_spec(spec: KernelSpec, cases: Sequence[TraceCase],
                files_by_rel: dict, root: str, report: KernelReport
                ) -> List[Finding]:
    import jax

    findings: List[Finding] = []
    seen_keys = set()
    pallas_calls = 0
    wide_ops = 0
    kc01_seen = set()
    kc02_seen = set()
    kc05_seen = set()

    for case in cases:
        try:
            closed = jax.make_jaxpr(case.fn)(*case.args)
        except Exception as e:  # loud, never silent: a spec that no
            # longer traces is a broken contract declaration
            report.trace_errors.append(
                f"{spec.name} [{case.rung}]: {type(e).__name__}: {e}")
            continue
        report.cases += 1
        seen_keys.add((case.key, _flat_avals(case.args)))

        # KC03: baked constants ride every lowering of this ladder
        const_bytes = 0
        big = []
        for c in closed.consts:
            try:
                import numpy as np

                nb = np.asarray(c).nbytes
            except Exception:
                nb = 0
            const_bytes += nb
            if nb >= 1024:
                big.append(f"{getattr(c, 'shape', ())}:{nb}B")
        if const_bytes > spec.const_budget:
            findings.append(Finding(
                "KC03", spec.path, _site_line(spec, files_by_rel), 0,
                f"kernel {spec.name} [{case.rung}]: {const_bytes} bytes of "
                f"baked consts (budget {spec.const_budget}) — "
                f"{', '.join(big[:4]) or 'many small consts'}; captured "
                "arrays re-upload and duplicate in HBM on every lowering "
                "of the regrow ladder; pass them as arguments instead",
            ))

        for eqn, inside in _walk(closed.jaxpr):
            name = eqn.primitive.name
            if "pallas" in name:
                pallas_calls += 1
            # KC01: 64-bit values inside Mosaic-destined regions
            if inside:
                for var in list(eqn.invars) + list(eqn.outvars):
                    if _aval_bits(var) == 64:
                        wide_ops += 1
                        loc = _loc_for(spec, eqn, files_by_rel, root)
                        key = (loc, name)
                        if key not in kc01_seen:
                            kc01_seen.add(key)
                            aval = getattr(var, "aval", None)
                            findings.append(Finding(
                                "KC01", loc[0], loc[1], 0,
                                f"kernel {spec.name} [{case.rung}]: 64-bit "
                                f"value ({aval}) reaches primitive "
                                f"{name!r} inside a pallas_call — Mosaic "
                                "cannot lower 64-bit types; keep the kernel "
                                "domain <=32-bit",
                            ))
            # KC02: order-sensitive scatter accumulation
            if name in _ACCUM_SCATTERS:
                import jax.numpy as jnp  # noqa: F401

                operand = eqn.invars[0] if eqn.invars else None
                aval = getattr(operand, "aval", None)
                dt = getattr(aval, "dtype", None)
                inexact = dt is not None and dt.kind in "fc"
                unique = bool(eqn.params.get("unique_indices", False))
                if inexact and not unique:
                    loc = _loc_for(spec, eqn, files_by_rel, root)
                    key = (loc, name)
                    if key not in kc02_seen:
                        kc02_seen.add(key)
                        findings.append(Finding(
                            "KC02", loc[0], loc[1], 0,
                            f"kernel {spec.name} [{case.rung}]: {name} on "
                            f"{dt} without unique_indices — float "
                            "accumulation order is unspecified, so two "
                            "replicas folding the same delta can diverge "
                            "bytewise and break the digest-equality "
                            "convergence oracle; use an integer lattice "
                            "fold (scatter-max) or guarantee unique "
                            "indices",
                        ))
            # KC05: host callbacks in hot paths
            if name in _CALLBACK_PRIMS and spec.hot_path:
                loc = _loc_for(spec, eqn, files_by_rel, root)
                key = (loc, name)
                if key not in kc05_seen:
                    kc05_seen.add(key)
                    findings.append(Finding(
                        "KC05", loc[0], loc[1], 0,
                        f"kernel {spec.name} [{case.rung}]: hidden host "
                        f"callback {name!r} in a hot-path kernel — every "
                        "launch round-trips to Python and serializes the "
                        "async dispatch pipeline; hoist the host work out "
                        "of the jit or declare the spec hot_path=False "
                        "with a justification",
                    ))

    # KC04: distinct lowerings across the declared ladder
    if len(seen_keys) > spec.compile_budget:
        findings.append(Finding(
            "KC04", spec.path, _site_line(spec, files_by_rel), 0,
            f"kernel {spec.name}: {len(seen_keys)} distinct lowerings "
            f"across the canonical ladder (budget {spec.compile_budget}) "
            "— the jit cache keys on more than the capacity rungs "
            "(shape-specialized statics? un-padded batch axes?); every "
            "extra key is a recompile on the regrow path",
        ))

    if spec.mosaic:
        report.mosaic[spec.name] = {
            "pallas_calls": pallas_calls, "wide_ops": wide_ops,
        }
        if pallas_calls == 0 and not report.trace_errors:
            findings.append(Finding(
                "KC01", spec.path, _site_line(spec, files_by_rel), 0,
                f"kernel {spec.name}: declared mosaic=True but the trace "
                "contains no pallas_call — a stale declaration disables "
                "the whole dtype-lowering check; fix the manifest row",
            ))
    return findings


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------


def run_kernelcheck(specs: Optional[Sequence[KernelSpec]] = None,
                    baseline: Optional[Baseline] = None,
                    root: Optional[str] = None,
                    ) -> tuple:
    """Trace every manifested kernel and lint the jaxprs.

    Returns ``(LintResult, KernelReport)``.  Mirrors
    :func:`crdt_tpu.analysis.core.run_lint`'s triage: pragma at the
    finding's line first, then the baseline; everything else is live.
    """
    t0 = time.perf_counter()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from ..config import enable_x64

    enable_x64()  # the batch package's import-time contract

    if specs is None:
        specs = MANIFEST
    root = root or repo_root()
    report = KernelReport(kernels=len(specs))

    # parse the spec'd source files once: jit-site lines for finding
    # anchors, pragma maps for suppression
    paths = sorted({s.path for s in specs})
    files, parse_errors = load_files(
        [os.path.join(root, p) for p in paths], root=root)
    files_by_rel = {f.rel: f for f in files}
    report.jit_sites = sum(
        len(iter_jit_sites(pf.tree)) for pf in files_by_rel.values()
        if pf.rel.startswith("crdt_tpu/"))

    raw: List[Finding] = []
    for spec in specs:
        if spec.build is None:
            report.skipped.append(
                {"kernel": spec.name, "reason": spec.notrace_reason})
            continue
        try:
            cases = spec.build()
        except Exception as e:
            report.trace_errors.append(
                f"{spec.name} [build]: {type(e).__name__}: {e}")
            continue
        report.traced += 1
        raw.extend(_check_spec(spec, cases, files_by_rel, root, report))

    # triage: pragmas, then baseline — the crdtlint machinery verbatim
    live: List[Finding] = []
    suppressed: List[Finding] = []
    baselined: List[Finding] = []
    for f in raw:
        pf = files_by_rel.get(f.path)
        if pf is None and os.path.exists(os.path.join(root, f.path)):
            extra, _ = load_files([os.path.join(root, f.path)], root=root)
            if extra:
                pf = files_by_rel[extra[0].rel] = extra[0]
        if pf is not None and pf.suppressed(f.rule, f.line):
            suppressed.append(f)
        elif baseline is not None and baseline.covers(f):
            baselined.append(f)
        else:
            live.append(f)

    # a KC01 pragma is never a valid sanction: Mosaic has no 64-bit
    # support, so the finding stays live until the op is gone
    for f in suppressed:
        if f.rule == "KC01":
            live.append(Finding(
                "KC01", f.path, f.line, 0,
                "stale KC01 sanction: a pragma suppresses a 64-bit "
                "Mosaic finding here — Mosaic has no 64-bit support, so "
                "remove the pragma and the 64-bit op",
            ))

    live.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    result = LintResult(
        findings=live,
        suppressed=suppressed,
        baselined=baselined,
        stale_baseline=baseline.stale_entries() if baseline else [],
        files=len(files_by_rel),
        parse_errors=parse_errors + report.trace_errors,
    )
    report.elapsed_s = round(time.perf_counter() - t0, 3)
    return result, report
