"""Telemetry-namespace lint — the PR 3 ``executor.regrow`` bug class.

The obs registry (:mod:`crdt_tpu.obs.metrics`) claims one metric TYPE
per name for the life of the process; a counter and a span histogram
sharing a name is a latent ``ValueError`` that only fires when tracing
is enabled on the path that registers second (exactly how PR 3's
``executor.regrow`` collision crashed executor recovery).  Both halves
of the contract are static properties of the source text:

* ``metric-type-collision`` — two call sites claim the same name (up to
  one-segment ``*`` wildcards from simple f-strings) with different
  registry types.
* ``metric-namespace`` — a claimed name matches no row of the
  documented manifest (:data:`crdt_tpu.obs.namespace.NAMESPACE`), or
  matches a row of a different type.  Adding a metric family means
  adding its manifest row first.

Extraction covers string literals and f-strings whose dynamic parts are
whole segments (``f"executor.recovery.{kind}"`` → ``executor.
recovery.*``); a name whose LEADING segment is dynamic cannot be
checked statically and is skipped.  The ``record_wire``/``record_sync``
helpers are expanded to the families they emit, so their call sites are
checked against the manifest too.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Iterable, List, Optional

from ..obs import namespace
from .core import (
    Finding, ParsedFile, literal_str, name_pattern, patterns_overlap, rule,
)

#: call-head -> registry type for direct declarations; the name is the
#: first argument
_DIRECT_HEADS = {
    "count": "counter",
    "counter": "counter",
    "counter_inc": "counter",
    "gauge": "gauge",
    "gauge_set": "gauge",
    "histogram": "histogram",
    "observe": "histogram",   # registry.observe(name, v) — needs >= 2 args
    "span": "histogram",      # spans forward into latency histograms
}

#: a statically-checkable metric name: dotted identifier segments
#: (wildcards included), at least two segments
_NAME_RE = re.compile(r"^[A-Za-z0-9_*]+(\.[A-Za-z0-9_*]+)+$")


@dataclasses.dataclass(frozen=True)
class MetricDecl:
    """One metric name claimed at one call site."""

    pattern: str   # dotted, '*' = one dynamic segment
    kind: str
    path: str
    line: int
    col: int
    via: str       # the call head that declared it (count/span/record_wire…)


def _seg_or_wild(node: ast.AST) -> str:
    s = literal_str(node)
    return s if s is not None and "." not in s and s else "*"


def _expand_record_wire(call: ast.Call) -> List[tuple[str, str]]:
    """``record_wire(leg, direction, ..., reason=...)`` → the counter
    families it increments (see wirebulk.record_wire)."""
    if len(call.args) < 2:
        return []
    leg = _seg_or_wild(call.args[0])
    direction = _seg_or_wild(call.args[1])
    prefix = f"wire.{leg}.{direction}"
    out = [(f"{prefix}.native", "counter"), (f"{prefix}.fallback", "counter")]
    for kw in call.keywords:
        if kw.arg == "reason":
            out.append((f"{prefix}.fallback_reason.{_seg_or_wild(kw.value)}",
                        "counter"))
    return out


def _expand_record_sync(call: ast.Call) -> List[tuple[str, str]]:
    """``record_sync(leg, ...)`` → per-leg byte/object counters plus the
    frame-size histogram (see tracing.record_sync)."""
    if not call.args:
        return []
    leg = _seg_or_wild(call.args[0])
    return [
        (f"wire.sync.{leg}.bytes", "counter"),
        (f"wire.sync.{leg}.objects", "counter"),
        (f"wire.sync.{leg}.frame_bytes", "histogram"),
    ]


def extract_decls(files: List[ParsedFile]) -> List[MetricDecl]:
    """Every statically-nameable metric declaration across ``files``."""
    decls: List[MetricDecl] = []

    def add(pattern: Optional[str], kind: str, pf: ParsedFile,
            call: ast.Call, via: str) -> None:
        if pattern is None or not _NAME_RE.match(pattern):
            return
        decls.append(MetricDecl(pattern, kind, pf.rel, call.lineno,
                                call.col_offset, via))

    for pf in files:
        for node in ast.walk(pf.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            head = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else "")
            if head == "record_wire":
                for pat, kind in _expand_record_wire(node):
                    add(pat, kind, pf, node, head)
            elif head == "record_sync":
                for pat, kind in _expand_record_sync(node):
                    add(pat, kind, pf, node, head)
            elif head in _DIRECT_HEADS:
                if head == "observe" and len(node.args) < 2:
                    continue  # Histogram.observe(v) — a value, not a name
                if not node.args:
                    continue
                add(name_pattern(node.args[0]), _DIRECT_HEADS[head],
                    pf, node, head)
    return decls


@rule("metric-type-collision")
def check_type_collisions(files: List[ParsedFile]) -> Iterable[Finding]:
    """Two call sites claiming overlapping names with different registry
    types — the exact PR 3 ``executor.regrow`` crash class."""
    decls = sorted(extract_decls(files),
                   key=lambda d: (d.path, d.line, d.col, d.kind))
    # first claimant of each (pattern, kind) speaks for all duplicates
    seen: dict[tuple[str, str], MetricDecl] = {}
    for d in decls:
        seen.setdefault((d.pattern, d.kind), d)
    reported: set[tuple] = set()
    for (pat_a, kind_a), a in seen.items():
        for (pat_b, kind_b), b in seen.items():
            if kind_a >= kind_b:  # one direction per unordered pair
                continue
            if not patterns_overlap(pat_a, pat_b):
                continue
            key = (pat_a, kind_a, pat_b, kind_b)
            if key in reported:
                continue
            reported.add(key)
            first, second = sorted([a, b], key=lambda d: (d.path, d.line))
            yield Finding(
                "metric-type-collision", second.path, second.line,
                second.col,
                f"metric name {second.pattern!r} is claimed as a "
                f"{second.kind} here (via {second.via}) but as a "
                f"{first.kind} at {first.path}:{first.line} (via "
                f"{first.via}); the obs registry allows one type per "
                "name — registering both raises ValueError at runtime",
            )


@rule("metric-namespace")
def check_namespace(files: List[ParsedFile]) -> Iterable[Finding]:
    """Every claimed name must fall under a documented manifest row of
    the same registry type (``crdt_tpu/obs/namespace.py``)."""
    for d in extract_decls(files):
        specs = [s for s in namespace.NAMESPACE
                 if patterns_overlap(d.pattern, s.pattern)]
        if any(s.kind == d.kind for s in specs):
            continue
        if specs:
            others = ", ".join(sorted({s.kind for s in specs}))
            yield Finding(
                "metric-namespace", d.path, d.line, d.col,
                f"metric {d.pattern!r} is declared as a {d.kind} (via "
                f"{d.via}) but the namespace manifest documents it as a "
                f"{others} — fix the call site or the manifest "
                "(crdt_tpu/obs/namespace.py), not both",
            )
        else:
            yield Finding(
                "metric-namespace", d.path, d.line, d.col,
                f"metric {d.pattern!r} ({d.kind}, via {d.via}) matches no "
                "row of the documented crdt_tpu_* namespace manifest — add "
                "a NameSpec to crdt_tpu/obs/namespace.py first",
            )
