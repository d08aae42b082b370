"""Wire/sync error-contract lint.

The transport boundary has a documented error taxonomy
(:mod:`crdt_tpu.error`): a malformed peer frame is an I/O-boundary
fault — :class:`~crdt_tpu.error.SyncProtocolError` or another
:class:`~crdt_tpu.error.CrdtError` subclass — never a bare
``ValueError`` (a local programming error a caller would not think to
catch at the socket), and never silently swallowed.  Telemetry rides
the same boundary: every bulk ``from_wire``/``to_wire`` leg feeds
``record_wire`` so a silent native→Python fallback shows up in the
``wire.*`` counters (the round-5 ingest-collapse lesson).

* ``wire-bare-valueerror`` — ``raise ValueError`` (or TypeError /
  KeyError / struct.error) lexically inside a decode-path function of
  the wire modules.  A raise inside a ``try`` whose handler catches it
  and re-raises a :class:`CrdtError` subclass is the accepted
  conversion idiom and is not flagged.
* ``wire-swallowed-except`` — an ``except Exception``/bare ``except``
  whose body never re-raises, inside a decode path: it eats
  ``SyncProtocolError`` evidence along with everything else.
* ``wire-missing-record`` — a ``from_wire``/``to_wire`` leg that
  neither calls ``record_wire`` nor delegates to a helper that does:
  its native-fraction accounting is invisible and a fallback
  regression is silent again.

Decode paths are functions named ``from_wire`` / ``decode*`` /
``_unpack*`` / ``*_from_wire`` in the wire modules (``sync/``,
``cluster/`` — its ARQ envelope decode and transport error paths
carry the same contract — ``batch/wirebulk.py``, the batch codecs).
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from .core import Finding, ParsedFile, ancestors, dotted_name, parents_of, rule

#: modules under the wire error contract (repo-relative prefixes)
WIRE_MODULES = (
    "crdt_tpu/sync/",
    "crdt_tpu/cluster/",
    # the op-frame codec (and the whole op front-end) rides the same
    # envelope discipline as the sync frames: decode paths must speak
    # SyncProtocolError/WireFormatError, never bare stdlib errors
    "crdt_tpu/oplog/",
    # the causal-GC layer mutates the same planes the wire codecs feed
    # and consumes the digest frames' version vectors; its (rare)
    # decode-adjacent paths are held to the same error contract
    "crdt_tpu/gc/",
    # the durable layer's snapshot/WAL decode paths parse disk bytes
    # that kill -9 may have torn mid-write — exactly the hostile-input
    # shape the wire contract exists for: CheckpointFormatError (a
    # CrdtError), never a bare zipfile/struct/ValueError leak
    "crdt_tpu/durable/",
    # the read front-end's request/result codec (serve/wire.py) rides
    # the same versioned+CRC envelope discipline; its decode paths must
    # reject with SyncProtocolError/WireFormatError, and its
    # consistency rejections speak the typed
    # ConsistencyUnavailableError — never bare stdlib errors
    "crdt_tpu/serve/",
    # the seed-level checkpoint loader doubles as the state-replication
    # receive path AND the snapshot store's payload decoder
    "crdt_tpu/utils/checkpoint.py",
    # the fleet-observatory snapshot codec rides the same envelope
    # discipline as the sync frames, so its decode paths are held to
    # the same error contract
    "crdt_tpu/obs/fleet.py",
    "crdt_tpu/batch/wirebulk.py",
    "crdt_tpu/batch/orswot_batch.py",
    "crdt_tpu/batch/vclock_batch.py",
    "crdt_tpu/batch/gcounter_batch.py",
    "crdt_tpu/batch/pncounter_batch.py",
    "crdt_tpu/batch/gset_batch.py",
    "crdt_tpu/batch/lwwreg_batch.py",
    "crdt_tpu/batch/mvreg_batch.py",
    "crdt_tpu/batch/map_batch.py",
    "crdt_tpu/batch/wireloop.py",
    # the lint's own fixture suite (never in the default scan set, but
    # tests/test_analysis.py lints it explicitly)
    "tests/analysis_fixtures/",
)

#: exception names whose raise inside a decode path violates the
#: contract (CrdtError subclasses — SyncProtocolError, WireFormatError,
#: CapacityOverflowError — are the sanctioned vocabulary)
_BARE_ERRORS = {"ValueError", "TypeError", "KeyError", "struct.error"}

#: known CrdtError-subclass names (kept in sync with crdt_tpu/error.py;
#: the lint is stdlib-only so it cannot import and introspect)
_CRDT_ERRORS = {
    "CrdtError", "SyncProtocolError", "WireFormatError",
    "CapacityOverflowError", "ConflictingMarker", "MergeConflict",
    "NestedOpFailed", "TransportError", "SyncTimeoutError",
    "PeerUnavailableError", "TransportClosedError", "TransportFrameError",
    "OpLogOverflowError",
    "DurabilityError", "CheckpointFormatError",
    "ConsistencyUnavailableError",
}


def _is_decode_fn(name: str) -> bool:
    return (
        name == "from_wire" or name.endswith("_from_wire")
        or name.startswith("decode") or name.startswith("_unpack")
    )


def _is_wire_leg(name: str) -> bool:
    return _is_decode_fn(name) or name == "to_wire" \
        or name.endswith("_to_wire")


def _decode_functions(tree: ast.AST, pred=_is_decode_fn):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                pred(node.name):
            yield node


def _handler_names(handler: ast.ExceptHandler) -> set[str]:
    t = handler.type
    if t is None:
        return {"BaseException"}  # bare except
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    return {dotted_name(e) for e in elts}


def _converted_in_try(raise_node: ast.Raise, parents: dict,
                      raised: str) -> bool:
    """True when an enclosing ``try`` catches ``raised`` (or a base of
    it) and its handler raises a CrdtError subclass — the sanctioned
    decode-conversion idiom (``except (struct.error, ValueError) as e:
    raise SyncProtocolError(...) from None``)."""
    for anc in ancestors(raise_node, parents):
        if not isinstance(anc, ast.Try):
            continue
        # only the try BODY is converted by its handlers
        if not any(raise_node is n or any(raise_node is d for d in ast.walk(n))
                   for n in anc.body):
            continue
        for handler in anc.handlers:
            names = {n.rsplit(".", 1)[-1] for n in _handler_names(handler)}
            if raised.rsplit(".", 1)[-1] not in names and \
                    not names & {"Exception", "BaseException"}:
                continue
            for inner in ast.walk(handler):
                if isinstance(inner, ast.Raise) and inner.exc is not None:
                    exc = inner.exc
                    name = dotted_name(
                        exc.func if isinstance(exc, ast.Call) else exc
                    ).rsplit(".", 1)[-1]
                    if name in _CRDT_ERRORS:
                        return True
    return False


@rule("wire-bare-valueerror")
def check_bare_valueerror(files: List[ParsedFile]) -> Iterable[Finding]:
    """Decode paths must raise CrdtError subclasses, not stdlib errors
    a transport caller would never catch."""
    for pf in files:
        if not pf.rel.startswith(WIRE_MODULES):
            continue
        parents = parents_of(pf.tree)
        for fn in _decode_functions(pf.tree):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Raise) or node.exc is None:
                    continue
                exc = node.exc
                name = dotted_name(
                    exc.func if isinstance(exc, ast.Call) else exc
                )
                if name.rsplit(".", 1)[-1] not in {
                    e.rsplit(".", 1)[-1] for e in _BARE_ERRORS
                }:
                    continue
                if _converted_in_try(node, parents, name):
                    continue
                yield Finding(
                    "wire-bare-valueerror", pf.rel, node.lineno,
                    node.col_offset,
                    f"decode path {fn.name}() raises bare {name} — wire "
                    "faults must be CrdtError subclasses "
                    "(SyncProtocolError / WireFormatError) so transport "
                    "callers can catch-and-drop without masking real "
                    "bugs",
                )


@rule("wire-swallowed-except")
def check_swallowed_except(files: List[ParsedFile]) -> Iterable[Finding]:
    """``except Exception`` with no re-raise inside a decode path eats
    protocol-error evidence."""
    for pf in files:
        if not pf.rel.startswith(WIRE_MODULES):
            continue
        for fn in _decode_functions(pf.tree):
            for node in ast.walk(fn):
                if not isinstance(node, ast.ExceptHandler):
                    continue
                names = {n.rsplit(".", 1)[-1] for n in _handler_names(node)}
                if not names & {"Exception", "BaseException"}:
                    continue
                if any(isinstance(inner, ast.Raise)
                       for inner in ast.walk(node)):
                    continue
                yield Finding(
                    "wire-swallowed-except", pf.rel, node.lineno,
                    node.col_offset,
                    f"decode path {fn.name}() swallows "
                    f"{'/'.join(sorted(names))} without re-raising — "
                    "SyncProtocolError evidence dies here; catch the "
                    "specific error or re-raise",
                )


#: calling any of these counts as feeding the wire accounting (they all
#: call record_wire themselves)
_RECORDING_HELPERS_SUFFIXES = ("from_wire", "to_wire")


def _feeds_record_wire(fn: ast.AST, own_name: str) -> bool:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        callee = dotted_name(node.func).rsplit(".", 1)[-1]
        if not callee and isinstance(node.func, ast.Attribute):
            callee = node.func.attr
        if callee == "record_wire":
            return True
        if callee != own_name and callee.endswith(_RECORDING_HELPERS_SUFFIXES):
            return True  # delegation: clockish_from_wire, planes_to_wire, …
    return False


@rule("wire-missing-record")
def check_missing_record(files: List[ParsedFile]) -> Iterable[Finding]:
    """Every bulk ``from_wire``/``to_wire`` leg must feed the
    native-vs-fallback counters (directly or via a recording helper)."""
    for pf in files:
        if not pf.rel.startswith(WIRE_MODULES):
            continue
        for fn in _decode_functions(pf.tree, pred=_is_wire_leg):
            # only the bulk batch legs carry the counter contract; the
            # scalar-path helpers (serde) and frame codecs do not
            if fn.name not in ("from_wire", "to_wire"):
                continue
            if _feeds_record_wire(fn, fn.name):
                continue
            yield Finding(
                "wire-missing-record", pf.rel, fn.lineno, fn.col_offset,
                f"bulk wire leg {fn.name}() never feeds record_wire — "
                "its native_fraction is invisible and a silent fallback "
                "regression (the round-5 ingest collapse) cannot be "
                "seen from the wire.* counters",
            )
