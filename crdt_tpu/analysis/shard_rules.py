"""shardcheck — static sharding contracts over the kernel manifest.

The third analysis tier (``python -m crdt_tpu.analysis --shard``): the
ROADMAP's mesh item shards the *object axis* of the dense planes
(``shard_map``/pjit over ``parallel/mesh.py``), and the decomposition
"local join per shard + ICI all-reduce for the global lattice join" is
provably safe only for kernels whose jaxprs respect that axis.  Every
:class:`~crdt_tpu.analysis.kernels.KernelSpec` row declares a
:class:`~crdt_tpu.analysis.kernels.ShardContract`; this module traces
each manifested kernel abstractly (the same TraceCase ladders
kernelcheck walks, plus mesh-shaped cases whose operands are re-shaped
to their per-shard extents under an abstract ``jax.sharding.Mesh`` of
sizes {1,2,4,8}) and walks the ``ClosedJaxpr`` tracking which dims
derive from the object axis:

* **SC01 cross-object flow** — a ``pointwise``-declared kernel whose
  jaxpr folds, slices, sorts, scans or re-groups the object axis, or
  gathers/scatters through it with indices NOT declared ``routed``:
  one shard's rows would need another shard's data, so shard-local
  execution silently computes the wrong lattice join.
* **SC02 collective contract** — ``reduction`` kernels must lower
  EXACTLY their declared collectives (today only the ``parallel/``
  shard_map joins lower any); ``pointwise``/``replicated`` kernels must
  lower none.  An undeclared collective is a hidden cross-shard
  dependency; a declared-but-absent one is a stale contract.
* **SC03 host round-trip** (AST, :mod:`tracer`-style lexical rules) —
  ``int()``/``float()``/``.item()``/``np.asarray()`` applied to a
  jitted kernel's output inside the ``parallel/``, ``batch/``,
  ``sync/``, ``serve/``, ``gc/`` hot paths: on a sharded fleet that is
  a device sync plus a cross-shard gather per call.
* **SC04 ragged shards** — every capacity-ladder rung of every
  object-axis operand must divide evenly by every declared mesh size
  (times the contract's ``granule``); a ragged shard means one device
  owns a different program shape than its peers.
* **SC05 mesh recompile budget** — distinct lowerings at each mesh
  size are bounded by the row's existing ``compile_budget`` (KC04
  bounds the unsharded ladder; this bounds each sharded replica of
  it).

Findings anchor at equation source frames (jax keeps user frames
through tracing) and reuse the ``# crdtlint: disable=SCxx`` pragma +
``baseline.json`` park/stale machinery unchanged.  One consistency
screw, KC01-style: an SC pragma that suppressed nothing this run —
the kernel's contract traces clean now — is re-flagged live as a
stale sanction, so sanctions rot loudly, never silently.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Set

from .core import (
    Baseline, Finding, LintResult, ParsedFile, load_files, repo_root,
)
from .jaxpr_rules import _eqn_loc, _flat_avals, _site_line, _walk
from .kernels import (
    ALL_LEAVES, MANIFEST, KernelSpec, ShardContract, iter_jit_sites,
)

SHARD_RULES = ("SC01", "SC02", "SC03", "SC04", "SC05")

#: hot-path packages SC03 scans for host round-trips on kernel outputs
SC03_SCOPE = ("crdt_tpu/parallel/", "crdt_tpu/batch/", "crdt_tpu/sync/",
              "crdt_tpu/serve/", "crdt_tpu/gc/")

#: jaxpr primitive name -> declarable collective name (psum_scatter is
#: how reduce_scatter spells itself in a traced jaxpr)
_COLLECTIVE_BY_PRIM = {
    "psum": "psum", "pmax": "pmax", "pmin": "pmin",
    "all_gather": "all_gather", "all_to_all": "all_to_all",
    "ppermute": "ppermute", "psum_scatter": "reduce_scatter",
}

#: primitives that FOLD an axis (params["axes"]/["dimensions"])
_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce",
}

_SCATTER_PRIMS = {
    "scatter", "scatter-add", "scatter-mul", "scatter-sub",
    "scatter-max", "scatter-min",
}

_CALL_PRIMS = {
    "pjit", "jit", "closed_call", "core_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "shard_map", "custom_partitioning",
}


@dataclasses.dataclass
class ShardReport:
    """Everything one shardcheck run learned beyond the findings."""

    kernels: int = 0
    traced: int = 0
    cases: int = 0            # base-ladder trace cases analyzed
    mesh_cases: int = 0       # mesh-shaped (sharded-operand) cases
    contracts: Dict[str, int] = dataclasses.field(default_factory=dict)
    collectives: Dict[str, list] = dataclasses.field(default_factory=dict)
    skipped: List[dict] = dataclasses.field(default_factory=list)
    trace_errors: List[str] = dataclasses.field(default_factory=list)
    unknown_prims: List[str] = dataclasses.field(default_factory=list)
    opaque: List[str] = dataclasses.field(default_factory=list)
    sc03_files: int = 0
    elapsed_s: float = 0.0

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# object-axis provenance over a ClosedJaxpr
# ---------------------------------------------------------------------------


class _Prov:
    """Walks one jaxpr propagating two taints per variable: the set of
    dims that derive from the object axis, and whether the *value*
    derives from a ``routed`` (object-id) operand.  Routed value-taint
    is sticky and conservative — it only ever SANCTIONS indexing, so
    over-propagation weakens SC01 toward silence, never toward a false
    positive.  Primitives with no handler and no shape match drop dim
    taint and are recorded in ``unknown`` for visibility."""

    def __init__(self, flag, unknown: Set[str]):
        self.flag = flag          # callable(eqn, what) -> None
        self.unknown = unknown
        self.opaque = False       # saw a pallas_call (refs: can't track)

    # -- var helpers --------------------------------------------------------

    @staticmethod
    def _is_lit(v) -> bool:
        return not hasattr(v, "count") and hasattr(v, "val")

    @staticmethod
    def _shape(v) -> tuple:
        return tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())

    def run(self, jaxpr, in_dims, in_routed) -> None:
        dims: dict = {}
        routed: set = set()
        for v, d in zip(jaxpr.invars, in_dims):
            if d:
                dims[v] = frozenset(d)
        for v, r in zip(jaxpr.invars, in_routed):
            if r:
                routed.add(v)
        self._eval(jaxpr, dims, routed)

    # -- the interpreter ----------------------------------------------------

    def _eval(self, jaxpr, dims: dict, routed: set) -> None:
        for eqn in jaxpr.eqns:
            self._step(eqn, dims, routed)

    def _get(self, dims, v) -> frozenset:
        if self._is_lit(v):
            return frozenset()
        return dims.get(v, frozenset())

    def _routed(self, routed, v) -> bool:
        return (not self._is_lit(v)) and v in routed

    def _set_out(self, eqn, dims, routed, taints, any_in_routed) -> None:
        for i, ov in enumerate(eqn.outvars):
            t = taints[i] if isinstance(taints, list) else taints
            t = frozenset(d for d in t if d < len(self._shape(ov)))
            if t:
                dims[ov] = t
            if any_in_routed:
                routed.add(ov)

    def _step(self, eqn, dims: dict, routed: set) -> None:  # noqa: C901
        name = eqn.primitive.name
        in_dims = [self._get(dims, v) for v in eqn.invars]
        in_routed = any(self._routed(routed, v) for v in eqn.invars)
        any_taint = any(in_dims)
        out = lambda t: self._set_out(eqn, dims, routed, t, in_routed)

        def fold_ok(taint, folded_dims, v, what) -> frozenset:
            """Dims of ``taint`` folded by this eqn: flag the ones with
            extent > 1 (folding a singleton object slice mixes
            nothing), return the surviving taint."""
            hit = {d for d in taint if d in folded_dims}
            if any(self._shape(v)[d] > 1 for d in hit
                   if d < len(self._shape(v))):
                self.flag(eqn, what)
            return frozenset(taint - hit)

        if "pallas" in name:
            self.opaque = True
            return  # refs/memory semantics: opaque to dim provenance

        if name in _CALL_PRIMS or name.endswith("_call"):
            self._recurse(eqn, dims, routed, in_dims, in_routed)
            return
        if name == "while":
            self._while(eqn, dims, routed, in_dims, in_routed)
            return
        if name == "scan":
            self._scan(eqn, dims, routed, in_dims, in_routed)
            return
        if name == "cond":
            self._cond(eqn, dims, routed, in_dims, in_routed)
            return

        if not any_taint:
            # nothing object-derived flows in: outputs inherit only
            # the routed value-taint
            out(frozenset())
            return

        v0 = eqn.invars[0]
        t0 = in_dims[0]

        if name in _REDUCE_PRIMS:
            axes = set(eqn.params.get("axes",
                                      eqn.params.get("dimensions", ())))
            union = frozenset().union(*in_dims)
            kept = fold_ok(union, axes, v0,
                           f"{name} folds the object axis")
            remap = {d: d - sum(1 for a in axes if a < d)
                     for d in kept}
            out(frozenset(remap.values()))
        elif name.startswith("cum"):
            axis = eqn.params.get("axis", 0)
            if axis in t0 and self._shape(v0)[axis] > 1:
                self.flag(eqn, f"{name} runs a prefix fold along the "
                               "object axis")
            out(t0)
        elif name == "sort":
            dim = eqn.params.get("dimension", -1)
            union = frozenset().union(*in_dims)
            if dim in union and self._shape(v0)[dim] > 1:
                self.flag(eqn, "sort permutes rows along the object axis")
            out([in_dims[i] if i < len(in_dims) else union
                 for i in range(len(eqn.outvars))])
        elif name == "rev":
            folded = set(eqn.params.get("dimensions", ()))
            hit = t0 & folded
            if any(self._shape(v0)[d] > 1 for d in hit):
                self.flag(eqn, "reverse reorders the object axis")
            out(t0)
        elif name == "concatenate":
            dim = eqn.params.get("dimension", 0)
            union = frozenset().union(*in_dims)
            if dim in union and self._shape(eqn.outvars[0])[dim] > 1:
                self.flag(eqn, "concatenate grows the object axis")
            out(union)
        elif name == "pad":
            cfg = eqn.params.get("padding_config", ())
            hit = {d for d in t0 if d < len(cfg) and any(cfg[d])}
            if any(self._shape(v0)[d] > 1 for d in hit):
                self.flag(eqn, "pad resizes the object axis")
            out(t0)
        elif name == "slice":
            starts = eqn.params.get("start_indices", ())
            limits = eqn.params.get("limit_indices", ())
            strides = eqn.params.get("strides") or (1,) * len(starts)
            shp = self._shape(v0)
            bad = {d for d in t0
                   if d < len(shp) and shp[d] > 1
                   and (starts[d] != 0 or limits[d] != shp[d]
                        or strides[d] != 1)}
            if bad:
                self.flag(eqn, "static slice selects a sub-range of the "
                               "object axis")
            out(t0 - bad)
        elif name == "squeeze":
            sq = set(eqn.params.get("dimensions", ()))
            out(frozenset(d - sum(1 for s in sq if s < d)
                          for d in t0 if d not in sq))
        elif name == "transpose":
            perm = list(eqn.params.get("permutation", ()))
            out(frozenset(perm.index(d) for d in t0 if d in perm))
        elif name == "broadcast_in_dim":
            bcd = list(eqn.params.get("broadcast_dimensions", ()))
            out(frozenset(bcd[d] for d in t0 if d < len(bcd)))
        elif name == "reshape":
            out(self._reshape(eqn, t0, v0))
        elif name == "dynamic_slice":
            self._dynamic_slice(eqn, dims, routed, t0, out)
        elif name == "dynamic_update_slice":
            self._dynamic_update(eqn, dims, routed, t0, out)
        elif name == "gather":
            self._gather(eqn, dims, routed, t0, out)
        elif name in _SCATTER_PRIMS:
            self._scatter(eqn, dims, routed, t0, out)
        elif name == "dot_general":
            self._dot(eqn, in_dims, out)
        elif name == "top_k":
            shp = self._shape(v0)
            last = len(shp) - 1
            if last in t0 and shp[last] > 1:
                self.flag(eqn, "top_k selects across the object axis")
            out(t0 - {last})
        elif name == "iota":
            out(frozenset())
        else:
            # elementwise family (add/mul/select_n/convert/bitwise/
            # compare/...): operands are scalar, output-shaped, or
            # rank-equal with degenerate (size-1) broadcast dims — dim
            # taint unions positionally either way (a broadcast
            # singleton's taint rides its dim index unchanged)
            oshape = self._shape(eqn.outvars[0])
            shapes = [self._shape(v) for v in eqn.invars]
            if all(s == oshape or s == ()
                   or (len(s) == len(oshape)
                       and all(x == y or x == 1
                               for x, y in zip(s, oshape)))
                   for s in shapes):
                out(frozenset().union(*in_dims))
            else:
                self.unknown.add(name)
                out(frozenset())

    # -- structured handlers ------------------------------------------------

    def _reshape(self, eqn, t0, v0) -> frozenset:
        a = list(self._shape(v0))
        b = list(self._shape(eqn.outvars[0]))
        # inserting/removing/moving size-1 dims can't mix objects: when
        # the nontrivial extents line up positionally, map them through
        # (a tainted singleton just drops — one row has nothing to leak)
        nta = [d for d in range(len(a)) if a[d] != 1]
        ntb = [d for d in range(len(b)) if b[d] != 1]
        if [a[d] for d in nta] == [b[d] for d in ntb]:
            return frozenset(ntb[nta.index(d)] for d in t0 if d in nta)
        mapped: dict = {}
        folded: set = set()
        i = j = 0
        while i < len(a) and j < len(b):
            ai, bj = [i], [j]
            pa, pb = a[i], b[j]
            i += 1
            j += 1
            while pa != pb:
                if pa < pb:
                    pa *= a[i]
                    ai.append(i)
                    i += 1
                else:
                    pb *= b[j]
                    bj.append(j)
                    j += 1
            if len(ai) == 1 and len(bj) == 1:
                mapped[ai[0]] = bj[0]
            else:
                folded.update(ai)
        folded.update(range(i, len(a)))  # trailing unmatched (size-1)
        hit = {d for d in t0 if d in folded and d < len(a) and a[d] > 1}
        if hit:
            self.flag(eqn, "reshape folds the object axis into/out of "
                           "other dims")
        return frozenset(mapped[d] for d in t0 if d in mapped)

    def _dynamic_slice(self, eqn, dims, routed, t0, out) -> None:
        sizes = eqn.params.get("slice_sizes", ())
        operand = eqn.invars[0]
        starts = eqn.invars[1:]
        shp = self._shape(operand)
        kept = set(t0)
        for d in sorted(t0):
            if d < len(sizes) and sizes[d] < shp[d] and shp[d] > 1:
                kept.discard(d)
                idx_ok = (d < len(starts)
                          and self._routed(routed, starts[d]))
                if not idx_ok:
                    self.flag(eqn, "dynamic_slice selects along the "
                                   "object axis with a non-routed start")
        out(frozenset(kept))

    def _dynamic_update(self, eqn, dims, routed, t0, out) -> None:
        operand, update = eqn.invars[0], eqn.invars[1]
        starts = eqn.invars[2:]
        oshp, ushp = self._shape(operand), self._shape(update)
        for d in sorted(t0):
            if (d < len(ushp) and ushp[d] < oshp[d] and oshp[d] > 1
                    and not (d < len(starts)
                             and self._routed(routed, starts[d]))):
                self.flag(eqn, "dynamic_update_slice writes along the "
                               "object axis at a non-routed offset")
        out(t0)

    def _gather(self, eqn, dims, routed, t0, out) -> None:
        dn = eqn.params.get("dimension_numbers")
        sizes = eqn.params.get("slice_sizes", ())
        operand, indices = eqn.invars[0], eqn.invars[1]
        shp = self._shape(operand)
        ishp = self._shape(indices)
        collapsed = set(getattr(dn, "collapsed_slice_dims", ()))
        offset = list(getattr(dn, "offset_dims", ()))
        ob = list(getattr(dn, "operand_batching_dims", ()) or ())
        ib = list(getattr(dn, "start_indices_batching_dims", ()) or ())
        out_rank = len(self._shape(eqn.outvars[0]))
        batch_out = [p for p in range(out_rank) if p not in offset]
        ivd = len(ishp) - 1  # lax fixes index_vector_dim last
        noncollapsed = [d for d in range(len(shp))
                        if d not in collapsed and d not in ob]
        taint = set()
        for d in sorted(t0):
            if d in ob:
                # operand batching dim (take_along_axis & friends):
                # element-aligned with the matching indices dim — the
                # object rows never cross, the taint rides through
                b = ib[ob.index(d)] if ob.index(d) < len(ib) else None
                if b is not None and b < ivd and b < len(batch_out):
                    taint.add(batch_out[b])
                continue
            full = d < len(sizes) and sizes[d] == shp[d]
            if full and d in noncollapsed:
                k = noncollapsed.index(d)
                if k < len(offset):
                    taint.add(offset[k])
            elif shp[d] > 1 and not self._routed(routed, indices):
                self.flag(eqn, "gather indexes the object axis with "
                               "non-routed indices")
        out(frozenset(taint))

    def _scatter(self, eqn, dims, routed, t0, out) -> None:
        dn = eqn.params.get("dimension_numbers")
        operand, indices = eqn.invars[0], eqn.invars[1]
        shp = self._shape(operand)
        sdims = set(getattr(dn, "scatter_dims_to_operand_dims", ()))
        for d in sorted(t0):
            if d in sdims and shp[d] > 1 \
                    and not self._routed(routed, indices):
                self.flag(eqn, f"{eqn.primitive.name} writes the object "
                               "axis through non-routed indices")
        out(t0)  # output aliases the operand's layout

    def _dot(self, eqn, in_dims, out) -> None:
        ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
        lhs, rhs = eqn.invars[0], eqn.invars[1]
        lshp, rshp = self._shape(lhs), self._shape(rhs)
        taint = set()
        for d in in_dims[0]:
            if d in lc:
                if lshp[d] > 1:
                    self.flag(eqn, "dot_general contracts the object axis")
            elif d in lb:
                taint.add(list(lb).index(d))
            else:
                free = [x for x in range(len(lshp))
                        if x not in lc and x not in lb]
                taint.add(len(lb) + free.index(d))
        nlfree = len(lshp) - len(lc) - len(lb)
        for d in in_dims[1] if len(in_dims) > 1 else ():
            if d in rc:
                if rshp[d] > 1:
                    self.flag(eqn, "dot_general contracts the object axis")
            elif d in rb:
                taint.add(list(rb).index(d))
            else:
                free = [x for x in range(len(rshp))
                        if x not in rc and x not in rb]
                taint.add(len(rb) + nlfree + free.index(d))
        out(frozenset(taint))

    # -- control flow -------------------------------------------------------

    @staticmethod
    def _inner(obj):
        return getattr(obj, "jaxpr", obj)

    def _run_inner(self, inner, in_dims, in_routed):
        inner = self._inner(inner)
        sub_dims: dict = {}
        sub_routed: set = set()
        for v, d in zip(inner.invars, in_dims):
            if d:
                sub_dims[v] = frozenset(d)
        for v, r in zip(inner.invars, in_routed):
            if r:
                sub_routed.add(v)
        self._eval(inner, sub_dims, sub_routed)
        return ([self._get(sub_dims, ov) for ov in inner.outvars],
                [self._routed(sub_routed, ov) for ov in inner.outvars])

    def _recurse(self, eqn, dims, routed, in_dims, in_routed) -> None:
        from .jaxpr_rules import _sub_jaxprs

        subs = _sub_jaxprs(eqn)
        inner = self._inner(subs[0]) if subs else None
        if inner is None or len(inner.invars) != len(eqn.invars):
            # arity mismatch (hidden consts): conservative same-shape
            self._set_out(eqn, dims, routed, frozenset(), in_routed)
            if any(in_dims):
                self.unknown.add(eqn.primitive.name)
            return
        routes = [self._routed(routed, v) for v in eqn.invars]
        out_dims, out_routed = self._run_inner(inner, in_dims, routes)
        for ov, t, r in zip(eqn.outvars, out_dims, out_routed):
            t = frozenset(d for d in t if d < len(self._shape(ov)))
            if t:
                dims[ov] = t
            if r or in_routed:
                routed.add(ov)

    def _while(self, eqn, dims, routed, in_dims, in_routed) -> None:
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        body = self._inner(eqn.params["body_jaxpr"])
        consts_d = in_dims[cn:cn + bn]
        carry_d = in_dims[cn + bn:]
        routes = [self._routed(routed, v) for v in eqn.invars]
        carry_r = routes[cn + bn:]
        for _ in range(2):  # taint fixpoint over the carry
            out_d, out_r = self._run_inner(
                body, consts_d + carry_d,
                routes[cn:cn + bn] + carry_r)
            new_d = [a | b for a, b in zip(carry_d, out_d)]
            new_r = [a or b for a, b in zip(carry_r, out_r)]
            if new_d == carry_d and new_r == carry_r:
                break
            carry_d, carry_r = new_d, new_r
        for ov, t, r in zip(eqn.outvars, carry_d, carry_r):
            t = frozenset(d for d in t if d < len(self._shape(ov)))
            if t:
                dims[ov] = t
            if r or in_routed:
                routed.add(ov)

    def _scan(self, eqn, dims, routed, in_dims, in_routed) -> None:
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        body = self._inner(eqn.params["jaxpr"])
        routes = [self._routed(routed, v) for v in eqn.invars]
        consts_d = in_dims[:nc]
        carry_d = list(in_dims[nc:nc + ncar])
        xs_d = []
        for v, t in zip(eqn.invars[nc + ncar:], in_dims[nc + ncar:]):
            if 0 in t and self._shape(v)[0] > 1:
                self.flag(eqn, "scan iterates over the object axis with "
                               "a sequential carry")
            xs_d.append(frozenset(d - 1 for d in t if d > 0))
        carry_r = routes[nc:nc + ncar]
        xs_r = routes[nc + ncar:]
        out_d = out_r = None
        for _ in range(2):
            out_d, out_r = self._run_inner(
                body, consts_d + carry_d + xs_d,
                routes[:nc] + carry_r + xs_r)
            new_d = [a | b for a, b in zip(carry_d, out_d[:ncar])]
            new_r = [a or b for a, b in zip(carry_r, out_r[:ncar])]
            if new_d == carry_d and new_r == carry_r:
                break
            carry_d, carry_r = new_d, new_r
        ys_d = [frozenset(d + 1 for d in t) for t in out_d[ncar:]]
        final_d = carry_d + ys_d
        final_r = carry_r + out_r[ncar:]
        for ov, t, r in zip(eqn.outvars, final_d, final_r):
            t = frozenset(d for d in t if d < len(self._shape(ov)))
            if t:
                dims[ov] = t
            if r or in_routed:
                routed.add(ov)

    def _cond(self, eqn, dims, routed, in_dims, in_routed) -> None:
        branches = eqn.params.get("branches", ())
        routes = [self._routed(routed, v) for v in eqn.invars]
        acc_d = acc_r = None
        for br in branches:
            out_d, out_r = self._run_inner(br, in_dims[1:], routes[1:])
            if acc_d is None:
                acc_d, acc_r = list(out_d), list(out_r)
            else:
                acc_d = [a | b for a, b in zip(acc_d, out_d)]
                acc_r = [a or b for a, b in zip(acc_r, out_r)]
        for ov, t, r in zip(eqn.outvars, acc_d or [], acc_r or []):
            t = frozenset(d for d in t if d < len(self._shape(ov)))
            if t:
                dims[ov] = t
            if r or in_routed:
                routed.add(ov)


# ---------------------------------------------------------------------------
# per-spec checking
# ---------------------------------------------------------------------------


def _resolve_obj(contract: ShardContract, leaves) -> Dict[int, int]:
    """Flattened-leaf index -> object-axis dim, for one case's args."""
    out: Dict[int, int] = {}
    for leaf, axis in contract.obj:
        if leaf == ALL_LEAVES:
            for i, x in enumerate(leaves):
                if len(x.shape) > axis:
                    out[i] = axis
        elif isinstance(leaf, int) and leaf < len(leaves) \
                and len(leaves[leaf].shape) > axis:
            out[leaf] = axis
    return out


def _shard_args(args, obj_axes: Dict[int, int], s: int):
    """The args re-shaped to their per-shard extents under an abstract
    ``Mesh(("objects", s))`` — exactly the operand shapes a shard_map
    body sees, without needing s physical devices."""
    import jax
    from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec

    mesh = AbstractMesh((s,), ("objects",))
    leaves, treedef = jax.tree_util.tree_flatten(args)
    out = []
    for i, leaf in enumerate(leaves):
        ax = obj_axes.get(i)
        if ax is None:
            out.append(leaf)
            continue
        spec = [None] * len(leaf.shape)
        spec[ax] = "objects"
        shard = NamedSharding(mesh, PartitionSpec(*spec)).shard_shape(
            tuple(leaf.shape))
        out.append(jax.ShapeDtypeStruct(shard, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _loc_for(spec, eqn, files_by_rel, root):
    loc = _eqn_loc(eqn, root) if eqn is not None else None
    if loc is not None:
        return loc
    return spec.path, _site_line(spec, files_by_rel)


def _check_spec(spec: KernelSpec, cases, files_by_rel: dict, root: str,
                report: ShardReport) -> List[Finding]:
    import jax

    c = spec.sharding
    findings: List[Finding] = []
    seen: set = set()
    found_coll: Dict[str, tuple] = {}  # collective -> anchor loc
    keys_by_s: Dict[int, set] = {}
    sc04_seen: set = set()
    unknown: Set[str] = set()
    opaque = False

    def analyze(closed, case, leaves, obj_axes, rung):
        nonlocal opaque
        report.cases += 1
        for eqn, _ in _walk(closed.jaxpr):
            coll = _COLLECTIVE_BY_PRIM.get(eqn.primitive.name)
            if coll is not None and coll not in found_coll:
                found_coll[coll] = _loc_for(spec, eqn, files_by_rel, root)
        if c.sclass != "pointwise":
            return
        invars = closed.jaxpr.invars
        if len(invars) != len(leaves):
            report.trace_errors.append(
                f"{spec.name} [{rung}]: {len(leaves)} arg leaves but "
                f"{len(invars)} jaxpr invars — contract leaf indices "
                "cannot be aligned")
            return

        def flag(eqn, what):
            loc = _loc_for(spec, eqn, files_by_rel, root)
            key = ("SC01", loc, what)
            if key in seen:
                return
            seen.add(key)
            findings.append(Finding(
                "SC01", loc[0], loc[1], 0,
                f"kernel {spec.name} [{rung}]: {what} — cross-object "
                "data flow in a pointwise-declared kernel: shard-local "
                "execution would need another shard's rows; declare a "
                "reduction contract with its collective, declare the "
                "index operand routed, or fix the kernel",
            ))

        prov = _Prov(flag, unknown)
        in_dims = [frozenset({obj_axes[i]}) if i in obj_axes
                   else frozenset() for i in range(len(leaves))]
        in_routed = [i in c.routed for i in range(len(leaves))]
        prov.run(closed.jaxpr, in_dims, in_routed)
        opaque = opaque or prov.opaque

    for case in cases:
        leaves = jax.tree_util.tree_leaves(case.args)
        obj_axes = _resolve_obj(c, leaves)

        # SC04: ragged shards, pure arithmetic on the declared ladder
        for s in c.mesh_sizes:
            if s == 1:
                continue
            for i, ax in sorted(obj_axes.items()):
                size = leaves[i].shape[ax]
                if size < s * c.granule:
                    continue  # below one granule per shard: stays dense
                if size % s == 0 and (size // s) % c.granule == 0:
                    continue
                key = (case.rung, s)
                if key in sc04_seen:
                    continue
                sc04_seen.add(key)
                findings.append(Finding(
                    "SC04", spec.path, _site_line(spec, files_by_rel), 0,
                    f"kernel {spec.name} [{case.rung}]: object-axis "
                    f"extent {size} (arg leaf {i}, dim {ax}) does not "
                    f"shard evenly over mesh size {s} (granule "
                    f"{c.granule}) — a ragged shard gives one device a "
                    "different program shape than its peers; pad the "
                    "rung or restrict the contract's mesh_sizes",
                ))

        try:
            closed = jax.make_jaxpr(case.fn)(*case.args)
        except Exception as e:
            report.trace_errors.append(
                f"{spec.name} [{case.rung}]: {type(e).__name__}: {e}")
            continue
        analyze(closed, case, leaves, obj_axes, case.rung)

        # mesh-shaped cases: the shard-local program at the declared
        # mesh sizes (pointwise only: its statics never bind the object
        # extent — a reduction kernel's factory rebinds per shard).
        # SC05's lowering keys are pure shape arithmetic, counted at
        # EVERY valid size; the jaxpr itself is traced once per case at
        # the largest valid size (extents never change the primitive
        # structure, only the budget counts care about each size)
        if c.sclass != "pointwise" or not obj_axes:
            continue
        valid = [s for s in c.mesh_sizes
                 if s > 1 and all(
                     leaves[i].shape[ax] % s == 0
                     and leaves[i].shape[ax] >= s * c.granule
                     and (leaves[i].shape[ax] // s) % c.granule == 0
                     for i, ax in obj_axes.items())]
        for s in valid:
            keys_by_s.setdefault(s, set()).add(
                (case.key, _flat_avals(_shard_args(case.args,
                                                   obj_axes, s))))
        if not valid:
            continue  # SC04 already spoke, or the rung stays dense
        s = max(valid)
        sliced = _shard_args(case.args, obj_axes, s)
        try:
            closed_s = jax.make_jaxpr(case.fn)(*sliced)
        except Exception as e:
            report.trace_errors.append(
                f"{spec.name} [{case.rung}.mesh{s}]: "
                f"{type(e).__name__}: {e} — the kernel's statics "
                "bind the object extent; it cannot trace at shard "
                "shapes")
            continue
        report.mesh_cases += 1
        sliced_leaves = jax.tree_util.tree_leaves(sliced)
        analyze(closed_s, case, sliced_leaves, obj_axes,
                f"{case.rung}.mesh{s}")

    # SC05: per-mesh-size lowering budget
    for s, keys in sorted(keys_by_s.items()):
        if len(keys) > spec.compile_budget:
            findings.append(Finding(
                "SC05", spec.path, _site_line(spec, files_by_rel), 0,
                f"kernel {spec.name}: {len(keys)} distinct lowerings at "
                f"mesh size {s} (budget {spec.compile_budget}) — every "
                "shard recompiles that many times on the regrow path; "
                "the jit cache keys on more than the capacity rungs",
            ))

    # SC02: the collective contract
    declared = set(c.collectives)
    found = set(found_coll)
    report.collectives[spec.name] = sorted(found)
    extra = found - declared
    missing = declared - found
    if extra:
        prim = sorted(extra)[0]
        loc = found_coll[prim]
        findings.append(Finding(
            "SC02", loc[0], loc[1], 0,
            f"kernel {spec.name}: lowers undeclared collective(s) "
            f"{sorted(extra)} (declared: {sorted(declared) or 'none'}, "
            f"class {c.sclass!r}) — an undeclared collective is a "
            "hidden cross-shard dependency; declare it on the "
            "reduction contract or remove it from the kernel",
        ))
    if missing:
        findings.append(Finding(
            "SC02", spec.path, _site_line(spec, files_by_rel), 0,
            f"kernel {spec.name}: declares collective(s) "
            f"{sorted(missing)} the traced jaxpr never lowers — a "
            "stale contract hides the cross-shard cost model; fix the "
            "declaration",
        ))

    if unknown:
        for u in sorted(unknown):
            if u not in report.unknown_prims:
                report.unknown_prims.append(u)
    if opaque and spec.name not in report.opaque:
        report.opaque.append(spec.name)
    return findings


# ---------------------------------------------------------------------------
# SC03: host round-trips on kernel outputs (AST tier, tracer.py style)
# ---------------------------------------------------------------------------

_HOST_COERCIONS = {"int", "float"}
_NP_MODULES = {"np", "numpy"}
_NP_FUNCS = {"asarray", "array"}


def _np_converter(func: ast.AST) -> bool:
    return (isinstance(func, ast.Attribute)
            and func.attr in _NP_FUNCS
            and isinstance(func.value, ast.Name)
            and func.value.id in _NP_MODULES)


def _base_name(node: ast.AST) -> Optional[ast.AST]:
    while isinstance(node, ast.Subscript):
        node = node.value
    return node


def check_host_roundtrips(files: Sequence[ParsedFile],
                          specs: Sequence[KernelSpec]) -> List[Finding]:
    """SC03, fully lexical (the tracer.py discipline): inside the mesh
    hot-path packages, a local bound from a jitted-kernel call that
    flows into ``int()``/``float()``/``.item()``/``np.asarray()`` is a
    host round-trip — on a sharded fleet, a device sync plus a
    cross-shard gather per call.  Deliberate sample points (the
    occupancy observatory's six-int fetch) carry pragmas with their
    cadence as the justification."""
    by_path: Dict[str, set] = {}
    for s in specs:
        by_path.setdefault(s.path, set()).add(s.jit_name.split(".")[0])
    findings: List[Finding] = []
    for pf in files:
        if not pf.rel.startswith(SC03_SCOPE):
            continue
        producers = {site.name.split(".")[0]
                     for site in iter_jit_sites(pf.tree)}
        producers |= by_path.get(pf.rel, set())
        producers.discard("<lambda>")
        if not producers:
            continue
        for fn in ast.walk(pf.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                findings.extend(_scan_fn(pf, fn, producers))
    return findings


def _scan_fn(pf: ParsedFile, fn: ast.AST, producers: set) -> List[Finding]:
    def is_producer_call(node) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            f.id if isinstance(f, ast.Name) else ""
        return name in producers

    # pass 1: taint locals bound (transitively) from producer calls;
    # two sweeps approximate a fixpoint over lexical order
    tainted: set = set()
    for _ in range(2):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            val = _base_name(node.value)
            src_tainted = (is_producer_call(val)
                           or (isinstance(val, ast.Name)
                               and val.id in tainted))
            if not src_tainted:
                continue
            for tgt in node.targets:
                tgts = tgt.elts if isinstance(tgt, (ast.Tuple, ast.List)) \
                    else [tgt]
                for t in tgts:
                    if isinstance(t, ast.Name):
                        tainted.add(t.id)

    def device_value(node) -> bool:
        base = _base_name(node)
        return (is_producer_call(base)
                or (isinstance(base, ast.Name) and base.id in tainted))

    out: List[Finding] = []
    emitted: set = set()

    def emit(node, conv):
        key = (node.lineno, conv)
        if key in emitted:
            return
        emitted.add(key)
        out.append(Finding(
            "SC03", pf.rel, node.lineno, node.col_offset,
            f"host round-trip: {conv} materializes a jitted kernel's "
            "output on the host inside a mesh hot path — on a sharded "
            "fleet this is a device sync + cross-shard gather per "
            "call; keep the value on device, fold the read into the "
            "kernel, or pragma the deliberate sample point with its "
            "cadence",
        ))

    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in _HOST_COERCIONS:
            if node.args and device_value(node.args[0]):
                emit(node, f"{f.id}()")
        elif _np_converter(f):
            if node.args and device_value(node.args[0]):
                emit(node, f"np.{f.attr}()")
        elif isinstance(f, ast.Attribute) and f.attr == "item" \
                and not node.args:
            if device_value(f.value):
                emit(node, ".item()")
    return out


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------


def run_shardcheck(specs: Optional[Sequence[KernelSpec]] = None,
                   baseline: Optional[Baseline] = None,
                   root: Optional[str] = None,
                   ) -> tuple:
    """Trace every manifested kernel against its sharding contract.

    Returns ``(LintResult, ShardReport)``.  Triage mirrors
    kernelcheck's: pragma at the finding's line, then the baseline,
    everything else live — plus the stale-sanction re-flag: an SC
    pragma that suppressed nothing this run is itself a live finding.
    """
    t0 = time.perf_counter()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from ..config import enable_x64

    enable_x64()  # the batch package's import-time contract

    if specs is None:
        specs = MANIFEST
    root = root or repo_root()
    report = ShardReport(kernels=len(specs))

    paths = sorted({s.path for s in specs})
    files, parse_errors = load_files(
        [os.path.join(root, p) for p in paths], root=root)
    files_by_rel = {f.rel: f for f in files}

    raw: List[Finding] = []
    for spec in specs:
        c = spec.sharding
        if c is None:
            report.skipped.append({
                "kernel": spec.name,
                "reason": "no sharding contract (the kernel-manifest "
                          "tier-1 rule flags this)"})
            continue
        report.contracts[c.sclass] = report.contracts.get(c.sclass, 0) + 1
        if spec.build is None or c.sclass == "host_only":
            report.skipped.append({
                "kernel": spec.name,
                "reason": c.reason or spec.notrace_reason or c.sclass})
            continue
        try:
            cases = spec.build()
        except Exception as e:
            report.trace_errors.append(
                f"{spec.name} [build]: {type(e).__name__}: {e}")
            continue
        report.traced += 1
        raw.extend(_check_spec(spec, cases, files_by_rel, root, report))

    # SC03 scans the whole hot-path scope, not just kernel-owning files
    sc03_paths = []
    for prefix in SC03_SCOPE:
        base = os.path.join(root, prefix)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(
                d for d in dirnames if d != "__pycache__")
            for fname in sorted(filenames):
                if fname.endswith(".py"):
                    sc03_paths.append(os.path.join(dirpath, fname))
    sc03_files, sc03_errors = load_files(sc03_paths, root=root)
    parse_errors += sc03_errors
    report.sc03_files = len(sc03_files)
    for pf in sc03_files:
        files_by_rel.setdefault(pf.rel, pf)
    raw.extend(check_host_roundtrips(sc03_files, specs))

    # findings anchor at equation user frames, which may live in helper
    # modules (ops/, gc/) that own no jit site — load those too so their
    # pragmas are honored
    missing = sorted({f.path for f in raw} - set(files_by_rel))
    if missing:
        extra, extra_errors = load_files(
            [os.path.join(root, p) for p in missing], root=root)
        parse_errors += extra_errors
        for pf in extra:
            files_by_rel.setdefault(pf.rel, pf)

    # triage: pragmas, then baseline — the crdtlint machinery verbatim
    live: List[Finding] = []
    suppressed: List[Finding] = []
    baselined: List[Finding] = []
    for f in raw:
        pf = files_by_rel.get(f.path)
        if pf is not None and pf.suppressed(f.rule, f.line):
            suppressed.append(f)
        elif baseline is not None and baseline.covers(f):
            baselined.append(f)
        else:
            live.append(f)

    # the stale-sanction screw (KC01 discipline, generalized): an SC
    # pragma that suppressed nothing this run means the contract now
    # traces clean — the sanction must come off so the check re-arms.
    # A pragma is only judged where its rule actually RAN this pass:
    # SC03 in the scanned hot-path set, the trace rules in
    # kernel-owning or finding-anchored files — a subset run (fixture
    # specs) must not re-flag the rest of the tree's sanctions
    used = {(f.rule, f.path, f.line) for f in suppressed}
    spec_paths = {s.path for s in specs}
    sc03_rels = {pf.rel for pf in sc03_files}
    anchored = set(missing)
    for pf in files_by_rel.values():
        for line, rules in sorted(pf._line_pragmas.items()):
            for r in sorted(rules):
                if r not in SHARD_RULES or (r, pf.rel, line) in used:
                    continue
                if r == "SC03":
                    if pf.rel not in sc03_rels:
                        continue
                elif pf.rel not in spec_paths and pf.rel not in anchored:
                    continue
                live.append(Finding(
                    r, pf.rel, line, 0,
                    f"stale {r} sanction: a pragma suppresses a "
                    f"{r} finding here, but the kernel's sharding "
                    "contract traces clean on this tree — remove "
                    "the pragma so the check re-arms",
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
