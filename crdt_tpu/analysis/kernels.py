"""The kernel-contract manifest: every jitted entry point, declared.

PR 4's crdtlint sees Python source only.  The contracts that keep
lattice joins byte-identical live one layer lower, in the *compiled*
program: an i64 primitive Mosaic cannot lower (the "jax 0.4.x Pallas
skew" class), a float scatter-add whose accumulation order varies run
to run, a closure-captured array baked into every lowering of the
capacity-regrow ladder, a kernel that silently recompiles per batch
size.  This module is the single source of truth those checks hang off:

* :class:`KernelSpec` — one row per jitted kernel: where it lives
  (``path`` + ``jit_name``, the AST coordinates of the ``jax.jit``
  site), its determinism class, whether it is Mosaic-destined, its
  compile budget across the canonical capacity ladder, and a ``build``
  hook producing the abstract trace cases
  (:mod:`crdt_tpu.analysis.jaxpr_rules` walks the resulting jaxprs).
* :data:`MANIFEST` — the rows.  100% coverage of ``@jax.jit`` entry
  points under ``crdt_tpu/`` is enforced by the ``kernel-manifest``
  AST rule below (tier 1, stdlib-only, no jax import), the same
  single-source discipline :mod:`crdt_tpu.obs.namespace` applies to
  metric names.
* :func:`iter_jit_sites` — the stdlib AST extractor both layers share:
  a jit site is a ``jax.jit``/``functools.partial(jax.jit, ...)``
  decorator or a direct ``jax.jit(fn)`` call, named by the enclosing
  def/class chain (``_scatter_adds_kernel.kernel``,
  ``_fold_merge_kernel.<jit>``).

The manifest is also the RUNTIME observatory's identity table
(:mod:`crdt_tpu.obs.kernels`): every row's jitted callable wears an
``observed_kernel(<row name>)`` wrapper publishing live compile counts
(KC04's budget as the ``kernel.<name>.compile_budget_frac`` gauge),
per-call wall histograms and device-memory accounting; the runtime
registry refuses names without a row here, and the manifest↔runtime
cross-check (``tests/test_kernel_obs.py``) walks every ``build``
closure to pin that each traceable row is instrumented.  ``build``
closures therefore double as instrumentation warm-ups: they must reach
each kernel through its public factory (``_derive_kernel()``,
``_fold_merge_kernel(...)``) rather than re-deriving the callable.

Import contract: importing this module must stay stdlib-only (the AST
rule gates tier-1 CI on jax-free boxes).  Everything jax-flavoured
lives inside the ``build`` closures, which only run under
``python -m crdt_tpu.analysis --kernels``.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Callable, List, Optional

from .core import Finding, ParsedFile, dotted_name, rule

# ---------------------------------------------------------------------------
# the canonical capacity ladder
# ---------------------------------------------------------------------------

#: (num_actors, member_capacity, deferred_capacity) rungs of the regrow
#: ladder kernelcheck traces every ORSWOT-shaped kernel across — the
#: same doubling walk ``with_capacity`` takes when a merge overflows
#: (parallel/executor.py regrow path).  One fresh lowering per rung is
#: the expected cost; KC04 fails a kernel whose ladder produces MORE
#: distinct lowerings than its declared budget.
LADDER = ((8, 8, 4), (8, 16, 8), (8, 32, 8))

#: actor-axis rungs for clock/counter-plane kernels (num_actors regrow)
ACTOR_LADDER = (8, 16, 32)

LADDER_N = 8   # objects per fleet in trace cases
LADDER_R = 3   # stacked replicas for fold kernels
LADDER_B = 16  # op-batch rows (power of two: the padded scatter shape)


# ---------------------------------------------------------------------------
# jit-site extraction (stdlib, shared by the AST rule and kernelcheck)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class JitSite:
    """One ``jax.jit`` application in one source file."""

    name: str  # enclosing def/class chain + target, "." joined
    line: int


def _is_jit_expr(node: ast.AST) -> bool:
    return dotted_name(node) == "jax.jit"


def _decorator_is_jit(dec: ast.AST) -> bool:
    if _is_jit_expr(dec):
        return True
    if isinstance(dec, ast.Call):
        if _is_jit_expr(dec.func):  # @jax.jit(...) factory form
            return True
        if (dotted_name(dec.func) in ("functools.partial", "partial")
                and dec.args and _is_jit_expr(dec.args[0])):
            return True
    return False


def iter_jit_sites(tree: ast.AST) -> List[JitSite]:
    """Every jit application in ``tree``, deterministically named:

    * a jit-decorated ``def`` → the def/class chain
      (``PipelinedWireLoop._merge_jnp`` style, dots, no ``<locals>``);
    * a direct ``jax.jit(target, ...)`` call → the enclosing chain plus
      the target's trailing identifier (``_jit.fn``), ``<lambda>`` for
      lambdas, ``<jit>`` for computed targets such as
      ``jax.jit(functools.partial(...))``.
    """
    sites: List[JitSite] = []
    deco_calls: set = set()

    def visit(node: ast.AST, scope: tuple) -> None:
        child_scope = scope
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and _decorator_is_jit(dec):
                    deco_calls.add(id(dec))
            if any(_decorator_is_jit(d) for d in node.decorator_list):
                sites.append(
                    JitSite(".".join(scope + (node.name,)), node.lineno))
            child_scope = scope + (node.name,)
        elif isinstance(node, ast.ClassDef):
            child_scope = scope + (node.name,)
        elif (isinstance(node, ast.Call) and id(node) not in deco_calls
              and _is_jit_expr(node.func)):
            arg = node.args[0] if node.args else None
            if isinstance(arg, ast.Name):
                leaf = arg.id
            elif isinstance(arg, ast.Attribute):
                leaf = arg.attr
            elif isinstance(arg, ast.Lambda):
                leaf = "<lambda>"
            else:
                leaf = "<jit>"
            sites.append(JitSite(".".join(scope + (leaf,)), node.lineno))
        for child in ast.iter_child_nodes(node):
            visit(child, child_scope)

    visit(tree, ())
    return sites


# ---------------------------------------------------------------------------
# the manifest rows
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TraceCase:
    """One abstract call of one kernel: statics pre-bound, array args as
    ``jax.ShapeDtypeStruct``\\s.  ``key`` fingerprints the static
    arguments; the harness appends the arg avals to form the jit cache
    key KC04 counts."""

    rung: str
    fn: Callable
    args: tuple
    key: tuple = ()


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Declared contract for one jitted kernel.

    ``determinism`` classes: ``"bitwise"`` (output is a pure lattice
    fold — byte-identical across devices and merge orders, the digest
    oracle's requirement), ``"integer-lattice"`` (integer scatter/fold —
    order-free by associativity, the sanctioned scatter-max witness
    idiom), ``"float-accum"`` (floating-point accumulation — order
    sensitivity must be justified; none shipped today).  KC02 sanctions
    integer lattice folds and flags unordered float scatter-adds
    everywhere.

    ``compile_budget`` bounds the DISTINCT lowerings the trace cases may
    produce (jit cache keys: static fingerprint + arg avals).  The
    regrow ladder legitimately recompiles once per rung; a kernel that
    retraces on anything else blows the budget — KC04.

    ``build`` returns the :class:`TraceCase` list, importing jax/numpy
    lazily.  ``build=None`` rows are manifest-covered but not traced
    (``notrace_reason`` says why; the CLI reports them, never silently).
    """

    name: str                     # stable kernel id, e.g. "batch.orswot.merge"
    path: str                     # repo-relative source file
    jit_name: str                 # AST site name (see iter_jit_sites)
    determinism: str = "bitwise"
    mosaic: bool = False          # Mosaic/TPU-destined (KC01 strict)
    compile_budget: int = 3
    const_budget: int = 1 << 16   # KC03: max baked-constant bytes per trace
    hot_path: bool = True         # KC05: host callbacks forbidden
    build: Optional[Callable[[], List[TraceCase]]] = None
    notrace_reason: str = ""
    sharding: Optional["ShardContract"] = None  # SC01-SC05 (shardcheck)


# ---------------------------------------------------------------------------
# sharding contracts (the third tier: shardcheck, SC01-SC05)
# ---------------------------------------------------------------------------

#: the declared object-axis shard counts every mesh-shaped kernel must
#: divide across — the {1,2,4,8} ladder the ROADMAP mesh item plans
#: shard_map over (SC04 checks every capacity rung against them)
MESH_SIZES = (1, 2, 4, 8)

SHARD_CLASSES = ("pointwise", "reduction", "replicated", "host_only")

#: collective primitive names a ``reduction`` contract may declare
#: (SC02: the jaxpr must lower EXACTLY the declared set)
COLLECTIVE_PRIMS = (
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter",
)

#: sentinel leaf index: "every array leaf of the flattened args"
ALL_LEAVES = "*"


@dataclasses.dataclass(frozen=True)
class ShardContract:
    """Declared object-axis sharding contract for one kernel.

    The mesh PR (ROADMAP: mesh-sharded fleets) shards the *object axis*
    of the dense planes: local kernels per shard + ICI collectives for
    the global lattice join.  That decomposition is provably safe only
    for kernels whose jaxprs respect the object axis — which is exactly
    what this contract declares and :mod:`shard_rules` verifies:

    ``sclass``
        * ``"pointwise"`` — every output row depends only on its own
          object's rows: shard-local execution IS the global answer
          (``out_specs`` keep the object axis, no collective).  SC01
          flags any cross-object data flow in the traced jaxpr.
        * ``"reduction"`` — legitimately folds the object axis (digest
          tree levels, occupancy totals, frontier folds) or joins
          across a mesh axis; the global answer needs the declared
          ``collectives`` (SC02: the jaxpr must lower exactly them —
          today only the parallel/ joins lower any).
        * ``"replicated"`` — no object-axis operand at all; runs
          identically (or shard-locally on routed values) on every
          shard and must lower no collective.
        * ``"host_only"`` — off the mesh hot path (snapshot
          compact/expand); never mesh-traced.

    ``obj`` — ``((leaf, axis), ...)``: which flattened arg leaves carry
    the object axis and at which dim (``(ALL_LEAVES, axis)`` = every
    leaf).  Leaf order is ``jax.tree_util.tree_leaves`` over the
    TraceCase args, stable across the ladder.

    ``routed`` — flattened leaf indices whose *values* are object ids
    (op/read batches): the mesh layer rebases them per shard, so
    gathers/scatters indexing the object axis through them are
    sanctioned cross-shard-safe (SC01 exempts routed indexing).

    ``mesh_sizes`` — shard counts this kernel must divide across
    (default :data:`MESH_SIZES`); restrict with a ``reason`` when the
    kernel is structurally pinned (e.g. an already-shard-local body).

    ``granule`` — object-axis alignment unit per shard (the digest
    tree folds in TREE_K=16 blocks); SC04 requires ``size % S == 0``
    and ``(size // S) % granule == 0`` for every rung with
    ``size >= S * granule`` (smaller rungs stay dense/replicated).
    """

    sclass: str
    obj: tuple = ()           # ((leaf, axis), ...) or ((ALL_LEAVES, axis),)
    routed: tuple = ()        # leaf indices carrying object-id values
    collectives: tuple = ()   # reduction: exact collective prims lowered
    mesh_sizes: tuple = MESH_SIZES
    granule: int = 1
    reason: str = ""


def _obj_axes(leaves: tuple, axis: int) -> tuple:
    out = []
    for leaf in leaves:
        if isinstance(leaf, (int, str)):
            out.append((leaf, axis))
        else:
            out.append(tuple(leaf))
    return tuple(out)


def pointwise(*leaves, axis: int = 0, routed=(), mesh_sizes=MESH_SIZES,
              granule: int = 1, reason: str = "") -> ShardContract:
    """Pointwise over objects; no ``leaves`` means every arg leaf
    carries the object axis at ``axis``."""
    obj = _obj_axes(leaves or (ALL_LEAVES,), axis)
    return ShardContract("pointwise", obj, tuple(routed), (),
                         tuple(mesh_sizes), granule, reason)


def reduction(*leaves, axis: int = 0, collectives=(), routed=(),
              mesh_sizes=MESH_SIZES, granule: int = 1,
              reason: str = "") -> ShardContract:
    """Folds the object axis (or joins a mesh axis with the declared
    collectives); ``leaves`` may be empty for pure mesh-axis joins."""
    return ShardContract("reduction", _obj_axes(leaves, axis),
                         tuple(routed), tuple(collectives),
                         tuple(mesh_sizes), granule, reason)


def replicated(reason: str, routed=()) -> ShardContract:
    return ShardContract("replicated", (), tuple(routed), (), (), 1, reason)


def host_only(reason: str) -> ShardContract:
    return ShardContract("host_only", (), (), (), (), 1, reason)


# -- builder helpers (jax/numpy imported lazily, never at module scope) ------


def _cfg(a: int, m: int, d: int, mv: int = 4, k: int = 4):
    from ..config import CrdtConfig

    return CrdtConfig(num_actors=a, member_capacity=m, deferred_capacity=d,
                      mv_capacity=mv, key_capacity=k)


def _sds(tree):
    """Every array leaf of ``tree`` replaced by its ShapeDtypeStruct."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _orswot_planes(a: int, m: int, d: int, n: int = LADDER_N):
    from ..batch.orswot_batch import OrswotBatch
    from ..utils.interning import Universe

    b = OrswotBatch.zeros(n, Universe.identity(_cfg(a, m, d)))
    return _sds((b.clock, b.ids, b.dots, b.d_ids, b.d_clocks))


def _stacked(planes, r: int = LADDER_R):
    import jax

    return tuple(
        jax.ShapeDtypeStruct((r,) + p.shape, p.dtype) for p in planes)


def _vec(n, dtype_name):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((n,), getattr(jnp, dtype_name))


def _mat(shape, dtype_name):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), getattr(jnp, dtype_name))


def _clock_dt():
    import jax.numpy as jnp

    from ..config import enable_x64

    return "uint64" if enable_x64() else "uint32"


def _cpu_mesh(axis: str = "replicas"):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices("cpu")[:1]), (axis,))


def _unjit(fn):
    """The traceable callable behind a jitted one (tracing through the
    pjit wrapper would work too — the walkers recurse into sub-jaxprs —
    but the bare function keeps static arguments plain Python)."""
    return getattr(fn, "__wrapped__", fn)


# -- builders ----------------------------------------------------------------


def _b_orswot_batch(kernel_attr: str, statics: Callable = None,
                    extra: Callable = None, fleets: bool = False):
    """Shared builder for the orswot_batch jitted kernels: planes across
    the ladder (``fleets``: one tuple of ``LADDER_R`` plane sets), plus
    ``extra(a, m, d) -> tuple`` trailing args and
    ``statics(a, m, d) -> dict`` pre-bound keywords."""

    def build():
        import functools

        from ..batch import orswot_batch as ob

        fn = _unjit(getattr(ob, kernel_attr))
        cases = []
        for (a, m, d) in LADDER:
            planes = _orswot_planes(a, m, d)
            if fleets:
                planes = ((planes,) * LADDER_R,)
            kw = statics(a, m, d) if statics else {}
            args = planes + (extra(a, m, d) if extra else ())
            cases.append(TraceCase(
                rung=f"A{a}.M{m}.D{d}",
                fn=functools.partial(fn, **kw) if kw else fn,
                args=args,
                key=tuple(sorted(kw.items())),
            ))
        return cases

    return build


def _b_orswot_merge():
    def build():
        import functools

        from ..batch import orswot_batch as ob

        fn = _unjit(ob._merge)
        cases = []
        for (a, m, d) in LADDER:
            planes = _orswot_planes(a, m, d)
            cases.append(TraceCase(
                rung=f"A{a}.M{m}.D{d}",
                fn=functools.partial(fn, m_cap=m, d_cap=d, impl="rank"),
                args=planes + planes,
                key=(m, d, "rank"),
            ))
        return cases

    return build


def _b_counter_merge(module: str, shape):
    """Clock/counter-plane pairwise merges across the actor ladder;
    ``shape(a) -> plane shape``."""

    def build():
        import importlib

        mod = importlib.import_module(f"crdt_tpu.batch.{module}")
        fn = _unjit(mod._merge)
        dt = _clock_dt()
        cases = []
        for a in ACTOR_LADDER:
            p = _mat(shape(a), dt)
            cases.append(TraceCase(rung=f"A{a}", fn=fn, args=(p, p)))
        return cases

    return build


def _b_gset_merge():
    def build():
        from ..batch import gset_batch as gb

        fn = _unjit(gb._merge)
        cases = []
        for cap in (64, 128, 256):  # member-bitmap capacity ladder
            p = _mat((LADDER_N, cap), "bool_")
            cases.append(TraceCase(rung=f"K{cap}", fn=fn, args=(p, p)))
        return cases

    return build


def _b_lww_merge():
    def build():
        from ..batch import lwwreg_batch as lb

        fn = _unjit(lb._merge)
        dt = _clock_dt()
        cases = []
        for n in (8, 64, 512):  # register-count ladder (no capacity axis)
            v, m = _vec(n, dt), _vec(n, dt)
            cases.append(TraceCase(rung=f"N{n}", fn=fn, args=(v, m, v, m)))
        return cases

    return build


def _b_mvreg(kernel_attr: str, with_op: bool = False, k_static: bool = True):
    def build():
        import functools

        from ..batch import mvreg_batch as mb
        from ..batch.mvreg_batch import MVRegBatch
        from ..utils.interning import Universe

        fn = _unjit(getattr(mb, kernel_attr))
        cases = []
        for (a, mv) in ((8, 4), (8, 8), (16, 8)):  # antichain regrow
            b = MVRegBatch.zeros(LADDER_N, Universe.identity(
                _cfg(a, 8, 4, mv=mv)))
            c, v = _sds((b.clocks, b.vals))
            if kernel_attr == "_merge":
                args = (c, v, c, v)
            elif kernel_attr == "_apply_put":
                args = (c, v, _mat((LADDER_N, a), _clock_dt()),
                        _vec(LADDER_N, _clock_dt()))
            else:  # _truncate
                args = (c, v, _mat((LADDER_N, a), _clock_dt()))
            kw = {"k_cap": mv} if k_static else {}
            cases.append(TraceCase(
                rung=f"A{a}.K{mv}",
                fn=functools.partial(fn, **kw) if kw else fn,
                args=args, key=tuple(sorted(kw.items())),
            ))
        return cases

    return build


def _map_fixture(a: int, k: int, d: int):
    from ..batch.map_batch import MapBatch
    from ..batch.val_kernels import MVRegKernel
    from ..utils.interning import Universe

    cfg = _cfg(a, 8, d, mv=2, k=k)
    uni = Universe.identity(cfg)
    batch = MapBatch.zeros(LADDER_N, uni, MVRegKernel.from_config(cfg))
    return batch


_MAP_LADDER = ((8, 4, 4), (8, 8, 4), (8, 16, 8))  # (A, key_cap, deferred)


def _b_map(kernel_attr: str):
    def build():
        import functools

        from ..batch import map_batch as mb

        fn = _unjit(getattr(mb, kernel_attr))
        dt = _clock_dt()
        cases = []
        for (a, k, d) in _MAP_LADDER:
            batch = _map_fixture(a, k, d)
            state = _sds(batch.state)
            kern = batch.kernel
            if kernel_attr == "_merge":
                args, kw = (state, state), {"kernel": kern}
            elif kernel_attr == "_truncate":
                args, kw = (state, _mat((LADDER_N, a), dt)), {"kernel": kern}
            elif kernel_attr == "_apply_rm":
                args = (state, _mat((LADDER_N, a), dt), _vec(LADDER_N, "int32"))
                kw = {"kernel": kern}
            else:  # _apply_up: nested MVReg put
                args = (
                    state, _vec(LADDER_N, "int32"), _vec(LADDER_N, dt),
                    _vec(LADDER_N, "int32"),
                    (_mat((LADDER_N, a), dt), _vec(LADDER_N, dt)),
                )
                kw = {"nested_op": "apply_put", "kernel": kern}
            cases.append(TraceCase(
                rung=f"A{a}.K{k}.D{d}",
                fn=functools.partial(fn, **kw), args=args,
                key=(kernel_attr, a, k, d),
            ))
        return cases

    return build


def _b_occupancy(which: str):
    """The plane-occupancy reductions (batch/occupancy.py): pure
    integer counting folds, traced across the same regrow rungs as the
    kernels whose planes they measure."""

    def build():
        from ..batch import occupancy as oc

        dt = _clock_dt()
        cases = []
        if which == "orswot":
            fn = _unjit(oc._orswot_occupancy)
            for (a, m, d) in LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}.M{m}.D{d}", fn=fn,
                    args=_orswot_planes(a, m, d)))
        elif which == "clock":
            fn = _unjit(oc._clock_occupancy)
            for a in ACTOR_LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}", fn=fn,
                    args=(_mat((LADDER_N, a), dt),)))
        elif which == "pn":
            fn = _unjit(oc._pn_occupancy)
            for a in ACTOR_LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}", fn=fn,
                    args=(_mat((LADDER_N, 2, a), dt),)))
        else:  # map
            fn = _unjit(oc._map_occupancy)
            for (a, k, d) in _MAP_LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}.K{k}.D{d}", fn=fn,
                    args=(_mat((LADDER_N, a), dt),
                          _mat((LADDER_N, k), "int32"),
                          _mat((LADDER_N, k, a), dt),
                          _mat((LADDER_N, d), "int32"),
                          _mat((LADDER_N, d, a), dt))))
        return cases

    return build


def _b_gc_settle():
    """The standalone defer plunger (gc/compact.py): the same replay
    stage merge's deferred pipeline runs, traced across the regrow
    ladder like the merge kernels whose planes it settles."""

    def build():
        from ..gc import compact as gc_compact

        fn = _unjit(gc_compact._settle)
        return [
            TraceCase(rung=f"A{a}.M{m}.D{d}", fn=fn,
                      args=_orswot_planes(a, m, d))
            for (a, m, d) in LADDER
        ]

    return build


def _b_gc_repack():
    """The shrink re-pack (gc/repack.py): every ladder rung re-packed
    one rung down — the shrink direction the executor's regrow ladder
    never exercises."""

    def build():
        import functools

        from ..gc import repack as gc_repack

        fn = _unjit(gc_repack._repack)
        cases = []
        for (a, m, d) in LADDER:
            m_new, d_new = max(1, m // 2), max(1, d // 2)
            cases.append(TraceCase(
                rung=f"A{a}.M{m}.D{d}->M{m_new}.D{d_new}",
                fn=functools.partial(fn, m_cap=m_new, d_cap=d_new),
                args=_orswot_planes(a, m, d), key=(m_new, d_new)))
        return cases

    return build


def _b_wireloop_merge():
    def build():
        from ..batch import wireloop

        cases = []
        for (a, m, d) in LADDER:
            planes = _orswot_planes(a, m, d)
            cases.append(TraceCase(
                rung=f"A{a}.M{m}.D{d}",
                fn=_unjit(wireloop._fold_merge_kernel(m, d)),
                args=planes + planes, key=(m, d),
            ))
        return cases

    return build


def _b_derive_ctx():
    def build():
        from ..oplog import records

        fn = _unjit(records._derive_kernel())
        cases = []
        for a in ACTOR_LADDER:
            cases.append(TraceCase(
                rung=f"A{a}.B{LADDER_B}", fn=fn,
                args=(_mat((LADDER_N, a), _clock_dt()),
                      _vec(LADDER_B, "int64"), _vec(LADDER_B, "int32")),
            ))
        return cases

    return build


def _b_scatter_adds():
    def build():
        import functools

        from ..oplog import apply as ap

        fn = _unjit(ap._scatter_adds_kernel())
        cases = []
        for i, (a, m, d) in enumerate(LADDER):
            planes = _orswot_planes(a, m, d)
            kb = kp = LADDER_B
            ops = (_vec(kb, "int64"), _vec(kb, "int32"), _vec(kb, _clock_dt()),
                   _vec(kb, "int64"), _vec(kp, "int64"), _vec(kp, "int64"),
                   _vec(kp, "int32"))
            # both sides of the deferred-replay dispatch on the first
            # rung, replay-only afterwards: budget = len(LADDER) + 1
            for replay in ((False, True) if i == 0 else (True,)):
                cases.append(TraceCase(
                    rung=f"A{a}.M{m}.D{d}.replay={replay}",
                    fn=functools.partial(fn, replay=replay),
                    args=planes + ops, key=(replay,),
                ))
        return cases

    return build


def _b_oplog_counter(factory_attr: str, pn: bool):
    def build():
        from ..oplog import apply as ap

        fn = _unjit(getattr(ap, factory_attr)())
        dt = _clock_dt()
        cases = []
        for a in ACTOR_LADDER:
            plane = _mat((LADDER_N, 2, a) if pn else (LADDER_N, a), dt)
            ops = (_vec(LADDER_B, "int64"),) + (
                (_vec(LADDER_B, "int32"),) if pn else ()) + (
                _vec(LADDER_B, "int32"), _vec(LADDER_B, dt))
            cases.append(TraceCase(rung=f"A{a}", fn=fn, args=(plane,) + ops))
        return cases

    return build


def _b_digest(which: str):
    def build():
        from ..sync import digest

        dt = digest._digest_dtype().__name__ \
            if hasattr(digest._digest_dtype(), "__name__") else "uint64"
        cases = []
        if which == "orswot":
            # identity universes: salts device-inline; one extra case
            # traces the interned-universe member-salt-table gather
            fn = _unjit(digest._orswot_kernel(False))
            for (a, m, d) in LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}.M{m}.D{d}", fn=fn,
                    args=_orswot_planes(a, m, d) + (_vec(a, dt),)))
            a, m, d = LADDER[0]
            cases.append(TraceCase(
                rung=f"A{a}.M{m}.D{d}.table",
                fn=_unjit(digest._orswot_kernel(True)),
                args=_orswot_planes(a, m, d) + (_vec(a, dt), _vec(64, dt)),
                key=("table",)))
        elif which == "counter":
            fn = _unjit(digest._counter_kernel())
            for a in ACTOR_LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}", fn=fn,
                    args=(_mat((LADDER_N, a), _clock_dt()), _vec(a, dt))))
            # the PNCounter plane shape is a distinct (legitimate)
            # lowering: [N, 2, A] reshapes to [N, 2A]
            cases.append(TraceCase(
                rung="A8.pn", fn=fn,
                args=(_mat((LADDER_N, 2, 8), _clock_dt()),
                      _vec(16, dt))))
        else:  # lww
            fn = _unjit(digest._lww_kernel(False))
            for n in (8, 64, 512):
                cases.append(TraceCase(
                    rung=f"N{n}", fn=fn,
                    args=(_vec(n, _clock_dt()), _vec(n, _clock_dt()))))
            cases.append(TraceCase(
                rung="N8.table", fn=_unjit(digest._lww_kernel(True)),
                args=(_vec(8, _clock_dt()), _vec(8, _clock_dt()),
                      _vec(64, dt)),
                key=("table",)))
        return cases

    return build


def _b_mesh_step():
    def build():
        from ..mesh import step as mesh_step
        from ..sync import digest

        mesh = _cpu_mesh("objects")
        dt = digest._digest_dtype().__name__ \
            if hasattr(digest._digest_dtype(), "__name__") else "uint64"
        cases = []
        for (a, m, d) in LADDER:
            planes = _orswot_planes(a, m, d)
            fn = _unjit(mesh_step._step_fn(mesh, "objects", m, d, False,
                                           "rank"))
            cases.append(TraceCase(
                rung=f"A{a}.M{m}.D{d}", fn=fn,
                args=(planes, planes, _vec(a, dt)),
                key=(m, d, "rank")))
        a, m, d = LADDER[0]
        planes = _orswot_planes(a, m, d)
        cases.append(TraceCase(
            rung=f"A{a}.M{m}.D{d}.table",
            fn=_unjit(mesh_step._step_fn(mesh, "objects", m, d, True,
                                         "rank")),
            args=(planes, planes, _vec(a, dt), _vec(64, dt)),
            key=(m, d, "rank", "table")))
        return cases

    return build


def _b_tree_fold(which: str):
    def build():
        import jax.numpy as jnp

        from ..sync import digest, tree

        dt = "uint64" if digest._digest_dtype() == jnp.uint64 else "uint32"
        if which == "fold":
            fn = _unjit(tree._fold_kernel())
            sizes = (16, 256, 4096)
        else:  # the elementwise leaf position-mix
            fn = _unjit(tree._leaf_kernel())
            sizes = (8, 256, 4096)
        # one legitimate lowering per level/vector length — the k-ary
        # walk a 64k..1M-leaf tree folds through
        return [TraceCase(rung=f"M{m}", fn=fn, args=(_vec(m, dt),))
                for m in sizes]

    return build


def _b_frontier_fold():
    """The convergence observatory's per-subtree version-vector fold
    (obs/stability.py): ``clock[S*span, W] -> vv[S, W]``, one reshape +
    max-reduce.  Traced across the subtree/span/actor ladder a real
    fleet walks (S is the factory's static; ≤ TREE_K by the digest-tree
    coverage rule) — one legitimate lowering per case."""

    def build():
        from ..obs import stability as stability_mod

        dt = _clock_dt()
        cases = []
        for (s, span, a) in ((16, 1, 8), (16, 16, 8), (16, 256, 16),
                             (8, 1, 8)):
            fn = _unjit(stability_mod._frontier_kernel(s))
            cases.append(TraceCase(
                rung=f"S{s}.P{span}.A{a}", fn=fn,
                args=(_mat((s * span, a), dt),), key=(s,)))
        return cases

    return build


def _b_heat_fold():
    """The heat observatory's per-subtree scatter-add
    (obs/heat.py): ``(ids[B], weights[B]) -> heat[S]`` with
    ``segment = id // span``.  Traced across the (subtrees, span)
    ladder subtree_layout walks plus the pow2 batch rungs record
    batches pad to — integer lattice, order-free by construction."""

    def build():
        from ..obs import heat as heat_mod

        idt = "int64" if _clock_dt() == "uint64" else "int32"
        cases = []
        for (s, span, b) in ((16, 1, 8), (16, 16, 64), (16, 256, 512),
                             (8, 1, 8)):
            fn = _unjit(heat_mod._fold_kernel(s, span))
            cases.append(TraceCase(
                rung=f"S{s}.P{span}.B{b}", fn=fn,
                args=(_vec(b, idt), _vec(b, idt)), key=(s, span)))
        return cases

    return build


def _b_heat_sketch():
    """The heat observatory's batched Space-Saving update
    (obs/heat.py): ``(table[3xC], ids[B], w[B]) -> table[3xC]`` —
    in-batch segment-sum aggregation, matched scatter-add, candidates
    entering at table-min with their error recorded, one top_k.
    Integer lattice: counts only grow, padding rows carry weight 0."""

    def build():
        from ..obs import heat as heat_mod

        idt = "int64" if _clock_dt() == "uint64" else "int32"
        cases = []
        for (c, b) in ((128, 8), (128, 256), (128, 1024), (64, 64)):
            fn = _unjit(heat_mod._sketch_kernel(c))
            cases.append(TraceCase(
                rung=f"C{c}.B{b}", fn=fn,
                args=(_vec(c, idt), _vec(c, idt), _vec(c, idt),
                      _vec(b, idt), _vec(b, idt)), key=(c,)))
        return cases

    return build


def _b_serve_gather(which: str):
    """The read front-end's gather kernels (serve/query.py): pure
    gathers from the dense planes (ORSWOT: from its row view, whose
    build is the ``view`` row) into columnar result frames.  Read
    batches pad to the power-of-two ladder (serve.query.PAD_FLOOR), so
    the traced rungs walk capacity x padded-batch — one legitimate
    lowering per rung."""

    def build():
        from ..serve import query as serve_query

        dt = _clock_dt()
        idt = "int64" if dt == "uint64" else "int32"
        cases = []
        if which == "view":
            fn = _unjit(serve_query._view_kernel())
            for (a, m, _d) in LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}.M{m}", fn=fn,
                    args=(_mat((LADDER_N, a), dt),
                          _mat((LADDER_N, m), "int32"),
                          _mat((LADDER_N, m, a), dt))))
        elif which == "orswot":
            for (a, m, _d) in LADDER:
                fn = _unjit(serve_query._orswot_kernel(a, m))
                w = serve_query._view_width(a, m)
                for b in (8, 64):
                    cases.append(TraceCase(
                        rung=f"A{a}.M{m}.B{b}", fn=fn,
                        args=(_mat((LADDER_N, w), dt),
                              _vec(b, idt), _vec(b, "int32")),
                        key=(a, m)))
        elif which == "counter":
            fn = _unjit(serve_query._counter_kernel())
            for a in ACTOR_LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}.B8", fn=fn,
                    args=(_mat((LADDER_N, a), dt), _vec(8, idt))))
        elif which == "lww":
            fn = _unjit(serve_query._lww_kernel())
            for b in (8, 64):
                cases.append(TraceCase(
                    rung=f"B{b}", fn=fn,
                    args=(_vec(LADDER_N, dt), _vec(LADDER_N, dt),
                          _vec(b, idt))))
        elif which == "mvreg":
            fn = _unjit(serve_query._mvreg_kernel())
            for a in ACTOR_LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}.V4.B8", fn=fn,
                    args=(_mat((LADDER_N, 4, a), dt),
                          _mat((LADDER_N, 4), dt), _vec(8, idt))))
        else:  # map
            fn = _unjit(serve_query._map_kernel())
            for (a, _m, _d) in LADDER:
                cases.append(TraceCase(
                    rung=f"A{a}.K4.B8", fn=fn,
                    args=(_mat((LADDER_N, a), dt),
                          _mat((LADDER_N, 4), "int32"),
                          _mat((LADDER_N, 4, a), dt),
                          _vec(8, idt), _vec(8, "int32"))))
        return cases

    return build


def _b_collective(which: str):
    def build():
        import functools

        from ..parallel import collective as co

        mesh = _cpu_mesh("replicas")
        dt = _clock_dt()
        cases = []
        if which == "clock":
            for a in ACTOR_LADDER:
                fn = _unjit(co._clock_join_fn(mesh, "replicas", 2))
                cases.append(TraceCase(
                    rung=f"A{a}", fn=fn, args=(_mat((1, a), dt),), key=(2,)))
        elif which == "lww":
            for n in (8, 64, 512):
                fn = _unjit(co._lww_join_fn(mesh, "replicas", 1))
                cases.append(TraceCase(
                    rung=f"N{n}", fn=fn,
                    args=(_vec(n, dt), _vec(n, dt)), key=(1,)))
        elif which == "mvreg":
            for (a, mv) in ((8, 4), (8, 8), (16, 8)):
                fn = _unjit(co._mvreg_join_fn(mesh, "replicas", mv, 3, 2))
                cases.append(TraceCase(
                    rung=f"A{a}.K{mv}", fn=fn,
                    args=(_mat((1, mv, a), dt), _mat((1, mv), dt)),
                    key=(mv,)))
        elif which == "orswot":
            for (a, m, d) in LADDER:
                planes = tuple(
                    _mat((1,) + p.shape, p.dtype.name)
                    for p in _orswot_planes(a, m, d, n=LADDER_N))
                fn = _unjit(co._orswot_join_fn(
                    mesh, "replicas", m, d,
                    tuple(p.ndim for p in planes), "rank", None))
                cases.append(TraceCase(
                    rung=f"A{a}.M{m}.D{d}", fn=fn, args=(planes,),
                    key=(m, d, "rank")))
        elif which == "map":
            import jax
            from jax.sharding import PartitionSpec as P

            for (a, k, d) in _MAP_LADDER:
                batch = _map_fixture(a, k, d)
                state = _sds(batch.state)
                state1 = jax.tree_util.tree_map(
                    lambda x: _mat((1,) + x.shape, x.dtype.name), state)
                specs = jax.tree_util.tree_map(
                    lambda x: P("replicas", *([None] * (x.ndim - 1))),
                    state1)
                flat_specs, spec_tree = jax.tree_util.tree_flatten(specs)
                fn = _unjit(co._map_join_fn(
                    mesh, "replicas", batch.kernel, tuple(flat_specs),
                    spec_tree))
                cases.append(TraceCase(
                    rung=f"A{a}.K{k}.D{d}", fn=fn, args=(state1,),
                    key=(a, k, d)))
        elif which in ("ae_fold", "ae_plunge"):
            for (a, m, d) in LADDER:
                fold, plunge = co._anti_entropy_kernels(m, d, "rank")
                fn = _unjit(fold if which == "ae_fold" else plunge)
                planes = _orswot_planes(a, m, d)
                args = (_stacked(planes),) if which == "ae_fold" \
                    else (planes,)
                cases.append(TraceCase(
                    rung=f"A{a}.M{m}.D{d}", fn=fn, args=args,
                    key=(m, d, "rank")))
        return cases

    return build


def _b_member_sharding(which: str):
    def build():
        from ..parallel import member_sharding as ms

        mesh = _cpu_mesh("members")
        dt = _clock_dt()
        cases = []
        for (a, m, d) in LADDER:
            planes = tuple(
                _mat((1,) + p.shape, p.dtype.name)
                for p in _orswot_planes(a, m, d))
            if which == "clock":
                fn = _unjit(ms._clock_join_fn(mesh, "members"))
                cases.append(TraceCase(
                    rung=f"A{a}", fn=fn, args=(planes[0],)))
            else:
                fn = _unjit(ms._apply_add_fn(mesh, "members", 1))
                ops = (_vec(1, "int32"), _vec(LADDER_N, "int32"),
                       _vec(LADDER_N, dt), _vec(LADDER_N, "int32"))
                cases.append(TraceCase(
                    rung=f"A{a}.M{m}.D{d}", fn=fn,
                    args=(planes,) + ops, key=(1,)))
        return cases

    return build


def _b_pallas(module: str, kernel_attr: str, fold: bool):
    """Mosaic kernels trace with ``interpret=False`` (abstract tracing
    never enters Mosaic; lowering does, which is exactly what KC01
    guards) and uint32 planes (their hard API precondition)."""

    def build():
        import functools
        import importlib

        mod = importlib.import_module(f"crdt_tpu.ops.{module}")
        fn = _unjit(getattr(mod, kernel_attr))
        cases = []
        for (a, m, d) in LADDER:
            planes = (
                _mat((LADDER_N, a), "uint32"),
                _mat((LADDER_N, m), "int32"),
                _mat((LADDER_N, m, a), "uint32"),
                _mat((LADDER_N, d), "int32"),
                _mat((LADDER_N, d, a), "uint32"),
            )
            if fold:
                args = _stacked(planes)
            else:
                args = planes + planes
            cases.append(TraceCase(
                rung=f"A{a}.M{m}.D{d}",
                fn=functools.partial(fn, m_cap=m, d_cap=d, interpret=False),
                args=args, key=(m, d)))
        return cases

    return build


# -- the rows ----------------------------------------------------------------

_OB = "crdt_tpu/batch/orswot_batch.py"
_CO = "crdt_tpu/parallel/collective.py"
_AP = "crdt_tpu/oplog/apply.py"

MANIFEST: tuple = (
    # batch/orswot_batch.py ---------------------------------------------------
    KernelSpec("batch.orswot.device_nnz", _OB, "_device_nnz",
               sharding=reduction(
                   ALL_LEAVES,
                   reason="global occupancy totals for compact sizing; "
                          "shard-local counts psum-join"),
               build=_b_orswot_batch("_device_nnz")),
    KernelSpec("batch.orswot.device_compact", _OB, "_device_compact",
               sharding=host_only(
                   "snapshot/export path: gathers every object's live "
                   "cells into flat columns with global-size statics; "
                   "per-shard snapshots rebind the sizes per shard"),
               build=_b_orswot_batch(
                   "_device_compact",
                   statics=lambda a, m, d: {
                       "sizes": (LADDER_N * a, LADDER_N * m,
                                 LADDER_N * m, LADDER_N * d, LADDER_N * d),
                       "with_entries": True})),
    KernelSpec("batch.orswot.device_expand", _OB, "_device_expand",
               determinism="integer-lattice",
               sharding=host_only(
                   "snapshot/import inverse of device_compact; the "
                   "object count is a baked static"),
               build=lambda: _build_device_expand()),
    KernelSpec("batch.orswot.densify_cells", _OB, "_densify_cells",
               determinism="integer-lattice",
               sharding=host_only(
                   "the wire loop's per-fleet ingest: one fleet's cells "
                   "scatter into its whole flat plane space"),
               build=lambda: _build_densify_cells()),
    KernelSpec("batch.orswot.merge", _OB, "_merge",
               sharding=pointwise(),
               build=_b_orswot_merge()),
    KernelSpec("batch.orswot.fold_tree", _OB, "_fold_tree",
               sharding=pointwise(),
               build=_b_orswot_batch(
                   "_fold_tree", fleets=True,
                   statics=lambda a, m, d: {
                       "m_cap": m, "d_cap": d, "plunger": True,
                       "impl": "rank"})),
    KernelSpec("batch.orswot.apply_add", _OB, "_apply_add",
               sharding=pointwise(),  # op rows align with object rows
               build=_b_orswot_batch(
                   "_apply_add",
                   extra=lambda a, m, d: (
                       _vec(LADDER_N, "int32"), _vec(LADDER_N, _clock_dt()),
                       _vec(LADDER_N, "int32")))),
    KernelSpec("batch.orswot.apply_remove", _OB, "_apply_remove",
               sharding=pointwise(),
               build=_b_orswot_batch(
                   "_apply_remove",
                   extra=lambda a, m, d: (
                       _mat((LADDER_N, a), _clock_dt()),
                       _vec(LADDER_N, "int32")))),
    KernelSpec("batch.orswot.truncate", _OB, "_truncate",
               sharding=pointwise(),
               build=_b_orswot_batch(
                   "_truncate",
                   statics=lambda a, m, d: {"m_cap": m, "d_cap": d},
                   extra=lambda a, m, d: (_mat((LADDER_N, a), _clock_dt()),))),
    # the scalar-plane batch merges ------------------------------------------
    KernelSpec("batch.vclock.merge", "crdt_tpu/batch/vclock_batch.py",
               "_merge", sharding=pointwise(),
               build=_b_counter_merge(
                   "vclock_batch", lambda a: (LADDER_N, a))),
    KernelSpec("batch.gcounter.merge", "crdt_tpu/batch/gcounter_batch.py",
               "_merge", sharding=pointwise(),
               build=_b_counter_merge(
                   "gcounter_batch", lambda a: (LADDER_N, a))),
    KernelSpec("batch.pncounter.merge", "crdt_tpu/batch/pncounter_batch.py",
               "_merge", sharding=pointwise(),
               build=_b_counter_merge(
                   "pncounter_batch", lambda a: (LADDER_N, 2, a))),
    KernelSpec("batch.gset.merge", "crdt_tpu/batch/gset_batch.py",
               "_merge", sharding=pointwise(), build=_b_gset_merge()),
    KernelSpec("batch.lwwreg.merge", "crdt_tpu/batch/lwwreg_batch.py",
               "_merge", sharding=pointwise(), build=_b_lww_merge()),
    KernelSpec("batch.mvreg.merge", "crdt_tpu/batch/mvreg_batch.py",
               "_merge", sharding=pointwise(), build=_b_mvreg("_merge")),
    KernelSpec("batch.mvreg.apply_put", "crdt_tpu/batch/mvreg_batch.py",
               "_apply_put", sharding=pointwise(),
               build=_b_mvreg("_apply_put")),
    KernelSpec("batch.mvreg.truncate", "crdt_tpu/batch/mvreg_batch.py",
               "_truncate", sharding=pointwise(),
               build=_b_mvreg("_truncate", k_static=False)),
    # batch/map_batch.py -----------------------------------------------------
    KernelSpec("batch.map.merge", "crdt_tpu/batch/map_batch.py", "_merge",
               sharding=pointwise(), build=_b_map("_merge")),
    KernelSpec("batch.map.truncate", "crdt_tpu/batch/map_batch.py",
               "_truncate", sharding=pointwise(), build=_b_map("_truncate")),
    KernelSpec("batch.map.apply_rm", "crdt_tpu/batch/map_batch.py",
               "_apply_rm", sharding=pointwise(), build=_b_map("_apply_rm")),
    KernelSpec("batch.map.apply_up", "crdt_tpu/batch/map_batch.py",
               "_apply_up", sharding=pointwise(), build=_b_map("_apply_up")),
    # batch/occupancy.py (the capacity observatory's reductions) -------------
    KernelSpec("batch.occupancy.orswot", "crdt_tpu/batch/occupancy.py",
               "_orswot_occupancy",
               sharding=reduction(
                   ALL_LEAVES,
                   reason="fleet occupancy totals; per-shard counts "
                          "psum-join"),
               build=_b_occupancy("orswot")),
    KernelSpec("batch.occupancy.clock", "crdt_tpu/batch/occupancy.py",
               "_clock_occupancy",
               sharding=reduction(
                   ALL_LEAVES,
                   reason="fleet occupancy totals; per-shard counts "
                          "psum-join"),
               build=_b_occupancy("clock")),
    KernelSpec("batch.occupancy.pncounter", "crdt_tpu/batch/occupancy.py",
               "_pn_occupancy",
               sharding=reduction(
                   ALL_LEAVES,
                   reason="fleet occupancy totals; per-shard counts "
                          "psum-join"),
               build=_b_occupancy("pn")),
    KernelSpec("batch.occupancy.map", "crdt_tpu/batch/occupancy.py",
               "_map_occupancy",
               sharding=reduction(
                   ALL_LEAVES,
                   reason="fleet occupancy totals; per-shard counts "
                          "psum-join"),
               build=_b_occupancy("map")),
    # gc/ (causal garbage collection) ----------------------------------------
    KernelSpec("gc.settle", "crdt_tpu/gc/compact.py", "_settle",
               sharding=pointwise(), build=_b_gc_settle()),
    KernelSpec("gc.repack", "crdt_tpu/gc/repack.py", "_repack",
               sharding=pointwise(), build=_b_gc_repack()),
    # batch/wireloop.py ------------------------------------------------------
    KernelSpec("batch.wireloop.fold_merge", "crdt_tpu/batch/wireloop.py",
               "_fold_merge_kernel.<jit>",
               sharding=pointwise(),
               build=_b_wireloop_merge()),
    # oplog ------------------------------------------------------------------
    KernelSpec("oplog.derive_add_ctx", "crdt_tpu/oplog/records.py",
               "_derive_kernel._derive_kernel_host",
               sharding=pointwise(0, routed=(1,)),  # clock rows by op obj id
               build=_b_derive_ctx()),
    KernelSpec("oplog.scatter_adds", _AP, "_scatter_adds_kernel.kernel",
               determinism="integer-lattice",
               compile_budget=len(LADDER) + 1,
               # planes carry the object axis; oo/po are the routed
               # object-id columns of the op batch
               sharding=pointwise(0, 1, 2, 3, 4, routed=(5, 9)),
               build=_b_scatter_adds()),
    KernelSpec("oplog.gcounter_scatter", _AP,
               "_counter_scatter_kernel._counter_scatter",
               determinism="integer-lattice",
               sharding=pointwise(0, routed=(1,)),
               build=_b_oplog_counter("_counter_scatter_kernel", pn=False)),
    KernelSpec("oplog.pncounter_scatter", _AP,
               "_pn_scatter_kernel._pn_scatter",
               determinism="integer-lattice",
               sharding=pointwise(0, routed=(1,)),
               build=_b_oplog_counter("_pn_scatter_kernel", pn=True)),
    # sync/digest.py ---------------------------------------------------------
    KernelSpec("sync.digest.orswot", "crdt_tpu/sync/digest.py", "_jit.fn",
               compile_budget=len(LADDER) + 1,  # +1: salt-table variant
               sharding=pointwise(0, 1, 2, 3, 4),  # salt/table leaves ride
               build=_b_digest("orswot")),
    KernelSpec("sync.digest.counter", "crdt_tpu/sync/digest.py", "_jit.fn",
               compile_budget=len(ACTOR_LADDER) + 1,
               sharding=pointwise(0),
               build=_b_digest("counter")),
    KernelSpec("sync.digest.lww", "crdt_tpu/sync/digest.py", "_jit.fn",
               compile_budget=4,  # 3 sizes + the salt-table variant
               sharding=pointwise(0, 1),
               build=_b_digest("lww")),
    # sync/tree.py -----------------------------------------------------------
    KernelSpec("sync.tree.fold", "crdt_tpu/sync/tree.py",
               "_fold_kernel.kernel",
               compile_budget=3,  # one lowering per traced level length
               sharding=reduction(
                   0, granule=16,  # TREE_K-block folds
                   reason="k=16 XOR fold over the leaf/level axis; a "
                          "shard folds its own subtree range, the cut "
                          "level all_gathers at the root"),
               build=_b_tree_fold("fold")),
    KernelSpec("sync.tree.leaf_mix", "crdt_tpu/sync/tree.py",
               "_leaf_kernel.kernel",
               compile_budget=3,
               sharding=pointwise(0),  # position mix is per leaf digest
               build=_b_tree_fold("leaf")),
    # obs/stability.py (the convergence observatory's frontier fold) ---------
    KernelSpec("obs.stability.frontier_fold", "crdt_tpu/obs/stability.py",
               "_frontier_kernel.kernel",
               compile_budget=4,  # one lowering per traced (S, span, A)
               sharding=reduction(
                   0,
                   reason="per-subtree VV max-fold over the leaf range; "
                          "shard-local frontiers pmax-join; the factory "
                          "rebinds its subtree-count static per shard"),
               build=_b_frontier_fold()),
    # obs/heat.py (the heat & placement observatory) -------------------------
    KernelSpec("obs.heat.subtree_fold", "crdt_tpu/obs/heat.py",
               "_fold_kernel.kernel",
               determinism="integer-lattice",
               compile_budget=8,  # (S, span) statics x pow2 batch rungs
               sharding=reduction(
                   routed=(0,),
                   reason="per-subtree heat accumulated from routed op "
                          "ids; shard-local heat vectors psum-join"),
               build=_b_heat_fold()),
    KernelSpec("obs.heat.sketch_update", "crdt_tpu/obs/heat.py",
               "_sketch_kernel.kernel",
               determinism="integer-lattice",
               compile_budget=8,  # capacity static x pow2 batch rungs
               sharding=replicated(
                   "fleet-global top-k sketch over routed op ids; each "
                   "shard keeps a local sketch, merged at read time",
                   routed=(3,)),
               build=_b_heat_sketch()),
    # serve/query.py (the read front-end's gather kernels) -------------------
    KernelSpec("serve.view.orswot", "crdt_tpu/serve/query.py",
               "_view_kernel.build",
               compile_budget=len(LADDER),
               sharding=pointwise(0, 1, 2),
               build=_b_serve_gather("view")),
    KernelSpec("serve.gather.orswot", "crdt_tpu/serve/query.py",
               "_orswot_kernel.kernel",
               compile_budget=2 * len(LADDER),  # capacity x padded batch
               sharding=pointwise(0, routed=(1,)),
               build=_b_serve_gather("orswot")),
    KernelSpec("serve.gather.counter", "crdt_tpu/serve/query.py",
               "_counter_kernel.kernel",
               compile_budget=len(ACTOR_LADDER),
               sharding=pointwise(0, routed=(1,)),
               build=_b_serve_gather("counter")),
    KernelSpec("serve.gather.lww", "crdt_tpu/serve/query.py",
               "_lww_kernel.kernel",
               sharding=pointwise(0, 1, routed=(2,)),
               build=_b_serve_gather("lww")),
    KernelSpec("serve.gather.mvreg", "crdt_tpu/serve/query.py",
               "_mvreg_kernel.kernel",
               compile_budget=len(ACTOR_LADDER),
               sharding=pointwise(0, 1, routed=(2,)),
               build=_b_serve_gather("mvreg")),
    KernelSpec("serve.gather.map", "crdt_tpu/serve/query.py",
               "_map_kernel.kernel",
               compile_budget=len(LADDER),
               sharding=pointwise(0, 1, 2, routed=(3,)),
               build=_b_serve_gather("map")),
    # parallel/collective.py (shard_map joins: the only kernels that
    # lower collectives TODAY — their contracts declare the exact set) -------
    KernelSpec("parallel.clock_join", _CO, "_clock_join_fn._join",
               sharding=reduction(
                   collectives=("pmax",),
                   reason="fleet-wide clock join over the replica mesh "
                          "axis; no object axis in the operand"),
               build=_b_collective("clock")),
    KernelSpec("parallel.lww_join", _CO, "_lww_join_fn._join",
               sharding=reduction(
                   0, 1, collectives=("all_gather",),
                   reason="register-wise (ts, mark) join over the "
                          "replica mesh axis: gathers both replicas' "
                          "registers and picks the max-ts lane"),
               build=_b_collective("lww")),
    KernelSpec("parallel.mvreg_join", _CO, "_mvreg_join_fn._join",
               sharding=reduction(
                   collectives=("all_gather",),
                   reason="antichain join gathers every replica's "
                          "candidates before the dominance filter"),
               build=_b_collective("mvreg")),
    KernelSpec("parallel.orswot_join", _CO, "_orswot_join_fn._join",
               sharding=reduction(
                   ALL_LEAVES, axis=1,  # axis 0 is the replica shard
                   collectives=("all_gather",),
                   reason="plane join gathers replica shards then folds "
                          "the lattice merge; object axis rides through"),
               build=_b_collective("orswot")),
    KernelSpec("parallel.shard_local_merge", _CO,
               "shard_local_merge_fn._local",
               sharding=pointwise(
                   mesh_sizes=(1,),
                   reason="already the per-shard body of the objects-"
                          "mesh merge: the object axis arrives pre-"
                          "sliced to this shard"),
               build=lambda: _build_shard_local_merge()),
    KernelSpec("parallel.map_join", _CO, "_map_join_fn._join",
               sharding=reduction(
                   ALL_LEAVES, axis=1,
                   collectives=("all_gather",),
                   reason="map-state join gathers replica shards then "
                          "folds the nested-kernel merge"),
               build=_b_collective("map")),
    KernelSpec("parallel.anti_entropy_fold", _CO,
               "_anti_entropy_kernels._fold",
               sharding=pointwise(axis=1),  # folds the replica stack
               build=_b_collective("ae_fold")),
    KernelSpec("parallel.anti_entropy_plunge", _CO,
               "_anti_entropy_kernels._plunge",
               sharding=pointwise(),
               build=_b_collective("ae_plunge")),
    # parallel/member_sharding.py --------------------------------------------
    KernelSpec("parallel.member_clock_join",
               "crdt_tpu/parallel/member_sharding.py",
               "_clock_join_fn._join",
               sharding=reduction(
                   (0, 1), collectives=("pmax",),
                   reason="clock join across the member-shard mesh "
                          "axis; object axis rides through at dim 1"),
               build=_b_member_sharding("clock")),
    KernelSpec("parallel.member_apply_add",
               "crdt_tpu/parallel/member_sharding.py",
               "_apply_add_fn._local",
               sharding=pointwise(
                   (0, 1), (1, 1), (2, 1), (3, 1), (4, 1),
                   (6, 0), (7, 0), (8, 0),
                   reason="member-routed add: every shard sees the op, "
                          "only the owner applies it — shard-local (no "
                          "collective; the clock rebroadcast is "
                          "member_clock_join's pmax)"),
               build=_b_member_sharding("apply_add")),
    # mesh/step.py (the fused whole-round anti-entropy step) -----------------
    KernelSpec("mesh.step.anti_entropy", "crdt_tpu/mesh/step.py",
               "_step_fn._step",
               determinism="integer-lattice",
               compile_budget=len(LADDER) + 1,  # +1: salt-table variant
               sharding=reduction(
                   0, 1, 2, 3, 4, 5, 6, 7, 8, 9,  # both state 5-tuples
                   collectives=("all_gather", "pmax", "psum"),
                   reason="whole anti-entropy round fused over the "
                          "objects mesh: shard-local pair merge + "
                          "digest slice, ONE all_gather for the fleet "
                          "digest vector, pmax clock join, psum member "
                          "fold; salt operands ride replicated"),
               build=_b_mesh_step()),
    # ops: the Mosaic-destined Pallas kernels --------------------------------
    KernelSpec("ops.pallas.merge", "crdt_tpu/ops/orswot_pallas.py",
               "merge", mosaic=True,
               sharding=pointwise(
                   reason="per-object-row Mosaic merge; SC01 cannot see "
                          "through the pallas_call region (opaque refs) "
                          "but the grid partitions the object axis"),
               build=_b_pallas("orswot_pallas", "merge", fold=False)),
    KernelSpec("ops.pallas.fold_merge", "crdt_tpu/ops/orswot_pallas.py",
               "fold_merge", mosaic=True,
               sharding=pointwise(
                   axis=1,
                   reason="replica-stack fold, per object row; pallas "
                          "region opaque to SC01"),
               build=_b_pallas("orswot_pallas", "fold_merge", fold=True)),
    KernelSpec("ops.fold_aligned.fold_merge",
               "crdt_tpu/ops/orswot_fold_aligned.py",
               "fold_merge", mosaic=True,
               sharding=pointwise(
                   axis=1,
                   reason="replica-stack fold, per object row; pallas "
                          "region opaque to SC01"),
               build=_b_pallas("orswot_fold_aligned", "fold_merge",
                               fold=True)),
)


def _build_device_expand():
    import functools

    from ..batch import orswot_batch as ob

    fn = _unjit(ob._device_expand)
    cases = []
    for (a, m, d) in LADDER:
        dt = _clock_dt()
        k = LADDER_B
        cells = (  # (clock, entry, dot, dref, dclk) compact columns
            (_vec(k, "int32"), _vec(k, "int32"), _vec(k, dt)),
            (_vec(k, "int32"), _vec(k, "int32"), _vec(k, "int32")),
            (_vec(k, "int32"), _vec(k, "int32"), _vec(k, "int32"),
             _vec(k, dt)),
            (_vec(k, "int32"), _vec(k, "int32"), _vec(k, "int32")),
            (_vec(k, "int32"), _vec(k, "int32"), _vec(k, "int32"),
             _vec(k, dt)),
        )
        cases.append(TraceCase(
            rung=f"A{a}.M{m}.D{d}",
            fn=functools.partial(fn, n=LADDER_N, a=a, m=m, d=d),
            args=(cells,), key=(LADDER_N, a, m, d)))
    return cases


def _build_densify_cells():
    import functools

    from ..batch import orswot_batch as ob

    fn = _unjit(ob._densify_cells)
    cases = []
    for (a, m, d) in LADDER:
        args = (_mat((LADDER_N, m), "int32"), _mat((LADDER_N, d), "int32"),
                _vec(LADDER_B, "int32"), _vec(LADDER_B, _clock_dt()))
        cases.append(TraceCase(
            rung=f"A{a}.M{m}.D{d}",
            fn=functools.partial(fn, a=a, m=m, d=d),
            args=args, key=(LADDER_N, a, m, d)))
    return cases


def _build_shard_local_merge():
    from ..parallel import collective as co

    mesh = _cpu_mesh("objects")
    cases = []
    for (a, m, d) in LADDER:
        planes = tuple(
            _mat((1,) + p.shape[1:], p.dtype.name)
            for p in _orswot_planes(a, m, d))
        fn = _unjit(co.shard_local_merge_fn(mesh, "objects", m, d, "rank"))
        cases.append(TraceCase(
            rung=f"A{a}.M{m}.D{d}", fn=fn, args=(planes, planes),
            key=(m, d, "rank")))
    return cases


def manifest_keys() -> set:
    """The ``(path, jit_name)`` pairs the manifest covers."""
    return {(s.path, s.jit_name) for s in MANIFEST}


def specs_by_name() -> dict:
    return {s.name: s for s in MANIFEST}


# ---------------------------------------------------------------------------
# the tier-1 AST rule: every jit site under crdt_tpu/ has a manifest row
# ---------------------------------------------------------------------------


@rule("kernel-manifest")
def _kernel_manifest_rule(files: List[ParsedFile]):
    """Single-source discipline for jitted kernels, enforced at the
    source tier (stdlib-only — runs before kernelcheck ever imports
    jax): every ``jax.jit`` application under ``crdt_tpu/`` must have a
    :class:`KernelSpec` row, and every row must still point at a live
    jit site (stale rows rot the jaxpr tier's coverage silently)."""
    covered = manifest_keys()
    sites_by_rel: dict = {}
    for pf in files:
        if not pf.rel.startswith("crdt_tpu/"):
            continue
        if pf.rel.startswith("crdt_tpu/analysis/"):
            continue  # the analyzer itself hosts no kernels
        sites = iter_jit_sites(pf.tree)
        sites_by_rel[pf.rel] = {s.name for s in sites}
        for site in sites:
            if (pf.rel, site.name) not in covered:
                yield Finding(
                    "kernel-manifest", pf.rel, site.line, 0,
                    f"jit entry point {site.name!r} has no KernelSpec row "
                    "in crdt_tpu/analysis/kernels.py — declare its shapes, "
                    "determinism class and compile budget (kernelcheck "
                    "cannot trace unmanifested kernels)",
                )
    # stale rows: only decidable for files actually in the scanned set
    for spec in MANIFEST:
        names = sites_by_rel.get(spec.path)
        if names is not None and spec.jit_name not in names:
            yield Finding(
                "kernel-manifest", "crdt_tpu/analysis/kernels.py", 1, 0,
                f"stale manifest row {spec.name!r}: no jit site named "
                f"{spec.jit_name!r} in {spec.path} — the kernel moved or "
                "was deleted; update the row",
            )
    # sharding contracts: 100% coverage, pinned at the source tier so
    # an un-declared kernel fails CI before shardcheck ever traces it
    for spec in MANIFEST:
        c = spec.sharding
        if c is None:
            yield Finding(
                "kernel-manifest", "crdt_tpu/analysis/kernels.py", 1, 0,
                f"manifest row {spec.name!r} declares no sharding "
                "contract — every kernel pins its object-axis class "
                "(pointwise | reduction | replicated | host_only) before "
                "the mesh PR lands; shardcheck (--shard) cannot verify "
                "an undeclared row",
            )
            continue
        bad = ""
        if c.sclass not in SHARD_CLASSES:
            bad = f"unknown sharding class {c.sclass!r}"
        elif c.sclass == "pointwise" and not c.obj:
            bad = "pointwise contracts must name their object-axis leaves"
        elif any(p not in COLLECTIVE_PRIMS for p in c.collectives):
            bad = f"unknown collective(s) {c.collectives!r}"
        elif c.collectives and c.sclass != "reduction":
            bad = "only reduction contracts declare collectives"
        elif spec.build is None and c.sclass != "host_only":
            bad = (f"a build=None row cannot carry a {c.sclass!r} "
                   "contract (nothing to verify it against) — host_only")
        if bad:
            yield Finding(
                "kernel-manifest", "crdt_tpu/analysis/kernels.py", 1, 0,
                f"manifest row {spec.name!r}: malformed sharding "
                f"contract: {bad}",
            )
