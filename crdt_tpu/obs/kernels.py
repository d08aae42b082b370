"""Runtime kernel observatory — the dynamic companion to kernelcheck.

PR 8's kernelcheck proves kernel contracts *statically*: it traces every
manifested ``jax.jit`` entry point abstractly and bounds its distinct
lowerings (KC04 ``compile_budget``).  Nothing watched the same kernels
*at runtime*: a shape-churn bug that recompiles a hot kernel per batch,
a regrow ladder walking further than planned, or a kernel whose device
time quietly doubled were all invisible until a bench diff.  This
module closes that gap with an always-on, always-cheap registry keyed
on the SAME single source of kernel identity — the
:data:`crdt_tpu.analysis.kernels.MANIFEST` rows:

* :func:`observed_kernel` — the one-line instrumentation every
  manifested jit entry point wears (decorator above the ``jax.jit``
  site, or a wrap around a factory's return).  Each call pays two
  ``perf_counter`` reads, one ``_cache_size()`` fetch, the shape-walk
  bytes estimate and a few dict increments under the profile's own
  lock; ``bench_kernel_obs`` gates the total below 1% of
  ``bench_e2e_wire`` wall.
* **Compile tracking** — a jit cache growing across a call IS a
  lowering+compile: counted per kernel (``kernel.<label>.compiles`` +
  the process-wide ``kernel.compiles``), flight-recorded as a
  ``kernel.compile`` event carrying the arg-shape signature and the
  call's wall, and classified against the executor's capacity-ladder
  stamps (:func:`note_ladder_transition`, bumped by
  ``executor.regrow``/``executor.shrink``) so an expected
  ladder-transition recompile is distinguishable from shape churn
  (:func:`storm_report`).  KC04's static budget becomes a runtime
  gauge: ``kernel.<label>.compile_budget_frac`` with an ok/warn/
  critical watermark like the PR 9 capacity gauges.
* **Dispatch accounting** — per-kernel log2 wall histograms
  (``kernel.<label>.wall``; compile calls are recorded on the compile
  event instead, so the histogram stays steady-state), bytes-moved
  counters, plus one-time-per-compilation XLA
  ``cost_analysis()`` capture (:meth:`KernelProfile.capture_cost`,
  lazy — triggered by ``/kernels?cost=1`` or the bench, never on the
  hot path) giving every kernel a roofline position.
* **Device memory** — :func:`sample_device_memory` folds
  ``jax.live_arrays()`` into ``devicemem.*`` gauges (total + per-dtype
  live bytes) and, when a
  :class:`~crdt_tpu.obs.capacity.CapacityTracker` is supplied, the
  tracked-vs-live fraction — closing the gap between "plane bytes by
  construction" and what the device actually holds.  Sampled on the
  PR 9 capacity cadence (``CapacityTracker.sample_device_memory``).

Timing semantics: a call's wall is the DISPATCH wall (jax dispatch is
async, and the wrapper never blocks on the outputs, so an observed
kernel overlaps exactly as a bare one does).  Device time per kernel
comes from a profiler trace.  The per-call fast path touches ONLY the
profile's own lock (dict increments); pending aggregates drain into
the registry at every read boundary (``/kernels``, ``/metrics``,
``json_snapshot``, fleet slice capture) via :func:`publish`, so
exported state is fresh and scrapes never see a torn histogram.

Single-source discipline, enforced both ways: :meth:`KernelObservatory.
instrument` REJECTS names without a manifest row, and the
manifest↔runtime cross-check test (``tests/test_kernel_obs.py``) walks
:func:`warm_manifest` and asserts every traceable row is instrumented.

Stdlib-only at module scope (the obs import-lightness contract): jax
and the analysis manifest import lazily, and a process that never calls
a kernel never pays for either.
"""

from __future__ import annotations

import math
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import events as events_mod
from . import metrics as metrics_mod

#: compile_budget_frac watermark thresholds: a long-lived process that
#: has compiled every declared ladder rung sits at 1.0; anything past
#: DOUBLE the declared budget is runtime shape churn kernelcheck never
#: sanctioned.  (Deliberately looser than the PR 9 capacity 0.7/0.9 —
#: warmup legitimately spends the whole budget.)
BUDGET_WARN_FRAC = 1.0
BUDGET_CRITICAL_FRAC = 2.0

WATERMARK_STATES = ("ok", "warn", "critical")

#: leaves summarized into a compile event's arg-shape signature
_SIG_LEAVES = 16


def _jax():
    """The already-imported jax module (kernel wrappers only ever run
    after their jitted target imported it)."""
    return sys.modules["jax"]


def _tree_bytes(*trees: Any) -> int:
    """Array bytes across call trees, on the always-on budget: computed
    as ``prod(shape) * itemsize`` (a jax Array's ``.nbytes`` property
    costs ~3us; the shape/dtype path is ~10x cheaper) over an
    iterative stdlib tuple/list/dict walk, with ONE jax
    ``tree_leaves`` fallback per registered-pytree node (the
    flax-struct map states).  Unknown leaves count 0 — the result is
    an HBM-traffic lower bound by contract."""
    total = 0
    stack = list(trees)
    while stack:
        obj = stack.pop()
        shape = getattr(obj, "shape", None)
        if shape is not None:
            dt = getattr(obj, "dtype", None)
            if dt is not None:
                try:
                    total += math.prod(shape) * dt.itemsize
                except (TypeError, AttributeError):
                    pass
                continue
        if isinstance(obj, (tuple, list)):
            stack.extend(obj)
        elif isinstance(obj, dict):
            stack.extend(obj.values())
        elif obj is None or isinstance(obj, (int, float, bool, str,
                                             bytes)):
            pass
        else:
            try:  # registered pytree node (flax struct state)
                leaves = _jax().tree_util.tree_leaves(obj)
            except Exception:
                continue
            if not (len(leaves) == 1 and leaves[0] is obj):
                stack.extend(leaves)
    return total


def _shape_signature(args: tuple, kwargs: dict) -> str:
    """A compact ``dtype[shape]`` signature of one call's arguments —
    what a ``kernel.compile`` event records so a recompile storm's
    churning axis is readable straight off ``/events``."""
    leaves = _jax().tree_util.tree_leaves((args, kwargs))
    parts = []
    for leaf in leaves[:_SIG_LEAVES]:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            dt = getattr(leaf.dtype, "name", str(leaf.dtype))
            parts.append(f"{dt}{list(leaf.shape)}")
        else:
            parts.append(repr(leaf)[:24])
    if len(leaves) > _SIG_LEAVES:
        parts.append(f"+{len(leaves) - _SIG_LEAVES} more")
    return ",".join(parts)


def _lower_args(args: tuple, kwargs: dict) -> tuple:
    """The call's arguments with array leaves abstracted to
    ``ShapeDtypeStruct`` (statics kept concrete) — enough to re-``lower``
    the kernel later for a cost_analysis capture without holding device
    buffers alive."""
    jax = _jax()

    def conv(x):
        if hasattr(x, "shape") and hasattr(x, "dtype") \
                and not isinstance(x, (bool, int, float)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return (jax.tree_util.tree_map(conv, args),
            jax.tree_util.tree_map(conv, kwargs))


# -- ladder-transition stamps (executor.regrow / executor.shrink) ------------

_LADDER_LOCK = threading.Lock()
_LADDER_EPOCH = 0
_LADDER_MONO: float = float("-inf")


def note_ladder_transition(kind: str = "regrow") -> None:
    """Stamp a capacity-ladder transition (called by the executor's
    regrow path and the GC re-pack next to their flight-recorder
    events).  The FIRST compile a kernel pays after a transition is
    ladder-attributed; repeats without a fresh transition are shape
    churn.  ``kind`` is informational (regrow/shrink)."""
    global _LADDER_EPOCH, _LADDER_MONO
    with _LADDER_LOCK:
        _LADDER_EPOCH += 1
        _LADDER_MONO = time.monotonic()


def _ladder_epoch() -> int:
    with _LADDER_LOCK:
        return _LADDER_EPOCH


class KernelProfile:
    """One manifested kernel's runtime record.

    ``label`` is the metric-segment form of the manifest ``name``
    (dots → underscores: ``batch.orswot.merge`` →
    ``batch_orswot_merge``), so every published name fits the
    one-dynamic-segment namespace grammar
    (``kernel.<label>.{calls,compiles,wall,...}``)."""

    def __init__(self, spec, registry: metrics_mod.MetricsRegistry):
        self.name: str = spec.name
        self.label: str = spec.name.replace(".", "_").replace("-", "_")
        self.compile_budget: int = spec.compile_budget
        self.traceable: bool = spec.build is not None
        self.notrace_reason: str = spec.notrace_reason
        self.instrumented = False
        self.instances = 0
        self.calls = 0
        self.compiles = 0
        self.errors = 0
        self.bytes_total = 0
        self.wall_total_s = 0.0
        self.last_signature: Optional[str] = None
        self.cost: Optional[dict] = None
        self._cost_at_compiles = -1
        self._lower_sig: Optional[tuple] = None
        self._last_fn: Any = None
        self._ladder_seen = _ladder_epoch()
        self._lock = threading.Lock()
        self._reg = registry
        self._handles: Optional[tuple] = None
        self._wall_name = f"kernel.{self.label}.wall"
        # pending (not-yet-published) per-call aggregates: the hot path
        # only touches these under the profile lock; publish() drains
        # them into the registry in one lock acquisition per metric
        self._pend_calls = 0
        self._pend_bytes = 0
        self._pend_buckets: Dict[int, int] = {}
        self._pend_count = 0
        self._pend_sum = 0.0
        self._pend_min = math.inf
        self._pend_max = -math.inf

    # handle creation claims the names once; the per-call path reuses
    # the cached handles (counters lock themselves, gauges are LWW)
    def _ensure_handles(self):
        if self._handles is None:
            reg = self._reg
            label = self.label
            self._handles = (
                reg.counter(f"kernel.{label}.calls"),
                reg.counter(f"kernel.{label}.compiles"),
                reg.counter(f"kernel.{label}.bytes"),
                reg.counter(f"kernel.{label}.errors"),
                reg.gauge(f"kernel.{label}.compile_budget_frac"),
            )
            reg.histogram(f"kernel.{label}.wall")
        return self._handles

    @property
    def budget_frac(self) -> float:
        return self.compiles / self.compile_budget \
            if self.compile_budget > 0 else float(self.compiles)

    @property
    def watermark(self) -> str:
        f = self.budget_frac
        if f >= BUDGET_CRITICAL_FRAC:
            return "critical"
        if f >= BUDGET_WARN_FRAC:
            return "warn"
        return "ok"

    # -- per-call recording (wrapper-driven) ---------------------------------

    def record_call(self, dt: float, nbytes: int) -> None:
        """The always-on per-call path: ONE profile-lock acquisition,
        dict increments only — no registry traffic.  publish() drains
        the pending aggregates at scrape/snapshot boundaries."""
        e = metrics_mod.log2_bucket(dt)
        with self._lock:
            self.calls += 1
            self.wall_total_s += dt
            self.bytes_total += nbytes
            self._pend_calls += 1
            self._pend_bytes += nbytes
            self._pend_buckets[e] = self._pend_buckets.get(e, 0) + 1
            self._pend_count += 1
            self._pend_sum += dt
            if dt < self._pend_min:
                self._pend_min = dt
            if dt > self._pend_max:
                self._pend_max = dt

    def publish(self) -> None:
        """Drain the pending per-call aggregates into the registry.
        Called at every read boundary (``/kernels``, ``/metrics``,
        ``json_snapshot``, fleet slice capture, :meth:`KernelObservatory.
        table`) so exported state is fresh without the hot path ever
        paying a registry round-trip."""
        with self._lock:
            if self._pend_count == 0 and self._pend_calls == 0:
                return
            calls, nbytes = self._pend_calls, self._pend_bytes
            buckets = self._pend_buckets
            count, total = self._pend_count, self._pend_sum
            vmin, vmax = self._pend_min, self._pend_max
            self._pend_calls = 0
            self._pend_bytes = 0
            self._pend_buckets = {}
            self._pend_count = 0
            self._pend_sum = 0.0
            self._pend_min = math.inf
            self._pend_max = -math.inf
        calls_c, _, bytes_c, _, _ = self._ensure_handles()
        if calls:
            calls_c.inc(calls)
        if nbytes:
            bytes_c.inc(nbytes)
        self._reg.observe_aggregate(self._wall_name, buckets, count,
                                    total, vmin, vmax)

    def record_compile(self, count: int, dt: float, args: tuple,
                       kwargs: dict, fn: Any, nbytes: int) -> None:
        calls, compiles_c, bytes_c, _, frac_g = self._ensure_handles()
        calls.inc()
        compiles_c.inc(count)
        self._reg.counter_inc("kernel.compiles", count)
        if nbytes:
            bytes_c.inc(nbytes)
        epoch = _ladder_epoch()
        try:
            sig = _shape_signature(args, kwargs)
        except Exception:  # a signature must never fail the kernel call
            sig = "<unavailable>"
        with self._lock:
            first = self.compiles == 0
            ladder = epoch > self._ladder_seen
            self._ladder_seen = epoch
            self.calls += 1
            self.compiles += count
            self.bytes_total += nbytes
            self.last_signature = sig
            self._last_fn = fn
            try:
                self._lower_sig = _lower_args(args, kwargs)
            except Exception:
                self._lower_sig = None
            n = self.compiles
        frac_g.set(self.budget_frac)
        _observatory_budget_refresh()
        events_mod.record(
            "kernel.compile", kernel=self.name, shapes=sig,
            wall_s=round(dt, 6), count=count, n=n,
            ladder=ladder, first=first,
        )

    def record_error(self) -> None:
        handles = self._ensure_handles()
        handles[3].inc()
        with self._lock:
            self.errors += 1

    # -- one-time-per-compilation XLA cost capture ---------------------------

    def capture_cost(self) -> Optional[dict]:
        """Lower+compile the last compiled signature and read the
        backend's ``cost_analysis()`` (flops / bytes accessed, where
        reported).  Deliberately LAZY — a second compile per signature
        is cheap next to the first but not free, so it runs on demand
        (``/kernels?cost=1``, the bench) and memoizes until the kernel
        compiles again.  Returns the cost dict or None."""
        with self._lock:
            if self._lower_sig is None or self._last_fn is None:
                return self.cost
            if self._cost_at_compiles == self.compiles:
                return self.cost
            fn, (la, lkw), at = self._last_fn, self._lower_sig, self.compiles
        try:
            lowered = fn.lower(*la, **lkw)
            ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            cost = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
        except Exception as e:  # backends legitimately decline
            self._reg.counter_inc("kernel.cost.unavailable")
            events_mod.record("kernel.cost_unavailable", kernel=self.name,
                              error=type(e).__name__)
            return self.cost
        reg = self._reg
        reg.gauge_set(f"kernel.{self.label}.cost_flops", cost["flops"])
        reg.gauge_set(f"kernel.{self.label}.cost_bytes",
                      cost["bytes_accessed"])
        with self._lock:
            self.cost = cost
            self._cost_at_compiles = at
        return cost


class _ObservedKernel:
    """The per-jit-site callable wrapper.  Transparent by construction:
    ``__wrapped__`` reaches the plain Python function (kernelcheck's
    ``_unjit`` discipline), unknown attributes (``lower``,
    ``clear_cache``) forward to the jitted target."""

    def __init__(self, profile: KernelProfile, jitted: Callable):
        self._fn = jitted
        self._profile = profile
        self._cache_seen = self._cache_size()
        self.__wrapped__ = getattr(jitted, "__wrapped__", jitted)
        self.__name__ = getattr(jitted, "__name__", profile.label)
        self.__doc__ = getattr(jitted, "__doc__", None)
        self.__module__ = getattr(jitted, "__module__", __name__)

    def _cache_size(self) -> int:
        try:
            return self._fn._cache_size()
        except Exception:
            return 0

    def __call__(self, *args, **kwargs):
        prof = self._profile
        t0 = time.perf_counter()
        try:
            out = self._fn(*args, **kwargs)
        except BaseException:
            prof.record_error()
            raise
        dt = time.perf_counter() - t0
        size = self._cache_size()
        compiled = size - self._cache_seen
        self._cache_seen = size
        try:
            nbytes = _tree_bytes(args, kwargs, out)
        except Exception:
            nbytes = 0
        if compiled > 0:
            # a compiling call's wall is dominated by the compile: it
            # rides the kernel.compile event, keeping the wall
            # histogram a steady-state distribution
            prof.record_compile(compiled, dt, args, kwargs, self._fn,
                                nbytes)
        else:
            prof.record_call(dt, nbytes)
        return out

    def __getattr__(self, item):
        return getattr(object.__getattribute__(self, "_fn"), item)

    def __repr__(self):
        return f"<observed kernel {self._profile.name!r} of {self._fn!r}>"


class KernelObservatory:
    """The process's runtime kernel registry: one
    :class:`KernelProfile` per manifest row, created eagerly from
    :data:`crdt_tpu.analysis.kernels.MANIFEST` so the ``/kernels``
    table shows un-instrumented rows as explicit gaps, not absences."""

    def __init__(self, registry: Optional[metrics_mod.MetricsRegistry]
                 = None):
        from ..analysis.kernels import MANIFEST  # stdlib-only import

        self._registry = registry if registry is not None \
            else metrics_mod.registry()
        self._lock = threading.Lock()
        self._profiles: Dict[str, KernelProfile] = {
            spec.name: KernelProfile(spec, self._registry)
            for spec in MANIFEST
        }

    def profile(self, name: str) -> KernelProfile:
        try:
            return self._profiles[name]
        except KeyError:
            raise ValueError(
                f"kernel {name!r} has no KernelSpec row in "
                "crdt_tpu/analysis/kernels.py — the runtime observatory "
                "shares the manifest's single source of kernel identity; "
                "add the row first (same discipline as obs/namespace.py)"
            ) from None

    def instrument(self, name: str, jitted: Callable) -> Callable:
        prof = self.profile(name)
        with self._lock:
            prof.instrumented = True
            prof.instances += 1
        return _ObservedKernel(prof, jitted)

    # -- views ---------------------------------------------------------------

    def profiles(self) -> Dict[str, KernelProfile]:
        return dict(self._profiles)

    def instrumented_names(self) -> set:
        return {n for n, p in self._profiles.items() if p.instrumented}

    def worst_budget_state(self) -> int:
        return max(
            (WATERMARK_STATES.index(p.watermark)
             for p in self._profiles.values() if p.instrumented),
            default=0,
        )

    def publish(self) -> None:
        """Drain every instrumented profile's pending per-call
        aggregates into the registry (see :meth:`KernelProfile.
        publish`)."""
        for prof in self._profiles.values():
            if prof.instrumented:
                prof.publish()

    def capture_costs(self, names: Optional[List[str]] = None) -> dict:
        """Run the lazy cost capture for every instrumented kernel (or
        the named subset); returns ``{name: cost}`` for the captures
        that succeeded."""
        out = {}
        for name, prof in sorted(self._profiles.items()):
            if names is not None and name not in names:
                continue
            cost = prof.capture_cost()
            if cost is not None:
                out[name] = cost
        return out

    def table(self) -> List[dict]:
        """The per-kernel runtime table ``/kernels?format=json``
        serves: identity, compile accounting vs the declared budget,
        wall quantiles from the registry histogram, throughput, and
        the captured XLA cost."""
        self.publish()
        snap = self._registry.snapshot()
        hists = snap.get("histograms", {})
        rows = []
        for name, p in sorted(self._profiles.items()):
            h = hists.get(f"kernel.{p.label}.wall")
            row = {
                "kernel": name,
                "label": p.label,
                "instrumented": p.instrumented,
                "instances": p.instances,
                "calls": p.calls,
                "compiles": p.compiles,
                "errors": p.errors,
                "compile_budget": p.compile_budget,
                "compile_budget_frac": round(p.budget_frac, 4),
                "watermark": p.watermark,
                "bytes_total": p.bytes_total,
                "wall_p50_s": _hist_quantile(h, 0.5),
                "wall_p99_s": _hist_quantile(h, 0.99),
                "last_compile_shapes": p.last_signature,
                "cost_flops": p.cost["flops"] if p.cost else None,
                "cost_bytes_accessed":
                    p.cost["bytes_accessed"] if p.cost else None,
            }
            if not p.traceable:
                row["notrace_reason"] = p.notrace_reason
            rows.append(row)
        return rows


def _hist_quantile(h: Optional[dict], q: float) -> Optional[float]:
    """Approximate quantile from a log2-bucket snapshot: the upper
    bound of the bucket where the cumulative count crosses ``q`` (an
    at-most-2x overestimate — the honest resolution of power-of-two
    buckets)."""
    if not h or not h.get("count"):
        return None
    target = q * h["count"]
    running = 0
    for e in sorted(h["buckets"]):
        running += h["buckets"][e]
        if running >= target:
            return 0.0 if e == metrics_mod.Histogram.ZERO_BUCKET \
                else math.ldexp(1.0, e)
    return h.get("max")


# -- the process-global observatory ------------------------------------------

_DEFAULT: Optional[KernelObservatory] = None
_DEFAULT_LOCK = threading.Lock()


def kernel_observatory() -> KernelObservatory:
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = KernelObservatory()
    return _DEFAULT


def publish() -> None:
    """Drain the process-global observatory's pending per-call
    aggregates into the default registry (no-op before any kernel was
    instrumented — this must not force the manifest import)."""
    obs = _DEFAULT
    if obs is not None:
        obs.publish()


def _observatory_budget_refresh() -> None:
    obs = _DEFAULT
    if obs is not None:
        obs._registry.gauge_set("kernel.budget.watermark",
                                obs.worst_budget_state())


def observed_kernel(name: str) -> Callable:
    """Instrument one manifested jit entry point::

        @observed_kernel("batch.orswot.merge")
        @functools.partial(jax.jit, static_argnums=(10, 11, 12))
        def _merge(...): ...

    or, for factory-built kernels,
    ``return observed_kernel("sync.tree.fold")(jax.jit(kernel))``.
    ``name`` must be a manifest row (ValueError otherwise — the
    runtime registry refuses names kernelcheck has never heard of).
    Factories re-invoked with different statics/meshes attach multiple
    instances to ONE profile; compile counts aggregate across them."""

    def deco(jitted: Callable) -> Callable:
        return kernel_observatory().instrument(name, jitted)

    return deco


def warm_manifest() -> set:
    """Instrument every traceable manifest row without executing a
    kernel: building each row's trace cases imports its module (
    decorated kernels attach at import) and invokes its kernel factory
    (factory kernels attach at build).  Returns the instrumented name
    set — what the manifest↔runtime cross-check asserts against."""
    from ..analysis.kernels import MANIFEST

    for spec in MANIFEST:
        if spec.build is not None:
            spec.build()
    return kernel_observatory().instrumented_names()


# -- recompile-storm detection -----------------------------------------------


def storm_report(recorder: Optional[events_mod.FlightRecorder] = None,
                 since_seq: int = 0) -> dict:
    """Classify the flight recorder's ``kernel.compile`` events (with
    ``seq > since_seq`` — pass the last event's seq after warmup to
    scope a steady-state epoch): per kernel, how many compiles were
    ladder-attributed (first compile after an ``executor.regrow``/
    ``executor.shrink`` stamp), how many were first-ever (warmup), and
    which were neither — the shape-churn residue.  ``storm`` is True
    when any unexplained compile exists in the window."""
    rec = recorder if recorder is not None else events_mod.recorder()
    kernels: Dict[str, dict] = {}
    total = 0
    unexplained_total = 0
    for ev in rec.snapshot(kind="kernel.compile"):
        if ev["seq"] <= since_seq:
            continue
        f = ev.get("fields", {})
        k = f.get("kernel", "<unknown>")
        d = kernels.setdefault(k, {
            "compiles": 0, "ladder": 0, "first": 0, "unexplained": [],
        })
        n = int(f.get("count", 1))
        d["compiles"] += n
        total += n
        if f.get("ladder"):
            d["ladder"] += n
        elif f.get("first"):
            d["first"] += n
        else:
            unexplained_total += n
            d["unexplained"].append({
                "seq": ev["seq"],
                "shapes": f.get("shapes"),
                "wall_s": f.get("wall_s"),
            })
    return {
        "kernels": kernels,
        "compiles": total,
        "unexplained": unexplained_total,
        "storm": unexplained_total > 0,
    }


def last_event_seq(recorder: Optional[events_mod.FlightRecorder]
                   = None) -> int:
    """The recorder's newest retained seq — the warmup boundary a
    steady-state assertion passes to :func:`storm_report`."""
    rec = recorder if recorder is not None else events_mod.recorder()
    evs = rec.snapshot()
    return evs[-1]["seq"] if evs else 0


# -- device-memory accounting ------------------------------------------------

_SEEN_DTYPES: set = set()
_DEVMEM_LOCK = threading.Lock()


def sample_device_memory(registry: Optional[metrics_mod.MetricsRegistry]
                         = None, tracker=None) -> Optional[dict]:
    """Fold ``jax.live_arrays()`` into the ``devicemem.*`` gauge family
    (total live bytes, array count, per-dtype bytes); with a
    :class:`~crdt_tpu.obs.capacity.CapacityTracker` the tracked-plane
    bytes and tracked fraction ride along — the construction-vs-device
    gap.  No-op (returns None) when jax was never imported: sampling
    must not drag the device runtime into a scalar process."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    reg = registry if registry is not None else metrics_mod.registry()
    total = 0
    count = 0
    by_dtype: Dict[str, int] = {}
    for arr in jax.live_arrays():
        nb = getattr(arr, "nbytes", None)
        if nb is None:
            continue
        count += 1
        total += int(nb)
        dt = getattr(arr.dtype, "name", str(arr.dtype))
        by_dtype[dt] = by_dtype.get(dt, 0) + int(nb)
    reg.counter_inc("devicemem.samples")
    reg.gauge_set("devicemem.live_bytes", total)
    reg.gauge_set("devicemem.arrays", count)
    with _DEVMEM_LOCK:
        stale = _SEEN_DTYPES - set(by_dtype)
        _SEEN_DTYPES.update(by_dtype)
    for dt, nb in sorted(by_dtype.items()):
        reg.gauge_set(f"devicemem.dtype.{dt}.bytes", nb)
    for dt in sorted(stale):  # a freed family drops to 0, not to stale
        reg.gauge_set(f"devicemem.dtype.{dt}.bytes", 0)
    out = {"live_bytes": total, "arrays": count, "by_dtype": by_dtype}
    if tracker is not None:
        tracked = sum(p.occupancy.bytes for p in tracker.planes().values())
        frac = tracked / total if total > 0 else 0.0
        reg.gauge_set("devicemem.tracked_bytes", tracked)
        reg.gauge_set("devicemem.tracked_frac", frac)
        out["tracked_bytes"] = tracked
        out["tracked_frac"] = frac
    return out
