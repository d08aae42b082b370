"""Observability — metrics registry, flight recorder, live export.

The reference crate has zero observability (SURVEY §5: no logging
crates, only ``Display`` impls); this package is the TPU port's
first-class answer, in five parts:

* :mod:`crdt_tpu.obs.metrics` — a typed registry (counters, gauges,
  log2-bucketed histograms) that every always-on instrument feeds; the
  legacy :mod:`crdt_tpu.utils.tracing` span/counter API re-routes into
  it, so existing call sites needed no churn.
* :mod:`crdt_tpu.obs.events` — a bounded ring-buffer flight recorder of
  structured events (sync phase transitions, digest collisions,
  full-state fallbacks, protocol errors, native-parse fallback reasons,
  wire-loop stalls), stamped with monotonic time and per-session IDs.
* :mod:`crdt_tpu.obs.export` — Prometheus text exposition + JSON
  snapshots, plus an opt-in stdlib-only HTTP thread serving
  ``/metrics``, ``/events``, ``/healthz``
  (``examples/replicate_tcp.py --metrics-port``).
* :mod:`crdt_tpu.obs.convergence` — per-peer digest-divergence gauges,
  rounds-to-converge, staleness age, and delta-ratio history, computed
  from the digest vectors the sync protocol already exchanges.
* :mod:`crdt_tpu.obs.fleet` — the cross-process plane: registry
  snapshots as a join-semilattice (counters G-Counter-merged per node,
  gauges LWW, histograms bucket-wise), CRC-guarded snapshot frames
  piggybacked on gossip sessions or all-gathered over a mesh, the
  ``/fleet`` aggregate, and the trace-ID timeline stitcher.
* :mod:`crdt_tpu.obs.latency` — the time plane: per-session
  critical-path profiles (serialize / network-wait / kernel, with the
  unaccounted residual as its own alertable series), Jacobson/Karels
  transport RTT estimation feeding adaptive retransmit timers, and
  write-to-visible replication lag per (origin, observer) pair with a
  convergence-SLO window.
* :mod:`crdt_tpu.obs.capacity` — the memory plane: dense-plane
  occupancy samples (jitted kernels in
  :mod:`crdt_tpu.batch.occupancy`) turned into ``crdt_tpu_capacity_*``
  gauges, EWMA growth rates, time-to-overflow ETAs against the
  executor's regrow ceiling, and the ok/warn/critical watermark
  ``/healthz`` reports.
* :mod:`crdt_tpu.obs.stability` — the agreement plane: divergence
  aging (birth→resolution tracking of diverged digest subtrees), the
  fleet stability frontier (the per-subtree clock below which every
  non-quarantined peer has provably converged — what coordinated
  truncation will consume, min-joined across the fleet lattice and
  served at ``/stability``), and the runtime lattice auditor (sampled
  merge-idempotence + frontier-soundness self-checks, the online
  tripwire for the whole lattice stack).
* :mod:`crdt_tpu.obs.heat` — the placement plane: per-subtree traffic
  attribution (read/write/repair heat folded by jitted scatter-add
  kernels onto the PR 15 ``subtree_layout``), an on-device
  Space-Saving top-k sketch with a Zipf-exponent estimator, and the
  shard/ring placement planner behind ``GET /heat`` — the measurement
  half of the mesh-sharding and partial-replication items.
* :mod:`crdt_tpu.obs.kernels` — the kernel plane: the runtime kernel
  observatory (dynamic companion to kernelcheck, keyed on the SAME
  :data:`crdt_tpu.analysis.kernels.MANIFEST` rows) — per-kernel
  compile/recompile tracking with ladder-vs-shape-churn
  classification, always-cheap wall histograms, lazy XLA
  ``cost_analysis`` capture, device-memory gauges, and the
  ``/kernels`` table.

Import-light by design: nothing here imports JAX or numpy, so the
scalar engine (and any process that only wants a counter) pays nothing
for it.  docs/GUIDE.md "Observability" documents naming conventions and how
to read the flight recorder after a failed sync.
"""

from . import (  # noqa: F401
    capacity,
    convergence,
    events,
    fleet,
    heat,
    kernels,
    latency,
    metrics,
    stability,
)
from .capacity import CapacityTracker, Occupancy, capacity_tracker  # noqa: F401
from .convergence import ConvergenceTracker, tracker  # noqa: F401
from .events import FlightRecorder, new_session_id, record, recorder  # noqa: F401
from .fleet import (  # noqa: F401
    FleetObservatory,
    FleetSnapshot,
    observatory,
    stitch_trace,
)
from .heat import HeatTracker, heat_tracker  # noqa: F401
from .kernels import (  # noqa: F401
    KernelObservatory,
    KernelProfile,
    kernel_observatory,
    observed_kernel,
    sample_device_memory,
    storm_report,
)
from .latency import (  # noqa: F401
    LagTracker,
    RttEstimator,
    SessionProfile,
    lag_tracker,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    registry,
)
from .stability import (  # noqa: F401
    AuditReport,
    FrontierReport,
    StabilityTracker,
    stability_tracker,
)

__all__ = [
    "AuditReport",
    "CapacityTracker",
    "ConvergenceTracker",
    "Counter",
    "FrontierReport",
    "HeatTracker",
    "heat_tracker",
    "StabilityTracker",
    "stability_tracker",
    "FleetObservatory",
    "FleetSnapshot",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "KernelObservatory",
    "KernelProfile",
    "LagTracker",
    "MetricsRegistry",
    "Occupancy",
    "RttEstimator",
    "SessionProfile",
    "capacity_tracker",
    "kernel_observatory",
    "lag_tracker",
    "observed_kernel",
    "sample_device_memory",
    "storm_report",
    "new_session_id",
    "observatory",
    "record",
    "recorder",
    "registry",
    "stitch_trace",
    "tracker",
]


def start_metrics_server(port: int = 0, host: str = "127.0.0.1"):
    """Start the background ``/metrics`` HTTP exporter (lazy import so
    merely importing :mod:`crdt_tpu.obs` never touches http.server)."""
    from .export import start_metrics_server as _start

    return _start(port=port, host=host)
