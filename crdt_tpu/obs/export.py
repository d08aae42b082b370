"""Export surfaces: Prometheus text, JSON snapshots, a live HTTP thread.

Three consumers, three shapes:

* :func:`prometheus_text` — text exposition (format 0.0.4) of the
  metric registry under the ``crdt_tpu_`` namespace: counters as
  ``*_total``, gauges bare, histograms as ``_bucket``/``_sum``/
  ``_count`` with power-of-two ``le`` bounds.  Dotted metric names
  sanitize to underscores at scrape time so hot paths never pay for it.
* :func:`json_snapshot` — one dict with the registry snapshot, the
  flight-recorder events, and the per-peer convergence state; what
  ``/events`` serves.
* :class:`MetricsServer` / :func:`start_metrics_server` — an opt-in,
  stdlib-only background HTTP thread serving ``GET /metrics`` (Prom
  text), ``GET /events`` (JSON; ``?session=`` / ``?kind=`` filters),
  ``GET /fleet`` (the CRDT-merged cross-process snapshot from
  :mod:`crdt_tpu.obs.fleet` — Prom text by default, ``?format=json``
  for per-node slices, ``?trace=<id>`` for a stitched cross-peer
  session timeline), ``GET /kernels`` (the runtime kernel observatory:
  per-kernel compile counts, budget fracs, wall quantiles and
  device-memory gauges — ``?format=json`` for the table +
  recompile-storm report, ``?cost=1`` to capture XLA cost analysis)
  and ``GET /healthz``.  Daemon threads throughout:
  an exporter must never
  keep a replica process alive or take it down — handler errors are
  swallowed into 500s and ``stop()`` is idempotent.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Optional

from . import convergence, events, metrics
from .namespace import PROM_PREFIX, sanitize as _sanitize


def _fmt(v: float) -> str:
    """Prometheus sample value: integers render bare, floats via repr,
    non-finite values in the exposition format's canonical spelling
    (the never-synced staleness sentinel is ``+Inf``)."""
    import math

    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, float) and not math.isfinite(v):
        if math.isnan(v):
            return "NaN"
        return "+Inf" if v > 0 else "-Inf"
    if isinstance(v, int) or (isinstance(v, float) and v.is_integer()):
        return str(int(v))
    return repr(float(v))


def prometheus_text(registry: Optional[metrics.MetricsRegistry] = None,
                    prefix: str = PROM_PREFIX,
                    tracker: Optional[convergence.ConvergenceTracker] = None,
                    name_prefixes: Optional[tuple] = None) -> str:
    """The registry as Prometheus text exposition.  Refreshes the
    read-time convergence gauges (staleness ages) first so a scrape
    sees live ages — the default tracker when rendering the default
    registry, else only a caller-supplied ``tracker`` (the one whose
    gauges land in ``registry``): scraping a private registry must not
    write the global tracker's gauges into the process-global one.
    ``name_prefixes`` restricts the rendered families to internal names
    starting with one of the given dotted prefixes (what ``/kernels``
    uses to serve just the ``kernel.``/``devicemem.`` plane)."""
    if tracker is None and registry is None:
        tracker = convergence.tracker()
    if tracker is not None:
        tracker.refresh()
    if registry is None:
        # read boundary: drain the kernel observatory's pending
        # per-call aggregates so the scrape sees fresh kernel.* rows
        # (default registry only, same discipline as the gauge below)
        from . import kernels as kernels_mod

        kernels_mod.publish()
        # scrape-time refresh of the flight recorder's eviction count:
        # `dropped` is a Python property, and an alert on "the ring is
        # overflowing faster than anyone reads it" needs it as a gauge.
        # Default registry only — a private-registry scrape must not
        # write global recorder state into the global registry's twin.
        metrics.registry().gauge_set(
            "obs.events.dropped", events.recorder().dropped
        )
    reg = registry if registry is not None else metrics.registry()
    snap = reg.snapshot()
    if name_prefixes is not None:
        def _keep(table):
            return {k: v for k, v in table.items()
                    if k.startswith(name_prefixes)}

        snap = {kind: _keep(table) for kind, table in snap.items()}
    lines = []
    for name in sorted(snap["counters"]):
        mname = f"{prefix}_{_sanitize(name)}_total"
        lines.append(f"# TYPE {mname} counter")
        lines.append(f"{mname} {_fmt(snap['counters'][name])}")
    for name in sorted(snap["gauges"]):
        mname = f"{prefix}_{_sanitize(name)}"
        lines.append(f"# TYPE {mname} gauge")
        lines.append(f"{mname} {_fmt(snap['gauges'][name])}")
    for name in sorted(snap["histograms"]):
        h = snap["histograms"][name]
        mname = f"{prefix}_{_sanitize(name)}"
        lines.append(f"# TYPE {mname} histogram")
        running = 0
        import math

        for e in sorted(h["buckets"]):
            running += h["buckets"][e]
            bound = 0.0 if e == metrics.Histogram.ZERO_BUCKET \
                else math.ldexp(1.0, e)
            lines.append(
                f'{mname}_bucket{{le="{_fmt(bound)}"}} {running}'
            )
        lines.append(f'{mname}_bucket{{le="+Inf"}} {h["count"]}')
        lines.append(f"{mname}_sum {_fmt(h['sum'])}")
        lines.append(f"{mname}_count {h['count']}")
    return "\n".join(lines) + "\n"


def json_snapshot(registry: Optional[metrics.MetricsRegistry] = None) -> dict:
    """One JSON-ready dict: metrics + flight-recorder events + per-peer
    convergence state (what ``/events`` embeds)."""
    if registry is None:
        from . import kernels as kernels_mod

        kernels_mod.publish()
    reg = registry if registry is not None else metrics.registry()
    rec = events.recorder()
    return {
        "metrics": reg.snapshot(),
        "events": rec.snapshot(),
        "events_dropped": rec.dropped,
        "convergence": convergence.tracker().snapshot(),
    }


# ---- the background HTTP exporter ------------------------------------------


class MetricsServer:
    """A daemon HTTP thread serving ``/metrics``, ``/events``,
    ``/fleet``, ``/kernels``, ``/healthz`` on localhost.  Construct via
    :func:`start_metrics_server`; ``port`` is the bound port (useful
    with ``port=0``), ``scrapes`` counts GETs per path (a peer that
    wants to linger "until someone scraped me" — the TCP example's
    ``--linger`` — polls it)."""

    def __init__(self, host: str, port: int,
                 registry: Optional[metrics.MetricsRegistry] = None,
                 tracker: Optional[convergence.ConvergenceTracker] = None,
                 observatory=None, capacity=None, stability=None,
                 heat=None):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self._registry = registry
        self._tracker = tracker
        self._observatory = observatory
        self._capacity = capacity
        self._stability = stability
        self._heat = heat
        self._t0 = time.monotonic()
        self.scrapes: dict = {}
        self._scrape_lock = threading.Lock()
        server_self = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # the exporter must be silent
                pass

            def do_GET(self):
                try:
                    body, ctype, status = server_self._render(self.path)
                except Exception as e:  # noqa: BLE001 — a scrape bug
                    # must 500, never kill the serving thread
                    body = f"exporter error: {type(e).__name__}: {e}\n".encode()
                    ctype, status = "text/plain; charset=utf-8", 500
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="obs-metrics-exporter",
            daemon=True,
        )
        self._thread.start()

    def _render(self, path: str) -> tuple:
        from urllib.parse import parse_qs, urlparse

        parsed = urlparse(path)
        route = parsed.path.rstrip("/") or "/"
        with self._scrape_lock:
            self.scrapes[route] = self.scrapes.get(route, 0) + 1
        if route == "/metrics":
            text = prometheus_text(self._registry, tracker=self._tracker)
            return text.encode(), "text/plain; version=0.0.4; charset=utf-8", 200
        if route == "/events":
            q = parse_qs(parsed.query)
            rec = events.recorder()
            evs = rec.snapshot(
                kind=q.get("kind", [None])[0],
                session=q.get("session", [None])[0],
            )
            body = json.dumps({
                "events": evs,
                "dropped": rec.dropped,
                "convergence": convergence.tracker().snapshot(),
            }).encode()
            return body, "application/json", 200
        if route == "/fleet":
            from . import fleet as fleet_mod

            obs = self._observatory if self._observatory is not None \
                else fleet_mod.observatory()
            snap = obs.merged()  # refreshes the local slice per scrape
            q = parse_qs(parsed.query)
            trace = q.get("trace", [None])[0]
            if trace is not None:
                body = json.dumps({
                    "trace": trace,
                    "timeline": fleet_mod.stitch_trace(snap, trace),
                }).encode()
                return body, "application/json", 200
            if q.get("format", [None])[0] == "json":
                return (json.dumps(snap.to_json()).encode(),
                        "application/json", 200)
            text = fleet_mod.fleet_prometheus_text(snap)
            return (text.encode(),
                    "text/plain; version=0.0.4; charset=utf-8", 200)
        if route == "/kernels":
            # the runtime kernel observatory (crdt_tpu/obs/kernels.py):
            # prom text of the kernel./devicemem. plane by default,
            # ?format=json for the per-kernel table (compiles, budget
            # frac, wall quantiles, cost analysis) + the
            # recompile-storm classification.  ?cost=1 triggers the
            # lazy XLA cost_analysis capture first (one extra
            # lower+compile per kernel signature — deliberate, so the
            # default scrape stays cheap).  Device-memory gauges
            # refresh per scrape on the default registry (same
            # discipline as obs.events.dropped above).
            from . import kernels as kernels_mod

            q = parse_qs(parsed.query)
            obs = kernels_mod.kernel_observatory()
            if self._registry is None:
                kernels_mod.sample_device_memory(tracker=self._capacity)
            if q.get("cost", [None])[0]:
                obs.capture_costs()
            if q.get("format", [None])[0] == "json":
                body = json.dumps({
                    "kernels": obs.table(),
                    "storm": kernels_mod.storm_report(),
                }).encode()
                return body, "application/json", 200
            text = prometheus_text(
                self._registry, tracker=self._tracker,
                name_prefixes=("kernel.", "devicemem."))
            return (text.encode(),
                    "text/plain; version=0.0.4; charset=utf-8", 200)
        if route == "/stability":
            # the convergence observatory (crdt_tpu/obs/stability.py):
            # the published frontier (per-subtree + fleet-min clocks —
            # what the future truncate-epoch proposer consumes), the
            # divergence-aging view (which subtrees are stuck diverged,
            # and for how long) and the lattice-audit totals.  JSON
            # only: the clock VECTORS are the payload, and the scalar
            # gauges already ride /metrics as crdt_tpu_stability_*.
            from . import stability as stability_mod

            trk = self._stability if self._stability is not None \
                else stability_mod.tracker()
            body = json.dumps(trk.snapshot()).encode()
            return body, "application/json", 200
        if route == "/heat":
            # the heat & placement observatory (crdt_tpu/obs/heat.py):
            # prom text of the heat. plane by default (counters,
            # EWMA rates, top-k gauges — publish() refreshes them
            # first so a scrape never reads a stale window),
            # ?format=json for the full attribution snapshot (layout,
            # per-subtree split, decoded hot list with error bounds,
            # Zipf fit), ?plan=mesh:8 / ?plan=ring:5,k=3 for a scored
            # placement report against the measured heat.
            from . import heat as heat_mod

            trk = self._heat if self._heat is not None \
                else heat_mod.tracker()
            trk.publish()
            q = parse_qs(parsed.query)
            plan = q.get("plan", [None])[0]
            if plan is not None:
                # ?granule=G (mesh plans): subtree-aligned shard
                # boundaries, so the report prices exactly the layout
                # crdt_tpu.mesh.state.choose_layout would build
                granule = q.get("granule", [None])[0]
                try:
                    report = trk.plan_report(
                        plan,
                        granule=int(granule) if granule is not None
                        else None)
                except ValueError as e:
                    return (f"{e}\n".encode(),
                            "text/plain; charset=utf-8", 400)
                body = json.dumps({"heat": trk.snapshot(),
                                   "report": report}).encode()
                return body, "application/json", 200
            if q.get("format", [None])[0] == "json":
                return (json.dumps(trk.snapshot()).encode(),
                        "application/json", 200)
            # render from the TRACKER's registry: a node-private heat
            # tracker publishes its counters there, not into the
            # server-wide registry
            text = prometheus_text(
                trk.registry(), tracker=self._tracker,
                name_prefixes=("heat.",))
            return (text.encode(),
                    "text/plain; version=0.0.4; charset=utf-8", 200)
        if route == "/healthz":
            # liveness + the capacity watermark: `status` mirrors the
            # tracker's overall watermark state (ok/warn/critical; "ok"
            # when nothing is tracked yet), with the per-plane
            # breakdown under `capacity` so an operator's first curl
            # answers "how close is this node to its regrow ceiling".
            # Always HTTP 200 — a critical watermark is an alert, not
            # a liveness failure (restarting the process would make
            # the memory story WORSE).
            from . import capacity as capacity_mod

            cap = self._capacity if self._capacity is not None \
                else capacity_mod.capacity_tracker()
            wm = cap.watermark()
            # the read front-end's vitals ride liveness too: an operator
            # diagnosing "reads are failing" wants the admit/park/reject
            # split from the same curl that answers "is it up".  Totals
            # only — the per-mode breakdown stays on /metrics.
            reg = self._registry if self._registry is not None \
                else metrics.registry()
            snap = reg.snapshot()
            counters = snap["counters"]
            hists = snap["histograms"]

            def _fam(prefix: str) -> int:
                return sum(v for k, v in counters.items()
                           if k.startswith(prefix))

            def _wall(name: str) -> Optional[dict]:
                h = hists.get(name)
                if not h or not h.get("count"):
                    return None
                return {"count": h["count"],
                        "mean_s": round(h["sum"] / h["count"], 6),
                        "max_s": round(h["max"], 6)}

            # duration, not just counts (the PR 17 gap): per-mode
            # serve walls + how long admission parks actually held
            latency = {}
            for mode in ("eventual", "ryw", "monotonic", "frontier"):
                w = _wall("serve.latency." + mode)
                if w is not None:
                    latency[mode] = w

            body = json.dumps({
                "status": wm["state"],
                "uptime_s": round(time.monotonic() - self._t0, 3),
                "capacity": wm,
                "serve": {
                    "reads": counters.get("serve.reads", 0),
                    "batches": counters.get("serve.batches", 0),
                    "admitted": _fam("serve.admit."),
                    "parked": _fam("serve.park."),
                    "rejected": _fam("serve.reject."),
                    "not_stable_rows": counters.get(
                        "serve.not_stable_rows", 0),
                    "latency": latency,
                    "park_wait": _wall("serve.park_wait_s"),
                },
            }).encode()
            return body, "application/json", 200
        return (b"not found (try /metrics, /events, /fleet, /kernels, "
                b"/stability, /heat, /healthz)\n"), \
            "text/plain; charset=utf-8", 404

    def scrape_counts(self) -> dict:
        """Per-route GET counts so far (a consistent copy) — take one as
        the ``since`` baseline for :meth:`scraped`."""
        with self._scrape_lock:
            return dict(self.scrapes)

    def scraped(self, *routes: str, since: Optional[dict] = None) -> bool:
        """True once every named route has been GET'd at least once —
        strictly more times than in ``since`` (a prior
        :meth:`scrape_counts` baseline) when given, so a linger can wait
        for scrapes of the *final* state rather than counting ones that
        raced the work itself."""
        base = since or {}
        with self._scrape_lock:
            return all(self.scrapes.get(r, 0) > base.get(r, 0)
                       for r in routes)

    def stop(self) -> None:
        """Shut the exporter down; idempotent."""
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:  # noqa: BLE001 — double-stop must be a no-op
            pass
        self._thread.join(timeout=5)


def start_metrics_server(port: int = 0, host: str = "127.0.0.1",
                         registry: Optional[metrics.MetricsRegistry] = None,
                         tracker: Optional[convergence.ConvergenceTracker]
                         = None, observatory=None,
                         capacity=None, stability=None,
                         heat=None) -> MetricsServer:
    """Start the opt-in background exporter; ``port=0`` picks a free
    port (read it back from ``server.port``).  ``tracker`` pairs a
    custom ``registry`` with the convergence tracker writing into it
    (see :func:`prometheus_text`); ``observatory`` is the
    :class:`~crdt_tpu.obs.fleet.FleetObservatory` behind ``/fleet``
    (default: the process-global one); ``capacity`` is the
    :class:`~crdt_tpu.obs.capacity.CapacityTracker` whose watermark
    ``/healthz`` reports (default: the process-global one);
    ``stability`` is the :class:`~crdt_tpu.obs.stability.
    StabilityTracker` behind ``/stability`` (default: the
    process-global one); ``heat`` is the
    :class:`~crdt_tpu.obs.heat.HeatTracker` behind ``/heat``
    (default: the process-global one)."""
    return MetricsServer(host, port, registry, tracker, observatory,
                         capacity, stability, heat)
