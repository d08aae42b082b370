"""The observability subsystem (`crdt_tpu.obs`).

Covers the obs PR's acceptance bar: the typed registry (counters,
gauges, log2 histograms) and its Prometheus/JSON export, the bounded
flight recorder, thread-safety of concurrent span/count/event appends
against exporter scrapes, sync-session phase events stamped with
session IDs (a forced digest collision must leave a
``full_state_fallback`` event), wire-loop gauges, convergence
telemetry, the counter-family regression differ, and the live
``/metrics`` + ``/events`` HTTP surface — in-process and through a real
``replicate_tcp --metrics-port`` run.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from crdt_tpu.batch import OrswotBatch
from crdt_tpu.config import CrdtConfig
from crdt_tpu.obs import convergence as obs_convergence
from crdt_tpu.obs import events as obs_events
from crdt_tpu.obs import export as obs_export
from crdt_tpu.obs import metrics as obs_metrics
from crdt_tpu.scalar.orswot import Orswot
from crdt_tpu.sync.session import SyncSession, sync_pair
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe

pytestmark = pytest.mark.obs


def _uni(**kw):
    cfg = dict(num_actors=8, member_capacity=16, deferred_capacity=4,
               counter_bits=32)
    cfg.update(kw)
    return Universe.identity(CrdtConfig(**cfg))


def _orswot_fleet(n, seed, actor=1, extra_on=()):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        s = Orswot()
        for _ in range(rng.randint(1, 5)):
            s.apply(s.add(int(rng.randint(0, 50)),
                          s.value().derive_add_ctx(0)))
        out.append(s)
    for i in extra_on:
        s = out[i]
        s.apply(s.add(900 + actor, s.value().derive_add_ctx(actor)))
    return out


def _http_get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


# ---- metrics registry ------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = obs_metrics.MetricsRegistry()
    reg.counter_inc("c", 3)
    reg.counter("c").inc(2)
    reg.gauge_set("g", 7.5)
    reg.observe("h", 3.0)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 7.5
    assert snap["histograms"]["h"]["count"] == 1
    assert snap["histograms"]["h"]["sum"] == 3.0


def test_registry_rejects_type_flips():
    reg = obs_metrics.MetricsRegistry()
    reg.counter_inc("x")
    with pytest.raises(ValueError):
        reg.gauge_set("x", 1.0)
    with pytest.raises(ValueError):
        reg.observe("x", 1.0)


def test_histogram_log2_buckets():
    h = obs_metrics.Histogram("h")
    # bucket e holds (2**(e-1), 2**e]: 3.0 -> bound 4.0, and exactly
    # 4.0 ALSO -> bound 4.0 (Prometheus le is inclusive); 5.0 -> 8.0
    h.observe(3.0)
    h.observe(4.0)
    h.observe(5.0)
    h.observe(0.0)       # zero/negative -> floor bucket, bound 0.0
    h.observe(1e-9)
    bounds = dict(h.cumulative())
    assert h.count == 5
    assert 4.0 in bounds and 8.0 in bounds
    assert 0.0 in bounds and bounds[0.0] == 1  # only the zero landed there
    assert bounds[4.0] == 4                    # 0.0, 1e-9, 3.0, 4.0 are <= 4
    assert bounds[8.0] - bounds[4.0] == 1      # only 5.0 sits in (4, 8]
    # cumulative counts are monotone and end at count
    cum = [c for _, c in h.cumulative()]
    assert cum == sorted(cum) and cum[-1] == h.count
    assert h.min == 0.0 and h.max == 5.0


def test_counter_handle_is_thread_safe():
    """Cached counter handles mutate outside the registry lock; the
    counter's own lock must keep concurrent increments exact (the
    monotonic contract — a gauge may lose races, a counter may not)."""
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("c")
    n_threads, per_thread = 8, 5_000
    threads = [
        threading.Thread(target=lambda: [c.inc() for _ in range(per_thread)])
        for _ in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert c.value == n_threads * per_thread


def test_prometheus_text_rendering():
    reg = obs_metrics.MetricsRegistry()
    reg.counter_inc("wire.sync.delta.bytes", 123)
    reg.gauge_set("wireloop.staging_free", 2)
    reg.observe("sync.digest_exchange", 0.003)
    text = obs_export.prometheus_text(reg)
    assert "# TYPE crdt_tpu_wire_sync_delta_bytes_total counter" in text
    assert "crdt_tpu_wire_sync_delta_bytes_total 123" in text
    assert "crdt_tpu_wireloop_staging_free 2" in text
    assert "# TYPE crdt_tpu_sync_digest_exchange histogram" in text
    assert 'crdt_tpu_sync_digest_exchange_bucket{le="+Inf"} 1' in text
    assert "crdt_tpu_sync_digest_exchange_count 1" in text


# ---- flight recorder -------------------------------------------------------


def test_flight_recorder_bounded_and_filtered():
    rec = obs_events.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("probe.tick", session="s1" if i % 2 else "s2", i=i)
    evs = rec.snapshot()
    assert len(evs) == 8
    assert rec.dropped == 12
    # oldest-first, monotone seq, latest retained
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and seqs[-1] == 20
    assert all(e["kind"] == "probe.tick" for e in evs)
    only_s1 = rec.snapshot(session="s1")
    assert only_s1 and all(e["session"] == "s1" for e in only_s1)
    # kind filter matches whole dotted segments only
    assert rec.snapshot(kind="probe") == evs
    assert rec.snapshot(kind="prob") == []
    rec.clear()
    assert rec.snapshot() == [] and rec.dropped == 0


def test_session_ids_are_unique():
    ids = {obs_events.new_session_id() for _ in range(100)}
    assert len(ids) == 100


# ---- thread-safety: writers vs scrapes -------------------------------------


def test_concurrent_spans_counts_events_vs_scrapes():
    """Wireloop-parse-thread shape: several writer threads hammer
    span/count/event appends while a scraper renders snapshots and
    Prometheus text; nothing tears, and the final totals are exact."""
    tracing.reset()
    rec = obs_events.recorder()
    rec.clear()
    tracing.enable(True)
    tracing.count("obs.threads.primer")  # scraper may win the race to an
    # otherwise-empty registry; give it one guaranteed sample
    n_threads, per_thread = 4, 500
    errors = []
    stop = threading.Event()

    def writer(tid):
        try:
            for i in range(per_thread):
                tracing.count("obs.threads.counter", 1)
                with tracing.span("obs.threads.span"):
                    pass
                obs_events.record("obs.threads.event", tid=tid, i=i)
        except Exception as e:  # noqa: BLE001 — surfaced after join
            errors.append(e)

    def scraper():
        try:
            while not stop.is_set():
                text = obs_export.prometheus_text()
                assert "crdt_tpu_" in text
                snap = obs_metrics.registry().snapshot()
                # a torn histogram would violate sum(buckets) == count
                for h in snap["histograms"].values():
                    assert sum(h["buckets"].values()) == h["count"]
                rec.snapshot()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    s = threading.Thread(target=scraper)
    s.start()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        stop.set()
        s.join(timeout=60)
        tracing.enable(False)
    assert not errors, errors[0]
    total = n_threads * per_thread
    assert tracing.counters()["obs.threads.counter"] == total
    assert tracing.get_tracer().stats["obs.threads.span"].count == total
    reg_snap = obs_metrics.registry().snapshot()
    assert reg_snap["counters"]["obs.threads.counter"] == total
    assert reg_snap["histograms"]["obs.threads.span"]["count"] == total
    assert len(rec.snapshot(kind="obs.threads.event")) + rec.dropped >= total
    tracing.reset()


# ---- sync session events + convergence telemetry ---------------------------


def test_sync_session_phase_events_and_convergence_gauges():
    obs_events.recorder().clear()
    uni = _uni()
    a = SyncSession(
        OrswotBatch.from_scalar(_orswot_fleet(32, 7, actor=1,
                                              extra_on=[3]), uni),
        uni, peer="b",
    )
    b = SyncSession(
        OrswotBatch.from_scalar(_orswot_fleet(32, 7, actor=2,
                                              extra_on=[9]), uni),
        uni, peer="a",
    )
    assert a.session_id != b.session_id
    ra, rb = sync_pair(a, b)
    assert ra.converged and rb.converged

    evs_a = obs_events.recorder().snapshot(session=a.session_id)
    phases = [e["fields"]["phase"] for e in evs_a
              if e["kind"] == "sync.phase"]
    assert phases[0] == "start"
    assert "digest_exchange" in phases
    assert "delta_exchange" in phases
    assert phases[-1] == "converged"
    # peer A's events never carry peer B's session id
    assert all(e["session"] == a.session_id for e in evs_a)

    conv = obs_convergence.tracker().snapshot()
    assert conv["b"]["divergence"] == 2  # rows 3 and 9 diverged
    assert conv["b"]["rounds_to_converge"] == ra.digest_rounds
    assert conv["b"]["staleness_s"] is not None
    g = obs_metrics.registry().snapshot()["gauges"]
    assert g["sync.peer.b.divergence"] == 2.0
    assert g["sync.peer.b.rounds_to_converge"] == float(ra.digest_rounds)


def test_forced_digest_collision_leaves_fallback_event():
    """Acceptance bar: a forced digest collision must leave a
    ``sync.full_state_fallback`` event (reason ``digest_collision``) in
    the flight recorder, and the session still converges."""
    obs_events.recorder().clear()
    uni = _uni()
    collide = lambda batch: np.zeros(  # noqa: E731 — constant digest
        batch.clock.shape[0], dtype=np.uint64
    )
    a = SyncSession(
        OrswotBatch.from_scalar(_orswot_fleet(16, 11, actor=1,
                                              extra_on=[2]), uni),
        uni, digest_fn=collide,
    )
    b = SyncSession(
        OrswotBatch.from_scalar(_orswot_fleet(16, 11, actor=2,
                                              extra_on=[5]), uni),
        uni, digest_fn=collide,
    )
    ra, rb = sync_pair(a, b)
    assert ra.converged and ra.full_state_fallback
    falls = obs_events.recorder().snapshot(kind="sync.full_state_fallback",
                                           session=a.session_id)
    assert falls and falls[0]["fields"]["reason"] == "digest_collision"
    colls = obs_events.recorder().snapshot(kind="sync.digest_collision",
                                           session=a.session_id)
    assert colls


def test_delta_ratio_gauge_populates_with_reference():
    """The per-peer delta_ratio gauge and history populate when the
    session knows a full-state reference — the constructor hint on the
    delta path, the shipped full frame itself on fallback paths."""
    uni = _uni()
    fa = OrswotBatch.from_scalar(_orswot_fleet(32, 21, actor=1,
                                               extra_on=[3]), uni)
    fb = OrswotBatch.from_scalar(_orswot_fleet(32, 21, actor=2,
                                               extra_on=[9]), uni)
    full_ref = sum(len(b) for b in fa.to_wire(uni))
    a = SyncSession(fa, uni, peer="ratio-b", full_state_bytes=full_ref)
    b = SyncSession(fb, uni, peer="ratio-a", full_state_bytes=full_ref)
    ra, _ = sync_pair(a, b)
    assert ra.converged and not ra.full_state_fallback
    g = obs_metrics.registry().snapshot()["gauges"]
    ratio = g["sync.peer.ratio-b.delta_ratio"]
    assert 0.0 < ratio < 1.0  # 2/32 rows diverged: far below full state
    hist = obs_convergence.tracker().snapshot()["ratio-b"][
        "delta_ratio_history"]
    assert hist and hist[-1] == pytest.approx(ratio)

    # fallback path, NO hint: the full frame itself is the reference,
    # so the ratio lands at >= 1.0 (full state shipped plus framing)
    collide = lambda batch: np.zeros(  # noqa: E731 — constant digest
        batch.clock.shape[0], dtype=np.uint64
    )
    fc = OrswotBatch.from_scalar(_orswot_fleet(16, 23, actor=1,
                                               extra_on=[2]), uni)
    fd = OrswotBatch.from_scalar(_orswot_fleet(16, 23, actor=2,
                                               extra_on=[5]), uni)
    c = SyncSession(fc, uni, digest_fn=collide, peer="ratio-d")
    d = SyncSession(fd, uni, digest_fn=collide, peer="ratio-c")
    rc, _ = sync_pair(c, d)
    assert rc.converged and rc.full_state_fallback
    g = obs_metrics.registry().snapshot()["gauges"]
    assert g["sync.peer.ratio-d.delta_ratio"] >= 1.0


def test_private_registry_scrape_keeps_global_state_untouched():
    """Rendering a caller-owned registry must refresh the caller's
    tracker (so its staleness gauges are live) and must NOT write the
    process-global tracker's gauges into the global registry."""
    import time

    # seed the global tracker so a buggy refresh would visibly rewrite
    # the global staleness gauge
    obs_convergence.tracker().observe_session("leak-probe", converged=True,
                                              rounds=1)
    time.sleep(0.01)
    before = obs_metrics.registry().snapshot()["gauges"]

    reg = obs_metrics.MetricsRegistry()
    trk = obs_convergence.ConvergenceTracker(reg)
    trk.observe_session("px", converged=True, rounds=2)
    time.sleep(0.01)
    text = obs_export.prometheus_text(reg, tracker=trk)
    assert "crdt_tpu_sync_peer_px_rounds_to_converge 2" in text
    staleness = [ln for ln in text.splitlines()
                 if ln.startswith("crdt_tpu_sync_peer_px_staleness_s ")]
    assert staleness and float(staleness[0].split()[1]) > 0.0  # refreshed

    obs_export.prometheus_text(reg)  # private registry, no tracker
    after = obs_metrics.registry().snapshot()["gauges"]
    assert after == before


def test_protocol_error_recorded():
    from crdt_tpu.error import SyncProtocolError
    from crdt_tpu.sync.delta import decode_frame

    obs_events.recorder().clear()
    before = tracing.counters().get("sync.frame.rejected.truncated", 0)
    with pytest.raises(SyncProtocolError):
        decode_frame(b"\x01")
    evs = obs_events.recorder().snapshot(kind="sync.protocol_error")
    assert evs and evs[-1]["fields"]["reason"] == "truncated"
    assert tracing.counters()["sync.frame.rejected.truncated"] == before + 1


# ---- wireloop gauges -------------------------------------------------------


def test_wireloop_publishes_gauges():
    from crdt_tpu.batch.wireloop import PipelinedWireLoop

    uni = _uni()
    fleet = _orswot_fleet(8, 13)
    blobs = OrswotBatch.from_scalar(fleet, uni).to_wire(uni)
    loop = PipelinedWireLoop(uni)
    res = loop.run([[blobs, blobs]], overlap=True)
    assert res["rounds"] == 1
    g = obs_metrics.registry().snapshot()["gauges"]
    assert "wireloop.staging_free" in g
    assert "wireloop.parsed_depth" in g
    text = obs_export.prometheus_text()
    assert "crdt_tpu_wireloop_staging_free" in text


# ---- the HTTP export surface -----------------------------------------------


def test_http_exporter_serves_metrics_events_healthz():
    tracing.count("obs.http.probe_counter", 9)
    obs_events.record("obs.http.probe_event", session="sess-http", x=1)
    srv = obs_export.start_metrics_server(port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        status, text = _http_get(f"{base}/metrics")
        assert status == 200
        assert "crdt_tpu_obs_http_probe_counter_total 9" in text

        status, body = _http_get(f"{base}/events?session=sess-http")
        assert status == 200
        doc = json.loads(body)
        assert any(e["kind"] == "obs.http.probe_event"
                   for e in doc["events"])
        assert all(e["session"] == "sess-http" for e in doc["events"])

        status, body = _http_get(f"{base}/healthz")
        assert status == 200
        assert json.loads(body)["status"] == "ok"

        try:
            _http_get(f"{base}/nope")
        except urllib.error.HTTPError as e:
            assert e.code == 404
        else:
            raise AssertionError("unknown route did not 404")
        assert srv.scraped("/metrics", "/events", "/healthz")
    finally:
        srv.stop()
    srv.stop()  # idempotent


def test_replicate_tcp_metrics_endpoint_live():
    """The acceptance criterion end-to-end: during a ``replicate_tcp
    --metrics-port`` sync session, ``GET /metrics`` serves Prometheus
    text with ``wire.sync.*`` counters and phase latency histograms,
    and ``GET /events`` serves the session's phase-transition events
    carrying its session ID."""
    import os
    import socket
    import subprocess
    import sys
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        sync_port = probe.getsockname()[1]
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        metrics_port = probe.getsockname()[1]

    base = [sys.executable, os.path.join(repo, "examples",
                                         "replicate_tcp.py")]
    common = ["--port", str(sync_port), "--objects", "64",
              "--divergence", "0.05", "--platform", "cpu"]
    srv = subprocess.Popen(
        base + ["server"] + common
        + ["--metrics-port", str(metrics_port), "--linger", "60"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    cli = subprocess.Popen(base + ["client"] + common,
                           stdout=subprocess.PIPE,
                           stderr=subprocess.PIPE, text=True)
    murl = f"http://127.0.0.1:{metrics_port}"
    text = events_doc = None
    try:
        # poll until the scrape shows the finished session: wire.sync
        # counters AND the span histograms (a scrape can race the sync
        # mid-phase, so wait for everything rather than asserting on a
        # half-told story)
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            try:
                _, text = _http_get(f"{murl}/metrics", timeout=5)
                if ("crdt_tpu_wire_sync_digest_bytes_total" in text
                        and "crdt_tpu_sync_digest_exchange_bucket" in text):
                    break
            except OSError:
                pass
            if srv.poll() is not None:
                break
            time.sleep(0.2)
        assert text is not None and \
            "crdt_tpu_wire_sync_digest_bytes_total" in text, (
                f"never saw wire.sync counters on /metrics; "
                f"server rc={srv.poll()} "
                f"stderr={(srv.stderr.read() or '')[-800:] if srv.poll() is not None else '(running)'}"
            )
        # latency histograms (spans are enabled by --metrics-port)
        assert "crdt_tpu_sync_digest_exchange_bucket" in text
        assert "crdt_tpu_sync_digest_exchange_count" in text
        # poll /events until the converged phase lands (mid-sync scrapes
        # see a prefix of the phase transitions); the server lingers
        # until both routes are scraped AFTER its sync finished, so the
        # polling itself is what eventually releases it
        while time.monotonic() < deadline:
            try:
                _, body = _http_get(f"{murl}/events?kind=sync.phase",
                                    timeout=5)
                events_doc = json.loads(body)
                if any(e["fields"]["phase"] == "converged"
                       for e in events_doc["events"]):
                    break
            except OSError:
                pass
            if srv.poll() is not None:
                break
            time.sleep(0.2)
        # release the linger: scrape both routes once more, tolerating
        # the server winning the race and exiting first
        for route in ("/metrics", "/events"):
            try:
                _http_get(f"{murl}{route}", timeout=5)
            except OSError:
                pass
    finally:
        try:
            srv.wait(timeout=120)
            cli.wait(timeout=120)
        except subprocess.TimeoutExpired:
            srv.kill()
            cli.kill()
    assert srv.returncode == 0, (srv.stderr.read() or "")[-800:]
    assert cli.returncode == 0, (cli.stderr.read() or "")[-800:]
    out = srv.stdout.read()
    assert "CONVERGED" in out
    # the printed session id matches the /events stream
    sid = next(tok.split("=", 1)[1] for tok in out.split()
               if tok.startswith("session="))
    phases = [e for e in events_doc["events"] if e.get("session") == sid]
    assert phases, f"no events for session {sid}: {events_doc['events'][:4]}"
    names = [e["fields"]["phase"] for e in phases]
    assert "digest_exchange" in names and "converged" in names
