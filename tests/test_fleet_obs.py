"""Fleet observatory tests — CRDT-merged telemetry, trace propagation.

The acceptance bar (ISSUE 6): a 5-node gossip fleet under 20% injected
frame loss converges AND yields a merged fleet snapshot in which every
fleet counter equals the sum of the per-node counters (despite the
duplicated snapshot delivery a lossy ARQ + gossip echo produce), and
both peers' flight-recorder events for one sync session carry the same
hello-negotiated trace ID.  Everything else here pins the pieces: the
snapshot lattice's ACI contract (seeded property sweep — the suite
must run on boxes without hypothesis), the frame codec's loud
rejections, the per-kind merge semantics, the ``/fleet`` surface under
concurrent gossip, the ring-overflow ``dropped`` gauge, and the
collective all-gather path.
"""

import itertools
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from crdt_tpu.batch import OrswotBatch
from crdt_tpu.cluster import (
    ClusterNode,
    FaultPlan,
    FaultyTransport,
    GossipScheduler,
    Membership,
    ResilientTransport,
    RetryPolicy,
    queue_pair,
)
from crdt_tpu.config import CrdtConfig
from crdt_tpu.error import SyncProtocolError
from crdt_tpu.obs import convergence as obs_convergence
from crdt_tpu.obs import events as obs_events
from crdt_tpu.obs import export as obs_export
from crdt_tpu.obs import fleet as obs_fleet
from crdt_tpu.obs import metrics as obs_metrics
from crdt_tpu.obs import namespace as obs_namespace
from crdt_tpu.scalar.orswot import Orswot
from crdt_tpu.sync.session import SyncSession, sync_pair
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe

pytestmark = pytest.mark.obs

FAST = RetryPolicy(send_deadline_s=3.0, recv_deadline_s=3.0,
                   ack_timeout_s=0.05, max_backoff_s=0.3,
                   retry_budget=400)


def _uni(**kw):
    cfg = dict(num_actors=8, member_capacity=16, deferred_capacity=4,
               counter_bits=32)
    cfg.update(kw)
    return Universe.identity(CrdtConfig(**cfg))


def _orswot_fleet(n, seed, actor=1, extra_on=()):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        s = Orswot()
        for _ in range(rng.randint(1, 5)):
            s.apply(s.add(int(rng.randint(0, 50)),
                          s.value().derive_add_ctx(0)))
        out.append(s)
    for i in extra_on:
        s = out[i]
        s.apply(s.add(900 + actor, s.value().derive_add_ctx(actor)))
    return out


# ---- the lattice: ACI property sweep ---------------------------------------


def _random_snapshot(rng: np.random.RandomState) -> obs_fleet.FleetSnapshot:
    """A structurally valid random snapshot: a few nodes from a shared
    pool (so merges collide on node ids), random counters/gauges/
    histograms with random capture stamps, a random event tail."""
    names = ["sync.sessions", "cluster.rounds", "wire.sync.delta.bytes",
             "sync.errors"]
    gnames = ["sync.peer.a.divergence", "cluster.peers.alive",
              "obs.fleet.nodes"]
    hnames = ["sync.digest_exchange", "cluster.round"]
    slices = {}
    for node in rng.choice(["n0", "n1", "n2", "n3"],
                           size=rng.randint(1, 4), replace=False):
        ts = float(rng.randint(0, 50))
        seq = int(rng.randint(1, 50))
        counters = {
            nm: int(rng.randint(0, 1000))
            for nm in rng.choice(names, size=rng.randint(1, len(names) + 1),
                                 replace=False)
        }
        gauges = {
            nm: [float(rng.randint(0, 50)), int(rng.randint(1, 50)),
                 float(rng.randint(0, 100))]
            for nm in rng.choice(gnames, size=rng.randint(0, len(gnames) + 1),
                                 replace=False)
        }
        hists = {
            nm: [float(rng.randint(0, 50)), int(rng.randint(1, 50)),
                 {"count": int(rng.randint(1, 20)),
                  "sum": float(rng.randint(0, 100)),
                  "min": 0.5, "max": 8.0,
                  "buckets": {str(int(e)): int(rng.randint(1, 9))
                              for e in rng.choice([0, 1, 2, 3],
                                                  size=rng.randint(1, 4),
                                                  replace=False)}}]
            for nm in rng.choice(hnames, size=rng.randint(0, len(hnames) + 1),
                                 replace=False)
        }
        events = [
            {"seq": int(s), "ts": float(s), "wall": float(s),
             "kind": "sync.phase", "fields": {"phase": "digest"}}
            for s in sorted(rng.choice(200, size=rng.randint(0, 6),
                                       replace=False))
        ]
        slices[str(node)] = {
            "ts": ts, "seq": seq,
            "counters": counters, "gauges": gauges, "histograms": hists,
            "convergence": [ts, seq, {"peer": {"divergence":
                                               int(rng.randint(0, 9))}}],
            "events_dropped": int(rng.randint(0, 9)),
            "events": events,
        }
    return obs_fleet.FleetSnapshot(slices)


def test_merge_is_commutative_associative_idempotent():
    """The ACI contract, property-swept with a seeded generator (this
    suite must run where hypothesis is absent): for random snapshots
    a, b, c — a∨b == b∨a, (a∨b)∨c == a∨(b∨c), a∨a == a, and
    re-delivering a constituent into the merge is a no-op (the
    duplicated-snapshot-delivery property the gossip transport needs)."""
    rng = np.random.RandomState(7)
    for _ in range(80):
        a, b, c = (_random_snapshot(rng) for _ in range(3))
        ab = a.merge(b)
        assert ab == b.merge(a), "merge is not commutative"
        assert ab.merge(c) == a.merge(b.merge(c)), "merge is not associative"
        assert a.merge(a) == a, "merge is not idempotent"
        # re-delivery of a constituent (a's own snapshot echoed back
        # by a peer, an ARQ retransmit) changes nothing
        assert ab.merge(a) == ab, "re-delivered snapshot was not a no-op"
        assert ab.merge(b) == ab, "re-delivered snapshot was not a no-op"


def test_fleet_counter_is_sum_of_per_node_g_counters():
    """Per-kind semantics: counters per-node max (G-Counter), summed
    fleet-wide; gauges LWW by capture stamp; histograms bucket-wise."""
    a = obs_fleet.FleetSnapshot({
        "n0": {"ts": 1.0, "seq": 1,
               "counters": {"sync.sessions": 10},
               "gauges": {"cluster.peers.alive": [1.0, 1, 3.0]},
               "histograms": {"cluster.round": [1.0, 1, {
                   "count": 2, "sum": 3.0, "min": 1.0, "max": 2.0,
                   "buckets": {"1": 2}}]},
               "convergence": [1.0, 1, {}], "events_dropped": 0,
               "events": []},
    })
    # a NEWER capture of n0 (counter grew, gauge moved) + a second node
    b = obs_fleet.FleetSnapshot({
        "n0": {"ts": 2.0, "seq": 2,
               "counters": {"sync.sessions": 15},
               "gauges": {"cluster.peers.alive": [2.0, 2, 4.0]},
               "histograms": {"cluster.round": [2.0, 2, {
                   "count": 5, "sum": 9.0, "min": 1.0, "max": 4.0,
                   "buckets": {"1": 2, "2": 3}}]},
               "convergence": [2.0, 2, {}], "events_dropped": 1,
               "events": []},
        "n1": {"ts": 1.5, "seq": 1,
               "counters": {"sync.sessions": 7},
               "gauges": {"cluster.peers.alive": [1.5, 1, 2.0]},
               "histograms": {"cluster.round": [1.5, 1, {
                   "count": 1, "sum": 0.5, "min": 0.5, "max": 0.5,
                   "buckets": {"0": 1}}]},
               "convergence": [1.5, 1, {}], "events_dropped": 0,
               "events": []},
    })
    merged = a.merge(b)
    # counter: n0 contributes its LATEST value once (15, not 10+15),
    # fleet = sum over nodes — and a re-delivery of `a` changes nothing
    assert merged.fleet_counters()["sync.sessions"] == 15 + 7
    assert merged.merge(a).fleet_counters()["sync.sessions"] == 22
    assert merged.counters_by_node("sync.sessions") == {"n0": 15, "n1": 7}
    # gauge: LWW by capture stamp fleet-wide (n0's ts=2.0 capture wins)
    assert merged.fleet_gauges()["cluster.peers.alive"] == 4.0
    # histogram: per-node LWW (n0's newer capture), bucket-wise summed
    # across nodes
    h = merged.fleet_histograms()["cluster.round"]
    assert h["count"] == 5 + 1 and h["buckets"] == {"1": 2, "2": 3, "0": 1}
    assert h["min"] == 0.5 and h["max"] == 4.0


def _synth_observatory(node: str) -> obs_fleet.FleetObservatory:
    """One node's observatory shaped like a live registry: counters,
    gauges, a histogram, convergence state and an event tail."""
    reg = obs_metrics.MetricsRegistry()
    for i in range(40):
        reg.counter_inc(f"wire.sync.leg{i}.bytes", i * 7 + 1)
    for i in range(10):
        reg.gauge_set(f"sync.peer.p{i}.divergence", float(i))
    for i in range(64):
        reg.observe("sync.digest_exchange", 0.0005 * (i + 1))
    trk = obs_convergence.ConvergenceTracker(registry=reg)
    trk.observe_session(node, converged=True, rounds=1,
                        payload_bytes=1024, full_state_bytes=65536)
    rec = obs_events.FlightRecorder(capacity=256)
    for i in range(128):
        rec.record("sync.phase", session=f"s{i:04d}", phase="digest",
                   trace=f"t{i:04d}")
    return obs_fleet.FleetObservatory(node, registry=reg, tracker=trk,
                                      recorder=rec)


@pytest.mark.parametrize("n_nodes", [2, 8, 32])
def test_encoded_frames_merge_into_one_slice_per_node(n_nodes):
    """Every node's encoded snapshot merged into one sink: one slice per
    node, each counter summed over the nodes once, and the sink's own
    frame re-delivered changes nothing."""
    observatories = [_synth_observatory(f"b{i}") for i in range(n_nodes)]
    frames = [o.encode() for o in observatories]
    sink = observatories[0]
    for f in frames:
        sink.merge_frame(f)
    merged = sink.merged(refresh=False)
    assert sorted(merged.slices) == sorted(f"b{i}" for i in range(n_nodes))
    fleet = merged.fleet_counters()
    assert fleet["wire.sync.leg1.bytes"] == 8 * n_nodes
    assert fleet["wire.sync.leg39.bytes"] == 274 * n_nodes
    sink.merge_frame(frames[-1])
    assert sink.merged(refresh=False) == merged


# ---- the frame codec -------------------------------------------------------


def test_snapshot_frame_roundtrip():
    reg = obs_metrics.MetricsRegistry()
    reg.counter_inc("sync.sessions", 3)
    reg.gauge_set("cluster.peers.alive", 2.0)
    reg.observe("cluster.round", 0.25)
    snap = obs_fleet.capture_slice(
        "node-a", registry=reg,
        tracker=obs_convergence.ConvergenceTracker(registry=reg),
        recorder=obs_events.FlightRecorder(capacity=8),
    )
    frame = obs_fleet.encode_snapshot(snap)
    assert obs_fleet.decode_snapshot(frame) == snap


@pytest.mark.parametrize(
    "mutate", ["truncate", "version", "type", "crc", "payload"]
)
def test_snapshot_frame_rejections_are_loud(mutate):
    """Every malformed fleet frame is a SyncProtocolError plus a
    reason-tagged rejection counter — never a misparse, never a crash
    in the JSON layer."""
    snap = obs_fleet.FleetSnapshot(
        {"n0": {"ts": 1.0, "seq": 1, "counters": {"sync.sessions": 1},
                "gauges": {}, "histograms": {},
                "convergence": [1.0, 1, {}], "events_dropped": 0,
                "events": []}}
    )
    frame = bytearray(obs_fleet.encode_snapshot(snap))
    if mutate == "truncate":
        frame = frame[:7]
    elif mutate == "version":
        frame[0] ^= 0x01
    elif mutate == "type":
        frame[1] = 0x7F
    elif mutate == "crc":
        frame[-1] ^= 0x40
    elif mutate == "payload":
        # valid envelope around non-object JSON
        import struct
        import zlib

        payload = b"[1, 2, 3]"
        frame = bytearray(struct.pack(
            "<BBIQ", obs_fleet.FLEET_PROTOCOL_VERSION,
            obs_fleet.FRAME_FLEET_SNAPSHOT, zlib.crc32(payload),
            len(payload)) + payload)
    before = tracing.counters()
    with pytest.raises(SyncProtocolError):
        obs_fleet.decode_snapshot(bytes(frame))
    deltas = tracing.counters_since(before)
    assert any(k.startswith("obs.fleet.frames.rejected.") for k in deltas), (
        f"rejection left no reason counter: {deltas}"
    )


def test_mixed_versions_fail_loudly():
    snap = obs_fleet.FleetSnapshot({})
    frame = bytearray(obs_fleet.encode_snapshot(snap))
    frame[0] = obs_fleet.FLEET_PROTOCOL_VERSION + 1
    with pytest.raises(SyncProtocolError, match="version mismatch"):
        obs_fleet.decode_snapshot(bytes(frame))


def test_bad_frame_does_not_touch_observatory_state():
    obs = obs_fleet.FleetObservatory(
        "iso", registry=obs_metrics.MetricsRegistry(),
        tracker=obs_convergence.ConvergenceTracker(),
        recorder=obs_events.FlightRecorder(capacity=8),
    )
    obs.capture()
    before = obs.merged(refresh=False)
    with pytest.raises(SyncProtocolError):
        obs.merge_frame(b"garbage")
    assert obs.merged(refresh=False) == before


# ---- trace propagation -----------------------------------------------------


def test_sync_session_peers_share_one_trace_id():
    """THE trace acceptance pin: one session's two halves mint distinct
    session IDs but adopt the SAME hello-negotiated trace ID, and every
    flight-recorder event either peer wrote for that session carries
    it."""
    uni = _uni()
    a = OrswotBatch.from_scalar(_orswot_fleet(24, seed=3, actor=1,
                                              extra_on=[1]), uni)
    b = OrswotBatch.from_scalar(_orswot_fleet(24, seed=3, actor=2,
                                              extra_on=[2]), uni)
    sa, sb = SyncSession(a, uni, peer="b"), SyncSession(b, uni, peer="a")
    ra, rb = sync_pair(sa, sb)
    assert ra.converged and rb.converged
    assert ra.trace_id is not None
    assert ra.trace_id == rb.trace_id == sa.trace_id == sb.trace_id
    # the shared ID is one of the two proposals (the lexicographic min)
    assert ra.trace_id == min(sa.session_id, sb.session_id)
    for session in (sa, sb):
        evs = obs_events.recorder().snapshot(session=session.session_id)
        assert evs, f"no events for {session.session_id}"
        stamped = [e for e in evs if "fields" in e]
        assert stamped and all(
            e["fields"].get("trace") == ra.trace_id for e in stamped
        ), f"events missing the shared trace: {stamped}"


def test_stitch_trace_interleaves_both_peers():
    uni = _uni()
    a = OrswotBatch.from_scalar(_orswot_fleet(16, seed=5, actor=1,
                                              extra_on=[0]), uni)
    b = OrswotBatch.from_scalar(_orswot_fleet(16, seed=5, actor=2), uni)
    oa = obs_fleet.FleetObservatory("peer-a")
    ob = obs_fleet.FleetObservatory("peer-b")
    sa = SyncSession(a, uni, peer="peer-b", observatory=oa)
    sb = SyncSession(b, uni, peer="peer-a", observatory=ob)
    ra, _rb = sync_pair(sa, sb)
    merged = oa.merged()
    timeline = obs_fleet.stitch_trace(merged, ra.trace_id)
    assert timeline, "stitcher found no events for the trace"
    sessions = {e.get("session") for e in timeline if "session" in e}
    # both halves of the session appear in one ordered timeline
    assert {sa.session_id, sb.session_id} <= sessions
    walls = [e["wall_ts"] for e in timeline]
    assert walls == sorted(walls)
    # duration math uses the monotonic stamp (skew-immune), which every
    # event carries NEXT TO the wall stamp — and per-process mono
    # deltas are non-negative in recording order
    assert all("mono_ts" in e for e in timeline)


# ---- the 5-node lossy-gossip acceptance run --------------------------------


def _gossip_fleet_with_observatories(n_nodes, n_objects, *, loss):
    uni = _uni(num_actors=max(8, n_nodes + 2))
    nodes = []
    for i in range(n_nodes):
        extra = [(3 * i + k) % n_objects for k in range(3)]
        batch = OrswotBatch.from_scalar(
            _orswot_fleet(n_objects, seed=41, actor=i + 1, extra_on=extra),
            uni)
        nodes.append(ClusterNode(
            f"n{i}", batch, uni, busy_timeout_s=5.0,
            observatory=obs_fleet.FleetObservatory(f"n{i}"),
        ))

    seeds = itertools.count(5000)

    def make_dialer(i):
        def dial(peer):
            j = int(peer.peer_id[1:])
            s = next(seeds)
            ta, tb = queue_pair(default_timeout=10.0)
            fa = FaultyTransport(ta, FaultPlan(seed=s, drop=loss),
                                 name=f"n{i}->n{j}")
            fb = FaultyTransport(tb, FaultPlan(seed=s + 1, drop=loss),
                                 name=f"n{j}->n{i}")
            ra = ResilientTransport(fa, FAST, name=f"n{i}->n{j}", seed=s + 2)
            rb = ResilientTransport(fb, FAST, name=f"n{j}->n{i}", seed=s + 3)

            def serve():
                try:
                    nodes[j].accept(rb, peer_id=f"n{i}")
                except Exception:
                    pass
                finally:
                    rb.close()

            threading.Thread(target=serve, daemon=True).start()
            return ra
        return dial

    scheds = []
    for i in range(n_nodes):
        m = Membership(suspect_after=2, dead_after=5)
        for j in range(n_nodes):
            if j != i:
                m.add(f"n{j}")
        scheds.append(GossipScheduler(
            nodes[i], m, make_dialer(i), fanout=2,
            session_timeout_s=60.0, seed=i,
        ))
    return nodes, scheds


def test_acceptance_five_node_fleet_snapshot_under_loss():
    """ISSUE 6 acceptance: 5 nodes gossiping under 20% frame loss on
    every link (duplicates/retransmits included) converge AND any one
    node's merged fleet snapshot (a) spans all 5 nodes — slices spread
    on the gossip itself, no scraper — (b) holds fleet counters equal
    to the sum of the per-node counters despite every snapshot having
    been delivered many times, and (c) stitches one sync session's
    cross-peer timeline from the shared trace ID."""
    nodes, scheds = _gossip_fleet_with_observatories(5, 32, loss=0.20)
    deadline = time.monotonic() + 240.0
    converged = False
    for _ in range(16):
        for sched in scheds:
            sched.run_round()
        digests = [n.digest() for n in nodes]
        if all(np.array_equal(digests[0], d) for d in digests[1:]):
            converged = True
            break
        assert time.monotonic() < deadline, "fleet failed to converge"
    assert converged, "5-node fleet did not converge under 20% loss"

    # every node's slice reached node 0 on the gossip piggyback alone
    merged = nodes[0].observatory.merged()
    assert merged.nodes() == ["n0", "n1", "n2", "n3", "n4"]

    # G-Counter identity: every fleet counter is the sum of per-node
    # values — duplicated snapshot delivery (ARQ retransmits, gossip
    # echoes, this node's own slice bounced back) must not double-count
    fleet_counters = merged.fleet_counters()
    assert fleet_counters, "merged snapshot carries no counters"
    for name, total in fleet_counters.items():
        per_node = merged.counters_by_node(name)
        assert total == sum(per_node.values()), (
            f"fleet counter {name}: {total} != sum {per_node}"
        )
    # and the fleet saw real gossip traffic
    assert fleet_counters.get("sync.sessions", 0) > 0
    assert fleet_counters.get("cluster.rounds", 0) > 0

    # the last converged session's trace stitches BOTH peers' events
    trace = next(
        (n.last_report.trace_id for n in reversed(nodes)
         if n.last_report is not None), None,
    )
    assert trace, "no converged session left a trace ID"
    evs = [e for e in obs_events.recorder().snapshot()
           if e.get("fields", {}).get("trace") == trace]
    sessions = {e["session"] for e in evs if "session" in e}
    assert len(sessions) == 2, (
        f"expected both halves of the session under trace {trace}, "
        f"got sessions {sessions}"
    )

    # round-health gauges landed (the /fleet "is the fleet converging"
    # surface): attempted peers recorded, divergence settled to 0
    gauges = obs_metrics.registry().snapshot()["gauges"]
    assert "cluster.gossip.attempted" in gauges
    assert gauges.get("cluster.gossip.fleet_divergence_max") == 0.0
    assert gauges.get("cluster.gossip.eta_rounds") == 0.0


def test_fleet_endpoint_concurrent_with_gossip_round():
    """Thread-safety: ``/fleet`` scraped (Prom text + JSON + trace
    query) while gossip rounds are actively merging snapshots — every
    response parses, no 500s, no torn snapshots."""
    nodes, scheds = _gossip_fleet_with_observatories(3, 16, loss=0.0)
    srv = obs_export.start_metrics_server(
        port=0, observatory=nodes[0].observatory
    )
    errors: list = []
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/fleet", timeout=10
                ) as r:
                    assert r.status == 200
                    text = r.read().decode()
                    assert "crdt_tpu_fleet_nodes" in text
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/fleet?format=json",
                    timeout=10,
                ) as r:
                    doc = json.loads(r.read().decode())
                    assert set(doc["slices"]) == set(doc["fleet"] and
                                                     doc["nodes"])
                    # every slice internally consistent under the scrape
                    for name, total in doc["fleet"]["counters"].items():
                        by_node = sum(
                            sl["counters"].get(name, 0)
                            for sl in doc["slices"].values()
                        )
                        assert total == by_node
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)
                return

    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    try:
        for _ in range(4):
            for sched in scheds:
                sched.run_round()
    finally:
        stop.set()
        t.join(timeout=30)
        srv.stop()
    assert not errors, f"concurrent /fleet scrape failed: {errors[0]!r}"


def test_fleet_endpoint_trace_query():
    uni = _uni()
    a = OrswotBatch.from_scalar(_orswot_fleet(12, seed=9, actor=1,
                                              extra_on=[1]), uni)
    b = OrswotBatch.from_scalar(_orswot_fleet(12, seed=9, actor=2), uni)
    oa = obs_fleet.FleetObservatory("qa")
    ob = obs_fleet.FleetObservatory("qb")
    sa = SyncSession(a, uni, peer="qb", observatory=oa)
    sb = SyncSession(b, uni, peer="qa", observatory=ob)
    ra, _ = sync_pair(sa, sb)
    srv = obs_export.start_metrics_server(port=0, observatory=oa)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/fleet?trace={ra.trace_id}",
            timeout=10,
        ) as r:
            doc = json.loads(r.read().decode())
    finally:
        srv.stop()
    assert doc["trace"] == ra.trace_id
    assert doc["timeline"], "trace query returned an empty timeline"
    assert all(
        e.get("fields", {}).get("trace") == ra.trace_id
        or e.get("session") == ra.trace_id
        for e in doc["timeline"]
    )


# ---- the dropped-count gauge (satellite) -----------------------------------


def test_ring_overflow_surfaces_as_dropped_gauge():
    """Overflow the (global) flight-recorder ring, then scrape: the
    ``crdt_tpu_obs_events_dropped`` gauge must report the eviction
    count — refreshed at scrape time, since ``dropped`` is a live
    property, not a write-through metric."""
    rec = obs_events.recorder()
    base_dropped = rec.dropped
    for i in range(rec.capacity + 64):
        rec.record("obs.overflow.probe", n=i)
    assert rec.dropped >= base_dropped + 64
    text = obs_export.prometheus_text()
    line = next(
        (ln for ln in text.splitlines()
         if ln.startswith("crdt_tpu_obs_events_dropped ")), None,
    )
    assert line is not None, "dropped gauge missing from /metrics"
    assert float(line.split()[1]) >= base_dropped + 64
    # and the name is manifest-documented (the namespace satellite)
    assert obs_namespace.match("obs.events.dropped", "gauge") is not None


def test_private_registry_scrape_leaves_dropped_gauge_alone():
    """The PR 3 review discipline: scraping a PRIVATE registry must not
    write global recorder state into it (or touch the global one)."""
    reg = obs_metrics.MetricsRegistry()
    reg.counter_inc("sync.sessions")
    text = obs_export.prometheus_text(reg)
    assert "crdt_tpu_obs_events_dropped" not in text


# ---- namespace coverage ----------------------------------------------------


def test_new_names_are_manifest_documented():
    for name, kind in [
        ("obs.fleet.merges", "counter"),
        ("obs.fleet.frames.decoded", "counter"),
        ("obs.fleet.frames.rejected.crc_mismatch", "counter"),
        ("obs.fleet.nodes", "gauge"),
        ("obs.fleet.exchange", "histogram"),
        ("obs.fleet.snapshot_bytes", "histogram"),
        ("obs.events.dropped", "gauge"),
        ("cluster.gossip.attempted", "gauge"),
        ("cluster.gossip.fleet_divergence_max", "gauge"),
        ("cluster.gossip.eta_rounds", "gauge"),
        ("wire.sync.hello.bytes", "counter"),
        ("wire.sync.fleet.bytes", "counter"),
        ("sync.frame.hello.decoded", "counter"),
    ]:
        assert obs_namespace.match(name, kind) is not None, (
            f"{name} ({kind}) is not manifest-documented"
        )


# ---- the collective all-gather path ----------------------------------------


def test_allgather_fleet_snapshots_single_process():
    """The mesh path (scraper-free aggregation for pjit deployments):
    on a single-process harness it degrades to a local capture+merge —
    the multi-process fan-in is the same merge over process_allgather
    frames."""
    from crdt_tpu.parallel.collective import allgather_fleet_snapshots

    obs = obs_fleet.FleetObservatory(
        "mesh-0", registry=obs_metrics.MetricsRegistry(),
        tracker=obs_convergence.ConvergenceTracker(),
        recorder=obs_events.FlightRecorder(capacity=8),
    )
    snap = allgather_fleet_snapshots(obs)
    assert "mesh-0" in snap.nodes()
    # and a frame from another "process" folds in via the same codec
    other = obs_fleet.FleetObservatory(
        "mesh-1", registry=obs_metrics.MetricsRegistry(),
        tracker=obs_convergence.ConvergenceTracker(),
        recorder=obs_events.FlightRecorder(capacity=8),
    )
    obs.merge_frame(other.encode())
    assert obs.merged(refresh=False).nodes() == ["mesh-0", "mesh-1"]
