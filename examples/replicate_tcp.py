"""Two-process anti-entropy over real TCP — digest-driven delta sync.

The reference deliberately ships no transport: "serialize state or op,
transport however you like, merge/apply on the other side"
(`/root/reference/src/lib.rs:62-83`; the ctx protocol docs even sketch
the ship-to-client pattern, `/root/reference/src/ctx.rs:5-9`).  This
example IS that missing piece: two OS processes, each owning a replica
of the same object partition, reconcile over a localhost TCP socket
through :class:`crdt_tpu.sync.SyncSession` — digest vectors first, then
only the diverged rows' wire blobs, so bytes-on-wire is O(divergence)
instead of O(total state).

Per peer:

1. build N ``Orswot`` objects from a SHARED op history (same seed), then
   apply divergent local ops under its own actor to a small fraction of
   objects — the realistic anti-entropy shape: replicas agree on almost
   everything;
2. pack the fleet into dense planes (``OrswotBatch.from_scalar``);
3. run a ``SyncSession`` over the socket: every frame is length-prefixed
   and carries a 1-byte protocol version, so a mixed-version peer fails
   loudly (`SyncProtocolError`) instead of misparsing;
4. print the per-phase wire accounting (digest vs delta bytes) and the
   convergence verdict from the session's digest verify.

``--full-state`` keeps the legacy behavior — full wire blobs both ways
(still version-tagged frames, still digest-verified) — as the A/B
comparator: at the default 5% divergence the delta session ships a
fraction of the full-state bytes.

Run it:

    python examples/replicate_tcp.py                    # delta sync demo
    python examples/replicate_tcp.py --full-state       # legacy full state
    python examples/replicate_tcp.py --objects 1000 --divergence 0.01
    python examples/replicate_tcp.py --gossip 5         # N-peer fleet mode
    python examples/replicate_tcp.py --window 16        # windowed ARQ session
    python examples/replicate_tcp.py --gossip 3 --window 0   # stop-and-wait

``--window N`` runs the session over the hardened windowed transport
(``crdt_tpu.cluster.ResilientTransport``): seq-numbered CRC-guarded
envelopes with up to N DATA frames in flight, selective acks, and (at
N >= 2 on both peers) the v4 streaming delta/descent protocol.  ``0``
pins a 1-frame window — stop-and-wait — as the A/B control; at
convergence the peers print frames-in-flight high-water, retransmit
counts and the descent round-trip count, and ``--gossip`` mode prints a
fleet digest fingerprint so a windowed fleet can be asserted
byte-identical to a stop-and-wait control fleet.

``--gossip N`` runs the cluster runtime instead of a single session: N
replicas (in-process nodes over real loopback TCP sockets), each with a
listener, a peer roster (``crdt_tpu.cluster.Membership``) and a
staleness-driven ``GossipScheduler``, reconcile through hardened
``ResilientTransport`` links until every node's digest vector is
byte-identical (docs/GUIDE.md "Cluster runtime").

``--metrics-port N`` starts the live observability exporter
(:mod:`crdt_tpu.obs`) in the peer process: ``GET /metrics`` is the
Prometheus view of the ``wire.sync.*`` counters and phase latency
histograms, ``GET /events`` is the flight recorder (filter to this
session with ``?session=<id>`` — the peer prints its session ID), and
``GET /healthz`` is the liveness probe.  ``--linger S`` keeps the
exporter up for up to S seconds after the sync finishes (returning as
soon as both ``/metrics`` and ``/events`` have been scraped after the
sync finished — scrapes that raced the sync don't count), so a
scraper — docs/GUIDE.md's ``curl`` walkthrough, or the automated test — can
read the final state before the process exits.

(`--platform cpu` forces the CPU backend, e.g. when no TPU is
reachable; the kernels are platform-agnostic.)
"""

from __future__ import annotations

import argparse
import os
import socket
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _send_frame(sock: socket.socket, frame: bytes) -> None:
    sock.sendall(struct.pack("<I", len(frame)))
    sock.sendall(frame)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> bytes:
    (ln,) = struct.unpack("<I", _recv_exact(sock, 4))
    return _recv_exact(sock, ln)


def _build_fleet(n_objects: int, actor: int, divergence: float, seed: int):
    """N scalar Orswots: a shared base history (seed-deterministic,
    actor 0) + this peer's own ops on a ``divergence`` fraction of
    objects.  Both peers call this with the SAME ``seed`` and different
    ``actor``, so they agree everywhere except the divergent rows."""
    import numpy as np

    from crdt_tpu import Orswot

    rng = np.random.RandomState(seed)
    fleet = []
    for i in range(n_objects):
        o = Orswot()
        for _ in range(int(rng.randint(1, 5))):
            member = int(rng.randint(0, 64))
            o.apply(o.add(member, o.value().derive_add_ctx(0)))
        if i % 7 == 0:  # a causal remove on some objects
            read = o.value()
            if read.val:
                m = sorted(read.val)[0]
                o.apply(o.remove(m, o.contains(m).derive_rm_ctx()))
        fleet.append(o)
    # divergent tail: per-peer ops the OTHER replica has not seen (the
    # rng is past the shared prefix here, so draws differ per peer only
    # through the actor-dependent op content below)
    n_div = int(n_objects * divergence)
    div_rng = np.random.RandomState(seed + 1)
    targets = div_rng.choice(n_objects, size=n_div, replace=False)
    for i in targets:
        o = fleet[int(i)]
        member = int(100 + actor * 10 + int(i) % 7)
        o.apply(o.add(member, o.value().derive_add_ctx(actor)))
    return fleet


def peer(role: str, port: int, n_objects: int, platform: str | None,
         full_state: bool = False, divergence: float = 0.05,
         metrics_port: int | None = None, linger_s: float = 0.0,
         window: int | None = None) -> str:
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)

    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.sync import SyncSession
    from crdt_tpu.utils.interning import Universe

    metrics_server = None
    if metrics_port is not None:
        from crdt_tpu.obs import export as obs_export
        from crdt_tpu.utils import tracing

        # enable spans so sync phase latencies land in the histograms
        # the exporter serves (counters/events are always-on anyway)
        tracing.enable(True)
        metrics_server = obs_export.start_metrics_server(port=metrics_port)
        print(
            f"{role}: metrics exporter on "
            f"http://127.0.0.1:{metrics_server.port}/metrics",
            flush=True,
        )

    # identity universe: int actors/members -> the native C++ bulk codec
    # parses/serializes the blobs with zero host-side interning state
    uni = Universe.identity(CrdtConfig(num_actors=8, member_capacity=32,
                                       deferred_capacity=8, counter_bits=32))
    actor = 1 if role == "server" else 2
    mine = OrswotBatch.from_scalar(
        _build_fleet(n_objects, actor, divergence, seed=42), uni
    )

    if role == "server":
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", port))
        srv.listen(1)
        srv.settimeout(120)  # a peer that never comes must not orphan us
        sock, _ = srv.accept()
        srv.close()
    else:
        # the peers race at startup: retry until the server's bind lands
        import time

        deadline = time.monotonic() + 120
        while True:
            try:
                sock = socket.create_connection(("127.0.0.1", port), timeout=10)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)

    other = "client" if role == "server" else "server"
    # full-state reference size (one serialization pass, outside the
    # timed sync): feeds the per-peer delta_ratio gauge the exporter
    # serves — what this sync cost vs shipping everything
    full_ref = sum(len(b) for b in mine.to_wire(uni))
    session = SyncSession(mine, uni, full_state=full_state, peer=other,
                          full_state_bytes=full_ref)
    transport = None
    with sock:
        if window is None:
            # legacy raw length-prefixed framing, no ARQ envelope
            report = session.sync(
                lambda frame: _send_frame(sock, frame),
                lambda: _recv_frame(sock),
            )
        else:
            # the hardened windowed transport: frames ride seq-numbered
            # CRC-guarded envelopes with up to `window` in flight
            # (window 0 = stop-and-wait = a 1-frame window); both peers
            # must run with --window for the envelopes to parse
            import dataclasses

            from crdt_tpu.cluster import (
                ResilientTransport, RetryPolicy, TcpTransport,
            )

            policy = dataclasses.replace(RetryPolicy(),
                                         window=max(1, window))
            transport = ResilientTransport(
                TcpTransport(sock, default_timeout=60.0), policy,
                name=role,
            )
            try:
                report = session.sync(transport)
            finally:
                transport.close()  # drains the window of stragglers

    status = "CONVERGED" if report.converged else "DIVERGED"
    mode = "full-state" if full_state else "delta"
    print(
        f"{role}: {n_objects} objects  mode={mode}  "
        f"session={session.session_id}  trace={report.trace_id}  "
        f"diverged={report.diverged}  delta_objects={report.delta_objects_sent}  "
        f"sent: digest={report.digest_bytes_sent}B delta="
        f"{report.delta_bytes_sent}B full={report.full_bytes_sent}B  {status}",
        flush=True,
    )
    if transport is not None:
        print(
            f"{role}: transport window={report.window} "
            f"streaming={report.streaming}  "
            f"inflight_hw={transport.window_hw}  "
            f"retransmits={transport.retransmits}  "
            f"sacks={transport.sacks_sent}  "
            f"delta_chunks={report.delta_chunks_sent}  "
            f"descent_rtts={report.tree_round_trips}",
            flush=True,
        )
    if metrics_server is not None and linger_s > 0:
        # hold the exporter up until someone has read the FINAL state
        # (or the linger budget runs out) — a sync finishing in
        # milliseconds must not close the scrape window with it, and a
        # scrape that raced the sync itself read a half-told story, so
        # only scrapes arriving from here on count
        import time

        baseline = metrics_server.scrape_counts()
        deadline = time.monotonic() + linger_s
        while time.monotonic() < deadline:
            if metrics_server.scraped("/metrics", "/events",
                                      since=baseline):
                break
            time.sleep(0.05)
    if metrics_server is not None:
        metrics_server.stop()
    return status


def mesh_demo(shards: int, n_objects: int, platform: str | None,
              divergence: float = 0.05, zipf_s: float = 1.1) -> int:
    """``--mesh S``: one logical replica sharded over an S-device
    object mesh (``crdt_tpu.mesh``), demonstrated on forced host
    devices.  Drives a Zipf-skewed write history through the heat
    observatory, lets the placement planner pick the subtree granule
    (the ``plan=mesh:S`` score), runs the whole anti-entropy round as
    ONE pjit'd step, and prints per-shard planner-predicted vs
    measured load plus the digest parity against the unsharded
    control."""
    # the mesh ladder needs 8 visible devices; force them BEFORE the
    # first jax import (a no-op on a real multi-device backend)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    if platform:
        os.environ["JAX_PLATFORMS"] = platform

    import numpy as np

    from crdt_tpu import mesh as mesh_mod
    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.obs import heat as heat_mod
    from crdt_tpu.obs import stability as stability_mod
    from crdt_tpu.sync import digest as digest_mod
    from crdt_tpu.utils.interning import Universe

    uni = Universe.identity(CrdtConfig(num_actors=8, member_capacity=32,
                                       deferred_capacity=8,
                                       counter_bits=32))
    a = OrswotBatch.from_scalar(
        _build_fleet(n_objects, actor=1, divergence=divergence, seed=17),
        uni)
    b = OrswotBatch.from_scalar(
        _build_fleet(n_objects, actor=2, divergence=divergence, seed=17),
        uni)

    # a Zipf-skewed write history feeds the heat observatory — the
    # planner prices shard boundaries against THIS, not a uniform guess
    _subtrees, span = stability_mod.subtree_layout(n_objects)
    trk = heat_mod.HeatTracker()
    rng = np.random.RandomState(7)
    ranks = np.arange(1, n_objects + 1, dtype=np.float64)
    probs = ranks ** -max(zipf_s, 1e-9)
    probs /= probs.sum()
    writes = rng.choice(n_objects, size=4096, p=probs)
    trk.record_writes(writes, n_objects)
    heat = trk.heat_vector()

    layout = mesh_mod.choose_layout(n_objects, shards, heat=heat,
                                    span=span)
    predicted = heat_mod.score_plan(f"mesh:{shards}", heat, n=n_objects,
                                    span=span, granule=layout.granule)
    print(f"mesh: {shards} shards over {n_objects} objects, planner "
          f"granule {layout.granule} (predicted imbalance "
          f"{predicted['imbalance']})")

    sa = mesh_mod.ShardedBatch.shard(a, uni, shards=shards, heat=heat,
                                     span=span)
    sb = mesh_mod.ShardedBatch.shard(b, uni, shards=shards, heat=heat,
                                     span=span)
    res = mesh_mod.anti_entropy_step(sa, sb)

    # unsharded control: same merge + digest, no mesh
    control = np.asarray(digest_mod.digest_of(a.merge(b), uni),
                         dtype=np.uint64)
    parity = bool(np.array_equal(res.digests, control))

    # measured load: the heat vector AFTER attributing the rows that
    # actually churned this round (the diverged digests) as repair heat
    pre = digest_mod.digest_of(a, uni)
    post = digest_mod.digest_of(b, uni)
    churned = np.nonzero(np.asarray(pre) != np.asarray(post))[0]
    if churned.size:
        trk.record_repair(churned, n_objects)
    measured = mesh_mod.shard_loads(layout, trk.heat_vector(), span)
    predicted_loads = predicted["loads"]
    print(f"{'shard':>5} {'objects':>8} {'predicted':>10} {'measured':>10}")
    for s, (lo, hi) in enumerate(layout.ranges()):
        print(f"{s:>5} {hi - lo:>8} {predicted_loads[s]:>10.1f} "
              f"{measured[s]:>10.1f}")
    sa.publish_gauges(heat_vector=trk.heat_vector(), span=span)

    print(f"digest parity vs unsharded control: "
          f"{'BYTE-IDENTICAL' if parity else 'DIVERGED'} "
          f"({res.digests.size} lanes, {res.live_members} live members)")
    return 0 if parity else 1


def gossip_demo(n_peers: int, n_objects: int, platform: str | None,
                divergence: float, max_sweeps: int = 20,
                fleet_port: int | None = None, ops_rate: int = 0,
                ops_sweeps: int = 3, reads_rate: int = 0,
                gc_enabled: bool = False,
                gc_interval: int = 1, gc_hysteresis: float = 0.5,
                digest_tree: bool = False, zipf_s: float = 0.0,
                burst_len: int = 1, durable_dir: str | None = None,
                kill_sweep: int = 2, window: int | None = None,
                heat: bool = False) -> int:
    """N in-process replicas over real loopback TCP, reconciled by the
    cluster runtime (``crdt_tpu/cluster``): each node owns a listener
    (accepted sessions run through the same hardened transport stack),
    a peer roster, and a staleness-driven ``GossipScheduler``.  The
    demo drives deterministic scheduler sweeps (round-robin
    ``run_round`` across nodes) until every node's digest vector is
    byte-identical — the same convergence oracle the sessions
    themselves use.

    Every node carries a ``FleetObservatory``, so telemetry snapshots
    piggyback on the gossip sessions; at convergence the demo prints
    ONE merged fleet snapshot (fleet counters = per-node sums) instead
    of N disjoint per-node ``/metrics`` views, plus the shared trace ID
    of the final session (both halves carry it — docs/GUIDE.md "Fleet
    observability" walks the curl side).  ``--fleet-port`` additionally
    serves the live merged view on ``GET /fleet``.

    ``--ops R`` turns the demo into a LIVE-WRITE run: for the first few
    sweeps, R random user writes per sweep land on random nodes through
    the op-based front-end (``ClusterNode.submit_ops`` — batched
    ``derive_add_ctx`` dots, :mod:`crdt_tpu.oplog`) WHILE gossip is
    reconciling, so anti-entropy and ingest genuinely overlap; once the
    writes stop, the fleet must still converge to byte-identical digest
    vectors — the mixed op+state acceptance shape (docs/GUIDE.md "Op-based
    replication").

    ``--reads R`` adds the READ half of the client protocol
    (:mod:`crdt_tpu.serve`): R live reads per sweep land on random
    nodes WHILE gossip reconciles — each injection writes a probe
    member through ``submit_writes``, takes the ack floor
    (``write_vv``), and reads it straight back under read-your-writes
    (a violation is an assertion, not a statistic), plus monotonic
    reads whose returned tokens must never regress per node and
    frontier-stable reads tallying per-row stability against the PR 15
    frontier.  At quiescence a final frontier-mode read on every node
    must come back all-rows-stable (docs/GUIDE.md "Read front-end").

    ``--durable DIR`` arms every node with a :class:`crdt_tpu.durable.
    Durability` manager (WAL-ahead ingest + a checkpoint at every
    gossip round end) and turns the run into the crash-recovery demo:
    at sweep ``kill_sweep`` node n1 is killed — listener closed, object
    dropped, nothing flushed, exactly what kill -9 leaves — and one
    sweep later it restores from its snapshot + WAL
    (:func:`crdt_tpu.durable.recover`), rejoins through NORMAL delta
    sync, and the demo prints the recovery wall, bytes replayed from
    the WAL vs bytes delta-synced during the rejoin, and asserts the
    rejoin shipped zero full-state frames (docs/GUIDE.md "Durability")."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)

    import threading

    import numpy as np

    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.cluster import (
        ClusterNode, GossipScheduler, Membership, ResilientTransport,
        RetryPolicy, TcpTransport, hello_accept, hello_dial,
    )
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.obs.fleet import FleetObservatory
    from crdt_tpu.utils.interning import Universe

    uni = Universe.identity(CrdtConfig(num_actors=max(8, n_peers + 2),
                                       member_capacity=32,
                                       deferred_capacity=8,
                                       counter_bits=32))
    policy = RetryPolicy(send_deadline_s=20.0, recv_deadline_s=20.0,
                         ack_timeout_s=0.25, max_backoff_s=2.0,
                         retry_budget=64)
    if window is not None:
        # --window 0 = stop-and-wait (a 1-frame window); any N >= 2
        # lets sessions pipeline DATA frames and stream v4 descents
        import dataclasses

        policy = dataclasses.replace(policy, window=max(1, window))

    from crdt_tpu.oplog import OpLog

    def make_gc_engine():
        if not gc_enabled:
            return None
        from crdt_tpu.gc import GcEngine, GcPolicy

        return GcEngine(GcPolicy(
            interval_rounds=gc_interval,
            shrink_hysteresis=gc_hysteresis,
        ))

    def make_durability(node_name):
        if durable_dir is None:
            return None
        from crdt_tpu.durable import Durability

        return Durability(os.path.join(durable_dir, node_name),
                          interval_rounds=1, retain=2)

    nodes = []
    for i in range(n_peers):
        fleet = _build_fleet(n_objects, actor=i + 1,
                             divergence=divergence, seed=42)
        batch = OrswotBatch.from_scalar(fleet, uni)
        gc_engine = make_gc_engine()
        if gc_enabled:
            # over-provision the planes as an earlier burst's regrow
            # would have, so the demo has real padding to reclaim
            batch = batch.with_capacity(uni.config.member_capacity * 4,
                                        uni.config.deferred_capacity * 4)
        nodes.append(ClusterNode(
            f"n{i}", batch, uni,
            busy_timeout_s=30.0,
            observatory=FleetObservatory(f"n{i}"),
            # op front-end armed up front so sessions advertise the
            # piggyback capability from the first hello (always armed
            # in durable mode — the WAL rides the op ingest path)
            oplog=OpLog(uni) if (ops_rate or reads_rate or durable_dir)
            else None,
            gc=gc_engine,
            # sync protocol v3: sessions compare digest-tree roots and
            # descend into diverged subtrees instead of shipping the
            # flat O(N) digest vector
            digest_tree=digest_tree,
            durability=make_durability(f"n{i}"),
        ))

    fleet_server = None
    if fleet_port is not None:
        from crdt_tpu.obs import export as obs_export

        fleet_server = obs_export.start_metrics_server(
            port=fleet_port, observatory=nodes[0].observatory
        )
        print(
            f"fleet: merged observatory on "
            f"http://127.0.0.1:{fleet_server.port}/fleet "
            f"(?format=json for per-node slices, ?trace=<id> for a "
            f"stitched session timeline)", flush=True,
        )

    # one listener per node; accepted connections run the acceptor leg
    # through the same ResilientTransport stack the dialers use.  The
    # served node is looked up LATE (nodes[i] at accept time), so a
    # killed slot refuses and a restarted one serves its new object.
    stop = threading.Event()
    servers: list = [None] * n_peers
    ports = {}

    def start_listener(i):
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", 0))
        srv.listen(n_peers)
        srv.settimeout(0.2)  # poll the stop flag between accepts
        ports[f"n{i}"] = srv.getsockname()[1]
        servers[i] = srv

        def listener():
            while not stop.is_set():
                try:
                    sock, _ = srv.accept()
                except socket.timeout:
                    continue
                except OSError:
                    return

                def serve(sock=sock):
                    node = nodes[i]
                    if node is None:  # killed between accept and serve
                        sock.close()
                        return
                    t = ResilientTransport(
                        TcpTransport(sock, default_timeout=20.0), policy,
                        name=f"{node.node_id}-accept",
                    )
                    try:
                        peer = hello_accept(t, timeout=20.0)
                        node.accept(t, peer_id=peer)
                    except Exception as e:  # a failed inbound session
                        print(f"{node.node_id}: inbound session failed: "
                              f"{type(e).__name__}: {e}", flush=True)
                    finally:
                        t.close()

                threading.Thread(target=serve, daemon=True).start()

        threading.Thread(target=listener, daemon=True,
                         name=f"listen-n{i}").start()

    for i in range(n_peers):
        start_listener(i)

    def make_dialer(node):
        def dial(peer):
            from crdt_tpu.error import PeerUnavailableError

            try:
                sock = socket.create_connection(
                    ("127.0.0.1", ports[peer.peer_id]), timeout=20.0)
            except OSError as e:
                # a killed peer's port refuses: that is a membership
                # fact (alive -> suspect -> dead), not a crash
                raise PeerUnavailableError(
                    f"dial {peer.peer_id} refused: {e}") from e
            t = ResilientTransport(
                TcpTransport(sock, default_timeout=20.0), policy,
                name=f"{node.node_id}->{peer.peer_id}",
            )
            hello_dial(t, node.node_id)
            return t
        return dial

    def make_sched(i):
        membership = Membership(suspect_after=2, dead_after=5)
        for j in range(n_peers):
            if j != i:
                membership.add(f"n{j}", address=ports[f"n{j}"])
        return GossipScheduler(
            nodes[i], membership, make_dialer(nodes[i]), fanout=2,
            session_timeout_s=60.0, seed=i,
        )

    scheds = [make_sched(i) for i in range(n_peers)]

    ops_rng = np.random.RandomState(4242)
    total_ops = 0
    # key-skew / burst knobs (crdt_tpu.utils.workload): Zipfian hot
    # keys cluster divergence into few digest subtrees — the descent's
    # best case — while the default stays uniform (its worst case)
    from crdt_tpu.utils.workload import WorkloadGen

    key_gen = WorkloadGen(n_objects, seed=4242, zipf_s=zipf_s,
                          burst_len=burst_len)

    def inject_writes(r):
        """R random user writes into random nodes, mid-round: each
        write mints its dot through the node's own write front-end
        (``submit_writes`` — batched clone-and-increment against the
        log-inclusive write clock, so a node mid-session can never
        reuse a dot), using a per-node writer actor; folded immediately
        when the node is idle, queued (and piggybacked to the next
        session peer) when it is busy."""
        nonlocal total_ops
        per_node = np.bincount(
            ops_rng.randint(0, n_peers, r), minlength=n_peers)
        for i, cnt in enumerate(per_node):
            if not cnt or nodes[i] is None:  # a killed node takes none
                continue
            nodes[i].submit_writes(
                key_gen.draw(int(cnt)),
                ops_rng.randint(200, 216, cnt).astype(np.int32),
                actor=i + 1,
            )
            total_ops += cnt

    # the read front-end (crdt_tpu.serve): its own key stream (so
    # toggling --reads never perturbs the write keys) and a per-node
    # ServeLoop with a generous park — a gossip session can hold the
    # node's fold lock for a while, and a parked RYW read waiting it
    # out is the designed behaviour, not a failure
    from crdt_tpu.error import ConsistencyUnavailableError
    from crdt_tpu.serve import ST_OK, ReadRequest, ServeLoop

    read_rng = np.random.RandomState(2424)
    read_gen = WorkloadGen(n_objects, seed=2424, zipf_s=zipf_s)
    serve_loops: dict = {}  # keyed by node OBJECT: restarts get fresh
    mono_tokens: dict = {}
    read_stats = {"reads": 0, "ryw": 0, "ryw_parked_out": 0, "mono": 0,
                  "frontier_ok": 0, "frontier_not_stable": 0,
                  "frontier_unformed": 0}

    def serve_on(i, req):
        node = nodes[i]
        loop = serve_loops.get(node)
        if loop is None:
            loop = ServeLoop(node, park_timeout_s=5.0)
            serve_loops[node] = loop
        return loop.serve(req)

    def inject_reads(r):
        """R live reads into random nodes, mid-round, one batch per
        mode.  Read-your-writes is probed end-to-end: write a marker
        member through the node's own front-end, take the ack floor
        (``write_vv`` — batch clock + everything parked in the log),
        and require the read to be admitted at or above it; an admitted
        read that misses the member is a protocol violation and dies
        loudly right here."""
        nonlocal total_ops
        per_node = np.bincount(
            read_rng.randint(0, n_peers, r), minlength=n_peers)
        for i, cnt in enumerate(per_node):
            node = nodes[i]
            if not cnt or node is None:
                continue
            # (1) read-your-writes on a fresh acknowledged write
            key = read_gen.draw(1)
            member = np.array([180 + i], np.int32)
            node.submit_writes(key, member, actor=i + 1)
            total_ops += 1
            ack = node.write_vv()
            try:
                frame = serve_on(i, ReadRequest.reads(
                    key, member=member, mode="ryw", require=ack))
                assert int(frame.val[0]) == 1, (
                    f"{node.node_id}: read-your-writes VIOLATED — "
                    f"admitted ryw read of obj {int(key[0])} does not "
                    f"see acknowledged member {int(member[0])}"
                )
                read_stats["ryw"] += 1
            except ConsistencyUnavailableError:
                # parked past the timeout behind a long fold lock —
                # loud and typed, never a silent stale read
                read_stats["ryw_parked_out"] += 1
            # (2) monotonic reads: the returned token may never regress
            keys = read_gen.draw(int(cnt))
            tok = mono_tokens.get(node)
            frame = serve_on(i, ReadRequest.reads(
                keys, mode="monotonic", require=tok))
            if tok is not None:
                assert np.all(frame.token >= tok), (
                    f"{node.node_id}: monotonic token REGRESSED "
                    f"{tok.tolist()} -> {frame.token.tolist()}"
                )
            mono_tokens[node] = frame.token
            read_stats["mono"] += int(cnt)
            # (3) frontier-stable reads: tally per-row stability
            try:
                frame = serve_on(i, ReadRequest.reads(
                    read_gen.draw(int(cnt)), mode="frontier"))
                ok = int((frame.status == ST_OK).sum())
                read_stats["frontier_ok"] += ok
                read_stats["frontier_not_stable"] += len(frame) - ok
            except ConsistencyUnavailableError:
                read_stats["frontier_unformed"] += int(cnt)
            read_stats["reads"] += 1 + 2 * int(cnt)

    victim = 1 if (durable_dir is not None and n_peers >= 2) else None
    killed_at = None
    rejoin_baseline = None
    recovery = None

    def kill_victim(sweep):
        """kill -9 in-process: close the listener, drop the object —
        no drain, no flush, no goodbye.  Everything the node will have
        after this moment is what its Durability manager already put
        on disk."""
        servers[victim].close()
        nodes[victim] = None
        scheds[victim] = None
        print(f"kill: n{victim} killed -9 at sweep {sweep} "
              "(listener closed, in-memory state dropped)", flush=True)

    def restart_victim():
        nonlocal rejoin_baseline, recovery
        from crdt_tpu.durable import recover
        from crdt_tpu.obs.stability import StabilityTracker
        from crdt_tpu.utils import tracing as _tracing

        c = _tracing.counters()
        rejoin_baseline = {
            "full_frames": c.get("sync.full_state_fallback", 0),
            "full_bytes": c.get("wire.sync.full.bytes", 0),
            "delta_bytes": c.get("wire.sync.delta.bytes", 0),
        }
        recovery = recover(os.path.join(durable_dir, f"n{victim}"))
        gc_engine = make_gc_engine()
        if gc_engine is not None and recovery.watermark is not None:
            # resume GC's stability frontier from the persisted clock
            gc_engine.restore_watermark(recovery.watermark)
        # the convergence observatory's frontier resumes the same way:
        # the persisted fleet-min clock is a monotone floor, so the
        # rejoined observer's published frontier never regresses
        stability = StabilityTracker()
        if recovery.frontier is not None:
            stability.restore(recovery.frontier)
        nodes[victim] = ClusterNode(
            f"n{victim}", recovery.batch, recovery.universe,
            busy_timeout_s=30.0,
            observatory=FleetObservatory(f"n{victim}"),
            oplog=OpLog(recovery.universe),
            applier=recovery.applier,
            gc=gc_engine,
            digest_tree=digest_tree,
            durability=make_durability(f"n{victim}"),
            stability_tracker=stability,
        )
        start_listener(victim)
        scheds[victim] = make_sched(victim)
        rep = recovery.report
        print(f"recovery: n{victim} restored generation "
              f"{rep.generation} in {rep.wall_s * 1e3:.1f}ms — "
              f"replayed {rep.replayed_frames} WAL frames / "
              f"{rep.replayed_ops} ops ({rep.replayed_bytes}B), "
              f"{rep.parked_ops} re-parked; rejoining via delta sync",
              flush=True)

    def roster_for(i):
        return [f"n{j}" for j in range(n_peers) if j != i]

    def fleet_vv_min(live):
        """Element-wise min over the live nodes' version vectors — what
        the stability frontier must equal once the fleet quiesced AND
        every observer re-converged with every peer."""
        from crdt_tpu.sync import digest as digest_mod

        vvs = [np.asarray(digest_mod.version_vector(n.batch), np.uint64)
               for n in live]
        width = max(v.size for v in vvs)
        out = None
        for v in vvs:
            if v.size < width:
                v = np.concatenate(
                    [v, np.zeros(width - v.size, np.uint64)])
            out = v if out is None else np.minimum(out, v)
        return out

    def frontier_settled(live):
        """Every live node's published fleet-min frontier clock equals
        the fleet VV min — needs each observer to have converged with
        each peer AFTER the last write, which the staleness-ranked
        scheduler reaches within a few post-quiescence sweeps."""
        target = fleet_vv_min(live)
        for n in live:
            rep = n.stability.frontier(
                n.batch, peers=roster_for(int(n.node_id[1:])))
            if rep is None:
                return False
            clock = np.asarray(rep.clock, np.uint64)
            w = max(clock.size, target.size)
            c = np.concatenate([clock, np.zeros(w - clock.size, np.uint64)])
            t = np.concatenate([target,
                                np.zeros(w - target.size, np.uint64)])
            if not np.array_equal(c, t):
                return False
        return True

    sweeps = 0
    converged = False
    settled = False
    try:
        for sweeps in range(1, max_sweeps + 1):
            if victim is not None and killed_at is None \
                    and sweeps == kill_sweep:
                kill_victim(sweeps)
                killed_at = sweeps
            elif killed_at is not None and nodes[victim] is None \
                    and sweeps == killed_at + 1:
                restart_victim()
            writing = ops_rate and sweeps <= ops_sweeps
            reading = reads_rate and sweeps <= ops_sweeps
            if writing:
                inject_writes(ops_rate)
            if reading:
                inject_reads(reads_rate)
            for sched in scheds:
                if sched is None:
                    continue  # the victim is down this sweep
                if writing:
                    # writes land between (and during) rounds, not just
                    # at sweep boundaries — the live-traffic shape
                    inject_writes(max(1, ops_rate // n_peers))
                if reading:
                    # reads interleave with the gossip rounds too, so
                    # admission races real fold-lock contention
                    inject_reads(max(1, reads_rate // n_peers))
                sched.run_round()
            live = [n for n in nodes if n is not None]
            digests = [n.digest() for n in live]
            converged = len(live) == n_peers and all(
                np.array_equal(digests[0], d) for d in digests[1:]
            )
            state = ("digest vectors identical" if converged
                     else "still diverged"
                     if len(live) == n_peers else
                     f"{n_peers - len(live)} node(s) down")
            if ops_rate:
                state += f" (ops submitted so far: {total_ops})"
            print(f"sweep {sweeps}: {state}", flush=True)
            # while writes flow, convergence is a moving target — only
            # the post-write sweeps decide the verdict; the stability
            # frontier additionally has to SETTLE (every observer
            # re-converged with every peer), so the final state's
            # frontier == fleet-VV-min identity below is assertable
            if converged and not writing and not reading:
                settled = frontier_settled(live)
                if settled:
                    break
    finally:
        stop.set()
        for srv in servers:
            if srv is not None:
                srv.close()

    if recovery is not None:
        from crdt_tpu.utils import tracing as _tracing

        c = _tracing.counters()
        full_frames = c.get("sync.full_state_fallback", 0) \
            - rejoin_baseline["full_frames"]
        delta_bytes = c.get("wire.sync.delta.bytes", 0) \
            - rejoin_baseline["delta_bytes"]
        print(
            f"rejoin: {recovery.report.replayed_bytes}B replayed from "
            f"the WAL vs {delta_bytes}B delta-synced fleet-wide during "
            f"the rejoin; full-state fallbacks={full_frames}",
            flush=True,
        )
        assert full_frames == 0, \
            "rejoin shipped a full-state frame (must be delta-only)"

    if ops_rate:
        print(f"ops: {total_ops} live writes ingested through "
              f"submit_ops while gossip ran; fleet "
              f"{'CONVERGED' if converged else 'DIVERGED'} after writes "
              "stopped", flush=True)
        assert not converged or all(
            len(n._oplog) == 0 for n in nodes if n._oplog is not None
        ), "converged with undrained op logs"

    if reads_rate:
        print(
            f"reads: {read_stats['reads']} live reads served while "
            f"gossip ran — ryw probes {read_stats['ryw']} "
            f"(0 violations; {read_stats['ryw_parked_out']} parked out "
            f"behind the fold lock), monotonic {read_stats['mono']} "
            f"(0 token regressions), frontier-stable rows "
            f"{read_stats['frontier_ok']} ok / "
            f"{read_stats['frontier_not_stable']} not-yet-stable / "
            f"{read_stats['frontier_unformed']} before a frontier "
            "formed", flush=True,
        )
        assert read_stats["ryw"] > 0, \
            "--reads ran but no read-your-writes probe was admitted"
        if converged and settled:
            # at quiescence the frontier IS the fleet VV min, so a
            # frontier-mode read of ANY row must come back stable
            for i, node in enumerate(nodes):
                if node is None:
                    continue
                frame = serve_on(i, ReadRequest.reads(
                    np.arange(min(64, n_objects)), mode="frontier"))
                assert bool((frame.status == ST_OK).all()), (
                    f"{node.node_id}: rows still not-stable under a "
                    "settled frontier"
                )
            print("reads: quiescent frontier-mode sweep all-rows-stable "
                  "on every node", flush=True)

    # ONE merged fleet snapshot (every node's slice reached node 0 on
    # the gossip itself — no scraper, no federation) instead of N
    # disjoint per-node /metrics views
    merged = nodes[0].observatory.merged()
    fc = merged.fleet_counters()
    sessions_by_node = merged.counters_by_node("sync.sessions")
    print(f"fleet: merged snapshot spans nodes={merged.nodes()}", flush=True)
    print(
        f"fleet: sync.sessions={fc.get('sync.sessions', 0)} "
        f"(per-node {sessions_by_node}; fleet counter == sum of "
        f"per-node values by G-Counter merge)", flush=True,
    )
    trace = next(
        (n.last_report.trace_id for n in reversed(nodes)
         if n.last_report is not None), None,
    )
    print(f"fleet: final session trace={trace} "
          f"(both peers' /events carry it)", flush=True)

    # the latency observatory's read of the run: the last session's
    # critical-path split, the per-link SRTT the transports measured,
    # and (on --ops runs) the write-to-visible lag each observer saw
    last = next((n.last_report for n in reversed(nodes)
                 if n is not None and n.last_report is not None), None)
    if last is not None and last.profile is not None:
        p = last.profile
        print(
            f"latency: last session wall {p.wall_ns / 1e6:.1f}ms = "
            f"serialize {p.serialize_ns / 1e6:.1f} + network "
            f"{p.network_ns / 1e6:.1f} + kernel {p.kernel_ns / 1e6:.1f} "
            f"+ other {p.other_ns / 1e6:.1f} + unaccounted "
            f"{p.unaccounted_ns / 1e6:.1f} "
            f"(network_wait {p.network_wait_frac:.0%})", flush=True,
        )
    from crdt_tpu.obs import metrics as _obs_metrics

    _gauges = _obs_metrics.registry().snapshot()["gauges"]
    srtts = {k.split(".")[2]: v for k, v in _gauges.items()
             if k.startswith("cluster.transport.") and
             k.endswith(".rtt_srtt_s")}
    if srtts:
        worst = max(srtts, key=srtts.get)
        print(f"latency: srtt over {len(srtts)} link(s), worst "
              f"{worst}={srtts[worst] * 1e3:.1f}ms", flush=True)
    if ops_rate:
        for node in nodes:
            if node is None:
                continue
            node.lag_tracker.refresh()
            lag = node.lag_tracker.snapshot()
            for origin, st in sorted(lag["peers"].items()):
                print(
                    f"latency: {node.node_id} sees {origin} "
                    f"write-to-visible p50={st['p50_s'] * 1e3:.1f}ms "
                    f"p99={st['p99_s'] * 1e3:.1f}ms "
                    f"({st['samples']} samples, "
                    f"{st['outstanding']} outstanding)", flush=True,
                )

    # the convergence observatory's read of the run: the fleet
    # stability frontier (the clock the future truncate-epoch proposer
    # consumes), how old the worst divergence got, and the lattice
    # auditor's verdict.  At quiescence, with every observer settled,
    # the frontier IS the fleet VV min — asserted, not just printed.
    live = [n for n in nodes if n is not None]
    if converged and live:
        target = fleet_vv_min(live)
        worst_age = 0.0
        checks = violations = 0
        for node in live:
            rep = node.stability.frontier(
                node.batch, peers=roster_for(int(node.node_id[1:])))
            assert rep is not None, "frontier unavailable on a clocked fleet"
            assert np.array_equal(
                np.asarray(rep.clock, np.uint64), target), (
                f"{node.node_id}: frontier {rep.clock.tolist()} != "
                f"fleet VV min {target.tolist()} at quiescence"
            )
            snap = node.stability.snapshot()
            worst_age = max(worst_age, snap["aging"]["resolved_age_max_s"]
                            or 0.0)
            checks += snap["audit"]["checks"]
            violations += snap["audit"]["violations"]
        print(
            f"stability: frontier == fleet VV min "
            f"(max_counter={int(target.max(initial=0))}, "
            f"{live[0].stability.snapshot()['frontier']['subtrees']} "
            f"subtree(s)); oldest divergence age "
            f"{max(n.stability.oldest_divergence_age_s() for n in live) * 1e3:.1f}ms "
            f"outstanding / {worst_age * 1e3:.1f}ms worst resolved; "
            f"audit checks={checks} violations={violations}", flush=True,
        )
        assert violations == 0, \
            "lattice auditor recorded violations on a healthy run"

    if heat and live:
        # the heat observatory's read of the run: every node carries a
        # private HeatTracker fed by its own serve loop (reads), op
        # drain (writes), and sync sessions (repair); here the per-node
        # views are joined host-side — the same reduction /fleet serves
        from crdt_tpu.obs import heat as heat_mod

        for node in live:
            node.heat.publish()
        vecs = [node.heat.heat_vector() for node in live]
        width = max((v.size for v in vecs), default=0)
        fleet_heat = np.zeros(max(width, 1), np.float64)
        for v in vecs:
            fleet_heat[:v.size] += v
        merged_hot = heat_mod.merge_hot([node.heat.hot(16) for node in live])
        layout = live[0].heat.snapshot()["layout"]
        rows = {cls: sum(n.heat.snapshot()["rows"][cls] for n in live)
                for cls in heat_mod.CLASSES}
        print(
            f"heat: {int(fleet_heat.sum())} attributed rows across "
            f"{width} subtree(s) (span={layout['span']}) — "
            f"reads={rows['reads']} writes={rows['writes']} "
            f"repair={rows['repair']}", flush=True)
        if merged_hot:
            top = ", ".join(f"#{h['obj']}x{h['count']}"
                            for h in merged_hot[:8])
            print(f"heat: top-k (fleet-merged, +-err<="
                  f"{max(h['err'] for h in merged_hot)}): {top}",
                  flush=True)
        for spec in (f"mesh:{n_peers}", f"ring:{n_peers},k=2"):
            rep = heat_mod.score_plan(
                spec, fleet_heat, n=n_objects, span=layout["span"])
            if rep["kind"] == "mesh":
                print(f"heat: plan {spec}: imbalance="
                      f"{rep['imbalance']} (max={rep['max_load']} "
                      f"mean={rep['mean_load']})", flush=True)
            else:
                print(f"heat: plan {spec}: skew={rep['skew']} "
                      f"movement_frac={rep['movement_frac']}",
                      flush=True)
        if zipf_s and len(merged_hot) >= heat_mod.MIN_FIT_RANKS:
            s_hat, r2 = heat_mod.zipf_fit(
                [h["count"] - h["err"]
                 for h in merged_hot[:heat_mod.ZIPF_FIT_RANKS]])
            if s_hat is not None and rows["writes"] >= 2_000:
                print(f"heat: zipf s_hat={s_hat:.3f} (r2={r2:.3f}) vs "
                      f"driver s={zipf_s}", flush=True)
                # loose bar: the demo's write volume is tiny next to
                # the bench's, and repair heat rides the same sketch
                assert abs(s_hat - zipf_s) <= 0.4, (
                    f"sketch-fitted Zipf exponent {s_hat:.3f} far from "
                    f"the driver's {zipf_s}")
            elif s_hat is not None:
                print(f"heat: zipf s_hat={s_hat:.3f} (r2={r2:.3f}; "
                      f"too few writes to assert)", flush=True)

    if gc_enabled:
        # per-node reclamation story + the watermark clock GC last
        # collected under (the element-wise min over every peer's
        # version vector — counters at or below it are fleet-stable)
        for node in nodes:
            rep = node.last_gc_report
            wm = "never-ran" if rep is None or rep.watermark is None \
                else rep.watermark.clock.tolist()
            print(
                f"gc: {node.node_id} reclaimed="
                f"{node.gc.total_reclaimed_bytes}B over {node.gc.runs} "
                f"pass(es)  member_capacity="
                f"{node.batch.member_capacity}  watermark={wm}",
                flush=True,
            )
    if fleet_server is not None:
        fleet_server.stop()

    if digest_tree:
        from crdt_tpu.utils import tracing as _tracing

        c = _tracing.counters()
        print(
            f"tree: descents={c.get('sync.tree.descents', 0)} "
            f"cutover={c.get('sync.tree.cutover', 0)} "
            f"fallbacks="
            f"{sum(v for k, v in c.items() if k.startswith('sync.tree.fallback.'))} "
            f"digest_cache_hits={c.get('sync.digest.cache.hit', 0)} "
            f"(wire.sync.tree.bytes={c.get('wire.sync.tree.bytes', 0)} vs "
            f"flat wire.sync.digest.bytes="
            f"{c.get('wire.sync.digest.bytes', 0)})", flush=True,
        )

    # the windowed-ARQ story of the run: fleet-wide recovery tallies,
    # the deepest any link pipelined, and the last session's descent
    # round-trip count — the numbers docs/GUIDE.md "Windowed transport" tracks
    from crdt_tpu.utils import tracing as _tracing

    c = _tracing.counters()
    hw = max(
        [int(v) for k, v in _gauges.items()
         if k.startswith("cluster.transport.")
         and k.endswith(".window_inflight_hw")] or [0],
    )
    print(
        f"transport: window={policy.window}  inflight_hw={hw}  "
        f"retransmits={c.get('cluster.transport.retransmits', 0)}  "
        f"frames_sacked={c.get('cluster.transport.window.sacked', 0)}  "
        f"window_fallbacks={c.get('cluster.transport.fallback.window', 0)}  "
        f"descent_rtts="
        f"{last.tree_round_trips if last is not None else 0}  "
        f"streaming_last={last.streaming if last is not None else False}",
        flush=True,
    )

    if converged and live:
        # a transport-independent fingerprint of the converged state,
        # so an A/B harness can assert a windowed fleet landed on the
        # byte-identical lattice point a stop-and-wait fleet did
        import hashlib

        sha = hashlib.sha256(live[0].digest().tobytes()).hexdigest()[:16]
        print(f"gossip: fleet digest sha256={sha}", flush=True)

    verdict = "CONVERGED" if converged else "DIVERGED"
    print(f"gossip: {n_peers} peers x {n_objects} objects  "
          f"sweeps={sweeps}  {verdict}", flush=True)
    return 0 if converged else 1


def _peer_backend(platform: str | None) -> str:
    """The JAX backend a peer process would get.  Asked of a child that
    exits before the peers start: the parent stays off JAX, since a
    process that touched it would hold the chip."""
    import subprocess

    platform = platform or os.environ.get("JAX_PLATFORMS", "")
    if platform.split(",")[0] == "cpu":
        return "cpu"
    env = dict(os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, env=env, timeout=300, check=True)
    return proc.stdout.strip().splitlines()[-1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("role", nargs="?", default="demo",
                    choices=["demo", "server", "client"])
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--objects", type=int, default=64)
    ap.add_argument("--divergence", type=float, default=0.05,
                    help="fraction of objects with peer-local ops")
    ap.add_argument("--full-state", action="store_true",
                    help="legacy behavior: ship full state instead of "
                         "digest-driven deltas")
    ap.add_argument("--platform", default=None,
                    help="force a JAX platform (e.g. cpu)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics, /events, /healthz on this port "
                         "(crdt_tpu.obs exporter; server/client roles only)")
    ap.add_argument("--linger", type=float, default=0.0,
                    help="with --metrics-port: keep the exporter alive up "
                         "to this many seconds after the sync (returns as "
                         "soon as /metrics and /events were both scraped)")
    ap.add_argument("--gossip", type=int, default=0, metavar="N",
                    help="N-peer gossip mode: N in-process replicas over "
                         "loopback TCP reconciled by the cluster runtime "
                         "(crdt_tpu.cluster) until their digest vectors "
                         "are byte-identical")
    ap.add_argument("--fleet-port", type=int, default=None,
                    help="with --gossip: serve the live CRDT-merged fleet "
                         "snapshot on GET /fleet at this port (0 picks a "
                         "free one); the demo prints the merged snapshot "
                         "at convergence either way")
    ap.add_argument("--ops", type=int, default=0, metavar="R",
                    help="with --gossip: drive R random user writes per "
                         "sweep into random nodes through the op-based "
                         "front-end (crdt_tpu.oplog / submit_ops) WHILE "
                         "gossip runs, then assert the fleet still "
                         "converges after writes stop")
    ap.add_argument("--reads", type=int, default=0, metavar="R",
                    help="with --gossip: drive R live reads per sweep "
                         "into random nodes through the batched read "
                         "front-end (crdt_tpu.serve) WHILE gossip runs "
                         "— read-your-writes asserted for every "
                         "acknowledged probe write, monotonic tokens "
                         "asserted never to regress, frontier-stable "
                         "rows tallied against the stability frontier")
    ap.add_argument("--gc", action="store_true",
                    help="with --gossip: enable causal GC (crdt_tpu.gc) — "
                         "each node starts with burst-over-provisioned "
                         "planes, the scheduler settles tombstones and "
                         "re-packs capacity between sessions, and the "
                         "demo prints per-node reclaimed bytes + the "
                         "fleet low-watermark clock at convergence")
    ap.add_argument("--gc-interval", type=int, default=1, metavar="N",
                    help="with --gc: collect every Nth gossip round "
                         "(GcPolicy.interval_rounds; default 1)")
    ap.add_argument("--digest-tree", action="store_true",
                    help="with --gossip: sync protocol v3 — sessions "
                         "compare k-ary digest-tree roots and descend "
                         "into diverged subtrees (O(log N) digest "
                         "frames) instead of shipping the flat O(N) "
                         "digest vector")
    ap.add_argument("--zipf", type=float, default=0.0, metavar="S",
                    help="with --ops: Zipf key-skew exponent for the "
                         "write driver (0 = uniform; ~1.2 = hot keys "
                         "clustered into few digest subtrees)")
    ap.add_argument("--burst", type=int, default=1, metavar="B",
                    help="with --ops: each drawn key repeats for B "
                         "consecutive writes (bursty sessions)")
    ap.add_argument("--heat", action="store_true",
                    help="with --gossip: print the heat observatory's "
                         "read of the run at convergence — fleet-merged "
                         "top-k hot objects, per-subtree read/write/"
                         "repair split, and scored mesh:N + ring:N,k=2 "
                         "placement plans (with --zipf: asserts the "
                         "sketch's fitted exponent against the driver's)")
    ap.add_argument("--durable", default=None, metavar="DIR",
                    help="with --gossip: arm every node with a durable "
                         "snapshot store + op-log WAL under DIR/n<i> "
                         "(crdt_tpu.durable), kill node n1 -9 mid-run, "
                         "restore it from disk, and print recovery "
                         "wall + bytes replayed vs bytes delta-synced "
                         "during the rejoin")
    ap.add_argument("--kill-sweep", type=int, default=2, metavar="K",
                    help="with --durable: kill n1 at sweep K and "
                         "restart it one sweep later (default 2)")
    ap.add_argument("--window", type=int, default=None, metavar="N",
                    help="ARQ window: run the session over the hardened "
                         "windowed transport with up to N frames in "
                         "flight (0 = stop-and-wait). Single-session "
                         "roles print frames-in-flight high-water, "
                         "retransmit and descent round-trip counts; "
                         "--gossip mode sets the fleet's transport "
                         "window and prints the fleet-wide tallies plus "
                         "a digest fingerprint at convergence")
    ap.add_argument("--mesh", type=int, default=0, metavar="S",
                    help="mesh-sharded fleet demo: shard ONE logical "
                         "replica over an S-device object mesh "
                         "(crdt_tpu.mesh; S in {1,2,4,8}, forced host "
                         "devices), run the whole anti-entropy round "
                         "as one pjit'd step, and print per-shard "
                         "planner-predicted vs measured load plus "
                         "digest parity against the unsharded control")
    ap.add_argument("--gc-hysteresis", type=float, default=0.5,
                    help="with --gc: shrink only when the fitted "
                         "capacity rung is at most this fraction of the "
                         "current one (GcPolicy.shrink_hysteresis; "
                         "default 0.5)")
    args = ap.parse_args()

    if args.mesh:
        if args.mesh not in (1, 2, 4, 8):
            ap.error("--mesh needs S in {1, 2, 4, 8}")
        zipf = args.zipf if args.zipf > 0 else 1.1
        return mesh_demo(args.mesh, args.objects, args.platform,
                         divergence=args.divergence, zipf_s=zipf)

    if args.gossip:
        if args.gossip < 2:
            ap.error("--gossip needs N >= 2 peers")
        if args.ops < 0:
            ap.error("--ops needs R >= 0")
        if args.reads < 0:
            ap.error("--reads needs R >= 0")
        if args.kill_sweep < 1:
            ap.error("--kill-sweep needs K >= 1")
        if args.window is not None and args.window < 0:
            ap.error("--window needs N >= 0")
        return gossip_demo(args.gossip, args.objects, args.platform,
                           divergence=args.divergence,
                           fleet_port=args.fleet_port,
                           ops_rate=args.ops, reads_rate=args.reads,
                           gc_enabled=args.gc,
                           gc_interval=args.gc_interval,
                           gc_hysteresis=args.gc_hysteresis,
                           digest_tree=args.digest_tree,
                           zipf_s=args.zipf, burst_len=args.burst,
                           durable_dir=args.durable,
                           kill_sweep=args.kill_sweep,
                           window=args.window, heat=args.heat)

    if args.window is not None and args.window < 0:
        ap.error("--window needs N >= 0")

    if args.role != "demo":
        if not args.port:
            ap.error("server/client roles need --port")
        status = peer(args.role, args.port, args.objects, args.platform,
                      full_state=args.full_state, divergence=args.divergence,
                      metrics_port=args.metrics_port, linger_s=args.linger,
                      window=args.window)
        return 0 if status == "CONVERGED" else 1

    # demo: spawn both peers as real OS processes
    import subprocess

    if _peer_backend(args.platform) == "tpu":
        print("replicate_tcp: the two-process demo needs two processes on "
              "one backend, and a TPU chip belongs to one process at a "
              "time.  On a TPU host run the in-process form instead — "
              "`--gossip N` (peers as threads of one process), or the sync "
              "phase of chip_smoke.py (two SyncSessions over a "
              "socketpair) — or pass --platform cpu.", file=sys.stderr)
        return 2

    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]

    base = [sys.executable, os.path.abspath(__file__)]
    extra = ["--port", str(port), "--objects", str(args.objects),
             "--divergence", str(args.divergence)]
    if args.full_state:
        extra += ["--full-state"]
    if args.platform:
        extra += ["--platform", args.platform]
    if args.window is not None:
        extra += ["--window", str(args.window)]
    srv_extra = list(extra)
    if args.metrics_port is not None:
        # one exporter per process; in demo mode the server peer gets it
        srv_extra += ["--metrics-port", str(args.metrics_port),
                      "--linger", str(args.linger)]
    srv = subprocess.Popen(base + ["server"] + srv_extra)
    cli = subprocess.Popen(base + ["client"] + extra)
    rc = srv.wait() | cli.wait()
    print("demo:", "CONVERGED" if rc == 0 else "DIVERGED/FAILED")
    return rc


if __name__ == "__main__":
    sys.exit(main())
