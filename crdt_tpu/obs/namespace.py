"""The metric-namespace manifest — one source of truth for `crdt_tpu_*`.

docs/GUIDE.md's "Metric naming" table used to be prose only; a counter and a
histogram silently sharing a name (`executor.regrow`, PR 3) showed that
the namespace needs to be machine-checkable.  This module IS the table:
every metric the process may emit matches exactly one :class:`NameSpec`
pattern here, with its registry type.  Two consumers:

* :mod:`crdt_tpu.obs.export` — the Prometheus prefix and name
  sanitization live here, so the exported name for any internal name is
  derivable without running the exporter.
* :mod:`crdt_tpu.analysis.telemetry` — the static namespace lint
  extracts every metric name declared in the source tree and fails on
  names outside this table (and on cross-type collisions).

Patterns are dotted, with ``*`` matching exactly one segment (segments
never contain dots by convention; dynamic segments — peer labels,
kernel names, fallback reasons — are single identifiers).  Adding a
metric family means adding a row here FIRST; the lint turns a missing
row into a CI failure, which is the point.

Stdlib-only: no jax, no numpy — the lint must be runnable without the
device runtime.
"""

from __future__ import annotations

from typing import Iterable, NamedTuple, Optional

#: the Prometheus metric-name prefix every exported name carries
PROM_PREFIX = "crdt_tpu"

#: registry types a name can claim (one per name, forever)
KINDS = ("counter", "gauge", "histogram")

_SAN = {ord(c): "_" for c in ".-/ "}


def sanitize(name: str) -> str:
    """Dotted internal metric name → Prometheus-legal metric name body
    (dots/dashes/slashes/spaces to underscores, anything else
    non-alphanumeric likewise)."""
    out = name.translate(_SAN)
    return "".join(c if c.isalnum() or c == "_" else "_" for c in out)


def prometheus_name(name: str, kind: str) -> str:
    """The exported Prometheus name for an internal dotted name:
    ``crdt_tpu_<sanitized>`` plus the ``_total`` suffix for counters
    (histograms grow ``_bucket``/``_sum``/``_count`` series at render
    time; the base name is returned here)."""
    base = f"{PROM_PREFIX}_{sanitize(name)}"
    return f"{base}_total" if kind == "counter" else base


class NameSpec(NamedTuple):
    """One documented metric family: a dotted pattern (``*`` = exactly
    one segment), its registry type, and what it measures."""

    pattern: str
    kind: str
    doc: str

    def matches(self, name: str) -> bool:
        pat = self.pattern.split(".")
        got = name.split(".")
        if len(pat) != len(got):
            return False
        return all(p == "*" or p == g for p, g in zip(pat, got))


#: Every metric family the process may emit.  The namespace lint
#: (`python -m crdt_tpu.analysis`) fails the build on any call site
#: whose name matches no row, or whose type disagrees with the row.
NAMESPACE: tuple[NameSpec, ...] = (
    # -- wire codec accounting (batch/wirebulk.record_wire) ------------------
    NameSpec("wire.*.*.native", "counter",
             "blobs that took the native path, per <type>.<direction>"),
    NameSpec("wire.*.*.fallback", "counter",
             "blobs that fell back to the Python codec"),
    NameSpec("wire.names.interned", "counter",
             "names (actors and members) first interned by a native "
             "named ORSWOT ingest, adopted into the universe's registries"),
    NameSpec("wire.*.*.fallback_reason.*", "counter",
             "fallback blobs by reason (no_engine/non_identity/key_type/"
             "unnamed_id/grammar/overflow_zigzag)"),
    # -- sync protocol frames (utils/tracing.record_sync + sync/delta) ------
    NameSpec("wire.sync.*.bytes", "counter",
             "bytes on the wire per sync leg (digest/delta/full)"),
    NameSpec("wire.sync.*.objects", "counter",
             "objects shipped per sync leg"),
    NameSpec("wire.sync.*.frame_bytes", "histogram",
             "per-frame size distribution per sync leg"),
    NameSpec("sync.frame.*.decoded", "counter",
             "accepted frames by type (digest/delta/full)"),
    NameSpec("sync.frame.rejected.*", "counter",
             "rejected frames by reason (truncated/version_mismatch/...)"),
    # -- sync sessions (sync/session.py) -------------------------------------
    NameSpec("sync.sessions", "counter", "sessions started"),
    NameSpec("sync.errors", "counter", "sessions that raised"),
    NameSpec("sync.digest_collision", "counter",
             "post-delta digest mismatches (64-bit collision / mode skew)"),
    NameSpec("sync.full_state_fallback", "counter",
             "sessions that shipped full state"),
    NameSpec("sync.full_state_fallback.*", "counter",
             "full-state fallbacks by reason (requested/threshold/"
             "digest_collision)"),
    NameSpec("sync.digest_exchange", "histogram",
             "digest-exchange phase wall time (span)"),
    # -- digest-tree descent (sync/session.py, sync/digest.py) ---------------
    NameSpec("sync.tree.descents", "counter",
             "sessions that ran the v3 subtree descent (root exchange)"),
    NameSpec("sync.tree.cutover", "counter",
             "descents that fell back to the flat exchange at the "
             "dense-divergence byte threshold"),
    NameSpec("sync.tree.collision", "counter",
             "descents where a differing parent had no differing child "
             "(truncated-lane collision / XOR cancellation) — fell back "
             "to the flat exchange"),
    NameSpec("sync.tree.fallback.*", "counter",
             "tree-capable sessions that ran flat, by reason "
             "(capability/version)"),
    NameSpec("sync.tree.spec_blasts", "counter",
             "descents that ran the v4 speculative streaming blast "
             "(all levels pipelined, ~1 RTT-equivalent)"),
    NameSpec("sync.tree.speculate.*", "counter",
             "speculated subtree lane blocks by outcome: hit = the "
             "true diverged walk used the block, miss = shipped but "
             "discarded (bounded by the dense-cutover byte budget)"),
    NameSpec("sync.delta.chunked_exchanges", "counter",
             "delta phases that streamed fixed-row DELTA_CHUNK frames "
             "through the ARQ window instead of one lock-step frame"),
    NameSpec("sync.digest.eager", "counter",
             "flat sessions that shipped phase 1 inside the hello "
             "flight (same wire sequence, one wait instead of two)"),
    NameSpec("sync.tree.exchange", "histogram",
             "tree root-compare + descent phase wall time (span)"),
    NameSpec("sync.digest.cache.*", "counter",
             "digest memo consults by outcome (hit/miss) — a converged "
             "re-sync must be all hits (zero digest-kernel launches)"),
    NameSpec("sync.delta_exchange", "histogram",
             "delta-exchange phase wall time (span)"),
    NameSpec("sync.full_state_exchange", "histogram",
             "full-state exchange wall time (span)"),
    # -- per-peer convergence gauges (obs/convergence.py) --------------------
    NameSpec("sync.peer.*.divergence", "gauge",
             "objects diverged at the last digest exchange (-1 = roster "
             "peer admitted but never exchanged — unknown, not zero)"),
    NameSpec("sync.peer.*.divergence_frac", "gauge",
             "diverged fraction of the fleet (-1 = never exchanged)"),
    NameSpec("sync.peer.*.rounds_to_converge", "gauge",
             "digest exchanges the last session needed"),
    NameSpec("sync.peer.*.staleness_s", "gauge",
             "seconds since the last converged sync (refreshed at "
             "scrape; +Inf = roster peer that has NEVER converged — "
             "seeded at membership admission so silent peers alert)"),
    NameSpec("sync.peer.*.delta_ratio", "gauge",
             "last session's payload bytes over the full-state reference"),
    NameSpec("sync.peer.*.diverged_subtrees", "gauge",
             "widest diverged internal frontier the last tree descent "
             "saw (0 = converged or flat-mode peer); urgency tiebreak"),
    # -- convergence observatory (obs/stability.py) ---------------------------
    NameSpec("sync.peer.*.divergence_age_s", "gauge",
             "age of this peer's OLDEST still-diverged subtree (0 = "
             "nothing outstanding) — a subtree stuck diverged across "
             "rounds shows up here, not as invisible churn"),
    NameSpec("sync.stability.divergence_age_s", "histogram",
             "birth-to-resolution age of diverged subtrees, per "
             "(peer, subtree) episode"),
    NameSpec("sync.stability.divergence_age_p50_s", "gauge",
             "median resolved divergence age over the bounded window "
             "(-1 = nothing resolved yet)"),
    NameSpec("sync.stability.divergence_age_max_s", "gauge",
             "worst resolved divergence age over the bounded window "
             "(-1 = nothing resolved yet)"),
    NameSpec("sync.stability.outstanding", "gauge",
             "(peer, subtree) pairs currently diverged at this observer"),
    NameSpec("sync.stability.resolved", "counter",
             "divergence episodes resolved (a later exchange found the "
             "subtree clean again)"),
    NameSpec("stability.frontier.*", "gauge",
             "fleet stability frontier state (peers/stale/unheard/"
             "excluded contributing counts, subtrees, age_s, "
             "max_counter of the fleet-min clock, lag behind the local "
             "frontier) — the clock below which every non-quarantined "
             "peer has provably converged"),
    NameSpec("stability.frontier.subtree.*.max_counter", "gauge",
             "per-subtree frontier clock (max over actors) — the "
             "structure the truncate-epoch proposer and op-log "
             "stability compaction will consume"),
    NameSpec("stability.audit.checks", "counter",
             "lattice-auditor checks performed (sampled self-merge "
             "idempotence + frontier soundness cross-checks)"),
    NameSpec("stability.audit.violations", "counter",
             "lattice-auditor violations — ANY nonzero value is a "
             "lattice-stack bug (loud stability.audit_violation event "
             "carries the plane that lied)"),
    NameSpec("stability.audit", "histogram",
             "one lattice-audit pass (span)"),
    # -- latency observatory (obs/latency.py, sync/session.py,
    # cluster/transport.py) ---------------------------------------------------
    NameSpec("sync.peer.*.network_wait_frac", "gauge",
             "fraction of the last session's wall spent blocked on the "
             "wire (~1 = RTT-bound, pipelining wins)"),
    NameSpec("sync.peer.*.unaccounted_frac", "gauge",
             "fraction of the last session's wall the profiler could "
             "not attribute — large values are a profiler finding"),
    NameSpec("sync.profile.*", "histogram",
             "per-session critical-path decomposition, seconds "
             "(wall/serialize/network_wait/kernel/other/unaccounted)"),
    NameSpec("sync.peer.*.lag_p50_s", "gauge",
             "median write-to-visible replication lag from this origin "
             "peer, over the bounded sample window"),
    NameSpec("sync.peer.*.lag_p99_s", "gauge",
             "p99 write-to-visible replication lag from this origin peer"),
    NameSpec("sync.peer.*.lag_outstanding", "gauge",
             "sidecar-stamped peer writes not yet visible locally"),
    NameSpec("sync.peer.*.lag_current_s", "gauge",
             "age of the oldest shipped-but-not-yet-visible peer write "
             "(0 = quiescent: everything stamped is visible)"),
    NameSpec("sync.lag.samples", "counter",
             "write-to-visible lag measurements taken (all peers)"),
    NameSpec("sync.lag.fallback.*", "counter",
             "lag sidecars degraded by reason (capability = peer too "
             "old to speak the sidecar; clock_domain = cross-process "
             "monotonic stamps, not comparable)"),
    NameSpec("sync.slo.converged_frac", "gauge",
             "fraction of recent gossip rounds that converged within "
             "the SLO budget (obs/latency.py LagTracker.observe_round)"),
    NameSpec("cluster.transport.*.rtt_srtt_s", "gauge",
             "per-link Jacobson/Karels smoothed RTT over ARQ ack "
             "round-trips (Karn-filtered)"),
    NameSpec("cluster.transport.*.rtt_rttvar_s", "gauge",
             "per-link RTT mean deviation"),
    NameSpec("cluster.transport.*.rtt_rto_s", "gauge",
             "per-link adaptive retransmit timer srtt + 4*rttvar, "
             "clamped to [min_rto_s, max_backoff_s]"),
    NameSpec("cluster.transport.*.rtt_samples", "gauge",
             "per-link RTT samples folded into the estimator"),
    # -- cluster runtime (cluster/membership.py, cluster/gossip.py,
    # cluster/transport.py, cluster/faults.py) -------------------------------
    NameSpec("cluster.peers.*", "gauge",
             "peer count per health state (alive/suspect/dead)"),
    NameSpec("cluster.peer.*.state", "gauge",
             "per-peer health as a level (0 alive, 1 suspect, 2 dead)"),
    NameSpec("cluster.peer.*.consecutive_failures", "gauge",
             "per-peer consecutive failed sessions (resets on success)"),
    NameSpec("cluster.peer_transition.*", "counter",
             "peer health transitions by destination state"),
    NameSpec("cluster.rounds", "counter", "gossip rounds started"),
    NameSpec("cluster.round", "histogram", "gossip round wall time (span)"),
    NameSpec("cluster.sessions.*", "counter",
             "gossip-driven sessions by outcome (ok/failed/skipped_busy)"),
    NameSpec("cluster.transport.retransmits", "counter",
             "ARQ data frames re-sent after an ack timeout"),
    NameSpec("cluster.transport.timeouts", "counter",
             "transport legs that blew their deadline (SyncTimeoutError)"),
    NameSpec("cluster.transport.corrupt", "counter",
             "ARQ envelopes dropped as malformed (treated as loss)"),
    NameSpec("cluster.transport.duplicates", "counter",
             "duplicate ARQ data frames suppressed at the receiver"),
    NameSpec("cluster.transport.transient_errors", "counter",
             "transport legs that failed and were retried with backoff"),
    NameSpec("cluster.transport.window.sacks", "counter",
             "selective-ack frames sent (out-of-order data buffered "
             "while a cumulative gap is outstanding)"),
    NameSpec("cluster.transport.window.ooo", "counter",
             "data frames accepted out of order into the reorder "
             "buffer (delivered once the gap fills)"),
    NameSpec("cluster.transport.window.sacked", "counter",
             "in-flight frames a peer SACK marked received (their "
             "retransmit timers stop; only the gap frames re-send)"),
    NameSpec("cluster.transport.fallback.window", "counter",
             "windowed transports degraded to a smaller window by "
             "hello negotiation (0/absent peer window = stop-and-wait "
             "peer) — mixed fleets degrade loudly, never error"),
    NameSpec("cluster.transport.*.window_inflight_hw", "gauge",
             "per-link high-water mark of unacked ARQ frames in "
             "flight (≤ the negotiated window)"),
    NameSpec("cluster.faults.*", "counter",
             "injected faults by kind (drop/delay/truncate/duplicate/"
             "disconnect) — nonzero outside tests means faults.py leaked "
             "into production wiring"),
    # -- gossip-round fleet health (cluster/gossip.py) -----------------------
    NameSpec("cluster.gossip.*", "gauge",
             "last gossip round's health (attempted/ok/failed/"
             "skipped_busy) + fleet convergence view (fleet_divergence_"
             "max, eta_rounds — peers still diverged over the fanout)"),
    # -- op-based write front-end (oplog/, cluster/gossip.py,
    # sync/session.py, batch/wireloop.py) ------------------------------------
    NameSpec("oplog.submitted", "counter",
             "ops appended to an op log (writers, wire frames, session "
             "piggybacks)"),
    NameSpec("oplog.pending", "gauge",
             "ops queued in the node's op log awaiting the fold"),
    NameSpec("oplog.parked", "gauge",
             "adds parked on a causal gap (missing predecessor dots)"),
    NameSpec("oplog.log_depth", "gauge",
             "ops buffered in the op log right now (refreshed by the "
             "log itself on every append/drain — nonzero while a "
             "session holds the fold lock)"),
    NameSpec("oplog.watermark", "gauge",
             "highest per-actor dot counter the op log has seen (max "
             "over actors) — the cheap write-progress signal"),
    NameSpec("oplog.apply.*", "counter",
             "apply_ops outcomes (ops/applied/duplicates/parked/"
             "released/rm_rounds)"),
    NameSpec("oplog.apply_ops", "histogram",
             "one scatter-fold apply call (span)"),
    NameSpec("oplog.exchange", "histogram",
             "session op-piggyback wall time (span)"),
    NameSpec("oplog.frames.decoded", "counter", "accepted op frames"),
    NameSpec("oplog.frames.rejected.*", "counter",
             "rejected op frames by reason (truncated/version_mismatch/"
             "crc_mismatch/bad_kind/...)"),
    NameSpec("wire.oplog.*.ops", "counter",
             "ops moved through the op-frame codec per direction "
             "(encode/decode)"),
    NameSpec("wire.oplog.*.bytes", "counter",
             "op-frame bytes per direction (encode/decode)"),
    # -- fleet observatory (obs/fleet.py, obs/export.py) ---------------------
    NameSpec("obs.events.dropped", "gauge",
             "flight-recorder events evicted by the ring bound "
             "(refreshed at scrape time)"),
    NameSpec("obs.fleet.merges", "counter",
             "peer fleet snapshots merged into this observatory"),
    NameSpec("obs.fleet.nodes", "gauge",
             "distinct nodes in the merged fleet snapshot"),
    NameSpec("obs.fleet.frames.decoded", "counter",
             "accepted fleet-snapshot frames"),
    NameSpec("obs.fleet.frames.rejected.*", "counter",
             "rejected fleet frames by reason (truncated/"
             "version_mismatch/crc_mismatch/...)"),
    NameSpec("obs.fleet.exchange", "histogram",
             "piggybacked snapshot-exchange wall time (span)"),
    NameSpec("obs.fleet.snapshot_bytes", "histogram",
             "encoded merged-snapshot frame size"),
    # -- capacity observatory (obs/capacity.py, batch/occupancy.py) ----------
    NameSpec("capacity.samples", "counter",
             "occupancy sampling passes (any plane family)"),
    NameSpec("capacity.watermark", "gauge",
             "overall capacity watermark (0 ok / 1 warn / 2 critical — "
             "the max across tracked planes; /healthz's status)"),
    NameSpec("capacity.*.bytes", "gauge",
             "exact plane bytes per tracked plane label (== device "
             "buffer nbytes by construction)"),
    NameSpec("capacity.*.objects", "gauge",
             "fleet rows per tracked plane (log segments for op logs)"),
    NameSpec("capacity.*.slots", "gauge",
             "padded cells along the binding slot axis"),
    NameSpec("capacity.*.live", "gauge",
             "live cells along the binding slot axis, fleet-wide"),
    NameSpec("capacity.*.live_max", "gauge",
             "busiest object's live slot count — the distance-to-"
             "overflow statistic growth rates and ETAs track"),
    NameSpec("capacity.*.tombstones", "gauge",
             "live deferred-remove/tombstone rows, fleet-wide"),
    NameSpec("capacity.*.utilization", "gauge",
             "live_max over the plane's regrow ceiling"),
    NameSpec("capacity.*.growth_rows_per_s", "gauge",
             "EWMA growth of live_max, rows/s (absent until two "
             "samples)"),
    NameSpec("capacity.*.eta_s", "gauge",
             "seconds until live_max hits the regrow ceiling at the "
             "EWMA rate (-1 = not growing, 0 = already there)"),
    NameSpec("capacity.*.watermark", "gauge",
             "per-plane watermark (0 ok / 1 warn / 2 critical)"),
    # -- causal GC (gc/watermark.py, gc/policy.py, gc/repack.py) -------------
    NameSpec("gc.runs", "counter", "causal-GC collection passes"),
    NameSpec("gc.shrinks", "counter",
             "plane re-packs that shrank a capacity rung"),
    NameSpec("gc.reclaimed_bytes", "counter",
             "bytes released by re-packing and op-buffer compaction"),
    NameSpec("gc.tombstones_cleared", "counter",
             "deferred-remove tombstone rows settled by GC"),
    NameSpec("gc.oplog_ops_dropped", "counter",
             "buffered ops dropped as already-witnessed below the "
             "fleet watermark"),
    NameSpec("gc.collect", "histogram",
             "one causal-GC collection pass (span)"),
    NameSpec("gc.watermark.*", "gauge",
             "fleet low-watermark state (peers/stale/unheard/excluded "
             "contributing counts, age_s of the oldest contribution, "
             "max_counter of the watermark clock, lag behind the local "
             "frontier)"),
    # -- durable replicas (durable/, cluster/gossip.py) ----------------------
    NameSpec("durable.snapshots", "counter",
             "snapshot generations written (atomic rename-into-place)"),
    NameSpec("durable.snapshot.decoded", "counter",
             "snapshot generations that decoded AND passed the "
             "digest-root self-check"),
    NameSpec("durable.snapshot.rejected.*", "counter",
             "snapshot loads rejected by reason (truncated/bad_magic/"
             "version_mismatch/crc_mismatch/root_mismatch/...)"),
    NameSpec("durable.snapshot.fallbacks", "counter",
             "recoveries that fell back past a rejected generation"),
    NameSpec("durable.wal.frames", "counter",
             "op frames appended to WAL segments (fsync'd before the "
             "in-memory fold)"),
    NameSpec("durable.wal.bytes", "counter",
             "bytes appended to WAL segments"),
    NameSpec("durable.wal.torn", "counter",
             "WAL segments whose torn tail was truncated (the expected "
             "kill -9 mid-append shape; the bytes were never "
             "acknowledged durable)"),
    NameSpec("durable.wal.segments_dropped", "counter",
             "WAL segments deleted by checkpoint/watermark truncation"),
    NameSpec("durable.snapshot.generation", "gauge",
             "latest snapshot generation number"),
    NameSpec("durable.snapshot.bytes", "gauge",
             "latest snapshot file size"),
    NameSpec("durable.snapshot.age_s", "gauge",
             "seconds since the last checkpoint (refreshed at "
             "round-end cadence checks)"),
    NameSpec("durable.wal.depth", "gauge",
             "op frames in retained WAL segments — the replay a "
             "recovery right now would face"),
    NameSpec("durable.wal.pending_bytes", "gauge",
             "bytes across retained WAL segments"),
    NameSpec("durable.replay.frames", "gauge",
             "WAL frames the last recovery replayed"),
    NameSpec("durable.replay.ops", "gauge",
             "ops the last recovery replayed through the causal-gap "
             "apply path"),
    NameSpec("durable.recovery.wall_s", "gauge",
             "last recovery's wall time (restore + verify + replay)"),
    NameSpec("durable.checkpoint", "histogram",
             "one checkpoint pass: snapshot write + WAL roll/truncate "
             "(span)"),
    NameSpec("durable.recover", "histogram",
             "one recovery: restore + root verify + WAL replay (span)"),
    # -- native engine (native/engine.py) ------------------------------------
    NameSpec("native.engine.*.calls", "counter",
             "native kernel invocations per entry point"),
    NameSpec("native.engine.*.objects", "counter",
             "objects processed per native entry point"),
    # -- the read front-end (crdt_tpu/serve) ---------------------------------
    NameSpec("serve.reads", "counter",
             "rows resolved by the gather engine (one per read row)"),
    NameSpec("serve.batches", "counter", "read batches gathered"),
    NameSpec("serve.batch_depth", "gauge",
             "decoded read batches staged ahead of the gather "
             "(the serve loop's bounded decode queue)"),
    NameSpec("serve.admit.*", "counter",
             "admitted read batches by consistency mode "
             "(eventual/ryw/monotonic/frontier)"),
    NameSpec("serve.park.*", "counter",
             "read batches that parked awaiting visibility, by mode"),
    NameSpec("serve.reject.*", "counter",
             "read batches terminally rejected by admission, by mode "
             "(the typed ConsistencyUnavailableError)"),
    NameSpec("serve.not_stable_rows", "counter",
             "frontier-mode rows above the stability frontier "
             "(stamped ST_NOT_STABLE instead of served as stable)"),
    NameSpec("serve.stalls", "counter",
             "serve-loop gather waits past the stall threshold "
             "(decode thread behind)"),
    NameSpec("serve.reads_per_s", "gauge",
             "rows/s of the most recent served batch"),
    NameSpec("serve.latency.*", "histogram",
             "per-batch serve wall by consistency mode "
             "(eventual/ryw/monotonic/frontier; admission park "
             "included)"),
    NameSpec("serve.park_wait_s", "histogram",
             "admission park duration in seconds per parked batch "
             "(what /healthz's serve section reports as wall)"),
    NameSpec("serve.frames.decoded", "counter", "accepted serve frames"),
    NameSpec("serve.frames.rejected.*", "counter",
             "rejected serve frames by reason (truncated/"
             "version_mismatch/bad_kind/...)"),
    NameSpec("wire.serve.*.ops", "counter",
             "read rows per serve wire direction (encode/decode)"),
    NameSpec("wire.serve.*.bytes", "counter",
             "serve frame bytes per direction"),
    NameSpec("serve.leg.*", "histogram",
             "one host leg of a served read frame (span): decode / "
             "admit / dispatch (padding, indices to the device, the "
             "gather call) / wait (the gather's device time) / fetch "
             "(outputs to the host) / heat (record_reads) / encode"),
    NameSpec("serve.view.build", "histogram",
             "one ORSWOT row-view build (span, outside every "
             "serve.leg.*): the snapshot's planes laid out "
             "object-major, once per served snapshot"),
    NameSpec("serve.view.builds", "counter",
             "ORSWOT row views built (one per new snapshot served)"),
    NameSpec("serve.view.hits", "counter",
             "ORSWOT gathers served from an already-built row view"),
    # -- pipelined wire loop (batch/wireloop.py) -----------------------------
    NameSpec("wireloop.stalls", "counter",
             "folds that waited on the parse thread past the threshold"),
    NameSpec("wireloop.staging_free", "gauge",
             "free staging plane sets (0 = parse-bound)"),
    NameSpec("wireloop.parsed_depth", "gauge",
             "parsed fleets queued ahead of the fold"),
    NameSpec("wireloop.parse", "histogram",
             "one fleet's blobs parsed into a staging set (span, on "
             "the parser thread when overlapped)"),
    NameSpec("wireloop.wait_parsed", "histogram",
             "the fold waiting for the next parsed fleet (span)"),
    NameSpec("wireloop.put", "histogram",
             "one staging set copied to the device, until the copy "
             "completes (span, jnp fold)"),
    NameSpec("wireloop.put.compact", "counter",
             "fleets shipped to the device fold as compact cells and "
             "densified there"),
    NameSpec("wireloop.put.dense", "counter",
             "fleets handed to the fold as dense planes (every fleet "
             "of the native CPU fold, which puts nothing)"),
    NameSpec("wireloop.put.bytes", "counter",
             "host bytes handed to device_put by the wire loop's puts"),
    NameSpec("wireloop.dispatch", "histogram",
             "one fold merge, or a staging set's densify, dispatched "
             "(span, both run async on the jnp fold)"),
    NameSpec("wireloop.wait", "histogram",
             "a round's fold finishing on the device, then its "
             "overflow check (span, jnp fold)"),
    NameSpec("wireloop.fetch", "histogram",
             "a round's fixpoint planes copied to the host (span)"),
    NameSpec("wireloop.encode", "histogram",
             "a round's fixpoint encoded as wire blobs (span)"),
    NameSpec("wireloop.intern", "histogram",
             "the names a native named ingest interned appended to the "
             "universe's registries (span, only when there are any)"),
    # -- executor (parallel/executor.py) -------------------------------------
    NameSpec("executor.recovery.*", "counter",
             "recoveries by kind (regrow/transient_retry) — disjoint from "
             "the executor.* spans by construction (the PR 3 collision)"),
    NameSpec("executor.join_all", "histogram", "sequential fold span"),
    NameSpec("executor.join_all_tree", "histogram", "tree join span"),
    NameSpec("executor.merge", "histogram", "one recoverable pair merge"),
    NameSpec("executor.regrow", "histogram", "capacity regrow span"),
    NameSpec("executor.shrink", "histogram",
             "capacity shrink (GC re-pack) span — the regrow path in "
             "reverse (crdt_tpu/gc/repack.py)"),
    # -- kernels (obs/kernels.py) --------------------------------------------
    NameSpec("kernel.*.errors", "counter",
             "raising calls per observed kernel label"),
    NameSpec("kernel.*.calls", "counter",
             "invocations per observed kernel label (manifest name with "
             "dots flattened to underscores)"),
    NameSpec("kernel.*.compiles", "counter",
             "jit cache misses (lowering+compile) per observed kernel"),
    NameSpec("kernel.*.bytes", "counter",
             "array bytes moved through an observed kernel (inputs + "
             "outputs; an HBM-traffic lower bound)"),
    NameSpec("kernel.*.wall", "histogram",
             "per-call dispatch wall per observed kernel (compiling "
             "calls excluded — they ride kernel.compile events)"),
    NameSpec("kernel.*.compile_budget_frac", "gauge",
             "runtime compiles over the kernelcheck KC04 compile_budget "
             "— KC04's static bound as a live watermark (>1 sustained "
             "in steady state = shape churn)"),
    NameSpec("kernel.*.cost_flops", "gauge",
             "XLA cost_analysis flops for the last captured lowering"),
    NameSpec("kernel.*.cost_bytes", "gauge",
             "XLA cost_analysis bytes-accessed for the last captured "
             "lowering"),
    NameSpec("kernel.compiles", "counter",
             "process-wide jit compiles across all observed kernels "
             "(zero growth after warmup = the steady-state invariant)"),
    NameSpec("kernel.budget.watermark", "gauge",
             "worst per-kernel compile-budget state (0 ok / 1 warn / 2 "
             "critical), like capacity.watermark"),
    NameSpec("kernel.cost.unavailable", "counter",
             "cost_analysis captures the backend declined"),
    # -- device memory (obs/kernels.sample_device_memory, capacity
    # cadence) ----------------------------------------------------------------
    NameSpec("devicemem.samples", "counter",
             "device-memory sampling passes (jax.live_arrays walks)"),
    NameSpec("devicemem.live_bytes", "gauge",
             "bytes held by live jax arrays process-wide — what the "
             "device actually holds vs plane bytes by construction"),
    NameSpec("devicemem.arrays", "gauge", "live jax array count"),
    NameSpec("devicemem.dtype.*.bytes", "gauge",
             "live array bytes by dtype family (a freed family reads "
             "0, never a stale level)"),
    NameSpec("devicemem.tracked_bytes", "gauge",
             "plane bytes the capacity tracker accounts for"),
    NameSpec("devicemem.tracked_frac", "gauge",
             "tracked_bytes over live_bytes — how much of device "
             "memory the capacity observatory explains"),
    # -- profiler capture (utils/tracing.profile) ----------------------------
    NameSpec("obs.profiler_unavailable", "counter",
             "XLA profiler trace setups that failed (exception class "
             "in the one-time obs.profiler_unavailable event) — why "
             "the trace directory is empty"),
    # -- heat & placement observatory (obs/heat.py) --------------------------
    NameSpec("heat.subtree.*.reads", "counter",
             "read rows attributed to digest-tree subtree <i> "
             "(serve gather batches folded by obs.heat.subtree_fold)"),
    NameSpec("heat.subtree.*.writes", "counter",
             "write rows attributed to subtree <i> (oplog drain "
             "batches)"),
    NameSpec("heat.subtree.*.repair", "counter",
             "sync delta rows applied in subtree <i> — anti-entropy "
             "churn, the objects that actually moved over the wire"),
    NameSpec("heat.subtree.*.reads_per_s", "gauge",
             "half-life-decayed read rate for subtree <i>"),
    NameSpec("heat.subtree.*.writes_per_s", "gauge",
             "half-life-decayed write rate for subtree <i>"),
    NameSpec("heat.subtree.*.repair_per_s", "gauge",
             "half-life-decayed repair rate for subtree <i>"),
    NameSpec("heat.reads.*", "counter",
             "read rows attributed per consistency mode "
             "(eventual/ryw/monotonic/frontier)"),
    NameSpec("heat.updates", "counter",
             "heat record batches folded (sketch + subtree kernels)"),
    NameSpec("heat.hot.*.obj", "gauge",
             "object id at hot rank <r> from the Space-Saving sketch"),
    NameSpec("heat.hot.*.count", "gauge",
             "sketch count at hot rank <r> (overestimate by at most "
             "the entry's recorded error)"),
    NameSpec("heat.zipf.s_hat", "gauge",
             "Zipf exponent fitted from the sketch's guaranteed "
             "rank-frequency counts (checkable vs WorkloadGen.zipf_s)"),
    NameSpec("heat.zipf.fit_r2", "gauge",
             "goodness of the Zipf rank-frequency fit (1 = a clean "
             "power law)"),
    # -- mesh-sharded fleets (crdt_tpu/mesh/) --------------------------------
    NameSpec("mesh.layout.shards", "gauge",
             "shard count of the active mesh layout"),
    NameSpec("mesh.layout.granule", "gauge",
             "shard-boundary granule (a pow2 subtree span) the layout "
             "snapped to — every boundary is a multiple of this"),
    NameSpec("mesh.layout.imbalance", "gauge",
             "planner-predicted max/mean shard load for the active "
             "layout (1.0 = perfectly balanced; matches "
             "/heat?plan=mesh:S&granule=G)"),
    NameSpec("mesh.shard.*.objects", "gauge",
             "logical (unpadded) object rows owned by shard <s>"),
    NameSpec("mesh.shard.*.load", "gauge",
             "measured heat (reads+writes+repair) attributed to shard "
             "<s>'s leaf range — compare against the planner's "
             "predicted loads"),
    NameSpec("mesh.step.rounds", "counter",
             "pjit'd anti-entropy steps executed (ONE kernel launch "
             "per round, all shards)"),
    NameSpec("mesh.step.digest_bytes", "counter",
             "bytes moved by the step's digest all_gather (the whole "
             "collective bill of a converged round)"),
    NameSpec("mesh.step.dispatch", "histogram",
             "one step's salts and program call (span)"),
    NameSpec("mesh.step.wait", "histogram",
             "one step's program finishing on the chips, then its "
             "overflow check (span)"),
    NameSpec("mesh.step.fetch", "histogram",
             "one step's digests, version vector and member count "
             "copied to the host and widened to u64 (span)"),
    NameSpec("mesh.sync.rounds", "counter",
             "shard-subset sync passes (digest compare + per-shard "
             "descent)"),
    NameSpec("mesh.sync.shards_synced", "counter",
             "diverged shards repaired by a shard-scoped descent"),
    NameSpec("mesh.sync.shards_skipped", "counter",
             "converged shards a sync pass never touched (their "
             "subtree bytes stayed home)"),
    NameSpec("mesh.sync.delta_bytes", "counter",
             "delta payload bytes shipped by shard-subset sync "
             "(diverged shards only)"),
    NameSpec("mesh.sync.objects", "counter",
             "diverged object rows repaired by shard-subset sync"),
    NameSpec("mesh.durable.snapshots", "counter",
             "fleet checkpoint passes (S per-shard generations + one "
             "manifest)"),
    NameSpec("mesh.durable.restores", "counter",
             "fleet restores that re-verified every shard's subtree "
             "root against the manifest"),
    NameSpec("mesh.durable.rejected.*", "counter",
             "fleet restore rejections by reason (manifest_missing/"
             "manifest_corrupt/shard_missing/root_mismatch/"
             "layout_mismatch)"),
    NameSpec("mesh.contract.refused", "counter",
             "kernel dispatches the runtime contract gate refused "
             "(host_only/replicated/mesh-size outside the contract "
             "ladder) — the typed MeshContractError path"),
)


def match(name: str, kind: Optional[str] = None) -> Optional[NameSpec]:
    """The manifest row ``name`` falls under, or None.  With ``kind``,
    the row must also agree on the registry type (a name matching a row
    of a different type is a namespace violation, not a match)."""
    for spec in NAMESPACE:
        if spec.matches(name):
            return spec if kind is None or spec.kind == kind else None
    return None


def patterns(kind: Optional[str] = None) -> Iterable[NameSpec]:
    """All manifest rows, optionally filtered by registry type."""
    return tuple(s for s in NAMESPACE if kind is None or s.kind == kind)
