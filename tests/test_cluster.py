"""Cluster runtime tests — hardened transports, membership, gossip.

The acceptance bar (ISSUE 5): a seeded fault-injection run converges a
5-replica fleet to byte-identical digest vectors under 20% injected
frame loss plus one flapping peer, with bounded retries, and the
flight recorder shows the retry/backoff/peer-state story afterwards.
Everything else here pins the pieces that make that possible: the ARQ
wrapper's exactly-once in-order delivery under each fault kind, the
deadline/budget bounds (`SyncTimeoutError`/`PeerUnavailableError`,
never a hang), the alive→suspect→dead→alive membership thresholds, and
the scheduler's staleness-first peer ranking with per-endpoint session
locks.
"""

import itertools
import threading
import time
from dataclasses import replace as policy_replace

import numpy as np
import pytest

from crdt_tpu.batch import OrswotBatch
from crdt_tpu.cluster import (
    ClusterNode,
    FaultPlan,
    FaultyTransport,
    FlappingDialer,
    GossipScheduler,
    Membership,
    ResilientTransport,
    RetryPolicy,
    queue_pair,
)
from crdt_tpu.cluster import membership as membership_mod
from crdt_tpu.cluster import transport as transport_mod
from crdt_tpu.config import CrdtConfig
from crdt_tpu.error import (
    PeerUnavailableError,
    SyncTimeoutError,
    TransportClosedError,
    TransportError,
    TransportFrameError,
)
from crdt_tpu.obs import convergence as obs_convergence
from crdt_tpu.obs import events as obs_events
from crdt_tpu.obs import metrics as obs_metrics
from crdt_tpu.scalar.orswot import Orswot
from crdt_tpu.sync import digest as sync_digest
from crdt_tpu.sync.session import SyncSession
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe

pytestmark = pytest.mark.cluster

#: test-speed retry policy: milliseconds where production defaults use
#: hundreds of ms, but the same shape (bounded budget, jittered backoff).
#: Deadlines are deliberately tight — a failed session leg must resolve
#: in seconds so failure cascades can't dominate the fleet tests.
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


# ---- raw transports --------------------------------------------------------


def test_queue_pair_roundtrip_and_close():
    a, b = queue_pair(default_timeout=1.0)
    a.send(b"hello")
    assert b.recv(timeout=1.0) == b"hello"
    b.send(b"back")
    assert a.recv(timeout=1.0) == b"back"
    # timeout surfaces as the taxonomy, not queue.Empty
    with pytest.raises(SyncTimeoutError):
        a.recv(timeout=0.01)
    # a closed peer is a loud TransportClosedError, repeatedly
    b.close()
    for _ in range(2):
        with pytest.raises(TransportClosedError):
            a.recv(timeout=1.0)
    with pytest.raises(TransportClosedError):
        b.send(b"after close")


def test_tcp_transport_symmetric_large_frames_do_not_deadlock():
    """Both peers send a frame far larger than the socket buffers
    before either reads — the session's hello+digest opening at fleet
    scale — and both frames arrive whole."""
    import socket

    sa, sb = socket.socketpair()
    ends = {"a": transport_mod.TcpTransport(sa, default_timeout=10.0),
            "b": transport_mod.TcpTransport(sb, default_timeout=10.0)}
    frames = {k: bytes([i]) * (8 << 20) for i, k in enumerate(ends)}
    got = {}

    def run(me, peer):
        ends[me].send(frames[me])
        ends[me].send(b"tail-" + me.encode())
        got[me] = (ends[me].recv(), ends[me].recv())

    threads = [threading.Thread(target=run, args=p, daemon=True)
               for p in (("a", "b"), ("b", "a"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    for tr in ends.values():
        tr.close()
    assert not any(t.is_alive() for t in threads), "send deadlocked"
    assert got["a"] == (frames["b"], b"tail-b")
    assert got["b"] == (frames["a"], b"tail-a")


def test_tcp_transport_close_wakes_a_blocked_recv():
    """Closing an end wakes the thread blocked reading it, so a failed
    session's threads end instead of sleeping out their timeout."""
    import socket

    sa, sb = socket.socketpair()
    end = transport_mod.TcpTransport(sa, default_timeout=120.0)
    errors = []

    def run():
        try:
            end.recv()
        except TransportClosedError as e:
            errors.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(0.2)
    end.close()
    t.join(timeout=10.0)
    sb.close()
    assert not t.is_alive(), "recv still blocked after close"
    assert len(errors) == 1


def test_decode_envelope_rejects_malformed():
    env = transport_mod.encode_envelope(transport_mod._DATA, 7, b"payload")
    kind, seq, payload = transport_mod.decode_envelope(env)
    assert (kind, seq, payload) == (transport_mod._DATA, 7, b"payload")
    with pytest.raises(TransportFrameError):
        transport_mod.decode_envelope(env[:10])        # truncated header
    with pytest.raises(TransportFrameError):
        transport_mod.decode_envelope(env[:-2])        # truncated payload
    corrupt = bytearray(env)
    corrupt[-1] ^= 0xFF
    with pytest.raises(TransportFrameError):
        transport_mod.decode_envelope(bytes(corrupt))  # CRC mismatch
    bad_kind = bytearray(env)
    bad_kind[0] = 0x7F
    with pytest.raises(TransportFrameError):
        transport_mod.decode_envelope(bytes(bad_kind))
    # TransportFrameError is catchable at the transport boundary
    assert issubclass(TransportFrameError, TransportError)


def _pump_frames(ra, rb, n, payload=b"frame-%04d"):
    """Ship ``n`` frames a→b through two resilient endpoints, driving
    the receive side in a thread (the ack path needs it live).  The
    sender flushes at the end: a windowed ``send`` only guarantees
    window admission, and the retransmit timers for any lost tail
    frames are serviced by the flush pump."""
    got = []
    err = []

    def consume():
        try:
            for _ in range(n):
                got.append(rb.recv(timeout=10.0))
        except BaseException as e:  # surfaced in the caller
            err.append(e)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    for i in range(n):
        ra.send(payload % i)
    ra.flush(timeout=30.0)
    t.join(timeout=30.0)
    assert not t.is_alive(), "receiver hung"
    if err:
        raise err[0]
    return got


def test_resilient_clean_channel_is_transparent():
    ta, tb = queue_pair(default_timeout=5.0)
    ra = ResilientTransport(ta, FAST, name="a", seed=1)
    rb = ResilientTransport(tb, FAST, name="b", seed=2)
    got = _pump_frames(ra, rb, 8)
    assert got == [b"frame-%04d" % i for i in range(8)]
    assert ra.retransmits == 0
    assert rb.corrupt == 0


@pytest.mark.parametrize("plan", [
    FaultPlan(seed=3, drop=0.3),
    FaultPlan(seed=4, truncate=0.3),
    FaultPlan(seed=5, duplicate=0.3),
    FaultPlan(seed=6, delay=0.3),
    FaultPlan(seed=7, drop=0.1, truncate=0.1, duplicate=0.1, delay=0.1),
], ids=["drop", "truncate", "duplicate", "delay", "mixed"])
def test_resilient_delivers_exactly_once_under_faults(plan):
    """Every fault kind: the ARQ still delivers every frame, in order,
    exactly once — and the recovery machinery demonstrably ran."""
    ta, tb = queue_pair(default_timeout=5.0)
    fa = FaultyTransport(ta, plan, name="faulty-a")
    ra = ResilientTransport(fa, FAST, name="a", seed=11)
    rb = ResilientTransport(tb, FAST, name="b", seed=12)
    got = _pump_frames(ra, rb, 24)
    assert got == [b"frame-%04d" % i for i in range(24)]
    assert sum(fa.injected.values()) > 0, "plan injected nothing"
    # dropped/truncated frames force retransmits; duplicates are
    # suppressed; a delay-reordered frame lands in the out-of-order
    # buffer and is selectively acked — some recovery path must fire
    recovered = (ra.retransmits + rb.duplicates + rb.corrupt
                 + ra.transient_errors + rb.ooo_buffered)
    assert recovered > 0


def test_resilient_send_deadline_and_budget_are_bounded():
    # a peer that never acks: the send leg must fail in bounded time.
    # window=1 keeps the classic blocking-send shape — the error
    # surfaces from send() itself, not a later flush
    ta, _tb = queue_pair(default_timeout=5.0)
    policy = RetryPolicy(send_deadline_s=0.3, recv_deadline_s=0.3,
                         ack_timeout_s=0.02, max_backoff_s=0.05,
                         retry_budget=1000, window=1)
    ra = ResilientTransport(ta, policy, name="deadline", seed=13)
    t0 = time.monotonic()
    with pytest.raises(SyncTimeoutError):
        ra.send(b"into the void")
    assert time.monotonic() - t0 < 5.0
    # a tiny retry budget: PeerUnavailableError before the deadline
    ta2, _tb2 = queue_pair(default_timeout=5.0)
    tight = RetryPolicy(send_deadline_s=30.0, recv_deadline_s=30.0,
                        ack_timeout_s=0.01, max_backoff_s=0.02,
                        retry_budget=3, window=1)
    ra2 = ResilientTransport(ta2, tight, name="budget", seed=14)
    with pytest.raises(PeerUnavailableError):
        ra2.send(b"into the void")
    assert ra2.retransmits <= 4  # budget bounds the spin, not the clock
    # the windowed shape of the same bound: send() admits the frame
    # (the window has room), flush() is the delivery barrier that
    # surfaces the deadline
    ta3, _tb3 = queue_pair(default_timeout=5.0)
    ra3 = ResilientTransport(ta3, policy_replace(policy, window=8),
                             name="deadline-w8", seed=15)
    ra3.send(b"into the void")
    t0 = time.monotonic()
    with pytest.raises(SyncTimeoutError):
        ra3.flush()
    assert time.monotonic() - t0 < 5.0


def test_resilient_recv_deadline():
    ta, _tb = queue_pair(default_timeout=5.0)
    policy = RetryPolicy(recv_deadline_s=0.2, ack_timeout_s=0.02)
    ra = ResilientTransport(ta, policy, name="recv-deadline", seed=15)
    t0 = time.monotonic()
    with pytest.raises(SyncTimeoutError):
        ra.recv()
    assert time.monotonic() - t0 < 5.0


# ---- windowed ARQ ----------------------------------------------------------


class _DropSeq(transport_mod.Transport):
    """Inner transport that drops the DATA envelope with one chosen seq
    exactly once — deterministic loss, so the selective-ack pin can say
    WHICH frame died (FaultyTransport's coin flips cannot)."""

    def __init__(self, inner, seq):
        self._inner = inner
        self._seq = seq
        self.dropped = 0

    def send(self, frame):
        if self.dropped == 0 and len(frame) >= transport_mod._ENV.size:
            kind, seq, _crc, _plen = transport_mod._ENV.unpack_from(frame)
            if kind == transport_mod._DATA and seq == self._seq:
                self.dropped += 1
                return
        self._inner.send(frame)

    def recv(self, timeout=None):
        return self._inner.recv(timeout)

    def close(self):
        self._inner.close()


def test_windowed_selective_ack_retransmits_only_lost_frames():
    """Drop exactly one DATA frame out of eight: the frames behind the
    hole are buffered out-of-order and selectively acked, so the sender
    retransmits ONE frame — the lost one — not the whole window."""
    before = tracing.counters()
    ta, tb = queue_pair(default_timeout=5.0)
    drop = _DropSeq(ta, seq=2)
    # a generous ack timeout so the seq-2 retransmit timer fires ONCE,
    # well after the SACKs for seqs 3..7 have landed
    ra = ResilientTransport(drop, policy_replace(FAST, ack_timeout_s=0.3),
                            name="a", seed=31)
    rb = ResilientTransport(tb, FAST, name="b", seed=32)
    got = _pump_frames(ra, rb, 8)
    assert got == [b"frame-%04d" % i for i in range(8)]
    assert drop.dropped == 1
    # the selective-repeat pin: exactly the one lost frame went again
    assert ra.retransmits == 1
    assert rb.ooo_buffered >= 1       # frames behind the hole were held
    assert rb.sacks_sent >= 1         # ...and advertised to the sender
    assert ra.frames_sacked >= 1      # ...which excluded them from timers
    assert ra.window_hw >= 2          # the window genuinely pipelined
    deltas = tracing.counters_since(before)
    assert deltas.get("cluster.transport.window.sacked", 0) >= 1
    assert deltas.get("cluster.transport.window.ooo", 0) >= 1
    ra.close()
    rb.close()


def test_windowed_close_drains_whole_window():
    """Regression pin: close() with SEVERAL unacked frames in flight
    drains the whole window over a lossy link — not just the classic
    stop-and-wait single straggler — and stays inside the documented
    drain cap (6 quiet periods, quiet ≤ 1s)."""
    ta, tb = queue_pair(default_timeout=5.0)
    fa = FaultyTransport(ta, FaultPlan(seed=41, drop=0.3), name="lossy")
    ra = ResilientTransport(fa, FAST, name="a", seed=42)
    rb = ResilientTransport(tb, FAST, name="b", seed=43)
    got, err = [], []

    def consume():
        try:
            for _ in range(6):
                got.append(rb.recv(timeout=10.0))
        except BaseException as e:
            err.append(e)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    for i in range(6):
        ra.send(b"drain-%04d" % i)
    # no flush: close() itself must be the delivery barrier
    t0 = time.monotonic()
    ra.close()
    elapsed = time.monotonic() - t0
    t.join(timeout=30.0)
    assert not t.is_alive(), "receiver hung"
    if err:
        raise err[0]
    assert got == [b"drain-%04d" % i for i in range(6)]
    assert elapsed < 8.0, f"close drained for {elapsed:.2f}s"


def _sync_sessions_over(a, b, uni, ta, tb, *, timeout_s=120.0, **session_kw):
    """Run one SyncSession pair over a pair of connected transports,
    peer side in a thread; returns ``(sa, sb, rep_a, rep_b)``."""
    sa = SyncSession(a, uni, peer="b", **session_kw)
    sb = SyncSession(b, uni, peer="a", **session_kw)
    res, err = {}, []
    a_done = threading.Event()

    def serve(tr, until):
        # a returned session stops pumping its transport, so over a
        # lossy link the peer's final in-flight frame (its ack lost)
        # can strand past the close-drain window — whichever side
        # finishes first keeps servicing acks until the other is done
        deadline = time.monotonic() + timeout_s
        while not until() and time.monotonic() < deadline:
            try:
                tr.recv(timeout=0.05)
            except SyncTimeoutError:
                continue
            except TransportError:
                return

    def run_b():
        try:
            res["b"] = sb.sync(tb)
            serve(tb, a_done.is_set)
        except BaseException as e:
            err.append(e)

    t = threading.Thread(target=run_b, daemon=True)
    t.start()
    try:
        res["a"] = sa.sync(ta)
    finally:
        a_done.set()
        serve(ta, lambda: not t.is_alive())
        ta.close()
        tb.close()
    t.join(timeout=timeout_s)
    assert not t.is_alive(), "peer session hung"
    if err:
        raise err[0]
    return sa, sb, res["a"], res["b"]


#: WAN-shaped retry policy: the initial RTO must sit near the injected
#: RTT or every first flight spuriously retransmits and burns budget
_WAN = RetryPolicy(send_deadline_s=20.0, recv_deadline_s=20.0,
                   ack_timeout_s=0.25, max_backoff_s=0.5,
                   retry_budget=2000)


@pytest.mark.parametrize("one_way_s", [0.025, 0.1],
                         ids=["rtt50ms", "rtt200ms"])
def test_windowed_sync_byte_identical_under_wan_faults(one_way_s):
    """The ISSUE acceptance rung: windowed sessions over 50–200ms RTT
    links with 20% loss and frame reordering converge byte-identical to
    a stop-and-wait control pair on the same histories."""
    from crdt_tpu.cluster import latency_pair

    uni = _uni()
    seed = int(one_way_s * 1000)
    rows_a = list(range(0, 64, 3))
    rows_b = list(range(1, 64, 5))
    a = OrswotBatch.from_scalar(
        _orswot_fleet(64, seed=61, actor=1, extra_on=rows_a), uni)
    b = OrswotBatch.from_scalar(
        _orswot_fleet(64, seed=61, actor=2, extra_on=rows_b), uni)
    ref = a.merge(b).to_wire(uni)

    def wan_link(s):
        la, lb = latency_pair(one_way_s, seed=s, default_timeout=30.0)
        fa = FaultyTransport(la, FaultPlan(seed=s + 10, drop=0.2,
                                           delay=0.25), name=f"wan-a{s}")
        fb = FaultyTransport(lb, FaultPlan(seed=s + 11, drop=0.2,
                                           delay=0.25), name=f"wan-b{s}")
        return fa, fb

    # windowed run
    fa, fb = wan_link(seed)
    ra = ResilientTransport(fa, _WAN, name="w-a", seed=seed + 1)
    rb = ResilientTransport(fb, _WAN, name="w-b", seed=seed + 2)
    sa, sb, rep_a, rep_b = _sync_sessions_over(a, b, uni, ra, rb)
    assert rep_a.converged and rep_b.converged
    assert rep_a.window > 1 and rep_b.window > 1
    assert sum(fa.injected.values()) + sum(fb.injected.values()) > 0
    assert sa.batch.to_wire(uni) == ref == sb.batch.to_wire(uni)

    # stop-and-wait control on the same histories
    fa2, fb2 = wan_link(seed + 100)
    ra2 = ResilientTransport(fa2, policy_replace(_WAN, window=1),
                             name="sw-a", seed=seed + 3)
    rb2 = ResilientTransport(fb2, policy_replace(_WAN, window=1),
                             name="sw-b", seed=seed + 4)
    sa2, sb2, rep2a, rep2b = _sync_sessions_over(a, b, uni, ra2, rb2)
    assert rep2a.converged and rep2b.converged
    assert rep2a.window == 1 and not rep2a.streaming
    # byte-identical across ARQ modes — the ISSUE's equivalence bar
    assert sa2.batch.to_wire(uni) == ref == sb2.batch.to_wire(uni)


def test_mixed_window_fleet_falls_back_to_stop_and_wait():
    """A window-16 node syncing with a window-1 node: the hello clamps
    both to stop-and-wait, the fallback counter fires, streaming stays
    off, and the result is still byte-identical to the merge."""
    before = tracing.counters()
    uni = _uni()
    a = OrswotBatch.from_scalar(
        _orswot_fleet(48, seed=91, actor=1, extra_on=[3, 9]), uni)
    b = OrswotBatch.from_scalar(
        _orswot_fleet(48, seed=91, actor=2, extra_on=[17]), uni)
    ref = a.merge(b).to_wire(uni)
    ta, tb = queue_pair(default_timeout=10.0)
    ra = ResilientTransport(ta, FAST, name="a", seed=92)
    rb = ResilientTransport(tb, policy_replace(FAST, window=1),
                            name="b", seed=93)
    sa, sb, rep_a, rep_b = _sync_sessions_over(a, b, uni, ra, rb)
    assert rep_a.converged and rep_b.converged
    assert rep_a.window == 1 and rep_b.window == 1
    assert not rep_a.streaming and not rep_b.streaming
    deltas = tracing.counters_since(before)
    assert deltas.get("cluster.transport.fallback.window", 0) >= 1
    assert sa.batch.to_wire(uni) == ref == sb.batch.to_wire(uni)


def _diverged_pair(uni, n, k, seed):
    """A plunged fleet, and a copy whose ``k`` sampled rows each take one
    fresh add."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.utils.testdata import anti_entropy_fleets

    cfg = uni.config
    rng = np.random.RandomState(seed)
    reps = anti_entropy_fleets(rng, n, cfg.num_actors, cfg.member_capacity,
                               cfg.deferred_capacity, 1, base=4, novel=0,
                               deferred_frac=0.25)
    a = OrswotBatch(*(jnp.asarray(x) for x in reps[0]))
    a = a.merge(a)
    rows = np.sort(rng.choice(n, size=k, replace=False)).astype(np.int64)
    sub = jax.tree_util.tree_map(lambda p: p[rows], a)
    sub = sub.apply_add(np.zeros(k, np.int32),
                        jnp.max(sub.clock, axis=-1) + 1,
                        np.full(k, 1 << 20, np.int32))
    b = jax.tree_util.tree_map(lambda p, s: p.at[rows].set(s), a, sub)
    return a, b


@pytest.mark.parametrize("window, chunks", [(16, 1), (16, 3), (1, 3)])
def test_streaming_delta_ships_one_chunk_per_chunk_rows(window, chunks):
    """A windowed session streams the shared diverged set in
    ``DELTA_CHUNK_ROWS``-row chunks, the last one partial: each side
    ships exactly ``ceil(k / DELTA_CHUNK_ROWS)`` chunk frames.  A
    window-1 pair never streams and ships none.  Both end byte-identical
    to the plain merge."""
    from crdt_tpu.sync.delta import DELTA_CHUNK_ROWS

    uni = _uni(member_capacity=8, deferred_capacity=2)
    k = (chunks - 1) * DELTA_CHUNK_ROWS + DELTA_CHUNK_ROWS // 2
    a, b = _diverged_pair(uni, 2048, k, seed=31)
    ta, tb = queue_pair(default_timeout=10.0)
    pol = policy_replace(FAST, window=window)
    ra = ResilientTransport(ta, pol, name="a", seed=94)
    rb = ResilientTransport(tb, pol, name="b", seed=95)
    sa, sb, rep_a, rep_b = _sync_sessions_over(a, b, uni, ra, rb)
    want = chunks if window > 1 else 0
    for rep in (rep_a, rep_b):
        assert rep.converged and rep.diverged == k
        assert rep.streaming == (window > 1)
        assert rep.delta_chunks_sent == want
    ref = a.merge(b).to_wire(uni)
    assert sa.batch.to_wire(uni) == ref == sb.batch.to_wire(uni)


def test_session_accepts_transport_directly():
    """The Transport-object API of SyncSession.sync — the callable pair
    stays as a shim, the cluster runtime passes transports."""
    uni = _uni()
    a = OrswotBatch.from_scalar(
        _orswot_fleet(16, seed=21, actor=1, extra_on=[1]), uni)
    b = OrswotBatch.from_scalar(
        _orswot_fleet(16, seed=21, actor=2, extra_on=[4]), uni)
    ta, tb = queue_pair(default_timeout=10.0)
    sa = SyncSession(a, uni, peer="tb")
    sb = SyncSession(b, uni, peer="ta")
    res = {}

    def run_b():
        res["b"] = sb.sync(tb)

    t = threading.Thread(target=run_b, daemon=True)
    t.start()
    res["a"] = sa.sync(ta)
    t.join(timeout=30.0)
    assert res["a"].converged and res["b"].converged
    assert np.array_equal(
        np.asarray(sync_digest.digest_of(sa.batch)),
        np.asarray(sync_digest.digest_of(sb.batch)),
    )


# ---- membership ------------------------------------------------------------


def test_membership_thresholds_and_gauges():
    reg = obs_metrics.MetricsRegistry()
    m = Membership(suspect_after=2, dead_after=4, registry=reg)
    m.add("p1")
    m.add("p2")
    assert m.get("p1").state == membership_mod.ALIVE

    m.record_failure("p1")
    assert m.get("p1").state == membership_mod.ALIVE  # one blip tolerated
    m.record_failure("p1")
    assert m.get("p1").state == membership_mod.SUSPECT
    m.record_failure("p1")
    m.record_failure("p1")
    assert m.get("p1").state == membership_mod.DEAD
    assert m.get("p1").consecutive_failures == 4

    # one success from ANY state re-admits
    m.record_success("p1")
    assert m.get("p1").state == membership_mod.ALIVE
    assert m.get("p1").consecutive_failures == 0
    assert m.get("p1").sessions_failed == 4
    assert m.get("p1").sessions_ok == 1

    snap = reg.snapshot()["gauges"]
    assert snap["cluster.peers.alive"] == 2.0
    assert snap["cluster.peers.suspect"] == 0.0
    assert snap["cluster.peers.dead"] == 0.0
    assert snap["cluster.peer.p1.state"] == 0.0
    assert snap["cluster.peer.p1.consecutive_failures"] == 0.0
    assert m.counts() == {"alive": 2, "suspect": 0, "dead": 0}


def test_membership_transitions_hit_recorder_and_counters():
    reg = obs_metrics.MetricsRegistry()
    m = Membership(suspect_after=1, dead_after=2, registry=reg)
    m.add("flappy")
    before = tracing.counters()
    m.record_failure("flappy")   # -> suspect
    m.record_failure("flappy")   # -> dead
    m.record_success("flappy")   # -> alive
    deltas = tracing.counters_since(before)
    assert deltas.get("cluster.peer_transition.suspect") == 1
    assert deltas.get("cluster.peer_transition.dead") == 1
    assert deltas.get("cluster.peer_transition.alive") == 1
    evs = [e for e in obs_events.recorder().snapshot(kind="cluster.peer_state")
           if e["fields"]["peer"] == "flappy"]
    assert [(e["fields"]["old"], e["fields"]["new"]) for e in evs[-3:]] == [
        ("alive", "suspect"), ("suspect", "dead"), ("dead", "alive")]


# ---- gossip scheduling -----------------------------------------------------


def _mk_node(node_id, uni, seed=31, extra_on=(1,)):
    batch = OrswotBatch.from_scalar(
        _orswot_fleet(12, seed=seed, actor=1, extra_on=extra_on), uni)
    return ClusterNode(node_id, batch, uni)


def test_rank_peers_staleness_first():
    uni = _uni()
    tracker = obs_convergence.ConvergenceTracker(
        registry=obs_metrics.MetricsRegistry())
    m = Membership(suspect_after=2, dead_after=4,
                   registry=obs_metrics.MetricsRegistry())
    for p in ("fresh", "stale", "never"):
        m.add(p)
    tracker.observe_session("stale", converged=True, rounds=1)
    time.sleep(0.05)
    tracker.observe_session("fresh", converged=True, rounds=1)
    sched = GossipScheduler(_mk_node("n0", uni), m,
                            dialer=lambda peer: (_ for _ in ()).throw(
                                PeerUnavailableError("unused")),
                            tracker=tracker)
    ranked = [p.peer_id for p in sched.rank_peers(round_no=1)]
    assert ranked[0] == "never"             # never-synced outranks all
    assert ranked[1:] == ["stale", "fresh"]  # then oldest converged sync


def test_rank_peers_dead_only_on_probe_rounds():
    uni = _uni()
    m = Membership(suspect_after=1, dead_after=2,
                   registry=obs_metrics.MetricsRegistry())
    m.add("ok")
    m.add("gone")
    m.record_failure("gone")
    m.record_failure("gone")
    assert m.get("gone").state == membership_mod.DEAD
    tracker = obs_convergence.ConvergenceTracker(
        registry=obs_metrics.MetricsRegistry())
    sched = GossipScheduler(_mk_node("n0", uni), m,
                            dialer=lambda p: None, probe_dead_every=4,
                            tracker=tracker)
    assert [p.peer_id for p in sched.rank_peers(round_no=1)] == ["ok"]
    assert sorted(p.peer_id for p in sched.rank_peers(round_no=4)) == \
        ["gone", "ok"]


def test_round_skips_endpoint_with_session_in_flight():
    """Per-endpoint session locks: a peer whose previous session is
    still running is SKIPPED (never queued behind), so two rounds can
    never interleave frames on one endpoint."""
    uni = _uni()
    m = Membership(registry=obs_metrics.MetricsRegistry())
    m.add("busy-peer")
    tracker = obs_convergence.ConvergenceTracker(
        registry=obs_metrics.MetricsRegistry())
    sched = GossipScheduler(
        _mk_node("n0", uni), m,
        dialer=lambda p: (_ for _ in ()).throw(
            PeerUnavailableError("dial should not happen")),
        tracker=tracker, session_timeout_s=5.0,
    )
    lock = sched._endpoint_lock("busy-peer")
    assert lock.acquire(blocking=False)
    try:
        report = sched.run_round()
    finally:
        lock.release()
    assert report.skipped_busy == ["busy-peer"]
    assert report.attempted == 0
    assert m.get("busy-peer").sessions_failed == 0  # a skip is not a failure


def test_cluster_node_busy_bound():
    uni = _uni()
    node = _mk_node("n0", uni)
    node.busy_timeout_s = 0.05
    assert node._busy.acquire(blocking=False)
    try:
        ta, _tb = queue_pair(default_timeout=1.0)
        with pytest.raises(PeerUnavailableError):
            node.accept(ta, peer_id="px")
    finally:
        node._busy.release()


# ---- the acceptance run ----------------------------------------------------


def _gossip_fleet(n_nodes, n_objects, *, loss, flap_schedule,
                  suspect_after=2, dead_after=4, probe_dead_every=4):
    """N in-process replicas over fault-injected queue links.  Node 0's
    link to the last node goes through ``flap_schedule`` at the dial
    level (the flapping peer); EVERY link drops ``loss`` of its frames.
    Returns (nodes, schedulers, the flapping peer id)."""
    uni = _uni(num_actors=max(8, n_nodes + 2))
    nodes = []
    for i in range(n_nodes):
        extra = [(3 * i + k) % n_objects for k in range(3)]
        batch = OrswotBatch.from_scalar(
            _orswot_fleet(n_objects, seed=41, actor=i + 1, extra_on=extra),
            uni)
        nodes.append(ClusterNode(f"n{i}", batch, uni, busy_timeout_s=5.0))

    seeds = itertools.count(1000)

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
                except Exception:  # failed inbound leg: the initiator's
                    pass           # error drives the bookkeeping
                finally:
                    rb.close()  # a stuck initiator must fail fast, not
                    #             wait out its deadline on a dead leg

            threading.Thread(target=serve, daemon=True).start()
            return ra
        return dial

    flappy = f"n{n_nodes - 1}"
    scheds = []
    for i in range(n_nodes):
        m = Membership(suspect_after=suspect_after, dead_after=dead_after)
        for j in range(n_nodes):
            if j != i:
                m.add(f"n{j}")
        dial = make_dialer(i)
        if i == 0 and flap_schedule:
            flap = FlappingDialer(dial, flap_schedule)

            def dial0(peer, _dial=dial, _flap=flap):
                return _flap(peer) if peer.peer_id == flappy else _dial(peer)

            dial = dial0
        # node 0 gossips to the whole roster each round so the flapping
        # link is exercised on a deterministic dial schedule
        scheds.append(GossipScheduler(
            nodes[i], m, dial,
            fanout=(n_nodes - 1) if i == 0 else 2,
            probe_dead_every=probe_dead_every,
            session_timeout_s=60.0, seed=i,
        ))
    return nodes, scheds, flappy


def test_acceptance_five_replicas_20pct_loss_flapping_peer():
    """THE acceptance run: 5 replicas, every link dropping 20% of its
    frames, node 4 flapping at the dial level through a full
    alive→suspect→dead→probe→alive cycle — the fleet must still reach
    byte-identical digest vectors, with bounded retries, and the flight
    recorder must tell the whole story afterwards."""
    before = tracing.counters()
    nodes, scheds, flappy = _gossip_fleet(
        5, 40, loss=0.20,
        # node 0's dials to n4: 4 refusals (alive→suspect→dead), then the
        # link comes back up; dead peers are probed every 4th round
        # (dials 5, 6, 7 — all scheduled up), which re-admits n4
        flap_schedule=[False] * 4 + [True] * 4,
        suspect_after=2, dead_after=4, probe_dead_every=4,
    )
    m0 = scheds[0].membership

    # the flight recorder is a 2048-event ring and a lossy fleet is
    # chatty — harvest new events every sweep so early peer-state
    # transitions can't be evicted before the assertions read them.
    # Start past whatever is already in the ring: earlier tests in this
    # process leave their own transport.retry events behind (with their
    # own policies' backoffs), and this test's assertions must read only
    # this fleet's story.
    events = []
    last_seq = max((e["seq"] for e in obs_events.recorder().snapshot()),
                   default=0)

    def harvest():
        nonlocal last_seq
        fresh = [e for e in obs_events.recorder().snapshot()
                 if e["seq"] > last_seq]
        if fresh:
            last_seq = fresh[-1]["seq"]
            events.extend(fresh)

    deadline = time.monotonic() + 240.0
    converged = False
    for _sweep in range(20):
        for sched in scheds:
            sched.run_round()
        harvest()
        digests = [n.digest() for n in nodes]
        identical = all(np.array_equal(digests[0], d) for d in digests[1:])
        flappy_back = m0.get(flappy).sessions_ok >= 1
        if identical and flappy_back:
            converged = True
            break
        assert time.monotonic() < deadline, "fleet failed to converge in time"
    assert converged, (
        f"not converged after sweeps: flappy={m0.snapshot().get(flappy)}"
    )

    # byte-identical digest vectors fleet-wide
    digests = [n.digest() for n in nodes]
    for d in digests[1:]:
        assert np.array_equal(digests[0], d)
        assert digests[0].tobytes() == d.tobytes()

    # the flapping peer went through the whole health cycle and came back
    transitions = [
        (e["fields"]["old"], e["fields"]["new"])
        for e in events
        if e["kind"] == "cluster.peer_state"
        and e["fields"]["peer"] == flappy
    ]
    assert ("alive", "suspect") in transitions
    assert ("suspect", "dead") in transitions
    assert ("dead", "alive") in transitions
    assert m0.get(flappy).state == membership_mod.ALIVE

    # retries/backoff happened, were recorded, and were BOUNDED: the
    # per-link budget is 400 and no link exhausted it (exhaustion would
    # have surfaced as PeerUnavailableError sessions that never heal)
    deltas = tracing.counters_since(before)
    assert deltas.get("cluster.transport.retransmits", 0) > 0
    assert deltas.get("cluster.rounds", 0) > 0
    assert deltas.get("cluster.sessions.ok", 0) > 0
    retry_events = [e for e in events
                    if e["kind"] == "cluster.transport.retry"]
    assert retry_events, "no retry/backoff events in the flight recorder"
    assert all(e["fields"]["backoff_s"] <= FAST.max_backoff_s * 2
               for e in retry_events)
    assert any(e["kind"] == "cluster.round" for e in events), \
        "rounds left no flight-recorder trace"


def test_small_fleet_converges_under_loss_fast():
    """The tier-1-sized sibling of the acceptance run: 3 replicas, 20%
    loss, no flap — seconds, not minutes."""
    nodes, scheds, _ = _gossip_fleet(3, 24, loss=0.20, flap_schedule=None)
    for _sweep in range(8):
        for sched in scheds:
            sched.run_round()
        digests = [n.digest() for n in nodes]
        if all(np.array_equal(digests[0], d) for d in digests[1:]):
            return
    raise AssertionError("3-replica fleet failed to converge in 8 sweeps")


def test_gossip_example_mode_converges():
    """The example's --gossip N mode end to end over real loopback TCP
    (subprocess, like the other replicate_tcp tests)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo, "examples", "replicate_tcp.py"),
            "--gossip", "3", "--objects", "24", "--platform", "cpu",
        ],
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, (proc.stdout[-400:], proc.stderr[-800:])
    assert "gossip: 3 peers" in proc.stdout
    assert "CONVERGED" in proc.stdout


def test_gossip_example_windowed_matches_stop_and_wait_control():
    """The example's --window smoke: a windowed gossip fleet must land
    on the byte-identical lattice point a stop-and-wait control fleet
    does — asserted via the digest fingerprint both runs print."""
    import os
    import re
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shas = {}
    for label, window in (("windowed", "16"), ("stopwait", "0")):
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(repo, "examples", "replicate_tcp.py"),
                "--gossip", "3", "--objects", "24", "--platform", "cpu",
                "--window", window,
            ],
            capture_output=True, text=True, timeout=240,
        )
        assert proc.returncode == 0, \
            (label, proc.stdout[-400:], proc.stderr[-800:])
        m = re.search(r"fleet digest sha256=([0-9a-f]+)", proc.stdout)
        assert m, (label, proc.stdout[-400:])
        shas[label] = m.group(1)
        assert f"transport: window={'16' if window == '16' else '1'}" \
            in proc.stdout
    assert shas["windowed"] == shas["stopwait"]
