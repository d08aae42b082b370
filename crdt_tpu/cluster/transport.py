"""Transports — the byte channels a :class:`SyncSession` runs over.

PR 2's session API takes raw ``send(bytes)`` / ``recv() -> bytes``
callables and assumes an ordered, reliable stream (TCP, in-process
queues).  That assumption is exactly what a real fleet cannot make:
peers hang mid-frame, links flap, and a lock-step protocol over a
silent socket blocks forever.  This module makes the channel a first-
class object:

* :class:`Transport` — the abstraction: ``send(frame)`` /
  ``recv(timeout) -> frame`` / ``close()``.  :class:`SyncSession.sync`
  accepts one directly (the callable API remains as a shim).
* :class:`CallableTransport` — wraps the legacy callable pair.
* :class:`QueuePairTransport` / :func:`queue_pair` — paired in-process
  endpoints over queues (the test/bench transport, fault-injectable).
* :class:`TcpTransport` — length-prefixed frames over a socket (the
  framing ``examples/replicate_tcp.py`` always used, as a class).
* :class:`ResilientTransport` — the hardening layer: wraps any frame
  transport in a windowed selective-repeat ARQ (sequence numbers,
  cumulative + selective acks, CRC-guarded envelopes) with per-leg
  deadlines, bounded exponential backoff with jitter, and a finite
  retry budget.  Loss, duplication, truncation, reordering-by-delay
  and transient disconnects below it are absorbed; what escapes is
  always a :class:`~crdt_tpu.error.TransportError` subclass —
  :class:`~crdt_tpu.error.SyncTimeoutError` when a leg deadline
  elapses, :class:`~crdt_tpu.error.PeerUnavailableError` when the
  retry budget runs dry — never an unbounded spin.

The ARQ keeps up to ``RetryPolicy.window`` frames in flight per
direction (default 16; ``window=1`` degenerates to the original PR 5
stop-and-wait, byte-for-byte).  ``send`` returns as soon as the frame
is on the wire and the window has room for the next one, so a
streaming producer overlaps encode with the wire instead of blocking
one RTT per frame; per-frame retransmit timers ride the PR 13
adaptive RTO.  The receive path delivers strictly in order: frames
that arrive ahead of a loss are buffered and answered with a
selective ack (SACK) so the sender retransmits only the missing
frames.  Acks are cumulative (``ACK k`` means every seq ``<= k``
arrived), which is exactly what a stop-and-wait peer already speaks —
mixed windows interoperate at the envelope level, and sessions
negotiate the window via the HELLO capability mechanism
(:meth:`ResilientTransport.negotiate_window`), degrading loudly to
stop-and-wait (``cluster.transport.fallback.window``) against a peer
that never advertised one.  Each direction of a link keeps an
independent sequence space; duplicates are re-acked without
re-delivery, so retransmits are idempotent end to end.
"""

from __future__ import annotations

import dataclasses
import queue
import random
import re
import select
import socket
import struct
import time
import zlib
from collections import deque
from typing import Callable, Optional, Tuple

from ..error import (
    PeerUnavailableError,
    SyncTimeoutError,
    TransportClosedError,
    TransportError,
    TransportFrameError,
)
from ..obs import events as obs_events
from ..obs import metrics as obs_metrics
from ..obs.latency import RttEstimator
from ..utils import tracing


class Transport:
    """A connected, frame-oriented byte channel between two peers.

    ``send`` ships one opaque frame; ``recv`` blocks up to ``timeout``
    seconds (None = the transport's own default) for the next frame.
    Failures speak the :class:`~crdt_tpu.error.TransportError` taxonomy:
    ``recv`` raises :class:`~crdt_tpu.error.SyncTimeoutError` on
    timeout and :class:`~crdt_tpu.error.TransportClosedError` when the
    peer hung up.
    """

    def send(self, frame: bytes) -> None:
        raise NotImplementedError

    def recv(self, timeout: Optional[float] = None) -> bytes:
        raise NotImplementedError

    def close(self) -> None:  # idempotent by contract
        pass

    def __enter__(self) -> "Transport":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class CallableTransport(Transport):
    """The legacy ``(send, recv)`` callable pair as a :class:`Transport`.

    The callables predate timeouts, so ``recv``'s ``timeout`` is advisory
    only (the underlying callable blocks however it always did); use a
    real transport class when deadlines matter.
    """

    def __init__(self, send: Callable[[bytes], None],
                 recv: Callable[[], bytes]):
        self._send = send
        self._recv = recv

    def send(self, frame: bytes) -> None:
        self._send(frame)

    def recv(self, timeout: Optional[float] = None) -> bytes:
        return self._recv()


class QueuePairTransport(Transport):
    """One endpoint of an in-process frame channel over two queues.

    ``close`` pushes a sentinel so the peer's ``recv`` raises
    :class:`~crdt_tpu.error.TransportClosedError` instead of waiting out
    its timeout — the in-process analogue of a TCP FIN.
    """

    _CLOSED = object()

    def __init__(self, out_q: "queue.Queue", in_q: "queue.Queue",
                 default_timeout: float = 120.0):
        self._out = out_q
        self._in = in_q
        self._default_timeout = default_timeout
        self._closed = False

    def send(self, frame: bytes) -> None:
        if self._closed:
            raise TransportClosedError("queue transport is closed")
        self._out.put(bytes(frame))

    def recv(self, timeout: Optional[float] = None) -> bytes:
        if self._closed:
            raise TransportClosedError("queue transport is closed")
        t = self._default_timeout if timeout is None else timeout
        try:
            item = self._in.get(timeout=t)
        except queue.Empty:
            raise SyncTimeoutError(
                f"no frame from peer within {t:.3f}s"
            ) from None
        if item is self._CLOSED:
            self._in.put(item)  # every later recv sees closed too
            raise TransportClosedError("peer closed the queue transport")
        return item

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._out.put(self._CLOSED)


def queue_pair(default_timeout: float = 120.0
               ) -> Tuple[QueuePairTransport, QueuePairTransport]:
    """Two connected in-process endpoints (A's sends are B's recvs and
    vice versa) — the bench/test link, and the substrate the fault
    injector (:mod:`crdt_tpu.cluster.faults`) wraps."""
    a_to_b: "queue.Queue" = queue.Queue()
    b_to_a: "queue.Queue" = queue.Queue()
    return (
        QueuePairTransport(a_to_b, b_to_a, default_timeout),
        QueuePairTransport(b_to_a, a_to_b, default_timeout),
    )


class TcpTransport(Transport):
    """Length-prefixed frames (``<I`` prefix) over a connected socket —
    the framing the TCP example always used, packaged so the cluster
    runtime and the example share one implementation.

    Sessions are symmetric: both peers send their hello and digest
    before either reads.  A frame larger than the socket buffers (a
    125,000-object digest vector is 1 MB) would then block both
    ``sendall``\\ s for ever, so ``send`` drains the peer's incoming
    bytes into a buffer while the socket is full, and ``recv`` reads
    that buffer first."""

    _LEN = struct.Struct("<I")
    _CHUNK = 1 << 20

    def __init__(self, sock: socket.socket, default_timeout: float = 120.0):
        self._sock = sock
        self._default_timeout = default_timeout
        self._rx = bytearray()  # bytes read ahead while sending
        self._eof = False

    def send(self, frame: bytes) -> None:
        data = memoryview(self._LEN.pack(len(frame)) + frame)
        poll = select.poll()
        try:
            while data:
                poll.register(self._sock, select.POLLOUT
                              | (0 if self._eof else select.POLLIN))
                events = poll.poll(self._default_timeout * 1000)
                if not events:
                    raise SyncTimeoutError(
                        f"socket send made no progress within "
                        f"{self._default_timeout:.3f}s")
                ev = events[0][1]
                if ev & select.POLLIN and not self._eof:
                    chunk = self._sock.recv(self._CHUNK)
                    self._rx.extend(chunk)
                    self._eof = not chunk
                if ev & (select.POLLOUT | select.POLLERR | select.POLLHUP):
                    try:  # what fits now; the rest after the next poll
                        data = data[self._sock.send(data,
                                                    socket.MSG_DONTWAIT):]
                    except BlockingIOError:
                        pass
        except (ConnectionError, BrokenPipeError, OSError) as e:
            raise TransportClosedError(f"socket send failed: {e}") from e

    def _recv_exact(self, n: int) -> bytes:
        buf = self._rx[:n]
        del self._rx[:n]
        while len(buf) < n:
            if self._eof:
                raise TransportClosedError("peer closed the socket mid-frame")
            try:
                chunk = self._sock.recv(n - len(buf))
            except socket.timeout:
                raise SyncTimeoutError(
                    f"socket recv timed out mid-frame ({len(buf)}/{n} bytes)"
                ) from None
            except (ConnectionError, OSError) as e:
                raise TransportClosedError(f"socket recv failed: {e}") from e
            if not chunk:
                raise TransportClosedError("peer closed the socket mid-frame")
            buf.extend(chunk)
        return bytes(buf)

    def recv(self, timeout: Optional[float] = None) -> bytes:
        t = self._default_timeout if timeout is None else timeout
        self._sock.settimeout(t)
        (ln,) = self._LEN.unpack(self._recv_exact(self._LEN.size))
        return self._recv_exact(ln)

    def close(self) -> None:
        # shutdown first: close() alone leaves a thread blocked in this
        # socket's recv/send asleep, and the peer never sees EOF
        for end in (lambda: self._sock.shutdown(socket.SHUT_RDWR),
                    self._sock.close):
            try:
                end()
            except OSError:
                pass


# ---- the resilient (ARQ) wrapper -------------------------------------------


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Deadlines, backoff shape, and the retry budget of one
    :class:`ResilientTransport`.

    ``ack_timeout_s`` is the initial retransmit timer; each retransmit
    multiplies it by ``backoff_factor`` up to ``max_backoff_s``, with
    ``jitter`` (a fraction of the delay) drawn from the transport's
    seeded RNG so a fleet of retrying peers doesn't beat in lockstep.
    ``retry_budget`` bounds the TOTAL retransmits + transient-error
    retries over the transport's lifetime — the no-unbounded-spin
    guarantee: a dead peer costs at most
    ``retry_budget × max_backoff_s`` seconds before
    :class:`~crdt_tpu.error.PeerUnavailableError`.

    With ``adaptive`` (the default), the retransmit timer tracks the
    link's measured round trip instead of the static ``ack_timeout_s``:
    the transport's Jacobson/Karels estimator yields ``srtt +
    4·rttvar``, clamped into ``[min_rto_s, max_backoff_s]`` — so a
    loopback link retransmits in milliseconds instead of waiting a
    WAN-sized static timer, and a 200 ms-RTT link stops spuriously
    retransmitting frames whose acks are merely in flight.  Until the
    first sample the static ``ack_timeout_s`` applies (clamped to the
    same bounds), and the bounds are HARD either way — an estimator
    poisoned by a clock step can never push the timer outside the
    policy (pinned in ``tests/test_latency.py``).

    ``window`` is the in-flight ceiling: how many DATA frames may be
    cumulatively unacked at once.  ``1`` is classic stop-and-wait
    (every ``send`` blocks for its ack — the pre-window behavior,
    exactly); the default ``16`` lets a streaming producer keep a
    window of frames on the wire and blocks ``send`` only when the
    window is full.  The window a session actually runs at is the
    minimum of both peers' configured windows, negotiated over HELLO
    (:meth:`ResilientTransport.negotiate_window`).
    """

    send_deadline_s: float = 30.0
    recv_deadline_s: float = 30.0
    ack_timeout_s: float = 0.1
    backoff_factor: float = 2.0
    max_backoff_s: float = 2.0
    jitter: float = 0.25
    retry_budget: int = 64
    adaptive: bool = True
    min_rto_s: float = 0.01
    window: int = 16


_DATA = 0x01
_ACK = 0x02
_SACK = 0x03

#: ARQ envelope: kind(1) | seq(8) | crc32(4) | payload_len(4) | payload
_ENV = struct.Struct("<BQII")


def encode_envelope(kind: int, seq: int, payload: bytes = b"") -> bytes:
    return _ENV.pack(kind, seq, zlib.crc32(payload), len(payload)) + payload


def decode_envelope(env: bytes) -> Tuple[int, int, bytes]:
    """``(kind, seq, payload)`` of a validated ARQ envelope.  Raises
    :class:`~crdt_tpu.error.TransportFrameError` on truncation, length
    or CRC mismatch, or an unknown kind — the receiver treats all of
    those exactly like loss (drop; the sender retransmits)."""
    if len(env) < _ENV.size:
        raise TransportFrameError(
            f"truncated ARQ envelope: {len(env)} bytes < "
            f"{_ENV.size}-byte header"
        )
    kind, seq, crc, plen = _ENV.unpack_from(env)
    if kind not in (_DATA, _ACK, _SACK):
        raise TransportFrameError(f"unknown ARQ envelope kind {kind:#04x}")
    payload = env[_ENV.size:]
    if len(payload) != plen:
        raise TransportFrameError(
            f"ARQ envelope length mismatch: header says {plen}, "
            f"envelope carries {len(payload)}"
        )
    if zlib.crc32(payload) != crc:
        raise TransportFrameError("ARQ envelope CRC mismatch")
    return kind, seq, payload


class _InFlight:
    """One unacked DATA frame on the sender side of the window."""

    __slots__ = ("env", "seq", "t_first", "deadline", "expiry",
                 "attempts", "sent", "sacked")

    def __init__(self, env: bytes, seq: int, now: float,
                 send_deadline_s: float):
        self.env = env
        self.seq = seq
        self.t_first = now          # first transmission (Karn base)
        self.deadline = now + send_deadline_s
        self.expiry = now           # due immediately: first tx rides the timer path
        self.attempts = 0           # successful retransmissions so far
        self.sent = False           # at least one successful inner.send
        self.sacked = False         # peer holds it (selective ack)


class ResilientTransport(Transport):
    """Reliable delivery over an unreliable frame transport.

    Wraps ``inner`` in a windowed selective-repeat ARQ: every ``send``
    ships a sequence-numbered, CRC-guarded DATA envelope and returns
    as soon as the in-flight window (``policy.window``, default 16)
    has room for the next frame; every ``recv`` delivers in-order
    payloads exactly once (out-of-order arrivals are buffered and
    selectively acked, duplicates re-acked and suppressed, corrupt
    envelopes dropped as loss).  A single pump services both
    directions: whichever public leg is blocked — ``send`` on a full
    window, ``recv`` on an empty inbox, ``flush``/``close`` on
    stragglers — retransmits expired frames, answers the peer's DATA,
    and retires acked frames.  With ``window=1`` the machine is the
    original stop-and-wait, behavior-identical: ``send`` blocks until
    its own ack.  Designed for one session thread per transport — the
    sync protocol drives exactly one leg at a time, so the state
    machine is deliberately single-threaded and lock-free.

    Ack grammar (the stop-and-wait compatible part): ``ACK k`` is
    cumulative — every DATA seq ``<= k`` is delivered.  ``SACK``
    carries the next-expected seq (everything BELOW it delivered) plus
    a u64 list of out-of-order seqs held past a gap, so the sender
    retransmits only the missing frames.  SACKs are only ever emitted
    when frames arrive out of order, which cannot happen against a
    stop-and-wait sender — an old peer never sees the new kind.

    Failure surface: a leg that exceeds its deadline raises
    :class:`~crdt_tpu.error.SyncTimeoutError`; a transport whose retry
    budget is exhausted (retransmits + transient inner errors) raises
    :class:`~crdt_tpu.error.PeerUnavailableError`.  Both are
    :class:`~crdt_tpu.error.TransportError`\\s, so the gossip layer
    catches one type.  A closed link is asymmetric by design: closure
    on the SEND side is retried with backoff (an injected flap window
    heals; a TCP write may race a peer's clean shutdown), but closure
    on the RECEIVE side is terminal (``PeerUnavailableError``
    immediately) — a peer that hung up sends no more frames, and
    waiting out the deadline would only hold session locks hostage.

    Per-instance tallies (``retransmits``, ``duplicates``, ``corrupt``,
    ``transient_errors``, ``sacks_sent``, ``frames_sacked``,
    ``ooo_buffered``, ``window_hw``) mirror the
    ``cluster.transport.*`` counters for tests that need this link's
    numbers rather than the process's.

    Every clean first-transmission ack also feeds a Jacobson/Karels
    :class:`~crdt_tpu.obs.latency.RttEstimator` (``rtt`` — Karn's rule:
    retransmitted frames never sample, their ack could answer either
    copy; a selectively-acked frame samples at SACK time, when the
    round trip actually completed), published per link as
    ``cluster.transport.<link>.rtt_*`` gauges and, under
    ``policy.adaptive``, driving the per-frame retransmit timers
    (:meth:`current_rto`) and the close-drain quiet window in place of
    the static ``ack_timeout_s``.
    """

    def __init__(self, inner: Transport,
                 policy: Optional[RetryPolicy] = None, *,
                 name: str = "link", seed: int = 0):
        self._inner = inner
        self.policy = policy or RetryPolicy()
        self.name = name
        self._rng = random.Random(seed)
        self._send_seq = 0     # next DATA sequence number to ship
        self._recv_next = 0    # next in-order sequence number to deliver
        self._inbox: deque = deque()
        self._inflight: "dict[int, _InFlight]" = {}  # seq -> window slot
        self._ooo: "dict[int, bytes]" = {}  # out-of-order receive buffer
        self._window = max(1, int(self.policy.window))
        self._budget = self.policy.retry_budget
        self.retransmits = 0
        self.duplicates = 0
        self.corrupt = 0
        self.transient_errors = 0
        self.sacks_sent = 0
        self.frames_sacked = 0
        self.ooo_buffered = 0
        self.window_hw = 0     # frames-in-flight high-water mark
        #: the link's RTT estimator — sampled by the ack loop, read by
        #: the adaptive retransmit timer and the rtt_* gauges
        self.rtt = RttEstimator()
        # metric-label form of the link name: one dotted segment
        # (cluster.transport.<label>.rtt_srtt_s must stay one family
        # per link for the namespace manifest)
        self._label = re.sub(r"[^A-Za-z0-9_]", "_", name) or "link"

    # -- window negotiation --------------------------------------------------

    @property
    def window(self) -> int:
        """The in-flight window currently in force (post-negotiation)."""
        return self._window

    def negotiate_window(self, peer_window: int) -> int:
        """Clamp the window to what the peer advertised over HELLO.

        A session runs at ``min(configured, peer)``; a peer that never
        advertised a window (``0`` — an old stop-and-wait build, or a
        session below protocol v4) forces ``1``.  Degrading below the
        configured window is LOUD (``cluster.transport.fallback.window``
        + a flight-recorder event) but never a protocol error: the
        cumulative-ack grammar is what a stop-and-wait peer already
        speaks, so mixed fleets converge byte-identically, just without
        pipelining on this link.
        """
        configured = max(1, int(self.policy.window))
        negotiated = max(1, min(configured, int(peer_window)))
        if negotiated < configured:
            tracing.count("cluster.transport.fallback.window")
            obs_events.record(
                "cluster.transport.fallback", link=self.name,
                reason="window", configured=configured,
                peer=int(peer_window), negotiated=negotiated,
            )
        self._window = negotiated
        return negotiated

    # -- budget / backoff ----------------------------------------------------

    def _spend(self, reason: str) -> None:
        self._budget -= 1
        if self._budget < 0:
            raise PeerUnavailableError(
                f"transport {self.name}: retry budget "
                f"({self.policy.retry_budget}) exhausted ({reason})"
            )

    def current_rto(self) -> float:
        """The retransmit timer in force: ``srtt + 4·rttvar`` clamped
        to ``[min_rto_s, max_backoff_s]`` once the estimator has a
        sample (and ``policy.adaptive``), else the static
        ``ack_timeout_s`` clamped to the same bounds — the timer can
        never leave the policy's envelope."""
        p = self.policy
        if not p.adaptive:
            return p.ack_timeout_s
        rto = self.rtt.rto(p.min_rto_s, p.max_backoff_s,
                           default_s=p.ack_timeout_s)
        return p.ack_timeout_s if rto is None else rto

    def _sample_rtt(self, sample_s: float) -> None:
        self.rtt.observe(sample_s)
        snap = self.rtt.snapshot()
        reg = obs_metrics.registry()
        reg.gauge_set(f"cluster.transport.{self._label}.rtt_srtt_s",
                      snap["srtt_s"] or 0.0)
        reg.gauge_set(f"cluster.transport.{self._label}.rtt_rttvar_s",
                      snap["rttvar_s"] or 0.0)
        reg.gauge_set(f"cluster.transport.{self._label}.rtt_rto_s",
                      self.current_rto())
        reg.gauge_set(f"cluster.transport.{self._label}.rtt_samples",
                      snap["samples"])

    def _delay(self, attempt: int) -> float:
        p = self.policy
        d = min(p.max_backoff_s,
                self.current_rto() * (p.backoff_factor ** attempt))
        return d * (1.0 + p.jitter * (2.0 * self._rng.random() - 1.0))

    def _transient(self, leg: str, err: TransportError) -> None:
        """One recoverable inner-transport failure: count it, spend
        budget, and let the caller back off and retry."""
        self.transient_errors += 1
        tracing.count("cluster.transport.transient_errors")
        self._spend(f"{leg}: {err}")

    # -- receive-path demux --------------------------------------------------

    def _ooo_cap(self) -> int:
        # the receive buffer must cover the peer's window (symmetric
        # fleets configure both ends alike); 4x + a floor absorbs a
        # misconfigured peer without unbounded memory
        return max(64, 4 * self._window)

    def _send_ack(self, seq: int) -> None:
        try:
            self._inner.send(encode_envelope(_ACK, seq))
        except TransportError as e:
            # a lost ack is identical to a dropped one: the peer
            # retransmits and we re-ack; spend budget so a dead link
            # still terminates
            self._transient("ack", e)

    def _send_sack(self) -> None:
        """Selective ack: next-expected seq plus the out-of-order seqs
        held past the gap (capped; the cumulative part alone keeps the
        sender correct, the list only suppresses retransmits)."""
        seqs = sorted(self._ooo)[:128]
        payload = struct.pack(f"<{len(seqs)}Q", *seqs)
        try:
            self._inner.send(encode_envelope(_SACK, self._recv_next, payload))
            self.sacks_sent += 1
            tracing.count("cluster.transport.window.sacks")
        except TransportError as e:
            self._transient("ack", e)

    def _ack_current(self) -> None:
        """Answer the sender with our current receive state: a SACK
        while a gap is open (so only the missing frames retransmit), a
        plain cumulative ACK otherwise — which re-acks the WHOLE
        delivered prefix, not just the last frame, so a close-drain
        answer covers every straggler in the peer's window at once."""
        if self._ooo:
            self._send_sack()
        elif self._recv_next > 0:
            self._send_ack(self._recv_next - 1)

    def _on_data(self, seq: int, payload: bytes) -> None:
        if seq == self._recv_next:
            self._recv_next += 1
            self._inbox.append(payload)
            # a gap just closed: drain every consecutive buffered frame
            while self._recv_next in self._ooo:
                self._inbox.append(self._ooo.pop(self._recv_next))
                self._recv_next += 1
            self._ack_current()
        elif seq < self._recv_next or seq in self._ooo:
            self.duplicates += 1
            tracing.count("cluster.transport.duplicates")
            self._ack_current()
        else:
            # ahead of a loss (or a delayed predecessor): buffer it and
            # tell the sender exactly what we hold — selective repeat
            if len(self._ooo) >= self._ooo_cap():
                return  # treat as loss; the peer retransmits
            self._ooo[seq] = payload
            self.ooo_buffered += 1
            tracing.count("cluster.transport.window.ooo")
            self._send_sack()

    def _on_ack(self, acked: int) -> None:
        """Cumulative ack: retire every in-flight frame ``<= acked``."""
        now = time.monotonic()
        for seq in [s for s in self._inflight if s <= acked]:
            p = self._inflight.pop(seq)
            if p.attempts == 0 and not p.sacked:
                # Karn's rule: only a frame transmitted exactly once
                # yields an unambiguous round-trip sample (sacked
                # frames already sampled at SACK time)
                self._sample_rtt(now - p.t_first)

    def _on_sack(self, next_expected: int, payload: bytes) -> None:
        self._on_ack(next_expected - 1)
        now = time.monotonic()
        n = len(payload) // 8
        for (seq,) in struct.iter_unpack("<Q", payload[:n * 8]):
            p = self._inflight.get(seq)
            if p is not None and not p.sacked:
                p.sacked = True
                self.frames_sacked += 1
                tracing.count("cluster.transport.window.sacked")
                if p.attempts == 0:
                    self._sample_rtt(now - p.t_first)

    def _dispatch(self, env: bytes) -> None:
        """Decode one envelope; deliver DATA into the inbox, retire
        acked window slots.  Corrupt envelopes count and vanish — loss
        semantics."""
        try:
            kind, seq, payload = decode_envelope(env)
        except TransportFrameError:
            self.corrupt += 1
            tracing.count("cluster.transport.corrupt")
            return
        if kind == _DATA:
            self._on_data(seq, payload)
        elif kind == _ACK:
            self._on_ack(seq)
        else:
            self._on_sack(seq, payload)

    # -- the unified pump ----------------------------------------------------

    def _service_timers(self) -> Optional[float]:
        """(Re)transmit every in-flight frame whose timer expired;
        return the next timer's due time (None when nothing is armed).
        A frame past its send deadline raises — from whichever public
        leg is pumping, which is the leg holding the session up."""
        now = time.monotonic()
        nxt: Optional[float] = None
        for p in list(self._inflight.values()):
            if p.sacked:
                continue
            if now >= p.deadline:
                tracing.count("cluster.transport.timeouts")
                raise SyncTimeoutError(
                    f"transport {self.name}: no ack for seq={p.seq} within "
                    f"{self.policy.send_deadline_s:.3f}s "
                    f"({p.attempts + 1} attempts)"
                )
            if now >= p.expiry:
                delay = self._delay(p.attempts)
                try:
                    self._inner.send(p.env)
                except TransportError as e:
                    # send-side closure/flap: retried with backoff (the
                    # injected window heals); budget bounds the spin
                    self._transient("send", e)
                    p.expiry = now + min(delay, self.policy.ack_timeout_s)
                else:
                    if p.sent:
                        p.attempts += 1
                        self.retransmits += 1
                        tracing.count("cluster.transport.retransmits")
                        self._spend(f"retransmit seq={p.seq}")
                        obs_events.record(
                            "cluster.transport.retry", link=self.name,
                            seq=p.seq, attempt=p.attempts - 1,
                            backoff_s=round(delay, 4),
                        )
                    else:
                        p.sent = True
                        p.t_first = now
                    p.expiry = now + self._delay(p.attempts)
            t = min(p.expiry, p.deadline)
            nxt = t if nxt is None else min(nxt, t)
        return nxt

    def _pump(self, deadline: float, *,
              idle_wait: Optional[float] = None) -> bool:
        """One scheduler step: service retransmit timers, then wait for
        at most one inner envelope (bounded by the nearest timer, the
        caller's deadline, and ``idle_wait``) and dispatch it.  Both
        peers of a streaming session sit in this loop at once — DATA,
        ACKs and SACKs are all handled regardless of which public leg
        is blocked.  Returns True when an envelope was dispatched."""
        nxt = self._service_timers()
        now = time.monotonic()
        wait = max(0.0, deadline - now)
        if nxt is not None:
            wait = min(wait, max(0.0, nxt - now))
        if idle_wait is not None:
            wait = min(wait, idle_wait)
        try:
            # floor: timeout=0 would flip a socket non-blocking and
            # surface EWOULDBLOCK as a closed link
            env = self._inner.recv(timeout=max(wait, 0.001))
        except SyncTimeoutError:
            return False
        except TransportClosedError as e:
            # closed on the RECEIVE path is terminal: a flap window
            # only ever closes the injected send side, and a peer
            # that hung up will never speak again — fail now, not at
            # the deadline (the lingering-acceptor cascade)
            raise PeerUnavailableError(
                f"transport {self.name}: peer closed the link: {e}"
            ) from e
        except TransportError as e:
            # a transient inner fault mid-pump: the peer's retransmit
            # covers any data; wait out the blip
            self._transient("recv", e)
            time.sleep(min(self.policy.ack_timeout_s,
                           max(deadline - time.monotonic(), 0)))
            return False
        self._dispatch(env)
        return True

    # -- the public legs -----------------------------------------------------

    def send(self, frame: bytes) -> None:
        """Ship one frame.  Returns once the frame is on the wire AND
        the window has room for the next one — so with ``window=1``
        this blocks for the frame's own ack (stop-and-wait), and with
        a wider window a streaming producer only blocks when a full
        window of frames is unacked."""
        p = self.policy
        seq = self._send_seq
        self._send_seq += 1
        now = time.monotonic()
        slot = _InFlight(encode_envelope(_DATA, seq, frame), seq, now,
                         p.send_deadline_s)
        self._inflight[seq] = slot
        if len(self._inflight) > self.window_hw:
            self.window_hw = len(self._inflight)
            obs_metrics.registry().gauge_set(
                f"cluster.transport.{self._label}.window_inflight_hw",
                self.window_hw)
        deadline = slot.deadline
        self._service_timers()  # first transmission (slot is due now)
        while len(self._inflight) >= self._window:
            # window full: pump until a slot retires (the per-frame
            # deadlines bound this — the oldest frame raises)
            self._pump(deadline)

    def flush(self, timeout: Optional[float] = None) -> None:
        """Pump until every in-flight frame is cumulatively acked —
        the delivery barrier a streaming producer calls before
        asserting on the peer's state (``send`` alone only guarantees
        window admission).  Raises like ``send``: per-frame deadlines
        and the retry budget both apply."""
        budget_s = self.policy.send_deadline_s if timeout is None else timeout
        deadline = time.monotonic() + budget_s
        while self._inflight:
            if time.monotonic() >= deadline:
                tracing.count("cluster.transport.timeouts")
                raise SyncTimeoutError(
                    f"transport {self.name}: {len(self._inflight)} frames "
                    f"still unacked after {budget_s:.3f}s flush"
                )
            self._pump(deadline)

    def recv(self, timeout: Optional[float] = None) -> bytes:
        p = self.policy
        budget_s = p.recv_deadline_s if timeout is None else timeout
        deadline = time.monotonic() + budget_s
        while not self._inbox:
            if time.monotonic() >= deadline:
                tracing.count("cluster.transport.timeouts")
                raise SyncTimeoutError(
                    f"transport {self.name}: no frame from peer within "
                    f"{budget_s:.3f}s"
                )
            self._pump(deadline)
        return self._inbox.popleft()

    def close(self) -> None:
        # the ARQ last-ack problem (TCP's TIME_WAIT, in miniature),
        # generalized to a window: our tail frames may still be
        # unacked, and our final ACK may have been lost — in which
        # case the peer is about to retransmit a whole window of
        # stragglers against a dead link and fail a session that
        # actually converged.  Drain briefly before closing: keep
        # servicing our own retransmit timers until the window empties
        # and keep answering the peer's envelopes (every answer is
        # CUMULATIVE, so one ACK/SACK re-covers the peer's whole
        # straggler window, not just its last frame) until the link
        # goes quiet for ~2 retransmit timers, the peer closes, or the
        # cap elapses.  Over a lossless inner transport (TCP) the peer
        # closes almost immediately and the drain costs one quiet
        # window at most.  The quiet window follows the ADAPTIVE timer
        # (the peer's retransmit would arrive within its RTO, which
        # tracks ours): a loopback link drains in milliseconds; the
        # policy bounds still cap the window at the static drain's 1 s
        # worst case, so the PR 5 TIME_WAIT fix keeps its wall-time
        # envelope — one extra envelope when a window of our own
        # frames needs flushing first.
        rto = self.current_rto()
        quiet_s = min(2.0 * rto, 1.0)
        cap = time.monotonic() + 3.0 * quiet_s + (
            3.0 * quiet_s if self._inflight else 0.0)
        last_activity = time.monotonic()
        while (time.monotonic() < cap
               and (self._inflight
                    or time.monotonic() - last_activity < quiet_s)):
            try:
                if self._pump(cap, idle_wait=min(
                        rto, max(cap - time.monotonic(), 0.001))):
                    last_activity = time.monotonic()
            except TransportError:
                break  # peer hung up, budget dry, or a frame deadline
                # lapsed mid-drain: stop being polite
        self._inner.close()
