"""The serve loop: read batches overlapped with op folds.

Reads ride the :mod:`crdt_tpu.batch.wireloop` staging discipline — a
bounded decode queue IS the staging pool (at most ``depth`` decoded
request batches buffered, so a slow gather backpressures the decoder
instead of ballooning host memory), frame decode on a background
thread while the main thread runs the jitted gathers, stall events
past ``stall_threshold_s``, and per-stage wall accounting so the
bench can show the overlap won.

Wired into :class:`~crdt_tpu.cluster.gossip.ClusterNode` via
``serve_reads``: reads take a consistent ``batch`` snapshot (the
property read under the node's state lock) and run OUTSIDE the
``_busy`` session lock — gossip, writes, and reads coexist; a read
can never block a sync session and vice versa.  The only waiting a
read ever does is an explicit consistency park: a read-your-writes /
monotonic floor not yet visible re-polls briefly (nudging the op
drain through the same non-blocking ``_busy`` acquire
``submit_ops`` uses) and then rejects loudly with
:class:`~crdt_tpu.error.ConsistencyUnavailableError`.  A
frontier-covered read (PR 15 stability frontier) is provably
converged — it is served lock-free with zero coordination, from any
replica.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterable, Optional

import numpy as np

from ..error import ConsistencyUnavailableError
from ..utils import tracing
from . import consistency as cons
from .query import (ReadRequest, ResultFrame, _plane_rows, gather,
                    infer_kind)

_SENTINEL = object()


def visible_vv(batch) -> np.ndarray:
    """The batch's visible version vector (``uint64[W]`` — pointwise
    max of every object's clock, flattened for PN planes), or a
    width-0 vector for clockless types.  Memoized per batch object
    beside the digest (:mod:`crdt_tpu.sync.digest`), so idle serving
    recomputes nothing."""
    from ..sync import digest as sync_digest

    vv = sync_digest.version_vector(batch)
    if vv is None:
        return np.zeros(0, np.uint64)
    return np.asarray(vv, np.uint64).reshape(-1)


class ServeLoop:
    """Session-consistent read serving against one cluster node.

    ``serve`` answers a decoded :class:`ReadRequest`;
    ``serve_frames`` runs whole encoded request streams through the
    decode→admit→gather→encode pipeline with the decode leg
    overlapped on a background thread."""

    def __init__(self, node, *, depth: int = 4,
                 park_timeout_s: float = 0.25,
                 park_poll_s: float = 0.005,
                 stall_threshold_s: float = 0.1):
        if depth < 2:
            raise ValueError("pipelining needs a decode queue depth >= 2")
        self.node = node
        self.depth = depth
        self.park_timeout_s = park_timeout_s
        self.park_poll_s = park_poll_s
        self.stall_threshold_s = stall_threshold_s

    # -- clocks -----------------------------------------------------------

    def token(self) -> np.ndarray:
        """The node's current monotonic-reads token — the visible
        version vector a client should carry into its next request."""
        return visible_vv(self.node.batch)

    def _frontier(self):
        """(frontier_vv, subtree_clocks, span) from the node's
        stability tracker — (None, None, 1) when no frontier has
        formed (no converged exchange evidence yet)."""
        tracker = getattr(self.node, "stability", None)
        if tracker is None:
            return None, None, 1
        fc = tracker.frontier_clock()
        if fc is None:
            return None, None, 1
        from ..obs.stability import subtree_layout

        n = int(self.node.batch.clock.shape[0]) \
            if hasattr(self.node.batch, "clock") else 0
        _, span = subtree_layout(n)
        return (np.asarray(fc, np.uint64),
                tracker.subtree_frontier_clocks(), span)

    # -- one batch --------------------------------------------------------

    def serve(self, req: ReadRequest) -> ResultFrame:
        """Admit → (park) → gather → stamp.  Raises
        :class:`ConsistencyUnavailableError` on a terminal rejection;
        every other path returns a frame whose ``token`` is the
        version vector of the exact snapshot the rows were gathered
        from."""
        from ..obs import metrics as obs_metrics

        reg = obs_metrics.registry()
        t0 = time.perf_counter()
        deadline = None
        parked = False
        with tracing.span("serve.leg.admit"):
            while True:
                # snapshot FIRST: admission evidence and the gather must
                # come from the same batch object, or a concurrent fold
                # could admit against a newer clock and gather older rows
                snapshot = self.node.batch
                vv = visible_vv(snapshot)
                frontier_vv, subtree_clocks, span = self._frontier()
                ruling = cons.admit(req.mode, req.require, vv,
                                    frontier_vv=frontier_vv)
                if ruling.admitted:
                    break
                if ruling.reason == "not_visible" and self.park_timeout_s > 0:
                    now = time.perf_counter()
                    if deadline is None:
                        deadline = now + self.park_timeout_s
                        parked = True
                        tracing.count(f"serve.park.{req.mode}")
                    if now < deadline:
                        # nudge pending ops toward visibility, then re-poll
                        drain = getattr(self.node, "try_drain", None)
                        if drain is not None:
                            drain()
                        time.sleep(self.park_poll_s)
                        continue
                tracing.count(f"serve.reject.{req.mode}")
                raise ConsistencyUnavailableError(
                    f"{req.mode} read not servable: {ruling.reason} "
                    f"(parked {'yes' if parked else 'no'}, "
                    f"timeout {self.park_timeout_s}s)",
                    mode=req.mode, reason=ruling.reason or "",
                )
            tracing.count(f"serve.admit.{req.mode}")
            if parked:
                reg.observe("serve.park_wait_s",
                            time.perf_counter() - t0)
            # node serving is single-kind (the node holds one dense batch);
            # a request naming a different kind is a caller error, not wire
            node_kind = infer_kind(snapshot)
            if len(req) and not (req.kind == node_kind).all():
                raise ValueError(
                    f"read batch names kind(s) "
                    f"{sorted(set(int(k) for k in req.kind))} but this node "
                    f"serves kind {node_kind} only"
                )
        # the node's view cache: the node drops its view before it folds
        frame = gather(snapshot, req.obj, member=req.member,
                       kind=node_kind,
                       views=getattr(self.node, "serve_views", None))
        frame.token = vv
        if len(req):
            # read heat: this gather batch's rows, attributed to the
            # admission mode (node-private tracker when the node has
            # one; the process-global otherwise)
            with tracing.span("serve.leg.heat"):
                heat = getattr(self.node, "heat", None)
                if heat is None:
                    from ..obs import heat as obs_heat
                    heat = obs_heat.tracker()
                heat.record_reads(req.obj, _plane_rows(snapshot, node_kind),
                                  mode=req.mode)
        if req.mode == cons.MODE_FRONTIER:
            frame.status = cons.stability_statuses(
                frame, subtree_clocks, span)
            bad = int(np.sum(frame.status != 0))
            if bad:
                tracing.count("serve.not_stable_rows", bad)
        wall = time.perf_counter() - t0
        reg.observe(f"serve.latency.{req.mode}", wall)
        if wall > 0 and len(frame):
            reg.gauge_set("serve.reads_per_s", len(frame) / wall)
        return frame

    # -- pipelined frame streams -----------------------------------------

    def serve_frames(self, frames: Iterable[bytes], *,
                     overlap: bool = True) -> tuple:
        """Serve every encoded read-request frame of ``frames``,
        returning ``(result_frames, stats)`` with the wire-loop
        per-stage accounting: ``stats = {"frames", "rows",
        "rejected", "pipeline", "stage_s": {decode, serve, encode},
        "e2e_s"}``.  A batch that terminally fails admission yields
        ``None`` in the result list (the typed error is counted and
        recorded, never silently dropped)."""
        from ..obs import events as obs_events
        from ..obs import metrics as obs_metrics
        from .wire import decode_read_request, encode_result_frame

        frames = list(frames)
        stage_s = {"decode": 0.0, "serve": 0.0, "encode": 0.0}
        stats = {"frames": len(frames), "rows": 0, "rejected": 0}
        t_all0 = time.perf_counter()
        reg = obs_metrics.registry()
        g_depth = reg.gauge("serve.batch_depth")
        num_objects = None
        batch = self.node.batch
        if hasattr(batch, "clock"):
            num_objects = int(batch.clock.shape[0])

        def decode_one(frame):
            t0 = time.perf_counter()
            req = decode_read_request(frame, num_objects=num_objects)
            stage_s["decode"] += time.perf_counter() - t0
            return req

        if overlap:
            parsed_q: "queue.Queue" = queue.Queue(maxsize=self.depth)

            def worker():
                try:
                    for frame in frames:
                        parsed_q.put(decode_one(frame))
                    parsed_q.put(_SENTINEL)
                except BaseException as e:  # surfaced in the main thread
                    parsed_q.put(e)

            thread = threading.Thread(target=worker, daemon=True,
                                      name="serve-decode")
            thread.start()

            def staged():
                while True:
                    t0 = time.perf_counter()
                    item = parsed_q.get()
                    waited = time.perf_counter() - t0
                    if self.stall_threshold_s \
                            and waited > self.stall_threshold_s:
                        tracing.count("serve.stalls")
                        obs_events.record(
                            "serve.stall", waited_s=round(waited, 4),
                            staging_free=self.depth - parsed_q.qsize(),
                        )
                    g_depth.set(parsed_q.qsize())
                    if item is _SENTINEL:
                        return
                    if isinstance(item, BaseException):
                        raise item
                    yield item

            stream = staged()
        else:
            stream = (decode_one(f) for f in frames)

        out = []
        try:
            for req in stream:
                t0 = time.perf_counter()
                try:
                    frame = self.serve(req)
                except ConsistencyUnavailableError:
                    stats["rejected"] += 1
                    out.append(None)
                    stage_s["serve"] += time.perf_counter() - t0
                    continue
                stage_s["serve"] += time.perf_counter() - t0
                stats["rows"] += len(frame)
                t0 = time.perf_counter()
                out.append(encode_result_frame(frame))
                stage_s["encode"] += time.perf_counter() - t0
        finally:
            if overlap:
                # drain so an abandoned worker never blocks on a full
                # queue holding stale buffers
                while True:
                    try:
                        parsed_q.get_nowait()
                    except queue.Empty:
                        break
                thread.join(timeout=30)

        stats["pipeline"] = "overlapped" if overlap else "serial"
        stats["stage_s"] = {k: round(v, 4) for k, v in stage_s.items()}
        stats["e2e_s"] = round(time.perf_counter() - t_all0, 4)
        return out, stats
