"""Closed loop of read frames against one served replica.

The frames are drawn and encoded at set-up, as a client encodes its
reads (``encode_read_request``), and served back to back by ``clients``
concurrent clients, each taking the next frame in their one order and
cycling, until the window closes: the window measures the reads the
server completes per second, with no arrival rate to cap it.  Each frame
takes the path a client's read takes on the server:
``decode_read_request``, ``ServeLoop(ClusterNode(...)).serve`` and
``encode_result_frame``.  When the time is up no client takes another
frame; the window ends when every frame taken has been answered.  The
checked frames the window did not reach are served after it, untimed.

Traffic keys: ``clients`` (concurrent clients, each a thread),
``frames`` (frames encoded at set-up),
``frame_min``/``frame_max`` (frame sizes, log-uniform), ``order_seed``
(the order of the sizes, the same for every run), ``value_frac`` (share
of ``value()`` reads; the rest are ``contains``, half of a live member
and half of an absent one), ``zipf_s`` (key skew; ranks scattered over
the objects), ``check_frames`` (frames sampled for the check, with the
largest ``check_largest`` always among them), ``limits``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import gen, reference

_KEYS, _MIX, _ORDER, _CHECK = 20, 21, 22, 2
_ABSENT_LO = 1 << 24  # member ids live below 2**24


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.n = ctx.config["objects"]

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from crdt_tpu.batch import OrswotBatch
        from crdt_tpu.cluster.gossip import ClusterNode
        from crdt_tpu.config import CrdtConfig
        from crdt_tpu.serve import ServeLoop
        from crdt_tpu.serve.wire import (decode_read_request,
                                         encode_result_frame)
        from crdt_tpu.utils.interning import Universe

        ctx, cfg = self.ctx, self.ctx.config
        with jax.default_device(ctx.devices[0]):
            planes = gen.fleet_builder(cfg, n=self.n, r=1)(
                gen.device_key(ctx.seed, 1))[0]
        self.draw(np.asarray(planes[1]))
        # the reference's operands, copied before the program holds them:
        # the checked frames' rows and the set clocks' pointwise max
        rows = np.unique(np.concatenate(
            [self.obj[self.bounds[i]:self.bounds[i + 1]]
             for i in self.check_set]))
        self.rows = {int(o): k for k, o in enumerate(rows)}
        self.planes = gen.host_rows(planes, rows)
        self.vv = np.asarray(jnp.max(planes[0], axis=0)).astype(np.uint64)
        self.batch = OrswotBatch(*planes)
        del planes
        uni = Universe.identity(CrdtConfig.tpu_default(
            num_actors=cfg["num_actors"],
            member_capacity=cfg["member_capacity"],
            deferred_capacity=cfg["deferred_capacity"]))
        self.loop = ServeLoop(ClusterNode("bench", self.batch, uni))
        self._decode, self._encode = decode_read_request, encode_result_frame
        self.warm()

    def draw(self, ids: np.ndarray) -> None:
        """Draw and encode the frames; ``ids`` is the served replica's
        member plane, where the live members of ``contains`` come from."""
        from crdt_tpu.serve.query import ReadRequest
        from crdt_tpu.serve.wire import encode_read_request

        ctx, t = self.ctx, self.ctx.traffic
        count = t["frames"]
        # the order of the sizes is the traffic's own (``order_seed``):
        # the window may end before the last frame, so every run serves
        # the same sizes, and the run's seed draws the keys and members
        sizes = gen.host_rng(t["order_seed"], _ORDER).permutation(
            gen.log_uniform_sizes(count, t["frame_min"], t["frame_max"]))
        keys = gen.host_rng(ctx.seed, _KEYS)
        scatter = keys.permutation(self.n)
        obj = scatter[gen.zipf_ranks(keys, self.n, t["zipf_s"],
                                     int(sizes.sum()))]
        mix = gen.host_rng(ctx.seed, _MIX)
        member = np.full(obj.shape, -1, np.int32)
        probe = mix.random(obj.size) >= t["value_frac"]
        live = probe & (mix.random(obj.size) < 0.5)
        row = ids[obj]
        # a live member: a random occupied slot of the object's row
        occupied = row != -1
        pick = np.argmax(occupied * mix.random(row.shape), axis=1)
        has = occupied.any(axis=1)
        member[live & has] = row[live & has, pick[live & has]]
        absent = probe & ~(live & has)
        member[absent] = mix.integers(_ABSENT_LO, 2 * _ABSENT_LO,
                                      int(absent.sum()))
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        self.frames = [encode_read_request(ReadRequest.reads(
            obj[lo:hi], member=member[lo:hi]))
            for lo, hi in zip(bounds[:-1], bounds[1:])]
        self.sizes, self.bounds = sizes, bounds
        self.obj, self.member = obj, member

        check = gen.host_rng(ctx.seed, _CHECK)
        largest = np.argsort(-sizes, kind="stable")[:t["check_largest"]]
        drawn = check.choice(count, size=min(t["check_frames"], count),
                             replace=False)
        self.check_set = set(int(i) for i in np.concatenate([largest,
                                                             drawn]))

    def warm(self) -> None:
        """Every padded gather size the window will use, through the
        whole frame path, twice: the first serve also turns the
        read-heat sketch's host table into a device one, which the
        second pass's programs take."""
        from crdt_tpu.serve.query import ReadRequest
        from crdt_tpu.serve.wire import encode_read_request

        top = int(self.sizes.max())
        ladder, pad = [], 8
        while True:
            ladder.append(min(pad, top))
            if pad >= top:
                break
            pad *= 2
        for b in ladder + ladder:
            self._serve(encode_read_request(ReadRequest.reads(
                self.obj[:b], member=self.member[:b])))

    def _serve(self, frame: bytes) -> bytes:
        req = self._decode(frame, num_objects=self.n)
        return self._encode(self.loop.serve(req))

    def window(self, seconds: float) -> dict:
        from crdt_tpu.error import ConsistencyUnavailableError

        kept = {}
        tally = {"served": 0, "reads": 0, "failed": 0}
        errors = []
        lock = threading.Lock()
        count = len(self.frames)

        def client():
            while True:
                with lock:
                    if errors or time.perf_counter() >= deadline:
                        return
                    i = tally["served"] % count
                    tally["served"] += 1
                with self.ctx.annotation("bench.frame"):
                    try:
                        out = self._serve(self.frames[i])
                    except ConsistencyUnavailableError:
                        out = None
                    except BaseException as e:  # raised after the join
                        with lock:
                            errors.append(e)
                        return
                with lock:
                    tally["reads"] += int(self.sizes[i])
                    tally["failed"] += out is None
                    if i in self.check_set:
                        kept[i] = out

        threads = [threading.Thread(target=client, daemon=True,
                                    name=f"bench-client-{k}")
                   for k in range(self.ctx.traffic["clients"])]
        t0 = time.perf_counter()
        deadline = t0 + seconds
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        elapsed = time.perf_counter() - t0
        if errors:
            raise errors[0]
        self.kept = kept
        served, reads = tally["served"], tally["reads"]
        return {"attempted": served, "failed": tally["failed"],
                "elapsed_s": elapsed, "reads": reads,
                "log": {"frames": served, "reads": reads,
                        "elapsed_s": elapsed, "passes": served / count}}

    def end_to_end(self, result: dict) -> dict:
        return {"reads_per_s": result["reads"] / result["elapsed_s"]}

    def layer_stats(self, result: dict) -> dict:
        return {"frames": result["attempted"], "reads": result["reads"]}

    def release(self) -> None:
        """Answer the checked frames the window did not reach (untimed),
        decode every checked answer, and drop the served state."""
        from crdt_tpu.error import ConsistencyUnavailableError

        for i in sorted(self.check_set - set(self.kept)):
            try:
                self.kept[i] = self._serve(self.frames[i])
            except ConsistencyUnavailableError:
                self.kept[i] = None
        self.answers = {}
        for i, frame in self.kept.items():
            try:
                self.answers[i] = None if frame is None else \
                    reference.decode_result_frame(frame)
            except reference.Malformed:
                self.answers[i] = None
        self.batch = self.loop = None

    def check(self) -> list:
        limits = self.ctx.traffic["limits"]
        width = self.ctx.config["num_actors"]
        states = {}
        wrong = 0
        for i, cols in self.answers.items():
            lo, hi = self.bounds[i], self.bounds[i + 1]
            if cols is None or len(cols["obj"]) != hi - lo or \
                    not np.array_equal(cols["token"], self.vv):
                wrong += int(hi - lo)
                continue
            for j in range(hi - lo):
                o, m = int(self.obj[lo + j]), int(self.member[lo + j])
                if o not in states:
                    k = self.rows[o]
                    states[o] = reference.row_state(
                        *(p[k] for p in self.planes))
                val, add, rm = reference.read(states[o], m, width)
                if not (int(cols["obj"][j]) == o
                        and int(cols["member"][j]) == m
                        and int(cols["status"][j]) == 0
                        and int(cols["val"][j]) == val
                        and np.array_equal(cols["add"][j], add)
                        and np.array_equal(cols["rm"][j], rm)):
                    wrong += 1
        return [("reads_wrong", wrong, limits["reads_wrong"])]
