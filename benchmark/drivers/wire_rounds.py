"""Closed loop of replication rounds through
``PipelinedWireLoop(universe).run``: each round takes the wire blobs of
the replicas for one slice of objects, folds them to fixpoint, and
egresses the fixpoint as blobs.  The blobs are encoded at set-up from
``slices`` distinct slices of the store, which the rounds cycle.

Traffic keys: ``slice_objects``, ``slices``, ``rounds_per_call`` (rounds
handed to one ``run`` call, so the loop's parse of the next round
overlaps this one's egress), ``check_objects``, ``held_round_max``,
``limits``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import gen, reference

_SLICE, _CHECK = 10, 2


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.n = t["slice_objects"]
        self.r = ctx.config["replicas"]
        self.slices = t["slices"]

    def setup(self) -> None:
        import jax

        from crdt_tpu.batch import OrswotBatch, PipelinedWireLoop
        from crdt_tpu.config import CrdtConfig
        from crdt_tpu.utils.interning import Universe

        ctx, cfg, t = self.ctx, self.ctx.config, self.ctx.traffic
        uni = Universe.identity(CrdtConfig.tpu_default(
            num_actors=cfg["num_actors"],
            member_capacity=cfg["member_capacity"],
            deferred_capacity=cfg["deferred_capacity"]))
        rng = gen.host_rng(ctx.seed, _CHECK)
        self.sample = np.sort(rng.choice(
            self.n, size=min(t["check_objects"], self.n), replace=False))
        self.held_round = int(rng.integers(0, t["held_round_max"]))
        build = gen.fleet_builder(cfg, n=self.n, r=self.r)
        self.blobs, self.inputs = [], []
        for s in range(self.slices):
            with jax.default_device(ctx.devices[0]):
                planes = build(gen.device_key(ctx.seed, _SLICE + s))
            host = [tuple(np.asarray(p) for p in rep) for rep in planes]
            del planes
            self.blobs.append([OrswotBatch(*rep).to_wire(uni)
                               for rep in host])
            self.inputs.append([tuple(p[self.sample] for p in rep)
                                for rep in host])
            del host
        self.loop = PipelinedWireLoop(uni)
        # warm-up: buffers sized and the fold program loaded, at the
        # window's round shape
        self.loop.run([self.blobs[0]], collect="none")

    def window(self, seconds: float) -> dict:
        per_call = self.ctx.traffic["rounds_per_call"]
        kept = {}
        stage = {"parse": 0.0, "fold": 0.0, "egress": 0.0}
        rounds, calls = 0, []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            t_call = time.perf_counter()
            first = rounds
            batch = [self.blobs[(first + i) % self.slices]
                     for i in range(per_call)]

            def on_round(i, out, first=first):
                g = first + i
                if g == self.held_round:
                    kept["held"] = (g, out)
                kept["last"] = (g, out)

            with self.ctx.annotation("bench.rounds"):
                res = self.loop.run(batch, collect="none",
                                    on_round=on_round)
            for k in stage:
                stage[k] += res["stage_s"][k]
            rounds += per_call
            calls.append(time.perf_counter() - t_call)
        elapsed = time.perf_counter() - t0
        self.checked = [kept["last"]]
        if "held" in kept and kept["held"][0] != kept["last"][0]:
            self.checked.append(kept["held"])
        self.stage = stage
        return {"attempted": rounds, "failed": 0, "elapsed_s": elapsed,
                "objects": rounds * self.n,
                "log": {"rounds": rounds, "elapsed_s": elapsed,
                        "stage_s": stage,
                        "call_s_median": float(np.median(calls)),
                        "call_s_max": max(calls)}}

    def end_to_end(self, result: dict) -> dict:
        return {"wire_objs_per_s": result["objects"] / result["elapsed_s"]}

    def layer_stats(self, result: dict) -> dict:
        return {"rounds": result["attempted"], "stage_s": self.stage}

    def release(self) -> None:
        self.outputs = [(g, [out[i] for i in self.sample])
                        for g, out in self.checked]
        self.checked = self.blobs = self.loop = None

    def check(self) -> list:
        wrong = 0
        for g, blobs in self.outputs:
            inputs = self.inputs[g % self.slices]
            want = [reference.fold([reference.row_state(*(p[j] for p in rep))
                                    for rep in inputs], "left")
                    for j in range(len(blobs))]
            got = []
            for blob in blobs:
                try:
                    got.append(reference.decode_orswot_blob(blob))
                except reference.Malformed as e:
                    got.append(e)
            wrong += reference.count_wrong(got, want)
        return [("objects_wrong", wrong,
                 self.ctx.traffic["limits"]["objects_wrong"])]
