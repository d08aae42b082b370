"""Closed loop of anti-entropy rounds: each round joins the chip's
replicas to fixpoint with ``OrswotBatch.join_fleet`` and waits for the
result.  The order of the replicas rotates each round, so no round's
inputs repeat the last's.

Traffic keys: ``check_objects`` (objects sampled for the check),
``held_round_max`` (the checked early round is drawn from
``[0, held_round_max)``), ``limits``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import gen, reference, work

_FLEET, _CHECK = 1, 2


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg = ctx.config
        self.n = cfg["objects"] // cfg["deployment_chips"]
        self.r = cfg["replicas"]
        self.bpo = gen.bytes_per_object(cfg)

    def setup(self) -> None:
        import jax

        from crdt_tpu.batch import OrswotBatch

        ctx, t = self.ctx, self.ctx.traffic
        rng = gen.host_rng(ctx.seed, _CHECK)
        self.sample = np.sort(rng.choice(
            self.n, size=min(t["check_objects"], self.n), replace=False))
        self.held_round = int(rng.integers(0, t["held_round_max"]))
        build = gen.fleet_builder(ctx.config, n=self.n, r=self.r)
        with jax.default_device(ctx.devices[0]):
            planes = build(gen.device_key(ctx.seed, _FLEET))
        # the reference's operands, copied before the program holds them
        self.inputs = [gen.host_rows(p, self.sample) for p in planes]
        self.fleets = [OrswotBatch(*p) for p in planes]
        del planes
        # warm-up: the window's one program, at the window's shapes
        jax.block_until_ready(OrswotBatch.join_fleet(self.fleets))

    def window(self, seconds: float) -> dict:
        import jax

        from crdt_tpu.batch import OrswotBatch

        rounds, held, out = 0, None, None
        t0 = time.perf_counter()
        while True:
            k = rounds % self.r
            out = None  # the last round's result is not kept
            with self.ctx.annotation("bench.round"):
                out = OrswotBatch.join_fleet(
                    self.fleets[k:] + self.fleets[:k])
                jax.block_until_ready(out)
            if rounds == self.held_round:
                held = (k, out)
            rounds += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.checked = [held, (k, out)] if held is not None and \
            held[1] is not out else [(k, out)]
        return {"attempted": rounds, "failed": 0, "elapsed_s": elapsed,
                "merges": rounds * self.r * self.n,
                "log": {"rounds": rounds, "elapsed_s": elapsed}}

    def end_to_end(self, result: dict) -> dict:
        return {"merges_per_s": result["merges"] / result["elapsed_s"]}

    def layer_stats(self, result: dict) -> dict:
        return {"join_bytes": work.join_bytes(self.r, self.n, self.bpo)}

    def release(self) -> None:
        """Copy the sampled rows of the checked rounds to the host, then
        drop every device array."""
        self.outputs = [(k, gen.host_rows(
            (b.clock, b.ids, b.dots, b.d_ids, b.d_clocks), self.sample))
            for k, b in self.checked]
        self.fleets = self.checked = None

    def check(self) -> list:
        wrong = 0
        for k, got in self.outputs:
            order = self.inputs[k:] + self.inputs[:k]
            want = [reference.fold([reference.row_state(*(p[j] for p in f))
                                    for f in order], "tree")
                    for j in range(len(self.sample))]
            wrong += reference.count_wrong(
                reference.dense_states(got, range(len(self.sample))), want)
        return [("objects_wrong", wrong,
                 self.ctx.traffic["limits"]["objects_wrong"])]
