"""Closed loop of ``mesh.anti_entropy_step`` over an object mesh of the
cell's chips: two replicas of one logical fleet, sharded by object, are
merged, digested and summarised in one program per step.  Each chip
builds its own rows of both replicas on the device from the seed.  The
operand order alternates each step, so no step repeats the last.

Traffic keys: ``check_objects``, ``held_round_max``, ``limits``.
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
        self.n = cfg["objects"]
        self.chips = len(ctx.devices)
        self.per = self.n // self.chips
        if self.per * self.chips != self.n:
            raise ValueError(f"{self.n} objects do not split over "
                             f"{self.chips} chips")
        self.bpo = gen.bytes_per_object(cfg)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from crdt_tpu import mesh
        from crdt_tpu.batch import OrswotBatch
        from crdt_tpu.config import CrdtConfig
        from crdt_tpu.parallel.mesh import make_mesh
        from crdt_tpu.utils.interning import Universe

        ctx, cfg, t = self.ctx, self.ctx.config, self.ctx.traffic
        axis = mesh.MESH_AXIS
        grid = make_mesh({axis: self.chips}, ctx.devices)

        def body(key):
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))
            return gen.fleet_planes(
                key, n=self.per, r=2, a=cfg["num_actors"],
                m=cfg["member_capacity"], d=cfg["deferred_capacity"],
                base=cfg["fleet"]["base"], novel=cfg["fleet"]["novel"],
                present_p=cfg["fleet"]["present_p"],
                deferred_frac=cfg["fleet"]["deferred_frac"])

        rng = gen.host_rng(ctx.seed, _CHECK)
        self.sample = np.sort(rng.choice(
            self.n, size=min(t["check_objects"], self.n), replace=False))
        self.held_round = int(rng.integers(0, t["held_round_max"]))
        build = jax.jit(shard_map(body, mesh=grid, in_specs=P(),
                                  out_specs=P(axis), check_vma=False))
        a, b = build(gen.device_key(ctx.seed, _FLEET))
        # the reference's operands, copied before the program holds them:
        # the sampled rows of both replicas and their clocks' pointwise max
        self.inputs = [gen.host_rows(x, self.sample) for x in (a, b)]
        self.vv = np.asarray(jnp.maximum(
            jnp.max(a[0], axis=0), jnp.max(b[0], axis=0))).astype(np.uint64)
        uni = Universe.identity(CrdtConfig.tpu_default(
            num_actors=cfg["num_actors"],
            member_capacity=cfg["member_capacity"],
            deferred_capacity=cfg["deferred_capacity"]))
        # the largest power-of-two granule dividing a shard: no padding
        layout = mesh.choose_layout(self.n, self.chips,
                                    granule=self.per & -self.per)
        self.pair = (mesh.ShardedBatch(OrswotBatch(*a), layout, grid, uni),
                     mesh.ShardedBatch(OrswotBatch(*b), layout, grid, uni))
        del a, b
        # warm-up: the step program at the window's shapes
        jax.block_until_ready(
            mesh.anti_entropy_step(*self.pair).batch.device)

    def window(self, seconds: float) -> dict:
        import jax

        from crdt_tpu import mesh

        steps, held, out = 0, None, None
        t0 = time.perf_counter()
        while True:
            flip = steps % 2
            out = None
            x, y = self.pair[flip], self.pair[1 - flip]
            with self.ctx.annotation("bench.step"):
                out = mesh.anti_entropy_step(x, y)
                jax.block_until_ready(out.batch.device)
            if steps == self.held_round:
                held = (flip, out)
            steps += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.checked = [(flip, out)]
        if held is not None and held[1] is not out:
            self.checked.append(held)
        return {"attempted": steps, "failed": 0, "elapsed_s": elapsed,
                "merges": steps * 2 * self.n,
                "log": {"steps": steps, "elapsed_s": elapsed}}

    def end_to_end(self, result: dict) -> dict:
        return {"merges_per_s": result["merges"] / result["elapsed_s"]}

    def layer_stats(self, result: dict) -> dict:
        return {"join_bytes": work.join_bytes(2, self.n, self.bpo)}

    def release(self) -> None:
        """Copy the sampled rows of the checked steps (planes, digests,
        version vector) to the host; drop every device array."""
        def rows(batch):
            return gen.host_rows((batch.clock, batch.ids, batch.dots,
                                  batch.d_ids, batch.d_clocks), self.sample)

        self.outputs = [
            (flip, rows(o.batch.device),
             np.asarray(o.digests)[self.sample],
             np.asarray(o.version_vector, np.uint64))
            for flip, o in self.checked]
        self.pair = self.checked = None

    def check(self) -> list:
        limits = self.ctx.traffic["limits"]
        rows_wrong = digests_wrong = vv_wrong = 0
        for flip, got, digests, vv in self.outputs:
            x, y = self.inputs[flip], self.inputs[1 - flip]
            want = [reference.merge(reference.row_state(*(p[j] for p in x)),
                                    reference.row_state(*(p[j] for p in y)))
                    for j in range(len(self.sample))]
            rows_wrong += reference.count_wrong(
                reference.dense_states(got, range(len(self.sample))), want)
            digests_wrong += sum(int(d) != reference.digest(w)
                                 for d, w in zip(digests, want))
            vv_wrong += int(not np.array_equal(vv, self.vv))
        return [("objects_wrong", rows_wrong, limits["objects_wrong"]),
                ("digests_wrong", digests_wrong, limits["digests_wrong"]),
                ("version_vectors_wrong", vv_wrong,
                 limits["version_vectors_wrong"])]
