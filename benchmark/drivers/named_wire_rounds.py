"""``wire_rounds`` with the store keyed by names: the same closed loop of
``PipelinedWireLoop(universe).run`` rounds over the same fleet, through
a universe whose actors are 8-byte binary vnode ids and whose members
are YCSB key names (``benchmark/named.py``).

Set-up interns the 64 actor names (actor ``a`` gets id ``a``) and the
member names of every slice in ascending member-number order, so every
name is known in the window: the steady state of a long-lived store.
The blobs are encoded with the program's ``OrswotBatch.to_wire``.  The
check decodes the sampled output blobs with the plain named decoder,
maps names back to numbers, and compares with the reference's left fold
of the integer rows.

A program whose wire codec does not take named universes natively
would spend minutes of Python per round; set-up refuses it at once.
"""

from __future__ import annotations

import numpy as np

from benchmark import gen, named, reference
from benchmark.drivers.wire_rounds import _CHECK, _SLICE
from benchmark.drivers.wire_rounds import Driver as WireRounds


def require_native_names(universe) -> None:
    """Exit non-zero unless the program encodes a named ORSWOT with its
    native codec (an empty set: nothing is interned)."""
    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.utils import tracing

    before = tracing.counters()
    OrswotBatch.zeros(1, universe).to_wire(universe)
    got = tracing.counters_since(before)
    if not got.get("wire.orswot.to_wire.native"):
        raise SystemExit(
            "named_wire_rounds: the program's wire codec does not take "
            "named universes natively (wire counters: "
            f"{sorted(got)}); the cell needs it")


def _rename(ids, nums):
    """Member numbers to member ids (the rank among ``nums``), -1 kept."""
    return np.where(ids >= 0, np.searchsorted(nums, ids), -1) \
        .astype(np.int32)


class Driver(WireRounds):
    def setup(self) -> None:
        import jax

        from crdt_tpu.batch import OrswotBatch, PipelinedWireLoop
        from crdt_tpu.config import CrdtConfig
        from crdt_tpu.utils.interning import Universe

        ctx, cfg, t = self.ctx, self.ctx.config, self.ctx.traffic
        uni = Universe(CrdtConfig.tpu_default(
            num_actors=cfg["num_actors"],
            member_capacity=cfg["member_capacity"],
            deferred_capacity=cfg["deferred_capacity"]))
        require_native_names(uni)
        rng = gen.host_rng(ctx.seed, _CHECK)
        self.sample = np.sort(rng.choice(
            self.n, size=min(t["check_objects"], self.n), replace=False))
        self.held_round = int(rng.integers(0, t["held_round_max"]))
        build = gen.fleet_builder(cfg, n=self.n, r=self.r)
        hosts = []
        for s in range(self.slices):
            with jax.default_device(ctx.devices[0]):
                planes = build(gen.device_key(ctx.seed, _SLICE + s))
            hosts.append([tuple(np.asarray(p) for p in rep)
                          for rep in planes])
            del planes
        actors = [named.actor_name(ctx.seed, a)
                  for a in range(cfg["num_actors"])]
        if len(set(actors)) != len(actors):
            raise RuntimeError("two vnode ids drawn alike; draw again")
        nums = np.unique(np.concatenate(
            [rep[k].ravel() for host in hosts for rep in host
             for k in (1, 3)]))
        nums = nums[nums >= 0]
        members = named.member_names(nums)
        if len(set(members)) != len(members):
            raise RuntimeError("two member numbers hash to one key name")
        uni.actors.intern_all(actors)
        uni.members.intern_all(members)
        self.actor_of = {x: a for a, x in enumerate(actors)}
        self.member_of = dict(zip(members, nums.tolist()))
        self.blobs, self.inputs = [], []
        for host in hosts:
            self.blobs.append([
                OrswotBatch(clock, _rename(ids, nums), dots,
                            _rename(d_ids, nums), d_clocks).to_wire(uni)
                for clock, ids, dots, d_ids, d_clocks in host])
            self.inputs.append([tuple(p[self.sample] for p in rep)
                                for rep in host])
        del hosts
        self.loop = PipelinedWireLoop(uni)
        # warm-up: buffers sized and the fold program loaded, at the
        # window's round shape
        self.loop.run([self.blobs[0]], collect="none")

    def window(self, seconds: float) -> dict:
        from crdt_tpu.utils import tracing

        before = tracing.counters()
        result = super().window(seconds)
        self.wire = tracing.counters_since(before)
        return result

    def layer_stats(self, result: dict) -> dict:
        blobs = fallback = 0
        for leg in ("from_wire", "to_wire"):
            for kind in ("native", "fallback"):
                got = self.wire.get(f"wire.orswot.{leg}.{kind}", 0)
                blobs += got
                fallback += got if kind == "fallback" else 0
        return dict(super().layer_stats(result), wire_blobs=blobs,
                    wire_fallback=fallback)

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
                    got.append(named.to_numbers(
                        named.decode_named_blob(blob), self.actor_of,
                        self.member_of))
                except reference.Malformed as e:
                    got.append(e)
            wrong += reference.count_wrong(got, want)
        return [("objects_wrong", wrong,
                 self.ctx.traffic["limits"]["objects_wrong"])]
