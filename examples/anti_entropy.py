"""End-to-end anti-entropy walkthrough: ops over the wire, batched
joins on device, and a sharded collective join over a device mesh.

The reference library stops at "serialize the op/state and transport it
however you like" (`/root/reference/src/lib.rs:62-83`; its only example,
`examples/pprint.rs`, pretty-prints two values).  This example shows the
same protocol end to end in the TPU-native framework, then scales it:

  1. op-based replication between scalar replicas over `to_binary` bytes
     (read → derive ctx → mutate → ship — `/root/reference/src/ctx.rs:5-9`);
  2. a causally-future remove that buffers in the deferred table and
     resolves after anti-entropy (`orswot.rs:195-211`);
  3. the same fleet packed into dense batches and joined on device with
     one pairwise-tree reduction (`OrswotBatch.join_fleet`);
  4. the join re-run as a *collective* over a device mesh — one replica
     shard per device, merge as the all-reduce combiner riding ICI
     (`parallel.allgather_join_orswot`).

Run on CPU with a virtual 8-device mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/anti_entropy.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the virtual mesh only applies to the CPU backend; on a chip the
# collective step uses the chip's own devices
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

from crdt_tpu import Orswot, from_binary, to_binary  # noqa: E402
from crdt_tpu.batch import OrswotBatch  # noqa: E402
from crdt_tpu.config import CrdtConfig  # noqa: E402
from crdt_tpu.utils.interning import Universe  # noqa: E402


def step1_op_replication():
    """Three replicas exchanging serialized ops (no shared memory)."""
    replicas = {name: Orswot() for name in ("alice", "bob", "carol")}

    def broadcast(op):
        wire = to_binary(op)  # what would cross the network
        for r in replicas.values():
            r.apply(from_binary(wire))

    a = replicas["alice"]
    broadcast(a.add("apple", a.value().derive_add_ctx("alice")))
    b = replicas["bob"]
    broadcast(b.add("pear", b.value().derive_add_ctx("bob")))
    values = {frozenset(r.value().val) for r in replicas.values()}
    assert values == {frozenset({"apple", "pear"})}
    print("1. op replication over the wire:", sorted(a.value().val))
    return replicas


def step2_deferred_remove(replicas):
    """A remove whose context is causally ahead buffers, then resolves."""
    carol = replicas["carol"]
    ctx = carol.contains("apple").derive_rm_ctx()
    ctx.clock.witness("dave", 1)  # dave's write hasn't reached carol yet
    rm = carol.remove("apple", ctx)

    bob = replicas["bob"]
    bob.apply(rm)
    assert len(bob.deferred) == 1  # buffered, not lost (orswot.rs:195-203)

    # dave's write arrives; anti-entropy flushes the buffered remove
    dave = Orswot()
    dave.apply(dave.add("fig", dave.value().derive_add_ctx("dave")))
    bob.merge(dave)
    bob.merge(Orswot())  # defer plunger (test/orswot.rs:61-62)
    assert "apple" not in bob.value().val and "fig" in bob.value().val
    print("2. deferred remove resolved after anti-entropy:",
          sorted(bob.value().val))


def step3_batched_join():
    """A fleet of replicas × objects joined as one device reduction."""
    rng = np.random.RandomState(0)
    # counter_bits=32 is the TPU-native width; u64 is the parity default
    uni = Universe(CrdtConfig(num_actors=8, member_capacity=16,
                              deferred_capacity=4, counter_bits=32))
    n_objects, n_replicas = 256, 8
    fleets = []
    for r in range(n_replicas):
        row = []
        for i in range(n_objects):
            s = Orswot()
            for j in range(int(rng.randint(1, 5))):
                member = f"item{(i * 7 + j * 3) % 11}"
                s.apply(s.add(member, s.value().derive_add_ctx(f"node{r}")))
            row.append(s)
        fleets.append(OrswotBatch.from_scalar(row, uni))

    joined = OrswotBatch.join_fleet(fleets)  # log-depth pairwise tree
    sets = joined.value_sets(uni)
    print(f"3. batched join: {n_replicas} fleets × {n_objects} objects → "
          f"e.g. object 0 = {sorted(sets[0])}")
    return uni, fleets, sets


def step4_collective_join(uni, fleets, expected_sets):
    """The same join as a mesh collective: one replica shard per device,
    merge as the all-reduce combiner (the ICI path on real hardware)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.parallel import allgather_join_orswot, make_mesh

    n_dev = len(jax.devices())
    if n_dev < len(fleets):
        print(f"4. collective join skipped ({n_dev} devices < {len(fleets)})")
        return
    mesh = make_mesh({"replicas": len(fleets)})
    stacked = OrswotBatch(
        clock=jnp.stack([f.clock for f in fleets]),
        ids=jnp.stack([f.ids for f in fleets]),
        dots=jnp.stack([f.dots for f in fleets]),
        d_ids=jnp.stack([f.d_ids for f in fleets]),
        d_clocks=jnp.stack([f.d_clocks for f in fleets]),
    )
    joined = allgather_join_orswot(stacked, mesh, axis="replicas")
    # every device holds the same joined state; check shard 0
    first = OrswotBatch(
        clock=joined.clock[0], ids=joined.ids[0], dots=joined.dots[0],
        d_ids=joined.d_ids[0], d_clocks=joined.d_clocks[0],
    )
    assert first.value_sets(uni) == expected_sets
    print(f"4. collective join over a {len(fleets)}-device mesh axis "
          "matches the batched join on every shard")


def step5_typed_collective_joins():
    """Every register/set type has its own mesh collective: LWW joins by
    marker-argmax (equal-marker conflicts surface host-side,
    `lwwreg.rs:56-66`), MVReg by antichain gather-fold (concurrent values
    all survive, `mvreg.rs:121-153`)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.batch import LWWRegBatch, MVRegBatch
    from crdt_tpu.parallel import (
        allgather_join_lww, allgather_join_mvreg, make_mesh,
    )
    from crdt_tpu.scalar.lwwreg import LWWReg
    from crdt_tpu.scalar.mvreg import MVReg

    n_dev = len(jax.devices())
    if n_dev < 8:
        print(f"5. typed collective joins skipped ({n_dev} devices < 8)")
        return
    mesh = make_mesh({"replicas": 8})
    uni = Universe(CrdtConfig(num_actors=8, mv_capacity=8))

    # LWW: 8 replicas each last-wrote one register at a distinct time
    fleet = [[LWWReg(val=f"edit-{r}", marker=100 + r)] for r in range(8)]
    stack = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[LWWRegBatch.from_scalar(row, uni) for row in fleet],
    )
    joined, conflict = allgather_join_lww(stack, mesh)
    assert not bool(jnp.any(conflict))
    winner = LWWRegBatch(
        vals=joined.vals[0], markers=joined.markers[0]
    ).to_scalar(uni)[0]
    assert winner.val == "edit-7"  # the largest marker wins everywhere

    # MVReg: 8 concurrent writers — the join keeps all eight values
    regs = []
    for r in range(8):
        reg = MVReg()
        reg.apply(reg.set(f"draft-{r}", reg.read().derive_add_ctx(r)))
        regs.append(reg)
    stack = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[MVRegBatch.from_scalar([reg], uni) for reg in regs],
    )
    joined_mv = allgather_join_mvreg(stack, mesh)
    survivors = MVRegBatch(
        clocks=joined_mv.clocks[0], vals=joined_mv.vals[0]
    ).to_scalar(uni)[0]
    assert len(survivors.read().val) == 8
    print("5. typed collective joins: LWW marker-argmax winner "
          f"{winner.val!r}; MVReg keeps all {len(survivors.read().val)} "
          "concurrent values")


def step6_elastic_regrowth():
    """Static capacities are the TPU build's one concession; the executor
    makes them elastic — an overflowing join regrows the padded axes and
    requeues (idempotent merge makes the retry safe)."""
    from crdt_tpu.parallel import JoinExecutor, JoinStats

    uni = Universe(CrdtConfig(num_actors=8, member_capacity=2,
                              deferred_capacity=2))
    fleets = []
    for r in range(4):
        s = Orswot()
        for j in range(2):
            s.apply(s.add(f"m{r}-{j}", s.value().derive_add_ctx(f"node{r}")))
        fleets.append(OrswotBatch.from_scalar([s], uni))

    stats = JoinStats()
    joined = JoinExecutor().join_all(fleets, stats=stats)
    sets = joined.value_sets(uni)
    assert len(sets[0]) == 8  # union exceeded capacity 2, nothing lost
    print(f"6. elastic regrowth: capacity 2 → "
          f"{stats.final_member_capacity} after "
          f"{stats.overflow_regrows} regrow(s); all {len(sets[0])} members "
          "survived")


def step7_bulk_wire_loop():
    """State-based replication at fleet scale, zero Python objects in the
    hot path: wire blobs (`to_binary` payloads) decode straight into
    dense planes through the native parallel codec, merge on device, and
    encode back to blobs byte-identical to `to_binary` — ~1M+ objects/s
    each way vs ~170k/~50k for the per-object walk (`docs/GUIDE.md`).  Needs an
    identity universe: int actors/members map to themselves, so there is
    no host-side interning state at all."""
    rng = np.random.RandomState(7)
    uni = Universe.identity(CrdtConfig.tpu_default(
        num_actors=8, member_capacity=8, deferred_capacity=4,
    ))
    n = 2000
    # replica A's fleet arrives as wire blobs (as if from the network)
    incoming = []
    for i in range(n):
        s = Orswot()
        for j in range(int(rng.randint(1, 4))):
            s.apply(s.add(int(rng.randint(0, 100)),
                          s.value().derive_add_ctx(j % 4)))
        incoming.append(to_binary(s))

    local = OrswotBatch.from_wire(incoming, uni)     # native parallel decode
    mine = OrswotBatch.zeros(n, uni)                 # this node starts empty
    merged = local.merge(mine, impl=uni.config.merge_impl)
    outgoing = merged.to_wire(uni)                   # native parallel encode
    # byte-faithful means byte-faithful: what we ship IS what to_binary
    # would have produced for the merged scalars
    assert outgoing[:64] == [to_binary(s) for s in merged.to_scalar(uni)[:64]]
    # and a plain-Python peer decodes it
    peer = from_binary(outgoing[0])
    assert peer.value().val == from_binary(incoming[0]).value().val
    print(f"7. bulk wire loop: {n} blobs in -> device merge -> {n} blobs "
          "out, byte-identical to the scalar codec")
    return uni, n, incoming


def step8_pipelined_wire_loop(uni, n, incoming):
    """The sustained form of step 7 — the SAME loop the bench times
    (`crdt_tpu.batch.wireloop.PipelinedWireLoop`, one implementation for
    bench and examples): reused staging buffers instead of a fresh plane
    set per fleet (the round-5 e2e ingest collapse was exactly that
    allocation churn, docs/GUIDE.md), with a background thread parsing the
    next fleet while the current one folds.  The result dict carries the
    per-stage times and the native-vs-fallback blob accounting the bench
    JSON publishes as ``native_fraction``."""
    from crdt_tpu.batch.wireloop import PipelinedWireLoop

    # two replica fleets of the same objects: fleet 0 is the step-7
    # traffic, fleet 1 a second replica's copy arriving in the same
    # anti-entropy round
    loop = PipelinedWireLoop(uni)
    res = loop.run([[incoming, incoming]])
    # fold of two identical replicas + plunger == scalar self-merge
    # (byte-level spot check on object 0 — the digest pass below is the
    # fleet-wide oracle)
    acc = from_binary(incoming[0])
    acc.merge(from_binary(incoming[0]))
    acc.merge(acc.clone())
    assert res["out_blobs"][0] == to_binary(acc)

    # convergence oracle: one digest pass per replica instead of a full
    # value() comparison — after the round, every replica that merges
    # the fold output must land on an identical digest vector (one
    # jitted kernel + an N×8-byte compare; a 1M-object fleet checks in
    # one launch where per-object value() comparison walks the heap)
    from crdt_tpu.sync import digest as sync_digest

    folded = OrswotBatch.from_wire(res["out_blobs"], uni)
    want = sync_digest.digest_of(folded)
    for r, blobs in enumerate((incoming, incoming)):
        replica = OrswotBatch.from_wire(blobs, uni).merge(folded)
        replica = replica.merge(replica)  # defer plunger
        got = sync_digest.digest_of(replica)
        assert np.array_equal(got, want), (
            f"replica {r} digest vector diverged after anti-entropy"
        )
    nf = res["ingest_native_fraction"]
    print(f"8. pipelined wire loop ({res['fold_path']} fold, "
          f"{res['pipeline']}): {res['merges']} replica-objects in "
          f"{res['e2e_s']:.3f}s, ingest native_fraction={nf}; all replica "
          "digest vectors converged")


def step9_causal_gc(uni, n, incoming):
    """Causal GC closes the loop: a fleet that regrew through step 6's
    elastic ladder carries padding (and settled-but-unswept tombstone
    rows) forever — until the GC layer (`crdt_tpu.gc`) settles the
    deferred tables and re-packs the slot axes back down the ladder.
    Compaction reclaims REPRESENTATION, never state: the digest vector
    — the same convergence oracle step 8 used — is byte-identical
    before and after."""
    from crdt_tpu.gc import GcEngine, GcPolicy
    from crdt_tpu.sync import digest as sync_digest

    fleet = OrswotBatch.from_wire(incoming, uni)
    fleet = fleet.merge(fleet)  # canonical (plunged) form
    # as a burst would leave it: slot axes regrown 4x above the config
    cfg = uni.config
    fleet = fleet.with_capacity(cfg.member_capacity * 4,
                                cfg.deferred_capacity * 4)
    before = sync_digest.digest_of(fleet)
    bytes_before = sum(
        x.nbytes for x in (fleet.clock, fleet.ids, fleet.dots,
                           fleet.d_ids, fleet.d_clocks))

    engine = GcEngine(GcPolicy(interval_rounds=1))
    compacted, report = engine.collect(fleet, universe=uni)
    after = sync_digest.digest_of(compacted)
    assert np.array_equal(np.asarray(before), np.asarray(after)), (
        "causal GC changed the digest vector — compaction must be "
        "representation-only"
    )
    assert report.reclaimed_bytes > 0 and report.shrunk
    print(f"9. causal GC: member capacity "
          f"{report.member_capacity[0]} -> {report.member_capacity[1]}, "
          f"{report.reclaimed_bytes} of {bytes_before} plane bytes "
          f"reclaimed; digest vector byte-identical before/after")


def main():
    replicas = step1_op_replication()
    step2_deferred_remove(replicas)
    uni, fleets, sets = step3_batched_join()
    step4_collective_join(uni, fleets, sets)
    step5_typed_collective_joins()
    step6_elastic_regrowth()
    uni, n, incoming = step7_bulk_wire_loop()
    step8_pipelined_wire_loop(uni, n, incoming)
    step9_causal_gc(uni, n, incoming)
    print("anti-entropy walkthrough: OK")


if __name__ == "__main__":
    main()
