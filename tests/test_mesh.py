"""Mesh-sharded fleet tests (crdt_tpu.mesh): one logical replica in S pieces.

The acceptance surface of the mesh subsystem, on the conftest-forced
8-device CPU mesh:

- layout math — subtree-granule shard bounds, rebase/unbase round-trip
  (the routed-leaf exemption's runtime half), heat-priced granule choice
  agreeing with the PR 18 planner's ``mesh:S`` pricing;
- mesh-size invariance — seeded random op/merge/GC histories run through
  the ONE pjit'd anti-entropy step on mesh {1,2,4,8} produce digest
  vectors and digest-tree roots byte-identical to the unsharded control,
  padding rows staying digest-invisible throughout;
- the one-launch pin — a 64k-object fleet's full anti-entropy round is
  ONE ``mesh.step.anti_entropy`` kernel call (kernel-observatory call
  counters; the flat-path kernels don't move);
- the runtime↔static contract cross-check — the mesh dispatch consumes
  exactly the kernels the shardcheck manifest declares shardable, and
  refuses host_only / replicated / unknown / wrong-mesh-size kernels
  with a typed :class:`~crdt_tpu.error.MeshContractError`;
- shard-subset sync — only the diverged shard's subtree bytes ship
  (counter-pinned), converged fleets ship nothing, and the ClusterNode
  wiring repairs under the session busy-lock discipline;
- per-shard durability — fleet checkpoint/restore round-trip, the
  shards-then-manifest write order surviving a simulated kill -9, and
  typed rejection (+ counters) for every manifest/shard corruption mode.
"""

import json
import os

import jax
import numpy as np
import pytest

from crdt_tpu import Dot, Orswot, mesh
from crdt_tpu.analysis.kernels import MANIFEST
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.cluster import ClusterNode
from crdt_tpu.config import CrdtConfig
from crdt_tpu.error import (
    CheckpointFormatError,
    DurabilityError,
    MeshContractError,
)
from crdt_tpu.gc.compact import settle_orswot
from crdt_tpu.mesh import durable as mesh_durable
from crdt_tpu.mesh import step as mesh_step
from crdt_tpu.obs import kernels as obs_kernels
from crdt_tpu.obs import metrics as obs_metrics
from crdt_tpu.obs.heat import HeatTracker, mesh_bounds
from crdt_tpu.obs.stability import subtree_layout
from crdt_tpu.scalar.orswot import Add, Rm
from crdt_tpu.sync import digest as digest_mod
from crdt_tpu.sync import tree as tree_mod
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe
from crdt_tpu.utils.testdata import anti_entropy_fleets

pytestmark = [
    pytest.mark.mesh,
    pytest.mark.skipif(
        len(jax.devices()) < 8,
        reason="needs the 8-device CPU mesh (see conftest)",
    ),
]


def small_universe():
    return Universe(CrdtConfig(num_actors=8, member_capacity=16,
                               deferred_capacity=8))


def _scalar_row(seed, n):
    """n scalar Orswots with seeded random op histories (actors 0-6;
    actor 7 is reserved for :func:`_with_extras` divergence dots)."""
    row = []
    for i in range(n):
        rng = np.random.RandomState(seed * 100_003 + i)
        s = Orswot()
        for _ in range(rng.randint(1, 7)):
            actor = int(rng.randint(0, 7))
            member = int(rng.randint(0, 8))
            counter = int(rng.randint(1, 6))
            if rng.rand() < 0.75:
                s.apply(Add(dot=Dot(actor, counter), member=member))
            else:
                s.apply(Rm(clock=Dot(actor, counter).to_vclock(),
                           member=member))
        row.append(s)
    return row


def _history_batches(n, uni):
    """Two replicas of one fleet with a seeded op/merge/GC history:
    each side is a merge of two independently grown batches, settled
    through the GC compaction pass (divergent at most rows)."""
    a = OrswotBatch.from_scalar(_scalar_row(1, n), uni).merge(
        OrswotBatch.from_scalar(_scalar_row(2, n), uni))
    a, _ = settle_orswot(a)
    b = OrswotBatch.from_scalar(_scalar_row(2, n), uni).merge(
        OrswotBatch.from_scalar(_scalar_row(3, n), uni))
    b, _ = settle_orswot(b)
    return a, b


def _with_extras(batch, uni, n, extra_ids):
    """``batch`` plus one fresh actor-7 dot at each of ``extra_ids``
    — divergence confined to exactly those rows (actor 7 appears in
    no base history, so the new dots always dominate)."""
    row = [Orswot() for _ in range(n)]
    for i in extra_ids:
        row[i].apply(Add(dot=Dot(7, 9), member=int(i) % 8))
    return batch.merge(OrswotBatch.from_scalar(row, uni))


# -- layout math -------------------------------------------------------------


def test_layout_bounds_rebase_roundtrip():
    lay = mesh.choose_layout(100, 4, granule=16)
    assert lay.bounds == tuple(mesh_bounds(100, 4, granule=16))
    assert lay.bounds == (0, 32, 64, 96, 100)
    assert lay.padded == 4 * lay.per_shard
    # ranges partition [0, n)
    covered = [i for lo, hi in lay.ranges() for i in range(lo, hi)]
    assert covered == list(range(100))
    ids = np.arange(100, dtype=np.int64)
    shard_idx, local = lay.rebase(ids)
    for s, (lo, hi) in enumerate(lay.ranges()):
        assert (shard_idx[lo:hi] == s).all()
        assert lay.objects_of(s) == hi - lo
        for i in (lo, hi - 1) if hi > lo else ():
            assert lay.shard_of(i) == s
    assert np.array_equal(lay.unbase(shard_idx, local), ids)
    with pytest.raises(IndexError):
        lay.rebase(np.array([100]))
    with pytest.raises(IndexError):
        lay.shard_of(-1)
    for bad in (0, 3, -16):
        with pytest.raises(ValueError):
            mesh.choose_layout(100, 4, granule=bad)


def test_choose_layout_prices_granules_like_the_planner():
    """With a heat tracker, choose_layout picks the candidate granule
    (span, 2*span, 4*span) whose mesh:S pricing has the lowest
    imbalance — the same score_plan the /heat route serves."""
    from crdt_tpu.obs.heat import score_plan

    n, shards = 64, 2
    span = subtree_layout(n)[1]
    trk = HeatTracker(registry=obs_metrics.MetricsRegistry())
    # heavy heat in the first subtree, light elsewhere: the coarsest
    # candidate granule (one shard-sized slab) prices terribly, the
    # finer ones balance — the search must pick a finer one
    trk.record_writes(np.zeros(500, dtype=np.int64), n)
    trk.record_writes(np.arange(n, dtype=np.int64), n)
    hv = trk.heat_vector()
    lay = mesh.choose_layout(n, shards, heat=hv)
    candidates = {
        g: score_plan(f"mesh:{shards}", hv, n=n, span=span,
                      granule=g)["imbalance"]
        for g in (span, 2 * span, 4 * span)
    }
    assert lay.granule in candidates
    assert lay.imbalance == pytest.approx(min(candidates.values()))
    assert lay.bounds == tuple(mesh_bounds(n, shards,
                                           granule=lay.granule))


def test_padding_rows_are_digest_invisible():
    """Empty rows digest to 0 (the XOR identity), so the tail shard's
    padding never shows in digests, shard roots, or tree roots."""
    uni = small_universe()
    zeros = OrswotBatch.zeros(8, uni)
    assert (np.asarray(digest_mod.digest_of(zeros, uni)) == 0).all()
    a, _ = _history_batches(12, uni)  # 12 rows: S=8 pads every shard
    control = np.asarray(digest_mod.digest_of(a, uni), dtype=np.uint64)
    sa = mesh.ShardedBatch.shard(a, uni, shards=8, granule=2)
    assert sa.layout.padded > sa.layout.n
    res = mesh.anti_entropy_step(sa, mesh.ShardedBatch.shard(
        a, uni, shards=8, granule=2))
    assert np.array_equal(res.digests, control)
    assert res.digests.size == sa.layout.n
    assert tree_mod.build_tree(res.digests).root == \
        tree_mod.build_tree(control).root


# -- mesh-size invariance ----------------------------------------------------


def test_mesh_size_invariance_digests_and_roots():
    """Seeded random op/merge/GC history on mesh {1,2,4,8} + the
    unsharded control: digest vectors and digest-tree roots must be
    byte-identical at every mesh size."""
    uni = small_universe()
    n = 48
    a, b = _history_batches(n, uni)
    b = _with_extras(b, uni, n, (3, 17, 40))
    control = np.asarray(
        digest_mod.digest_of(a.merge(b), uni), dtype=np.uint64)
    control_root = tree_mod.build_tree(control).root
    for shards in mesh.MESH_SIZES:
        sa = mesh.ShardedBatch.shard(a, uni, shards=shards, granule=4)
        sb = mesh.ShardedBatch.shard(b, uni, shards=shards, granule=4)
        res = mesh.anti_entropy_step(sa, sb)
        assert control.dtype == res.digests.dtype
        assert np.array_equal(res.digests, control), \
            f"digest vector diverged from control at mesh={shards}"
        assert tree_mod.build_tree(res.digests).root == control_root
        # the merged fleet re-digests to the same vector off-mesh
        merged = np.asarray(
            digest_mod.digest_of(res.batch.logical(), uni),
            dtype=np.uint64)
        assert np.array_equal(merged, control)


def test_mesh_step_version_vector_and_members_match_control():
    uni = small_universe()
    a, b = _history_batches(24, uni)
    merged = a.merge(b)
    vv = np.asarray(jax.device_get(merged.clock)).max(axis=0)
    from crdt_tpu.ops import orswot_ops
    live = int((np.asarray(jax.device_get(merged.ids))
                != orswot_ops.EMPTY).sum())
    for shards in (1, 4):
        res = mesh.anti_entropy_step(
            mesh.ShardedBatch.shard(a, uni, shards=shards, granule=4),
            mesh.ShardedBatch.shard(b, uni, shards=shards, granule=4))
        assert np.array_equal(res.version_vector, vv.astype(np.uint64))
        assert res.live_members == live


# -- the one-launch acceptance pin -------------------------------------------


def _profile_calls(names):
    obs = obs_kernels.kernel_observatory()
    return {name: obs.profile(name).calls for name in names}


def test_64k_fleet_one_pjit_step_on_8way_mesh():
    """The acceptance run: a 64k-object fleet's FULL anti-entropy round
    (merge + digests + fleet summaries) is ONE mesh.step.anti_entropy
    launch on the 8-way mesh — the flat-path kernels (per-row digest,
    shard-local merge, batch merge) never fire during the step."""
    n = 65_536
    a_cap, m_cap, d_cap = 8, 8, 2
    uni = Universe.identity(CrdtConfig(
        num_actors=a_cap, member_capacity=m_cap, deferred_capacity=d_cap,
        counter_bits=32))
    rng = np.random.RandomState(29)
    reps = anti_entropy_fleets(rng, n, a_cap, m_cap, d_cap, 2,
                               base=3, novel=1, deferred_frac=0.25)
    A, B = OrswotBatch(*reps[0]), OrswotBatch(*reps[1])
    # control digest BEFORE the baselines: digest_of is itself a
    # sync.digest.orswot launch and must not pollute the deltas
    control = np.asarray(digest_mod.digest_of(A.merge(B), uni),
                         dtype=np.uint64)
    sa = mesh.ShardedBatch.shard(A, uni, shards=8)
    sb = mesh.ShardedBatch.shard(B, uni, shards=8)
    assert sa.layout.bounds == tuple(mesh_bounds(n, 8,
                                                 granule=sa.layout.granule))
    watched = ("mesh.step.anti_entropy", "sync.digest.orswot",
               "parallel.shard_local_merge", "batch.orswot.merge")
    before = _profile_calls(watched)
    trace_before = tracing.counters()
    res = mesh.anti_entropy_step(sa, sb)
    deltas = {k: v - before[k] for k, v in _profile_calls(watched).items()}
    assert deltas == {"mesh.step.anti_entropy": 1,
                      "sync.digest.orswot": 0,
                      "parallel.shard_local_merge": 0,
                      "batch.orswot.merge": 0}, deltas
    assert np.array_equal(res.digests, control)
    trace = tracing.counters_since(trace_before)
    assert trace.get("mesh.step.rounds") == 1
    assert trace.get("mesh.step.digest_bytes") == control.nbytes


# -- runtime <-> static contract cross-check ---------------------------------


def test_contract_map_mirrors_shardcheck_manifest():
    """The runtime gate reads THE manifest shardcheck checks: every
    contract-bearing kernel row, nothing else."""
    declared = {s.name for s in MANIFEST if s.sharding is not None}
    assert set(mesh.contract_map()) == declared
    # full coverage is shardcheck's SC04; the runtime gate inherits it
    assert "mesh.step.anti_entropy" in declared


def test_step_consumes_exactly_the_declared_contract_set():
    """The runtime-consumed contract set == the step's declared kernel
    bill, and every consumed contract is statically shardable."""
    uni = small_universe()
    a, b = _history_batches(8, uni)
    mesh.anti_entropy_step(
        mesh.ShardedBatch.shard(a, uni, shards=2, granule=2),
        mesh.ShardedBatch.shard(b, uni, shards=2, granule=2))
    expected = set(mesh_step._SHARDED_KERNELS) | \
        set(mesh_step._SHARD_LOCAL_KERNELS)
    assert expected == {"mesh.step.anti_entropy", "sync.digest.orswot",
                        "parallel.shard_local_merge"}
    consumed = mesh.consumed_contracts()
    assert consumed == frozenset(expected)
    cmap = mesh.contract_map()
    for name in consumed:
        assert cmap[name].sclass in mesh.SHARDABLE_CLASSES


def test_contract_gate_refuses_with_typed_errors():
    cases = [
        ("batch.orswot.device_compact", 1, "host_only"),
        ("obs.heat.sketch_update", 2, "replicated"),
        ("parallel.shard_local_merge", 2, "pointwise"),  # mesh_sizes=(1,)
    ]
    for name, size, sclass in cases:
        before = tracing.counters()
        with pytest.raises(MeshContractError) as ei:
            mesh.require_shardable(name, size)
        assert isinstance(ei.value, TypeError)  # typed: a contract error
        assert ei.value.kernel == name
        assert ei.value.sclass == sclass
        assert tracing.counters_since(before).get(
            "mesh.contract.refused") == 1
    with pytest.raises(MeshContractError) as ei:
        mesh.require_shardable("no.such.kernel", 1)
    assert ei.value.kernel == "no.such.kernel"
    # refusals never enter the consumed set
    assert "batch.orswot.device_compact" not in mesh.consumed_contracts()


# -- shard-subset sync -------------------------------------------------------


def test_shard_subset_sync_ships_only_the_diverged_shard():
    """Divergence confined to one shard: its subtree bytes ship, the
    skipped shards contribute ZERO descent/delta bytes (counter-pinned),
    and the merged fleet matches the full-merge control."""
    uni = small_universe()
    n = 40
    lay = mesh.choose_layout(n, 4, granule=16)  # bounds (0,16,32,40,40)
    diverged_ids = (33, 34, 36)                 # all inside shard 2
    a, _ = _history_batches(n, uni)
    b = _with_extras(a, uni, n, diverged_ids)
    before = tracing.counters()
    merged, stats = mesh.shard_subset_sync(a, b, lay, uni)
    control = np.asarray(digest_mod.digest_of(a.merge(b), uni),
                         dtype=np.uint64)
    assert np.array_equal(
        np.asarray(digest_mod.digest_of(merged, uni), dtype=np.uint64),
        control)
    assert stats.shards_synced == 1
    assert stats.shards_skipped == 3
    assert set(stats.per_shard) == {2}
    assert stats.objects == len(diverged_ids)
    assert sorted(stats.object_ids.tolist()) == sorted(diverged_ids)
    assert stats.root_bytes == 8 * lay.shards
    assert stats.descent_bytes == stats.per_shard[2]["descent_bytes"] > 0
    assert stats.delta_bytes == stats.per_shard[2]["delta_bytes"] > 0
    # the rebased local rows land inside shard 2's leaf range
    lo, hi = lay.ranges()[2]
    assert all(0 <= r < hi - lo for r in stats.per_shard[2]["local_rows"])
    deltas = tracing.counters_since(before)
    assert deltas.get("mesh.sync.rounds") == 1
    assert deltas.get("mesh.sync.shards_synced") == 1
    assert deltas.get("mesh.sync.shards_skipped") == 3
    assert deltas.get("mesh.sync.objects") == len(diverged_ids)
    assert deltas.get("mesh.sync.delta_bytes") == stats.delta_bytes


def test_shard_roots_detect_identical_twin_updates():
    """Two rows in ONE shard taking IDENTICAL updates must still
    diverge the shard root: the roots are position-mixed digest-tree
    roots, not a raw XOR fold of row digests (whose twin per-row
    deltas would cancel and silently skip the repair)."""
    uni = small_universe()
    n = 32
    a, _ = _history_batches(n, uni)
    row = [Orswot() for _ in range(n)]
    for i in (20, 21):  # same shard, same extra dot AND member; member 9
        # appears in NO base history, so both rows take the IDENTICAL
        # digest delta (same new cell, same actor-7 clock bump)
        row[i].apply(Add(dot=Dot(7, 9), member=9))
    b = a.merge(OrswotBatch.from_scalar(row, uni))
    lay = mesh.choose_layout(n, 4, granule=8)  # rows 20,21 -> shard 2
    da = np.asarray(digest_mod.digest_of(a, uni), dtype=np.uint64)
    db = np.asarray(digest_mod.digest_of(b, uni), dtype=np.uint64)
    assert int((da != db).sum()) == 2
    # the raw XOR fold really would cancel here — the screw is live
    lo, hi = lay.ranges()[2]
    assert np.bitwise_xor.reduce(da[lo:hi]) == \
        np.bitwise_xor.reduce(db[lo:hi])
    assert mesh.diverged_shards(da, db, lay).tolist() == [2]
    # same roots the fleet snapshot manifest records per shard
    for s, (slo, shi) in enumerate(lay.ranges()):
        assert mesh.shard_roots(da, lay)[s] == \
            mesh_durable.shard_root_of(da[slo:shi])
    merged, stats = mesh.shard_subset_sync(a, b, lay, uni)
    assert stats.shards_synced == 1 and stats.objects == 2
    assert np.array_equal(
        np.asarray(digest_mod.digest_of(merged, uni), dtype=np.uint64),
        db)


def test_shard_subset_sync_converged_ships_nothing():
    uni = small_universe()
    a, _ = _history_batches(32, uni)
    lay = mesh.choose_layout(32, 4, granule=8)
    merged, stats = mesh.shard_subset_sync(a, a, lay, uni)
    assert stats.shards_synced == 0
    assert stats.shards_skipped == 4
    assert stats.objects == stats.descent_bytes == stats.delta_bytes == 0
    assert stats.object_ids.size == 0
    assert stats.root_bytes == 8 * 4  # the only bytes a converged pass pays


def test_cluster_node_shard_subset_sync_repairs_and_records_heat():
    """The ClusterNode wiring: both busy locks held, only the diverged
    shard pulled, repaired rows fed to the initiator's heat tracker —
    zero full-state frames by construction (no session ran)."""
    uni = small_universe()
    n = 40
    lay = mesh.choose_layout(n, 4, granule=16)
    diverged_ids = (17, 20)  # shard 1 of bounds (0,16,32,40,40)
    a, _ = _history_batches(n, uni)
    b = _with_extras(a, uni, n, diverged_ids)
    n0 = ClusterNode("n0", a, uni)
    n1 = ClusterNode("n1", b, uni)
    before = tracing.counters()
    stats = n0.sync_shard_subset(n1, lay)
    assert stats.shards_synced == 1 and set(stats.per_shard) == {1}
    control = np.asarray(digest_mod.digest_of(a.merge(b), uni),
                         dtype=np.uint64)
    with n0._lock:
        repaired = n0._batch
    assert np.array_equal(
        np.asarray(digest_mod.digest_of(repaired, uni), dtype=np.uint64),
        control)
    # repair heat landed on the initiator's tracker, at the right rows
    span = subtree_layout(n)[1]
    heat = np.asarray(n0.heat.heat_vector())
    hot = {i for i in diverged_ids}
    assert sum(heat[i // span] for i in hot) > 0
    deltas = tracing.counters_since(before)
    assert deltas.get("mesh.sync.rounds") == 1
    # no sync session ran: no full-state frames, no session counters
    assert not any(k.startswith("sync.full_state") for k in deltas)


# -- per-shard durability ----------------------------------------------------


def _digest(batch, uni):
    return np.asarray(digest_mod.digest_of(batch, uni), dtype=np.uint64)


def test_fleet_snapshot_roundtrip(tmp_path):
    uni = small_universe()
    n = 24
    lay = mesh.choose_layout(n, 4, granule=4)
    a, _ = _history_batches(n, uni)
    store = mesh_durable.MeshSnapshotStore(tmp_path, lay)
    before = tracing.counters()
    manifest = store.write_fleet(a, uni, node_id="n0", wal_seq=7)
    assert manifest["wal_seq"] == 7
    assert len(manifest["generations"]) == lay.shards
    restored, loaded = store.load_fleet(uni)
    assert loaded["node_id"] == "n0"
    assert np.array_equal(_digest(restored, uni), _digest(a, uni))
    deltas = tracing.counters_since(before)
    assert deltas.get("mesh.durable.snapshots") == 1
    assert deltas.get("mesh.durable.restores") == 1


def test_fleet_snapshot_kill9_before_manifest_restores_old_cut(tmp_path):
    """Simulated kill -9 between the per-shard writes and the manifest
    rename: the manifest still points at generation-1 everywhere, so
    the restore is the CONSISTENT old cut — never a torn mix."""
    uni = small_universe()
    n = 24
    lay = mesh.choose_layout(n, 4, granule=4)
    a, _ = _history_batches(n, uni)
    store = mesh_durable.MeshSnapshotStore(tmp_path, lay)
    store.write_fleet(a, uni, node_id="n0")
    # the crash: every shard store advances a generation, the manifest
    # write never happens (write_fleet's order is shards-then-manifest)
    newer = _with_extras(a, uni, n, (2, 9, 21))
    for s, (lo, hi) in enumerate(lay.ranges()):
        part = jax.tree_util.tree_map(lambda x: x[lo:hi], newer)
        store.store(s).write(part, uni, node_id="n0")
    restored, manifest = store.load_fleet(uni)
    assert np.array_equal(_digest(restored, uni), _digest(a, uni))
    # ...and a rejoin from a live peer ships ONLY the diverged shards'
    # rows, no full-state frames (the snapshot restore + subset-sync
    # recovery path)
    merged, stats = mesh.shard_subset_sync(restored, newer, lay, uni)
    assert np.array_equal(_digest(merged, uni), _digest(newer, uni))
    assert 0 < stats.shards_synced < lay.shards
    assert stats.delta_bytes > 0


def test_fleet_restore_rejections_are_typed_and_counted(tmp_path):
    uni = small_universe()
    n = 16
    lay = mesh.choose_layout(n, 4, granule=4)
    a, _ = _history_batches(n, uni)

    # manifest_missing: a fresh directory is "nothing to restore"
    empty = mesh_durable.MeshSnapshotStore(tmp_path / "empty", lay)
    assert empty.latest_manifest() is None
    before = tracing.counters()
    with pytest.raises(DurabilityError):
        empty.load_fleet(uni)
    assert tracing.counters_since(before).get(
        "mesh.durable.rejected.manifest_missing") == 1

    store = mesh_durable.MeshSnapshotStore(tmp_path / "fleet", lay)
    store.write_fleet(a, uni, node_id="n0")

    # root_mismatch: tamper a recorded root, keep the CRC honest
    manifest = store.read_manifest()
    manifest["roots"][0] ^= 0xDEAD
    del manifest["crc"]
    manifest["crc"] = mesh_durable._manifest_crc(manifest)
    with open(store.manifest_path, "w") as f:
        json.dump(manifest, f)
    before = tracing.counters()
    with pytest.raises(CheckpointFormatError):
        store.load_fleet(uni)
    assert tracing.counters_since(before).get(
        "mesh.durable.rejected.root_mismatch") == 1

    # manifest_corrupt: torn write (CRC mismatch)
    store.write_fleet(a, uni, node_id="n0")
    raw = open(store.manifest_path).read()
    with open(store.manifest_path, "w") as f:
        f.write(raw[: len(raw) // 2])
    before = tracing.counters()
    with pytest.raises(CheckpointFormatError):
        store.read_manifest()
    assert tracing.counters_since(before).get(
        "mesh.durable.rejected.manifest_corrupt") == 1

    # layout_mismatch: same directory, different shard map
    store.write_fleet(a, uni, node_id="n0")
    other = mesh_durable.MeshSnapshotStore(
        tmp_path / "fleet", mesh.choose_layout(n, 2, granule=4))
    before = tracing.counters()
    with pytest.raises(CheckpointFormatError):
        other.load_fleet(uni)
    assert tracing.counters_since(before).get(
        "mesh.durable.rejected.layout_mismatch") == 1

    # shard_missing: a shard directory vanished out from under the
    # manifest
    import shutil

    shutil.rmtree(os.path.join(store.dirpath, "shard-01"))
    fresh = mesh_durable.MeshSnapshotStore(tmp_path / "fleet", lay)
    before = tracing.counters()
    with pytest.raises(CheckpointFormatError):
        fresh.load_fleet(uni)
    assert tracing.counters_since(before).get(
        "mesh.durable.rejected.shard_missing") == 1


# -- gauges ------------------------------------------------------------------


def test_publish_gauges_rows_the_placement_surface():
    uni = small_universe()
    a, _ = _history_batches(32, uni)
    sa = mesh.ShardedBatch.shard(a, uni, shards=4, granule=8)
    span = subtree_layout(32)[1]
    heat = np.ones(-(-32 // span), dtype=np.float64)
    reg = obs_metrics.MetricsRegistry()
    sa.publish_gauges(registry=reg, heat_vector=heat, span=span)
    gauges = reg.snapshot()["gauges"]
    assert gauges["mesh.layout.shards"] == 4
    assert gauges["mesh.layout.granule"] == 8
    for s, (lo, hi) in enumerate(sa.layout.ranges()):
        assert gauges[f"mesh.shard.{s}.objects"] == hi - lo
        assert f"mesh.shard.{s}.load" in gauges
    # measured loads cover the whole fleet's heat
    loads = mesh.shard_loads(sa.layout, heat, span)
    assert float(loads.sum()) == pytest.approx(float(heat.sum()))
