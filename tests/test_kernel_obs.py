"""The runtime kernel observatory (`crdt_tpu.obs.kernels`).

Covers the PR 14 acceptance bar: the manifest↔runtime cross-check
(every traceable KernelSpec row instruments, every runtime label IS a
manifest row), compile/recompile tracking with arg-shape-stamped
``kernel.compile`` events and the KC04 budget as a live gauge, the
recompile-storm oracle (a steady-state sync+GC epoch records ZERO
compile events after warmup; a forced regrow-ladder walk records
exactly the ladder's compiles, each ladder-attributed), wrapper
transparency (``__wrapped__``/attribute forwarding/error accounting),
device-memory gauges against the capacity tracker, and the
``/kernels`` HTTP surface.
"""

import json
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu.analysis.kernels import MANIFEST
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.batch import vclock_batch
from crdt_tpu.config import CrdtConfig
from crdt_tpu.obs import events as obs_events
from crdt_tpu.obs import kernels as obs_kernels
from crdt_tpu.obs import metrics as obs_metrics
from crdt_tpu.obs import namespace
from crdt_tpu.parallel.executor import JoinExecutor, JoinStats
from crdt_tpu.scalar.orswot import Orswot
from crdt_tpu.utils.interning import Universe

pytestmark = pytest.mark.obs


def _counters():
    obs_kernels.publish()  # drain pending per-call aggregates first
    return obs_metrics.registry().counters_snapshot()


def _snap():
    obs_kernels.publish()
    return obs_metrics.registry().snapshot()


def _delta(before, after, name):
    return after.get(name, 0) - before.get(name, 0)


def _consume_ladder_credit(name):
    """Earlier tests may have regrown fleets (bumping the ladder
    epoch) after this kernel's last compile; consume the stale credit
    so classification assertions below see only THIS test's
    transitions."""
    prof = obs_kernels.kernel_observatory().profile(name)
    with prof._lock:
        prof._ladder_seen = obs_kernels._ladder_epoch()


# ---- manifest <-> runtime cross-check --------------------------------------


def test_manifest_runtime_crosscheck():
    """Single source of kernel identity, pinned dynamically: warming
    the manifest instruments EVERY traceable row (imports attach the
    decorated kernels, build closures attach the factory kernels), and
    the runtime registry holds nothing the manifest doesn't name."""
    instrumented = obs_kernels.warm_manifest()
    traceable = {s.name for s in MANIFEST if s.build is not None}
    notrace = {s.name for s in MANIFEST if s.build is None}
    assert instrumented == traceable, (
        f"missing from runtime registry: {sorted(traceable - instrumented)}; "
        f"unmanifested runtime labels: {sorted(instrumented - traceable)}"
    )
    # declared-no-trace rows are visible as explicit, reasoned gaps
    table = {r["kernel"]: r for r in obs_kernels.kernel_observatory().table()}
    assert set(table) == traceable | notrace
    for name in notrace:
        assert not table[name]["instrumented"]
        assert table[name]["notrace_reason"]


def test_instrument_rejects_unmanifested_names():
    with pytest.raises(ValueError, match="no KernelSpec row"):
        obs_kernels.kernel_observatory().instrument(
            "batch.orswot.not_a_kernel", lambda: None)


def test_every_published_kernel_name_has_a_namespace_row():
    obs_kernels.warm_manifest()
    prof = obs_kernels.kernel_observatory().profile("batch.vclock.merge")
    prof._ensure_handles()
    snap = obs_metrics.registry().snapshot()
    for kind in ("counters", "gauges", "histograms"):
        for name in snap[kind]:
            if name.startswith(("kernel.", "devicemem.")):
                assert namespace.match(name, kind[:-1]) is not None, (
                    kind, name)


# ---- compile tracking ------------------------------------------------------


def test_compile_counting_events_and_budget_gauge():
    _consume_ladder_credit("batch.vclock.merge")
    before = _counters()
    seq = obs_kernels.last_event_seq()
    # a shape no other test uses: N=97 guarantees a fresh jit cache key
    plane = jnp.zeros((97, 8), dtype=jnp.uint32)
    vclock_batch._merge(plane, plane)
    vclock_batch._merge(plane, plane)  # same shape: cache hit, no compile
    after = _counters()
    assert _delta(before, after, "kernel.batch_vclock_merge.compiles") == 1
    assert _delta(before, after, "kernel.batch_vclock_merge.calls") == 2
    assert _delta(before, after, "kernel.compiles") == 1
    evs = [e for e in obs_events.recorder().snapshot(kind="kernel.compile")
           if e["seq"] > seq
           and e["fields"]["kernel"] == "batch.vclock.merge"]
    assert len(evs) == 1
    f = evs[0]["fields"]
    assert "uint32[97, 8]" in f["shapes"]
    assert f["count"] == 1 and f["wall_s"] > 0
    assert not f["ladder"]  # no regrow stamped around this compile
    prof = obs_kernels.kernel_observatory().profile("batch.vclock.merge")
    gauges = _snap()["gauges"]
    assert gauges["kernel.batch_vclock_merge.compile_budget_frac"] == \
        pytest.approx(prof.compiles / prof.compile_budget)
    assert gauges["kernel.budget.watermark"] in (0, 1, 2)


def test_wall_histogram_steady_state_and_storm_report():
    plane = jnp.zeros((89, 8), dtype=jnp.uint32)
    vclock_batch._merge(plane, plane)  # warm (compiles)
    seq = obs_kernels.last_event_seq()
    hist_before = _snap()["histograms"].get(
        "kernel.batch_vclock_merge.wall", {"count": 0})["count"]
    for _ in range(20):
        vclock_batch._merge(plane, plane)
    storm = obs_kernels.storm_report(since_seq=seq)
    assert storm["compiles"] == 0 and not storm["storm"]
    hist_after = _snap()["histograms"][
        "kernel.batch_vclock_merge.wall"]["count"]
    assert hist_after - hist_before == 20


def test_cost_analysis_capture_is_lazy_and_memoized():
    plane = jnp.zeros((79, 8), dtype=jnp.uint32)
    vclock_batch._merge(plane, plane)
    prof = obs_kernels.kernel_observatory().profile("batch.vclock.merge")
    cost = prof.capture_cost()
    assert cost is not None and cost["bytes_accessed"] > 0
    assert prof.capture_cost() is cost  # memoized until the next compile
    gauges = _snap()["gauges"]
    assert gauges["kernel.batch_vclock_merge.cost_bytes"] == \
        cost["bytes_accessed"]


# ---- wrapper transparency --------------------------------------------------


def test_wrapper_is_transparent():
    wrapped = vclock_batch._merge
    assert isinstance(wrapped, obs_kernels._ObservedKernel)
    # kernelcheck's _unjit discipline: __wrapped__ is the PLAIN function
    plain = wrapped.__wrapped__
    assert not hasattr(plain, "_cache_size")
    out = plain(np.zeros((2, 2), np.uint32), np.ones((2, 2), np.uint32))
    assert np.asarray(out).max() == 1
    # unknown attributes forward to the jitted target
    assert callable(wrapped.lower)
    assert wrapped._cache_size() >= 0


def test_wrapper_counts_raising_kernels():
    before = _counters()
    with pytest.raises(Exception):
        # mismatched ranks: jax rejects at trace time; the error must
        # be counted, never swallowed
        vclock_batch._merge(jnp.zeros((4, 4), jnp.uint32),
                            jnp.zeros((3, 3), jnp.uint32))
    after = _counters()
    assert _delta(before, after, "kernel.batch_vclock_merge.errors") == 1


# ---- the recompile-storm oracle --------------------------------------------


def _fleet_batches(uni, member_rows):
    batches = []
    for row in member_rows:
        s = Orswot()
        for member, actor in row:
            s.apply(s.add(member, s.value().derive_add_ctx(actor)))
        batches.append(OrswotBatch.from_scalar([s], uni))
    return batches


def test_regrow_ladder_walk_compiles_exactly_once_per_rung():
    """The forced ladder walk: member_capacity 2 -> 4 -> 8 under the
    executor's overflow recovery.  The merge kernel compiles exactly
    once per rung (base warmup + one per regrow), and every
    post-regrow compile is ladder-attributed — the storm oracle's
    negative control."""
    # num_actors=5 keeps every shape unique to this test, so compile
    # counts are exact regardless of suite order
    uni = Universe(CrdtConfig(num_actors=5, member_capacity=2,
                              deferred_capacity=2, counter_bits=32))
    rows = [[("a", 0), ("b", 0)], [("c", 1), ("d", 1)], [("e", 2), ("f", 2)]]
    batches = _fleet_batches(uni, rows)
    _consume_ladder_credit("batch.orswot.merge")
    before = _counters()
    seq = obs_kernels.last_event_seq()
    stats = JoinStats()
    JoinExecutor(strategy="sequential").join_all(batches, stats=stats)
    after = _counters()
    assert stats.overflow_regrows == 2  # 2 -> 4 -> 8
    rungs = stats.overflow_regrows + 1
    assert _delta(before, after,
                  "kernel.batch_orswot_merge.compiles") == rungs
    evs = [e["fields"] for e in
           obs_events.recorder().snapshot(kind="kernel.compile")
           if e["seq"] > seq
           and e["fields"]["kernel"] == "batch.orswot.merge"]
    assert len(evs) == rungs
    # base-rung compile precedes any regrow stamp; the two post-regrow
    # compiles are each ladder-attributed
    assert [f["ladder"] for f in evs] == [False, True, True]
    report = obs_kernels.storm_report(since_seq=seq)
    merge = report["kernels"]["batch.orswot.merge"]
    assert merge["ladder"] == stats.overflow_regrows


def test_steady_state_sync_gc_epoch_records_zero_compiles():
    """The storm oracle's positive control: after a warmup epoch
    (diverged sync + GC settle), an identical steady-state epoch — an
    idle re-sync and another settle over unchanged shapes — must not
    produce a single compile event."""
    from crdt_tpu.gc.compact import settle_orswot
    from crdt_tpu.sync.session import SyncSession, sync_pair

    uni = Universe(CrdtConfig(num_actors=6, member_capacity=8,
                              deferred_capacity=4, counter_bits=32))

    def batch_of(member_rows, actor):
        scalars = []
        for ms in member_rows:
            s = Orswot()
            for m in ms:
                s.apply(s.add(m, s.value().derive_add_ctx(actor)))
            scalars.append(s)
        return OrswotBatch.from_scalar(scalars, uni)

    a = batch_of([["a1", "a2"], ["shared"]], 0)
    b = batch_of([["b1"], ["shared", "b2"]], 1)
    # warmup epoch: digest + delta + merge + settle kernels all compile
    sa, sb = SyncSession(a, uni), SyncSession(b, uni)
    ra, rb = sync_pair(sa, sb)
    assert ra.converged and rb.converged
    settled, _ = settle_orswot(sa.batch)
    # ...and one converged-idle session: a CLEAN re-sync is where the
    # stability frontier records its evidence (PR 15), so its fold
    # kernel belongs to the warmup's kernel set like every other
    sw_a = SyncSession(settled, uni)
    sw_b = SyncSession(sb.batch, uni)
    rw_a, _rw_b = sync_pair(sw_a, sw_b)
    assert rw_a.converged and rw_a.delta_objects_sent == 0
    seq = obs_kernels.last_event_seq()
    before = _counters()
    # steady-state epoch: idle re-sync over the converged fleet +
    # another settle at unchanged capacities — zero compiles allowed
    sa2, sb2 = SyncSession(settled, uni), SyncSession(sb.batch, uni)
    ra2, rb2 = sync_pair(sa2, sb2)
    assert ra2.converged and ra2.delta_objects_sent == 0
    settle_orswot(sa2.batch)
    after = _counters()
    storm = obs_kernels.storm_report(since_seq=seq)
    assert storm["compiles"] == 0, (
        f"steady-state epoch recompiled: {storm['kernels']}"
    )
    assert _delta(before, after, "kernel.compiles") == 0
    assert not storm["storm"]


# ---- device memory ---------------------------------------------------------


def test_device_memory_gauges_track_live_arrays():
    from crdt_tpu.obs.capacity import CapacityTracker

    reg = obs_metrics.MetricsRegistry()
    trk = CapacityTracker(registry=reg)
    uni = Universe.identity(CrdtConfig(
        num_actors=8, member_capacity=8, deferred_capacity=4,
        counter_bits=32))
    batch = OrswotBatch.zeros(64, uni)
    occ = trk.sample(batch)
    out = trk.sample_device_memory()
    snap = reg.snapshot()["gauges"]
    assert out["arrays"] > 0
    # the device holds AT LEAST the tracked planes
    assert out["live_bytes"] >= occ.bytes
    assert snap["devicemem.live_bytes"] == out["live_bytes"]
    assert snap["devicemem.tracked_bytes"] == occ.bytes
    assert 0.0 < snap["devicemem.tracked_frac"] <= 1.0
    # per-dtype families cover the total
    dtype_bytes = sum(v for k, v in snap.items()
                      if k.startswith("devicemem.dtype."))
    assert dtype_bytes == out["live_bytes"]
    assert reg.snapshot()["counters"]["devicemem.samples"] == 1


def test_kernel_rows_ride_the_fleet_lattice():
    """Per-node kernel health rides the PR 6 fleet observatory for
    free: a fleet slice captured from the default registry carries the
    kernel counters (publish() drains the pending aggregates at slice
    capture, same read-boundary discipline as /metrics)."""
    from crdt_tpu.obs import fleet as obs_fleet

    plane = jnp.zeros((71, 8), dtype=jnp.uint32)
    vclock_batch._merge(plane, plane)
    snap = obs_fleet.capture_slice("n-kernel-obs")
    counters = snap.slices["n-kernel-obs"]["counters"]
    assert counters["kernel.batch_vclock_merge.calls"] >= 1
    assert counters["kernel.batch_vclock_merge.compiles"] >= 1
    assert "kernel.batch_vclock_merge.wall" in \
        snap.slices["n-kernel-obs"]["histograms"]


# ---- the /kernels surface --------------------------------------------------


def test_kernels_endpoint_prom_and_json():
    from crdt_tpu.obs.export import start_metrics_server

    plane = jnp.zeros((73, 8), dtype=jnp.uint32)
    vclock_batch._merge(plane, plane)
    server = start_metrics_server()
    try:
        base = f"http://127.0.0.1:{server.port}/kernels"
        text = urllib.request.urlopen(base).read().decode()
        assert "crdt_tpu_kernel_batch_vclock_merge_compiles_total" in text
        assert "crdt_tpu_devicemem_live_bytes" in text
        # the kernel plane only: no sync/cluster families leak in
        assert "crdt_tpu_sync_" not in text
        j = json.loads(
            urllib.request.urlopen(base + "?format=json").read())
        rows = {r["kernel"]: r for r in j["kernels"]}
        assert len(rows) == len(MANIFEST)
        row = rows["batch.vclock.merge"]
        assert row["instrumented"] and row["calls"] >= 1
        assert row["compile_budget_frac"] == pytest.approx(
            row["compiles"] / row["compile_budget"], abs=1e-4)
        assert row["wall_p50_s"] is None or row["wall_p50_s"] >= 0
        assert "storm" in j and "unexplained" in j["storm"]
    finally:
        server.stop()
