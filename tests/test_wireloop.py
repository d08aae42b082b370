"""PipelinedWireLoop — the double-buffered wire replication loop
(`crdt_tpu/batch/wireloop.py`).

Contract under test: the loop's blobs-out are BYTE-identical to
``to_binary`` of the scalar engine's left fold + defer-plunger
self-merge over ``from_binary`` of the blobs-in, for every mode
(native/jnp fold, overlapped/serial), with reused staging buffers never
leaking state between rounds, and with the per-stage native-vs-fallback
accounting the bench JSON reports.
"""

import numpy as np
import pytest

from crdt_tpu import Orswot, from_binary, to_binary
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.batch.wireloop import PipelinedWireLoop, _native_fold_engine
from crdt_tpu.config import CrdtConfig
from crdt_tpu.utils.interning import Universe
from crdt_tpu.utils.testdata import anti_entropy_fleets

_HAVE_ENGINE = _native_fold_engine() is not None


def _identity_uni(**kw):
    base = dict(num_actors=8, member_capacity=8, deferred_capacity=4,
                counter_bits=32)
    base.update(kw)
    return Universe.identity(CrdtConfig(**base))


def _fleet_blobs(uni, rng, n, r, **kw):
    cfg = uni.config
    shape = dict(base=4, novel=1, deferred_frac=0.25,
                 dtype=np.uint64 if cfg.counter_bits == 64 else np.uint32)
    shape.update(kw)
    reps = anti_entropy_fleets(
        rng, n, cfg.num_actors, cfg.member_capacity, cfg.deferred_capacity,
        r, **shape,
    )
    return [OrswotBatch(*rep).to_wire(uni) for rep in reps]


def _scalar_fold_blob(rep_blobs, i):
    acc = from_binary(rep_blobs[0][i])
    for rr in range(1, len(rep_blobs)):
        acc.merge(from_binary(rep_blobs[rr][i]))
    acc.merge(acc.clone())  # defer plunger, as the loop
    return to_binary(acc)


_FOLD_PATHS = (["native"] if _HAVE_ENGINE else []) + ["jnp"]


@pytest.mark.parametrize("fold_path", _FOLD_PATHS)
@pytest.mark.parametrize("overlap", [True, False])
def test_loop_matches_scalar_fold(fold_path, overlap):
    uni = _identity_uni(num_actors=16)
    rng = np.random.RandomState(3)
    rep_blobs = _fleet_blobs(uni, rng, 200, 4)
    loop = PipelinedWireLoop(uni, fold_path=fold_path)
    res = loop.run([rep_blobs], overlap=overlap)
    assert res["pipeline"] == ("overlapped" if overlap else "serial")
    assert res["fold_path"] == fold_path
    assert res["merges"] == 200 * 4
    assert len(res["out_blobs"]) == 200
    for i in range(0, 200, 23):
        assert res["out_blobs"][i] == _scalar_fold_blob(rep_blobs, i)


@pytest.mark.parametrize("fold_path", _FOLD_PATHS)
def test_staging_reuse_does_not_leak_between_rounds(fold_path):
    """Rounds with DIFFERENT data through one loop instance: the reused
    staging/accumulator buffers must not leak rows between rounds (this
    is the contract the native parser's self-clearing `clear` flag
    exists for)."""
    uni = _identity_uni(num_actors=16)
    loop = PipelinedWireLoop(uni, fold_path=fold_path)
    outs = {}
    for seed in (7, 8):
        rep_blobs = _fleet_blobs(uni, np.random.RandomState(seed), 64, 3)
        res = loop.run([rep_blobs], overlap=True)
        outs[seed] = (rep_blobs, res["out_blobs"])
    for seed, (rep_blobs, blobs) in outs.items():
        for i in range(64):
            assert blobs[i] == _scalar_fold_blob(rep_blobs, i), (seed, i)
    # and a denser round after a sparser one (stale high slots)
    sparse = _fleet_blobs(uni, np.random.RandomState(9), 64, 3, base=1,
                          deferred_frac=0.0)
    res = loop.run([sparse], overlap=True)
    for i in range(64):
        assert res["out_blobs"][i] == _scalar_fold_blob(sparse, i)


def test_overlapped_equals_serial_bytes():
    uni = _identity_uni()
    rep_blobs = _fleet_blobs(uni, np.random.RandomState(5), 128, 4)
    loop = PipelinedWireLoop(uni)
    a = loop.run([rep_blobs] * 2, overlap=True)["out_blobs"]
    b = loop.run([rep_blobs] * 2, overlap=False)["out_blobs"]
    assert a == b


@pytest.mark.skipif(not _HAVE_ENGINE, reason="native engine unavailable")
def test_e2e_shaped_blobs_take_native_path():
    """Regression for the round-5 ingest-collapse hypothesis: e2e-shaped
    blobs (A=64, ~7 members, deferred sections, native-encoded) must
    report native_fraction == 1.0 through the loop — the collapse was
    allocation churn, NOT a silent fallback, and this pins that the
    realistic shapes stay on the native parser."""
    uni = _identity_uni(num_actors=64, member_capacity=16,
                        deferred_capacity=2)
    rep_blobs = _fleet_blobs(
        uni, np.random.RandomState(11), 256, 8, base=6, novel=1,
        deferred_frac=0.25,
    )
    loop = PipelinedWireLoop(uni, fold_path="native")
    res = loop.run([rep_blobs], overlap=True)
    assert res["ingest_native_fraction"] == 1.0
    assert res["egress_native_fraction"] == 1.0
    assert not any(
        ".fallback_reason." in k for k in res["wire_counters"]
    ), res["wire_counters"]
    for i in range(0, 256, 37):
        assert res["out_blobs"][i] == _scalar_fold_blob(rep_blobs, i)


@pytest.mark.skipif(not _HAVE_ENGINE, reason="native engine unavailable")
def test_grammar_fallback_blob_splices_through_loop():
    """A blob outside the fast-path grammar (u64 counter >= 2^63 zigzags
    past the native varint) rides the per-blob Python splice inside the
    loop's staging parse, and the accounting shows a fractional
    native_fraction with the `grammar` reason."""
    uni = _identity_uni(counter_bits=64)
    n, r = 32, 2
    rep_blobs = _fleet_blobs(uni, np.random.RandomState(6), n, r,
                             deferred_frac=0.0)
    big = Orswot()
    big.clock.witness(1, 1 << 63)
    big.entries[5] = big.clock.clone()
    rep_blobs[0][3] = to_binary(big)
    loop = PipelinedWireLoop(uni, fold_path="native")
    res = loop.run([rep_blobs], overlap=True)
    assert res["ingest_native_fraction"] == pytest.approx(
        (n * r - 1) / (n * r)
    )
    assert res["wire_counters"][
        "wire.orswot.from_wire.fallback_reason.grammar"
    ] == 1
    assert res["out_blobs"][3] == _scalar_fold_blob(rep_blobs, 3)


def test_non_identity_universe_python_route():
    """Tuple members, keys neither identity ints nor names: no native
    path at all — the loop still produces byte-faithful output through
    the Python codec, and the counters say why."""
    uni = Universe(CrdtConfig(num_actors=4, member_capacity=4,
                              deferred_capacity=2))
    states = []
    for i in range(8):
        s = Orswot()
        s.apply(s.add(("m", i), s.value().derive_add_ctx("alice")))
        states.append(s)
    blobs = [to_binary(s) for s in states]
    loop = PipelinedWireLoop(uni, fold_path="jnp")
    res = loop.run([[blobs]])  # one round, one fleet
    assert res["ingest_native_fraction"] == 0.0
    assert res["egress_native_fraction"] == 0.0
    reasons = {k for k in res["wire_counters"] if ".fallback_reason." in k}
    assert any("key_type" in k for k in reasons)
    for i in range(8):
        acc = from_binary(blobs[i])
        acc.merge(acc.clone())
        assert res["out_blobs"][i] == to_binary(acc)


@pytest.mark.parametrize("fold_path", _FOLD_PATHS)
def test_single_replica_round_is_plunger_only(fold_path):
    uni = _identity_uni()
    rep_blobs = _fleet_blobs(uni, np.random.RandomState(2), 16, 1)
    res = PipelinedWireLoop(uni, fold_path=fold_path).run([rep_blobs])
    for i in range(16):
        acc = from_binary(rep_blobs[0][i])
        acc.merge(acc.clone())
        assert res["out_blobs"][i] == to_binary(acc)


def test_empty_rounds_and_collect_modes():
    uni = _identity_uni()
    loop = PipelinedWireLoop(uni)
    assert loop.run([])["merges"] == 0
    rep_blobs = _fleet_blobs(uni, np.random.RandomState(4), 8, 2)
    seen = []
    res = loop.run([rep_blobs] * 3, collect="all",
                   on_round=lambda i, b: seen.append(i))
    assert seen == [0, 1, 2]
    assert len(res["out_blobs"]) == 3
    assert res["out_blobs"][0] == res["out_blobs"][2]
    assert loop.run([rep_blobs], collect="none")["out_blobs"] == []
    with pytest.raises(ValueError):
        loop.run([rep_blobs], collect="bogus")


@pytest.mark.skipif(not _HAVE_ENGINE, reason="native engine unavailable")
def test_fold_overflow_raises():
    """Disjoint member sets that overflow member_capacity on the join
    must raise CapacityOverflowError, not silently truncate."""
    from crdt_tpu.error import CapacityOverflowError

    uni = _identity_uni(num_actors=4, member_capacity=2,
                        deferred_capacity=2)
    fleets = []
    for rep in range(3):
        row = []
        for i in range(4):
            s = Orswot()
            for j in range(2):  # 3 fleets x 2 distinct members > cap 2
                s.apply(s.add(rep * 2 + j,
                              s.value().derive_add_ctx(rep)))
            row.append(s)
        fleets.append([to_binary(s) for s in row])
    loop = PipelinedWireLoop(uni, fold_path="native")
    with pytest.raises(CapacityOverflowError):
        loop.run([fleets])
