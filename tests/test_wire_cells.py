"""The compact ORSWOT ingest of the device wire fold: a fleet parsed into
id rows plus its nonzero counters (`wirebulk.OrswotCells`, native
``orswot_ingest_cells``), shipped to the device and densified there
(`orswot_batch._densify_cells`).

Contract under test: densified cells equal the dense parse
(``orswot_planes_from_wire``) plane for plane and bit for bit, on
identity and named universes, through the Python decode of refused
blobs and the named pass over unseen names, with the same hard errors;
the jnp wire loop egresses what the native CPU fold egresses; the
``wireloop.put.*`` counters say which way each fleet went; and the
staging pool is sized once and never reallocated.
"""

import numpy as np
import pytest

from crdt_tpu import Orswot, from_binary, to_binary
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.batch.orswot_batch import _densify_cells
from crdt_tpu.batch.wirebulk import (OrswotCells, orswot_cells_from_wire,
                                     orswot_planes_from_wire)
from crdt_tpu.batch.wireloop import PipelinedWireLoop, _native_fold_engine
from crdt_tpu.config import CrdtConfig
from crdt_tpu.error import WireFormatError
from crdt_tpu.native import loader
from crdt_tpu.scalar.vclock import VClock
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe
from crdt_tpu.utils.testdata import anti_entropy_fleets

from test_wire_named import _blob, _late, actor_name, named_fleets

pytestmark = pytest.mark.skipif(not loader.available(),
                                reason="native library unavailable")

_PLANES = ("clock", "ids", "dots", "d_ids", "d_clocks")


def _cfg(bits=32, a=8, m=8, d=4):
    return CrdtConfig(num_actors=a, member_capacity=m, deferred_capacity=d,
                      counter_bits=bits)


def _identity_blobs(cfg, n, seed=1, deferred_frac=0.3):
    dt = np.uint64 if cfg.counter_bits == 64 else np.uint32
    (rep,) = anti_entropy_fleets(
        np.random.RandomState(seed), n, cfg.num_actors, cfg.member_capacity,
        cfg.deferred_capacity, 1, base=3, novel=1,
        deferred_frac=deferred_frac, dtype=dt)
    return OrswotBatch(*rep).to_wire(Universe.identity(cfg))


def _densified(blobs, uni, cells=None):
    cfg = uni.config
    cells = cells or OrswotCells(len(blobs), cfg)
    orswot_cells_from_wire(blobs, uni, cells)
    return tuple(np.asarray(p) for p in _densify_cells(
        *cells.padded(), a=cfg.num_actors, m=cfg.member_capacity,
        d=cfg.deferred_capacity))


# each case: (universe factory, warm-up blobs parsed first, blobs)
def _case(name):
    cfg = _cfg()
    ident = lambda c=cfg: Universe.identity(c)  # noqa: E731
    if name == "identity":
        return ident, [], _identity_blobs(cfg, 150, deferred_frac=0.0)
    if name == "identity_deferred":
        return ident, [], _identity_blobs(cfg, 150, deferred_frac=0.9)
    if name == "identity_u64":
        c = _cfg(bits=64)
        return (lambda: Universe.identity(c)), [], _identity_blobs(c, 90)
    if name == "noncanonical_identity":
        blobs = _identity_blobs(cfg, 20)
        blobs[7] = _blob([(1, 3)], [(5, {1: 3}), (2, {1: 2})])
        return ident, [], blobs
    if name == "duplicate_clock_actor":
        blobs = _identity_blobs(cfg, 12)
        blobs[4] = _blob([(1, 3), (2, 4), (1, 5)], [(6, {1: 5, 3: 1})])
        return ident, [], blobs
    if name == "zero_counters":
        blobs = _identity_blobs(cfg, 12)
        blobs[2] = _blob([(1, 0), (2, 7)], [(3, {1: 0}), (4, {2: 7})])
        return ident, [], blobs
    if name == "empty_states":
        return ident, [], [to_binary(Orswot())] * 9
    if name == "no_objects":
        return ident, [], []
    named = lambda: Universe(cfg)  # noqa: E731
    fleet = named_fleets(2, 120, 1, cfg)[0]
    if name == "named_known":
        return named, fleet, fleet
    if name == "named_unseen":
        return named, fleet[:40], fleet
    if name == "named_noncanonical_unseen":
        a = actor_name(0)
        odd = _blob([(a, 3)], [("zeta", {a: 3}), ("beta", {a: 2})])
        tail = named_fleets(2, 6, 1, cfg, mn=_late)[0]
        return named, fleet[:50], fleet[:50] + [odd] + tail
    raise AssertionError(name)


_CASES = ["identity", "identity_deferred", "identity_u64",
          "noncanonical_identity", "duplicate_clock_actor", "zero_counters",
          "empty_states", "no_objects", "named_known", "named_unseen",
          "named_noncanonical_unseen"]


@pytest.mark.parametrize("name", _CASES)
def test_densified_cells_equal_the_dense_parse(name):
    factory, warm, blobs = _case(name)
    uni_c, uni_d = factory(), factory()
    if warm:
        orswot_planes_from_wire(warm, uni_c)
        orswot_planes_from_wire(warm, uni_d)
    before = tracing.counters()
    got = _densified(blobs, uni_c)
    c_cells = tracing.counters_since(before)
    before = tracing.counters()
    want = orswot_planes_from_wire(blobs, uni_d)
    c_dense = tracing.counters_since(before)
    for plane, g, w in zip(_PLANES, got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, plane
        np.testing.assert_array_equal(g, w, err_msg=plane)
    assert uni_c.actors.values() == uni_d.actors.values()
    assert uni_c.members.values() == uni_d.members.values()
    for key in ("native", "fallback", "fallback_reason.grammar"):
        key = f"wire.orswot.from_wire.{key}"
        assert c_cells.get(key, 0) == c_dense.get(key, 0), key


def test_duplicate_actor_resolves_last_write_wins():
    cfg = _cfg()
    blob = _blob([(1, 3), (2, 4), (1, 5)], [(6, {2: 4})])
    clock, ids, dots, _, _ = _densified([blob], Universe.identity(cfg))
    assert clock[0].tolist() == [0, 5, 4, 0, 0, 0, 0, 0]
    assert ids[0, 0] == 6 and dots[0, 0, 2] == 4


def test_cells_regrow_when_a_fleet_overflows_them():
    """Cell columns too short for the fleet grow (to a power of two) and
    the parse runs again: the same planes."""
    cfg = _cfg()
    uni = Universe.identity(cfg)
    blobs = _identity_blobs(cfg, 200)
    cells = OrswotCells(200, cfg)
    cells.idx, cells.val = cells.idx[:4], cells.val[:4]
    got = _densified(blobs, uni, cells)
    assert cells.count > 4 and cells.idx.shape[0] >= cells.count
    assert cells.idx.shape[0] & (cells.idx.shape[0] - 1) == 0
    for g, w in zip(got, orswot_planes_from_wire(blobs, uni)):
        np.testing.assert_array_equal(g, w)


def _overflow_blob(kind, named):
    key = (lambda i: f"k{i}") if named else (lambda i: i)
    s = Orswot()
    if kind == "member_overflow":
        for m in range(3):
            s.apply(s.add(key(m), s.value().derive_add_ctx(key(0))))
    elif kind == "deferred_overflow":
        s.apply(s.add(key(1), s.value().derive_add_ctx(key(0))))
        for k in range(2):
            s.deferred[VClock({key(0): 5 + k}).key()] = {key(2)}
    else:  # actor_range: one actor past the A columns
        for a in range(9):
            s.apply(s.add(key(1), s.value().derive_add_ctx(key(a))))
    return to_binary(s)


@pytest.mark.parametrize("named", [False, True], ids=["identity", "named"])
@pytest.mark.parametrize("kind", ["member_overflow", "deferred_overflow",
                                  "actor_range"])
def test_hard_statuses_raise_as_the_dense_parse(kind, named):
    cfg = _cfg(m=2, d=1)
    factory = (lambda: Universe(cfg)) if named else \
        (lambda: Universe.identity(cfg))
    ok = Orswot()
    ok.apply(ok.add("k1" if named else 1,
                    ok.value().derive_add_ctx("k0" if named else 0)))
    blobs = [to_binary(ok)] * 3 + [_overflow_blob(kind, named)] + \
        [to_binary(ok)] * 2
    with pytest.raises(WireFormatError) as dense:
        orswot_planes_from_wire(blobs, factory())
    with pytest.raises(WireFormatError) as cells:
        orswot_cells_from_wire(blobs, factory(), OrswotCells(6, cfg))
    assert str(cells.value) == str(dense.value)
    assert str(cells.value).startswith("object 3:")


def _rounds(named, r, n=60):
    cfg = _cfg()
    if named:
        return cfg, [named_fleets(seed, n, r, cfg) for seed in (8, 9)]
    uni = Universe.identity(cfg)
    out = []
    for seed in (8, 9):
        reps = anti_entropy_fleets(
            np.random.RandomState(seed), n, cfg.num_actors,
            cfg.member_capacity, cfg.deferred_capacity, r, base=3, novel=1,
            deferred_frac=0.3, dtype=np.uint32)
        out.append([OrswotBatch(*rep).to_wire(uni) for rep in reps])
    return cfg, out


def _universe(cfg, named):
    return Universe(cfg) if named else Universe.identity(cfg)


@pytest.mark.skipif(_native_fold_engine() is None,
                    reason="native fold unavailable")
@pytest.mark.parametrize("r", [1, 3])
@pytest.mark.parametrize("named", [False, True], ids=["identity", "named"])
def test_jnp_loop_egresses_what_the_native_fold_egresses(named, r):
    cfg, rounds = _rounds(named, r)
    got = PipelinedWireLoop(_universe(cfg, named), fold_path="jnp").run(
        rounds, collect="all")["out_blobs"]
    want = PipelinedWireLoop(_universe(cfg, named), fold_path="native").run(
        rounds, collect="all")["out_blobs"]
    assert got == want
    for rep_blobs, out in zip(rounds, got):
        acc = from_binary(rep_blobs[0][5])
        for rep in rep_blobs[1:]:
            acc.merge(from_binary(rep[5]))
        acc.merge(acc.clone())
        assert out[5] == to_binary(acc)


_FOLD_PATHS = (["native"] if _native_fold_engine() is not None else []) \
    + ["jnp"]


@pytest.mark.parametrize("fold_path", _FOLD_PATHS)
def test_put_counters_say_how_each_fleet_went(fold_path):
    """jnp: every fleet as cells; native: every fleet dense and nothing
    put."""
    cfg, rounds = _rounds(False, 3)
    loop = PipelinedWireLoop(Universe.identity(cfg), fold_path=fold_path)
    res = loop.run(rounds, collect="all")
    c = res["wire_counters"]
    fleets = sum(len(rnd) for rnd in rounds)
    compact = c.get("wireloop.put.compact", 0)
    dense = c.get("wireloop.put.dense", 0)
    put = c.get("wireloop.put.bytes", 0)
    dense_bytes = 60 * 4 * (8 + 8 + 8 * 8 + 4 + 4 * 8)
    if fold_path == "jnp":
        assert (compact, dense) == (fleets, 0)
        assert 0 < put < fleets * dense_bytes / 2
    else:
        assert (compact, dense, put) == (0, fleets, 0)
    for rep_blobs, out in zip(rounds, res["out_blobs"]):
        acc = from_binary(rep_blobs[0][7])
        for rep in rep_blobs[1:]:
            acc.merge(from_binary(rep[7]))
        acc.merge(acc.clone())
        assert out[7] == to_binary(acc)


def _buffers(loop):
    return [tuple(a.ctypes.data for a in (s.ids, s.d_ids, s.status, s.idx,
                                          s.val)) for s in loop._staging]


def test_staging_pool_holds_a_round_ahead_and_is_never_reallocated():
    cfg, rounds = _rounds(False, 3)
    loop = PipelinedWireLoop(Universe.identity(cfg), fold_path="jnp")
    loop.run(rounds[:1], collect="none")
    assert len(loop._staging) == 4  # r + 1 compact sets
    assert all(isinstance(s, OrswotCells) for s in loop._staging)
    first = _buffers(loop)
    for _ in range(3):
        loop.run(rounds, collect="none")
    assert _buffers(loop) == first


def test_a_fleet_outgrowing_the_int32_cell_index_is_refused():
    """The device fold stages compact cells only: a fleet whose flat cell
    space outgrows int32 is refused before anything is allocated, with
    the largest slice that fits."""
    cfg = _cfg(a=1024, m=1024, d=1024)
    per_object = (1 + 1024 + 1024) * 1024
    fits = (2**31 - 1) // per_object
    OrswotCells(fits, cfg)
    loop = PipelinedWireLoop(Universe.identity(cfg), fold_path="jnp")
    with pytest.raises(ValueError, match=f"at most {fits} objects"):
        loop._ensure_buffers(fits + 1, 2)
