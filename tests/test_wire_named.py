"""The named ORSWOT wire codec: universes whose actors and members are
``str`` / ``bytes`` names take the native parser and encoder
(`crdt_tpu/native/wire_ingest.cpp`, named section).

Contract under test: a native ingest equals ``from_binary`` →
``OrswotBatch.from_scalar`` on a fresh copy of the universe — the same
planes and the same registries, names first seen interned in blob
order (unseen members under one deferred clock in wire order, where
``from_scalar`` takes set order: the same states, the ids may differ)
— and a native egress is byte-identical to ``to_binary`` of the
scalar states.  The wire loop and delta sync take the same route with
no fallback, a universe the native path refuses falls back with its
reason counted, and a parse that interns on one thread while another
encodes gives what a serial run gives.
"""

import threading

import numpy as np
import pytest

from crdt_tpu import Orswot, from_binary, to_binary
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.batch.wireloop import PipelinedWireLoop
from crdt_tpu.config import CrdtConfig
from crdt_tpu.error import WireFormatError
from crdt_tpu.native import loader
from crdt_tpu.scalar.vclock import VClock
from crdt_tpu.sync.delta import apply_delta_rows
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe
from crdt_tpu.utils.testdata import anti_entropy_fleets

pytestmark = pytest.mark.skipif(not loader.available(),
                                reason="native library unavailable")

_PLANES = ("clock", "ids", "dots", "d_ids", "d_clocks")


def _cfg(bits=32, a=8, m=8, d=4):
    return CrdtConfig(num_actors=a, member_capacity=m, deferred_capacity=d,
                      counter_bits=bits)


def actor_name(a: int):
    """Even actors are bytes whose byte order inverts the id order, odd
    ones non-ASCII str."""
    return bytes([255 - a, a, 0]) if a % 2 == 0 else f"vnode-{a}-é"


def member_name(m: int):
    """str and bytes members, ASCII and not, 1 to 12 bytes."""
    kind = m % 4
    if kind == 0:
        return f"user{m}"
    if kind == 1:
        return f"ü{m}"
    if kind == 2:
        return (m % (1 << 24)).to_bytes(3, "big")
    return f"日本{m % 97}"


def _rename(state, an=actor_name, mn=member_name):
    """An integer-keyed scalar ORSWOT with every actor and member named."""
    out = Orswot()
    out.clock = VClock({an(a): c for a, c in state.clock.dots.items()})
    for m, vc in state.entries.items():
        out.entries[mn(m)] = VClock({an(a): c for a, c in vc.dots.items()})
    for key, members in state.deferred.items():
        k = VClock({an(a): c for a, c in key}).key()
        out.deferred.setdefault(k, set()).update(mn(m) for m in members)
    return out


def named_fleets(seed, n, r, cfg, mn=member_name, empty_every=7):
    """``r`` replica blob lists of ``n`` named objects (the test-data
    anti-entropy shape: shared members, novel ones, deferred removes on
    replica 0), every ``empty_every``-th object of replica 1 empty;
    ``mn`` names the members."""
    dt = np.uint64 if cfg.counter_bits == 64 else np.uint32
    reps = anti_entropy_fleets(
        np.random.RandomState(seed), n, cfg.num_actors, cfg.member_capacity,
        cfg.deferred_capacity, r, base=3, novel=1, deferred_frac=0.3,
        dtype=dt)
    ident = Universe.identity(cfg)
    fleets = []
    for k, rep in enumerate(reps):
        states = [_rename(s, mn=mn)
                  for s in OrswotBatch(*rep).to_scalar(ident)]
        if k == 1:
            for i in range(0, n, empty_every):
                states[i] = Orswot()
        fleets.append([to_binary(s) for s in states])
    return fleets


def _python_ingest(blobs, universe):
    return OrswotBatch.from_scalar([from_binary(b) for b in blobs], universe,
                                   via_device=False)


def _assert_same(batch, want, uni, uni_want):
    assert uni.actors.values() == uni_want.actors.values()
    assert uni.members.values() == uni_want.members.values()
    for name in _PLANES:
        np.testing.assert_array_equal(np.asarray(getattr(batch, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)


def _counted(fn):
    before = tracing.counters()
    out = fn()
    return out, tracing.counters_since(before)


@pytest.mark.parametrize("bits", [32, 64])
def test_ingest_equals_python_decode(bits):
    cfg = _cfg(bits)
    blobs = [b for rep in named_fleets(1, 120, 3, cfg) for b in rep]
    uni, uni_py = Universe(cfg), Universe(cfg)
    batch, c = _counted(lambda: OrswotBatch.from_wire(blobs, uni,
                                                      via_device=False))
    want = _python_ingest(blobs, uni_py)
    _assert_same(batch, want, uni, uni_py)
    assert c["wire.orswot.from_wire.native"] == len(blobs)
    assert c.get("wire.orswot.from_wire.fallback", 0) == 0
    assert c["wire.names.interned"] == len(uni.actors) + len(uni.members)
    # the fleet holds deferred removes and empty sets, and names whose
    # byte order is not their id order
    assert (np.asarray(batch.d_ids) >= 0).any()
    assert (np.asarray(batch.ids) < 0).all(axis=1).any()
    names = uni.actors.values()
    assert sorted(names, key=to_binary) != names


@pytest.mark.parametrize("bits", [32, 64])
def test_encode_is_byte_identical_to_to_binary(bits):
    cfg = _cfg(bits)
    uni = Universe(cfg)
    blobs = [b for rep in named_fleets(2, 100, 3, cfg) for b in rep]
    batch = OrswotBatch.from_wire(blobs, uni, via_device=False)
    out, c = _counted(lambda: batch.to_wire(uni))
    assert out == [to_binary(s) for s in batch.to_scalar(uni)]
    assert out == blobs  # canonical blobs round-trip
    assert c["wire.orswot.to_wire.native"] == len(blobs)
    assert c.get("wire.orswot.to_wire.fallback", 0) == 0


def test_names_first_seen_mid_fleet_are_interned_in_blob_order():
    cfg = _cfg()
    blobs = named_fleets(3, 150, 2, cfg)[0]
    uni, uni_py = Universe(cfg), Universe(cfg)
    # the universe already knows the first third's names
    OrswotBatch.from_wire(blobs[:50], uni, via_device=False)
    _python_ingest(blobs[:50], uni_py)
    known = len(uni.members)
    batch, c = _counted(lambda: OrswotBatch.from_wire(blobs, uni,
                                                      via_device=False))
    want = _python_ingest(blobs, uni_py)
    _assert_same(batch, want, uni, uni_py)
    assert len(uni.members) > known
    assert c["wire.names.interned"] == (len(uni.members) - known)
    for reg in (uni.actors, uni.members):
        for i, name in enumerate(reg.values()):
            assert reg.lookup(i) == name and reg.intern(name) == i


def _blob(clock, entries):
    """An ORSWOT blob written by hand, entries in the order given (so
    possibly not canonical); no deferred removes."""
    def uv(n):
        return bytes([n]) if n < 0x80 else bytes([n & 0x7F | 0x80, n >> 7])

    out = bytes([0x26]) + uv(len(clock))
    out += b"".join(to_binary(a) + to_binary(c) for a, c in clock)
    out += uv(len(entries))
    out += b"".join(to_binary(m) + to_binary(VClock(d)) for m, d in entries)
    return out + b"\x00"


def _late(m):
    return f"late{m}"


def test_blob_outside_the_grammar_is_decoded_in_python_at_its_turn():
    """A blob whose entries are out of order falls back to the Python
    codec, which interns its unseen names before any later blob's."""
    cfg = _cfg()
    head = named_fleets(4, 20, 1, cfg)[0]
    a = actor_name(0)
    odd = _blob([(a, 3)], [("zeta", {a: 3}), ("beta", {a: 2})])
    tail = named_fleets(4, 5, 1, cfg, mn=_late)[0]
    blobs = head + [odd] + tail
    uni, uni_py = Universe(cfg), Universe(cfg)
    batch, c = _counted(lambda: OrswotBatch.from_wire(blobs, uni,
                                                      via_device=False))
    # the out-of-order entries decode as the Python codec takes them
    # (slot order is wire order), all else equal
    _assert_same(batch, _python_ingest(blobs, uni_py), uni, uni_py)
    assert c["wire.orswot.from_wire.fallback"] == 1
    assert c["wire.orswot.from_wire.fallback_reason.grammar"] == 1
    vals = uni.members.values()
    late = [i for i, v in enumerate(vals) if str(v).startswith("late")]
    assert late and vals.index("beta") < min(late)


def test_overlong_duplicate_int_member_is_decoded_in_python():
    """The parsers compare keys by their wire bytes; member 2 written a
    second time as an overlong varint (``03 84 00``) must not take a
    second slot, as the Python decode keeps one entry."""
    cfg = _cfg()
    blob = bytes([0x26, 1, 0x03, 0x00, 0x03, 0x06,    # clock {0: 3}
                  2,
                  0x03, 0x04, 0x20, 1, 0x03, 0x00, 0x03, 0x06,
                  0x03, 0x84, 0x00, 0x20, 1, 0x03, 0x00, 0x03, 0x04,
                  0])
    blobs = [to_binary(from_binary(blob)), blob]
    uni, uni_py = Universe.identity(cfg), Universe.identity(cfg)
    batch, c = _counted(lambda: OrswotBatch.from_wire(blobs, uni,
                                                      via_device=False))
    _assert_same(batch, _python_ingest(blobs, uni_py), uni, uni_py)
    assert (np.asarray(batch.ids)[1] >= 0).sum() == 1
    assert c["wire.orswot.from_wire.fallback_reason.grammar"] == 1


def test_known_name_with_overlong_length_keeps_its_id():
    """A known name whose length varint is overlong is the same name:
    no second id, and egress writes it canonically."""
    cfg = _cfg()
    a = to_binary(b"x")
    canon = _blob([(b"x", 3)], [("user1", {b"x": 3})])
    overlong = bytes([0x26, 1]) + a + to_binary(3) + bytes(
        [1, 0x05, 0x85, 0x00]) + b"user1" + to_binary(VClock({b"x": 2})) \
        + b"\x00"
    assert from_binary(overlong) == from_binary(
        _blob([(b"x", 3)], [("user1", {b"x": 2})]))
    uni, uni_py = Universe(cfg), Universe(cfg)
    batch, c = _counted(lambda: OrswotBatch.from_wire(
        [canon, overlong], uni, via_device=False))
    _assert_same(batch, _python_ingest([canon, overlong], uni_py), uni,
                 uni_py)
    assert uni.members.values() == ["user1"]
    assert c["wire.orswot.from_wire.fallback_reason.grammar"] == 1
    assert batch.to_wire(uni) == [to_binary(s) for s in
                                  batch.to_scalar(uni)]


def test_unseen_members_under_one_deferred_clock_match_up_to_id_order():
    """Unseen members buffered under one deferred clock take ids in wire
    order natively and in set order in ``from_scalar``: the states and
    the sets of names agree, the ids may not."""
    cfg = _cfg(d=8)
    s = Orswot()
    s.apply(s.add("seen", s.value().derive_add_ctx(b"x")))
    future = VClock({b"x": 5, "y": 1})
    s.deferred[future.key()] = {f"d{k}" for k in range(6)}
    blobs = [to_binary(s)]
    uni, uni_py = Universe(cfg), Universe(cfg)
    batch, c = _counted(lambda: OrswotBatch.from_wire(blobs, uni,
                                                      via_device=False))
    want = _python_ingest(blobs, uni_py)
    assert c.get("wire.orswot.from_wire.fallback", 0) == 0
    assert [to_binary(x) for x in batch.to_scalar(uni)] == \
        [to_binary(x) for x in want.to_scalar(uni_py)] == blobs
    assert sorted(map(to_binary, uni.members.values())) == \
        sorted(map(to_binary, uni_py.members.values()))
    assert uni.actors.values() == uni_py.actors.values()


def test_int_key_in_named_universe_falls_back_for_the_rest():
    """An int member among names: that blob and every later unseen name
    decode in Python, in order; the next call refuses the universe."""
    cfg = _cfg()
    head = named_fleets(5, 30, 1, cfg)[0]
    s = Orswot()
    s.apply(s.add(7, s.value().derive_add_ctx(actor_name(0))))
    late = named_fleets(5, 4, 1, cfg, mn=_late)[0]
    blobs = head[:10] + [to_binary(s)] + late + head[10:]
    uni, uni_py = Universe(cfg), Universe(cfg)
    batch, c = _counted(lambda: OrswotBatch.from_wire(blobs, uni,
                                                      via_device=False))
    _assert_same(batch, _python_ingest(blobs, uni_py), uni, uni_py)
    # the int blob, then every later blob holding an unseen name
    assert c["wire.orswot.from_wire.fallback"] >= 1 + len(late)
    assert c["wire.orswot.from_wire.fallback"] + \
        c["wire.orswot.from_wire.native"] == len(blobs)
    _, c = _counted(lambda: batch.to_wire(uni))
    assert c["wire.orswot.to_wire.fallback_reason.key_type"] == len(blobs)


@pytest.mark.parametrize("members", [[("m", 1)], [1.5]])
def test_refused_universe_falls_back_with_its_reason(members):
    cfg = _cfg()
    uni = Universe(cfg)
    states = []
    for m in members:
        s = Orswot()
        s.apply(s.add(m, s.value().derive_add_ctx("alice")))
        states.append(s)
    blobs = [to_binary(s) for s in states]
    uni.members.intern(members[0])  # the registry holds a non-name
    batch, c = _counted(lambda: OrswotBatch.from_wire(blobs, uni,
                                                      via_device=False))
    assert c["wire.orswot.from_wire.fallback_reason.key_type"] == len(blobs)
    out, c = _counted(lambda: batch.to_wire(uni))
    assert out == blobs
    assert c["wire.orswot.to_wire.fallback_reason.key_type"] == len(blobs)


def test_hard_errors_raise():
    cfg = _cfg(m=2)
    s = Orswot()
    for m in ("a", "b", "c"):
        s.apply(s.add(m, s.value().derive_add_ctx(b"x")))
    with pytest.raises(WireFormatError, match="member_capacity"):
        OrswotBatch.from_wire([to_binary(s)], Universe(cfg))
    full = Orswot()
    for a in range(cfg.num_actors + 1):
        full.apply(full.add("m", full.value().derive_add_ctx(f"a{a}")))
    with pytest.raises(WireFormatError, match="actor"):
        OrswotBatch.from_wire([to_binary(full)], Universe(_cfg(m=8)))
    bad = bytes([0x26, 1, 0x05, 2, 0xC3, 0x28, 0x03, 2, 0, 0])  # bad UTF-8
    with pytest.raises(ValueError):
        OrswotBatch.from_wire([bad], Universe(cfg))


def _scalar_fold(rep_blobs, i):
    acc = from_binary(rep_blobs[0][i])
    for rep in rep_blobs[1:]:
        acc.merge(from_binary(rep[i]))
    acc.merge(acc.clone())
    return to_binary(acc)


@pytest.mark.parametrize("fold_path", ["native", "jnp"])
def test_wire_loop_named_round_equals_scalar_fold(fold_path):
    cfg = _cfg()
    rep_blobs = named_fleets(6, 80, 4, cfg)
    uni = Universe(cfg)
    res = PipelinedWireLoop(uni, fold_path=fold_path).run([rep_blobs])
    c = res["wire_counters"]
    assert c.get("wire.orswot.from_wire.fallback", 0) == 0
    assert c.get("wire.orswot.to_wire.fallback", 0) == 0
    assert res["ingest_native_fraction"] == 1.0
    assert res["egress_native_fraction"] == 1.0
    assert c["wire.names.interned"] == len(uni.actors) + len(uni.members)
    for i in range(80):
        assert res["out_blobs"][i] == _scalar_fold(rep_blobs, i)


def test_overlapped_parse_interning_beside_encode_equals_serial():
    """Each round brings names never seen, so the parser thread interns
    round k + 1's names while the main thread encodes round k."""
    cfg = _cfg()
    rounds = [named_fleets(10 + k, 300, 3, cfg,
                           mn=lambda m, k=k: f"r{k}-{m}")
              for k in range(4)]
    outs = {}
    for overlap in (True, False):
        uni = Universe(cfg)
        res = PipelinedWireLoop(uni, fold_path="native").run(
            rounds, overlap=overlap, collect="all")
        outs[overlap] = (res["out_blobs"], uni.members.values())
        assert res["wire_counters"].get("wire.orswot.from_wire.fallback",
                                        0) == 0
    assert outs[True] == outs[False]
    for k, rnd in enumerate(rounds):
        assert outs[True][0][k][::37] == [_scalar_fold(rnd, i)
                                           for i in range(0, 300, 37)]


def test_encode_beside_an_interning_parse_on_another_thread():
    cfg = _cfg()
    uni = Universe(cfg)
    blobs = named_fleets(20, 200, 1, cfg)[0]
    batch = OrswotBatch.from_wire(blobs, uni, via_device=False)
    want = batch.to_wire(uni)
    fresh = [named_fleets(20, 200, 1, cfg, mn=lambda m, k=k: f"t{k}-{m}")[0]
             for k in range(6)]
    errors = []

    def parse():
        try:
            for f in fresh:
                OrswotBatch.from_wire(f, uni, via_device=False)
        except BaseException as e:  # surfaced below
            errors.append(e)

    t = threading.Thread(target=parse)
    t.start()
    encoded = []
    while t.is_alive() or not encoded:
        encoded.append(batch.to_wire(uni))
    t.join()
    assert not errors
    assert all(out == want for out in encoded)
    names = uni.members.values()
    assert len(set(names)) == len(names)
    for f in fresh:
        assert OrswotBatch.from_wire(f, uni, via_device=False) \
            .to_wire(uni) == f


def test_delta_apply_takes_the_named_ingest():
    cfg = _cfg()
    uni = Universe(cfg)
    local_blobs, peer_blobs = named_fleets(30, 40, 2, cfg)
    batch = OrswotBatch.from_wire(local_blobs, uni, via_device=False)
    ids = np.arange(0, 40, 3)
    out, c = _counted(lambda: apply_delta_rows(
        batch, ids, [peer_blobs[i] for i in ids], uni))
    assert c.get("wire.orswot.from_wire.fallback", 0) == 0
    assert c["wire.orswot.from_wire.native"] == len(ids)
    got = out.to_wire(uni)
    for i in range(40):
        s = from_binary(local_blobs[i])
        if i in set(ids.tolist()):
            s.merge(from_binary(peer_blobs[i]))
        assert got[i] == to_binary(s)
