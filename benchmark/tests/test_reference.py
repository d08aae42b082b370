"""The plain reference agrees with the store's scalar engine, wire codec
and digest on random states, and the data generator is a function of
the seed."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import gen, reference

SEEDS = [0, 1, 2, (1 << 33) + 3]


def _random_scalar(rng, actors=6, members=8, ops=12):
    """An op-built scalar ORSWOT with adds, removes and one deferred
    remove (a clock ahead of the set's)."""
    from crdt_tpu.scalar.orswot import Add, Orswot, Rm
    from crdt_tpu.scalar.vclock import Dot, VClock

    s = Orswot()
    for _ in range(ops):
        m = int(rng.integers(0, members))
        if rng.random() < 0.7:
            a = int(rng.integers(0, actors))
            s.apply(Add(dot=Dot(a, s.clock.get(a) + 1), member=m))
        elif m in s.entries:
            s.apply(Rm(clock=s.entries[m].clone(), member=m))
    a = int(rng.integers(0, actors))
    s.apply(Rm(clock=VClock({a: s.clock.get(a) + 2}),
               member=int(rng.integers(0, members))))
    return s


def _state(s):
    return ({a: c for a, c in s.clock.dots.items()},
            {m: dict(vc.dots) for m, vc in s.entries.items()},
            {tuple(sorted(k)): set(v) for k, v in s.deferred.items()})


@pytest.mark.parametrize("seed", SEEDS)
def test_merge_matches_scalar_engine(seed):
    rng = np.random.default_rng(seed)
    for _ in range(40):
        x, y = _random_scalar(rng), _random_scalar(rng)
        want = x.clone()
        want.merge(y)
        got = reference.merge(_state(x), _state(y))
        assert reference.canonical(got) == reference.canonical(_state(want))


@pytest.mark.parametrize("seed", SEEDS)
def test_blob_decoder_reads_the_wire_format(seed):
    from crdt_tpu.utils.serde import to_binary

    rng = np.random.default_rng(seed)
    for _ in range(40):
        s = _random_scalar(rng)
        got = reference.decode_orswot_blob(to_binary(s))
        assert reference.canonical(got) == reference.canonical(_state(s))


def test_blob_decoder_refuses_garbage():
    with pytest.raises(reference.Malformed):
        reference.decode_orswot_blob(b"\x26\x05")
    with pytest.raises(reference.Malformed):
        reference.decode_orswot_blob(b"\x27")


@pytest.mark.parametrize("seed", SEEDS)
def test_digest_matches_the_published_one(seed):
    import jax.numpy as jnp

    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.sync.digest import digest_of

    cfg = {"num_actors": 8, "member_capacity": 16, "deferred_capacity": 2,
           "fleet": {"base": 6, "novel": 1, "present_p": 0.9,
                     "deferred_frac": 0.5}}
    planes = gen.fleet_builder(cfg, n=64, r=2)(gen.device_key(seed, 1))
    a, b = (OrswotBatch(*p) for p in planes)
    merged = a.merge(b)
    host = tuple(np.asarray(p) for p in (merged.clock, merged.ids,
                                         merged.dots, merged.d_ids,
                                         merged.d_clocks))
    want = np.asarray(digest_of(merged), np.uint64)
    for i in range(64):
        st = reference.row_state(*(p[i] for p in host))
        assert reference.digest(st) == int(want[i])
    del jnp


def test_fleet_is_a_function_of_the_seed():
    cfg = {"num_actors": 8, "member_capacity": 16, "deferred_capacity": 2,
           "fleet": {"base": 6, "novel": 1, "present_p": 0.9,
                     "deferred_frac": 0.25}}
    build = gen.fleet_builder(cfg, n=32, r=3)
    big = (1 << 40) + 5
    one, two, other = (build(gen.device_key(s, 1)) for s in (big, big, 7))
    for p, q in zip(one[0], two[0]):
        assert np.array_equal(np.asarray(p), np.asarray(q))
    assert not np.array_equal(np.asarray(one[0][2]), np.asarray(other[0][2]))
    # every fleet is a valid state: live slots carry dots, the set clock
    # covers them, and members are unique per object
    for clock, ids, dots, d_ids, _ in one:
        ids, dots, clock = (np.asarray(x) for x in (ids, dots, clock))
        assert ((ids != -1) == dots.any(axis=2)).all()
        assert (dots.max(axis=1) <= clock).all()
        for row in ids:
            live = row[row != -1]
            assert len(set(live.tolist())) == len(live)


def test_frame_sizes_are_the_same_multiset_for_every_seed():
    sizes = gen.log_uniform_sizes(500, 1, 4096)
    assert sizes.min() == 1 and 0.95 * 4096 < sizes.max() <= 4096
    assert np.array_equal(sizes, gen.log_uniform_sizes(500, 1, 4096))
