"""Random dense CRDT state generators for tests.

Shared by the tests and ``__graft_entry__.py`` so the state-layout
invariants live in one place.  Invariants a valid ORSWOT batch must hold
(`/root/reference/src/orswot.rs:26-30` via the dense mapping in
``crdt_tpu/ops/orswot_ops.py``):

* member ids are unique within an object (the sort/align kernel assumes
  runs of length <= 2);
* live member slots carry non-empty dot clocks;
* the set clock covers every entry dot (op-generated states always do).
"""

from __future__ import annotations

import numpy as np


def random_orswot_arrays(
    rng, n, a, m, d, dtype=np.uint32, max_counter=100,
    min_live=1, deferred_frac=0.0,
):
    """Random valid dense ORSWOT batch of ``n`` objects as numpy arrays
    ``(clock, ids, dots, d_ids, d_clocks)``.

    ``min_live`` raises the lower bound of members per object (``m`` for a
    near-capacity load, the honest case for the BASELINE.md north star);
    ``deferred_frac`` populates a causally-ahead deferred remove row on
    that fraction of objects — its clock cites a counter past the set
    clock, so it stays buffered until a later merge covers it
    (`/root/reference/src/orswot.rs:195-203` semantics)."""
    ids = np.full((n, m), -1, dtype=np.int32)
    dots = np.zeros((n, m, a), dtype=dtype)
    live = rng.randint(min(min_live, m), m + 1, size=n)
    # unique-within-object member ids: random base + strictly increasing
    # slot offsets (uniqueness is an alignment-kernel invariant)
    base = rng.randint(0, 1 << 20, size=n)
    stride = rng.randint(1, 64, size=n)
    for j in range(m):
        mask = live > j
        k = int(mask.sum())
        if k == 0:
            continue
        ids[mask, j] = (base[mask] + j * stride[mask]) % (1 << 24)
        actor = rng.randint(0, a, size=k)
        dots[mask, j, actor] = rng.randint(1, max_counter, size=k)
    clock = dots.max(axis=1)  # set clock covers every entry dot
    d_ids = np.full((n, d), -1, dtype=np.int32)
    d_clocks = np.zeros((n, d, a), dtype=dtype)
    if deferred_frac > 0 and d > 0:
        _plant_deferred(rng, deferred_frac, live, clock, ids, d_ids, d_clocks, dtype)
    return clock, ids, dots, d_ids, d_clocks


def _plant_deferred(rng, frac, live, clock, ids, d_ids, d_clocks, dtype):
    """Give ``frac`` of the objects one causally-future deferred remove: a
    live member cited under a clock one tick past what the set witnessed
    for a random actor, so it buffers until the cluster catches up
    (`/root/reference/src/orswot.rs:195-203`)."""
    n, a = clock.shape
    hit = (rng.rand(n) < frac) & (live > 0)
    rows = np.where(hit)[0]
    if rows.size == 0:
        return
    slot = np.argmax(ids[rows] != -1, axis=1)  # first live slot
    d_ids[rows, 0] = ids[rows, slot]
    actor = rng.randint(0, a, size=rows.size)
    ahead = clock[rows, actor].astype(np.int64) + 1
    d_clocks[rows, 0, actor] = ahead.astype(dtype)


def anti_entropy_fleets(
    rng, n, a, m_cap, d, r, base=6, novel=1, present_p=0.9,
    deferred_frac=0.0, dtype=np.uint32, max_counter=100,
):
    """R replica fleets of the same N logical objects, shaped like a real
    anti-entropy round: every replica holds (most of) a shared ``base``
    member set — with concurrent, per-replica dots on the shared members —
    plus up to ``novel`` members only it has witnessed.  The union is
    bounded by ``base + r*novel ≤ m_cap`` so the N-way join never
    overflows; shared members exercise the both-present dot algebra
    (`/root/reference/src/orswot.rs:105-129`), missing members
    (``present_p``) the one-sided branches, and ``deferred_frac`` plants
    causally-future removes on fleet 0 (`orswot.rs:195-203`).

    Returns a list of ``r`` tuples ``(clock, ids, dots, d_ids, d_clocks)``.
    """
    if base + r * novel > m_cap:
        raise ValueError(
            f"union bound base+r*novel = {base + r * novel} exceeds m_cap={m_cap}"
        )
    base_val = rng.randint(0, 1 << 20, size=n)
    stride = rng.randint(1, 64, size=n)

    def member_id(slot_no):
        return (base_val + slot_no * stride) % (1 << 24)

    fleets = []
    for rep in range(r):
        ids = np.full((n, m_cap), -1, dtype=np.int32)
        dots = np.zeros((n, m_cap, a), dtype=dtype)
        slot = 0
        for j in range(base):
            present = rng.rand(n) < present_p
            ids[present, slot] = member_id(j)[present]
            actor = rng.randint(0, a, size=n)
            cnt = rng.randint(1, max_counter, size=n)
            dots[np.arange(n)[present], slot, actor[present]] = cnt[present]
            slot += 1
        for j in range(novel):
            ids[:, slot] = member_id(base + rep * novel + j)
            actor = rng.randint(0, a, size=n)
            dots[np.arange(n), slot, actor] = rng.randint(1, max_counter, size=n)
            slot += 1
        clock = dots.max(axis=1)
        d_ids = np.full((n, d), -1, dtype=np.int32)
        d_clocks = np.zeros((n, d, a), dtype=dtype)
        if rep == 0 and deferred_frac > 0 and d > 0:
            live = (ids != -1).sum(axis=1)
            _plant_deferred(
                rng, deferred_frac, live, clock, ids, d_ids, d_clocks, dtype
            )
        fleets.append((clock, ids, dots, d_ids, d_clocks))
    return fleets


def fleet_columns(
    rng, n, a, m_cap, d, r, base=6, novel=1, present_p=0.9,
    deferred_frac=0.0, max_counter=100,
):
    """Compact column encoding of an anti-entropy fleet — the host-side
    half of the resident north-star path.  Same statistical shape as
    :func:`anti_entropy_fleets` (shared ``base`` members with concurrent
    per-replica dots, per-replica ``novel`` members, causally-future
    deferred removes on replica 0) but ~200x smaller than the dense
    planes: ship THESE to the device and let
    :func:`build_fleet_planes` scatter them into dense form there —
    through a remote-device link the dense [R,N,M,A] planes are the
    transfer cost, the columns are not.

    Returns a dict of numpy arrays totalling ~(2·r·(base+novel) + 7)
    bytes/object."""
    if base + r * novel > m_cap:
        raise ValueError(
            f"union bound base+r*novel = {base + r * novel} exceeds m_cap={m_cap}"
        )
    if a > 256 or max_counter > 255:
        raise ValueError("columns encode actor/counter as uint8")
    s = base + novel
    return {
        "base_val": rng.randint(0, 1 << 20, size=n).astype(np.uint32),
        "stride": rng.randint(1, 64, size=n).astype(np.uint8),
        "present": rng.rand(r, base, n) < present_p,
        "actor": rng.randint(0, a, size=(r, s, n)).astype(np.uint8),
        "counter": rng.randint(1, max_counter, size=(r, s, n)).astype(np.uint8),
        "def_hit": (
            rng.rand(n) < deferred_frac
            if deferred_frac > 0 and d > 0
            else np.zeros(n, dtype=bool)
        ),
        "def_actor": rng.randint(0, a, size=n).astype(np.uint8),
    }


def build_fleet_planes(cols, *, a, m_cap, d, base, novel, dtype=None):
    """Dense fleet planes from :func:`fleet_columns` output — pure jnp,
    jittable, so the scatter runs ON DEVICE and only the compact columns
    cross the host↔device boundary.

    Member id for logical slot ``k`` is ``(base_val + k*stride) % 2^24``
    (unique within an object — strictly increasing offsets, the alignment
    kernel invariant); slots ``[0, base)`` are the shared members gated by
    ``present``, slot ``base+j`` of replica ``rep`` is its novel member
    ``base + rep*novel + j``.  Replica 0 gets one deferred remove row on
    ``def_hit`` objects: its first live member cited one tick past the
    set clock for ``def_actor`` (`orswot.rs:195-203` buffering semantics).

    Returns ``(clock, ids, dots, d_ids, d_clocks)`` with leading axes
    ``[r, n, ...]``."""
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.uint32
    base_val = cols["base_val"]
    stride = cols["stride"]
    present = cols["present"]
    actor = cols["actor"]
    counter = cols["counter"]
    r, s, n = actor.shape

    j = jnp.arange(s, dtype=jnp.int32)[None, :, None]  # [1, S, 1]
    rep = jnp.arange(r, dtype=jnp.int32)[:, None, None]  # [r, 1, 1]
    slot_no = jnp.where(j < base, j, base + rep * novel + (j - base))
    mid = (
        (base_val[None, None, :].astype(jnp.int32)
         + slot_no * stride[None, None, :].astype(jnp.int32))
        % (1 << 24)
    ).astype(jnp.int32)
    pres = jnp.concatenate(
        [present, jnp.ones((r, s - base, n), dtype=bool)], axis=1
    )  # [r, S, n]
    ids_s = jnp.where(pres, mid, jnp.int32(-1))  # [r, S, n]
    onehot = jnp.arange(a)[None, None, None, :] == actor[..., None]  # [r,S,n,a]
    dots_s = jnp.where(
        onehot & pres[..., None], counter[..., None].astype(dtype), 0
    )

    # [r, S, n, ...] -> [r, n, m_cap, ...] (pad the slot axis)
    ids = jnp.moveaxis(ids_s, 1, 2)  # [r, n, S]
    dots = jnp.moveaxis(dots_s, 1, 2)  # [r, n, S, a]
    pad = m_cap - s
    ids = jnp.pad(ids, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
    dots = jnp.pad(dots, ((0, 0), (0, 0), (0, pad), (0, 0)))
    clock = dots.max(axis=2)  # [r, n, a]

    d_ids = jnp.full((r, n, d), -1, dtype=jnp.int32)
    d_clocks = jnp.zeros((r, n, d, a), dtype=dtype)
    live = jnp.any(ids[0] != -1, axis=-1)  # [n]
    hit = cols["def_hit"] & live
    first_slot = jnp.argmax(ids[0] != -1, axis=-1)  # [n]
    first_mid = jnp.take_along_axis(ids[0], first_slot[:, None], axis=-1)[:, 0]
    d_ids = d_ids.at[0, :, 0].set(jnp.where(hit, first_mid, -1))
    def_actor = cols["def_actor"].astype(jnp.int32)
    # counters are < 255 here so +1 cannot overflow any counter dtype
    ahead = jnp.take_along_axis(clock[0], def_actor[:, None], axis=-1)[:, 0] + dtype(1)
    oh_def = jnp.arange(a)[None, :] == def_actor[:, None]  # [n, a]
    d_clocks = d_clocks.at[0, :, 0, :].set(
        jnp.where(oh_def & hit[:, None], ahead[:, None], 0)
    )
    return clock, ids, dots, d_ids, d_clocks


def random_mvreg_map(rng, n_keys=5, n_actors=6, max_ops=10, rm_p=0.3,
                     max_counter=6, max_val=9):
    """Random op-built scalar ``Map<int, MVReg>`` (`test/map.rs:13-46`
    idiom), used by the multichip dryrun.  (The batch-parity and
    collective-join tests still carry their own inline op generators.)
    ``rng``: ``np.random.RandomState``."""
    from ..scalar.map import Map, Rm as MapRm, Up
    from ..scalar.mvreg import MVReg, Put
    from ..scalar.vclock import Dot, VClock

    m = Map(MVReg)
    for _ in range(int(rng.randint(0, max_ops))):
        actor = int(rng.randint(0, n_actors))
        counter = int(rng.randint(1, max_counter))
        key = int(rng.randint(0, n_keys))
        clock = VClock.from_iter([(actor, counter)])
        if rng.rand() < rm_p:
            m.apply(MapRm(clock=clock, key=key))
        else:
            m.apply(Up(dot=Dot(actor, counter), key=key,
                       op=Put(clock=clock, val=int(rng.randint(0, max_val)))))
    return m


def dense_row_to_scalar(clock_row, ids_row, dots_row, dids_row, dclocks_row):
    """Scalar Orswot from one dense object's rows — actors are the dense
    column indices, members the raw interned ids (no Universe needed).
    The shared oracle-side converter for the fold parity tests."""
    from ..scalar.orswot import Orswot
    from ..scalar.vclock import VClock

    o = Orswot()
    o.clock = VClock({i: int(c) for i, c in enumerate(clock_row) if int(c)})
    for s, mid in enumerate(ids_row):
        if int(mid) != -1:
            o.entries[int(mid)] = VClock(
                {i: int(c) for i, c in enumerate(dots_row[s]) if int(c)}
            )
    for s, mid in enumerate(dids_row):
        if int(mid) != -1:
            vc = VClock({i: int(c) for i, c in enumerate(dclocks_row[s]) if int(c)})
            o.deferred.setdefault(vc.key(), set()).add(int(mid))
    return o
