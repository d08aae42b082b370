"""Data and traffic made from ``--seed``.

The fleet shape is the anti-entropy fleet of the store's own test data
(shared members with concurrent per-replica dots, one novel member per
replica, causally-future removes on replica 0), built here so that a
later change to the program cannot change the benchmark's data.  Unlike
that copy, counters are drawn over the whole configured ``u32`` range
(``[1, 2**32 - 2]``, so the remove one tick ahead cannot wrap), as a
long-lived store's per-actor counters are.

Every seed gets the same amount of work: the same sizes, and for read
frames the same sizes in the same order.
"""

from __future__ import annotations

import functools

import numpy as np

#: member ids live below 2**24 (the identity universe's interning range)
MEMBER_SPACE = 1 << 24
#: largest counter drawn: one below the u32 maximum, so the planted
#: remove (counter + 1) still fits
MAX_COUNTER = (1 << 32) - 2


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent host stream per purpose (``stream``), any seed."""
    return np.random.default_rng([int(seed), int(stream)])


def device_key(seed: int, stream: int):
    """A jax PRNG key for ``(seed, stream)``; seeds may exceed 32 bits."""
    import jax

    key = jax.random.key(int(seed) & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, int(stream))


def fleet_planes(key, *, n: int, r: int, a: int, m: int, d: int, base: int,
                 novel: int, present_p: float, deferred_frac: float):
    """``r`` replica states of the same ``n`` objects, as a list of
    ``(clock, ids, dots, d_ids, d_clocks)`` tuples of dense planes
    (``u32`` counters, ``int32`` member ids, ``-1`` for an empty slot).
    Pure jnp and jittable: the caller jits it so the planes are made on
    the device in one program.

    Object ``i``'s member for logical slot ``k`` is
    ``(base_val + k * stride) % 2**24``: slots ``[0, base)`` are shared by
    every replica (each present with ``present_p``, each with its own
    dot), slot ``base + rep * novel + j`` is replica ``rep``'s novel
    member.  On ``deferred_frac`` of the objects replica 0 holds one
    remove of its first live member cited one tick past its set clock
    for one actor, so it stays buffered until a merge covers it."""
    import jax
    import jax.numpy as jnp

    if base + r * novel > m:
        raise ValueError(f"union of members base + r * novel = "
                         f"{base + r * novel} exceeds m = {m}")
    s = base + novel
    kb, ks, kp, ka, kc, kh, kd = jax.random.split(key, 7)
    base_val = jax.random.randint(kb, (n,), 0, 1 << 20, jnp.int32)
    stride = jax.random.randint(ks, (n,), 1, 64, jnp.int32)
    present = jax.random.bernoulli(kp, present_p, (r, n, base))
    actor = jax.random.randint(ka, (r, n, s), 0, a, jnp.int32)
    bits = jax.random.bits(kc, (r, n, s), jnp.uint32)
    counter = bits % jnp.uint32(MAX_COUNTER) + jnp.uint32(1)
    def_hit = jax.random.bernoulli(kh, deferred_frac, (n,))
    def_actor = jax.random.randint(kd, (n,), 0, a, jnp.int32)

    iota_a = jnp.arange(a, dtype=jnp.int32)
    j = jnp.arange(m, dtype=jnp.int32)
    fleets = []
    for rep in range(r):
        slot_no = jnp.where(j < base, j, base + rep * novel + (j - base))
        mid = (base_val[:, None] + slot_no[None, :] * stride[:, None]) \
            % MEMBER_SPACE
        pres = jnp.concatenate(
            [present[rep], jnp.ones((n, novel), bool),
             jnp.zeros((n, m - s), bool)], axis=1)
        ids = jnp.where(pres, mid, -1).astype(jnp.int32)
        act = jnp.pad(actor[rep], ((0, 0), (0, m - s)))
        cnt = jnp.pad(counter[rep], ((0, 0), (0, m - s)))
        hot = (iota_a[None, None, :] == act[..., None]) & pres[..., None]
        dots = jnp.where(hot, cnt[..., None], jnp.uint32(0))
        clock = dots.max(axis=1)
        d_ids = jnp.full((n, d), -1, jnp.int32)
        d_clocks = jnp.zeros((n, d, a), jnp.uint32)
        if rep == 0 and d > 0:
            live = ids != -1
            hit = def_hit & live.any(axis=1)
            first = jnp.argmax(live, axis=1)
            first_mid = jnp.take_along_axis(ids, first[:, None], axis=1)[:, 0]
            ahead = jnp.take_along_axis(
                clock, def_actor[:, None], axis=1)[:, 0] + jnp.uint32(1)
            d_ids = d_ids.at[:, 0].set(jnp.where(hit, first_mid, -1))
            oh = (iota_a[None, :] == def_actor[:, None]) & hit[:, None]
            d_clocks = d_clocks.at[:, 0, :].set(
                jnp.where(oh, ahead[:, None], jnp.uint32(0)))
        fleets.append((clock, ids, dots, d_ids, d_clocks))
    return fleets


def fleet_builder(cfg: dict, *, n: int, r: int):
    """The jitted ``key -> [r planes tuples]`` program for one
    configuration's width and fleet shape."""
    import jax

    return jax.jit(functools.partial(
        fleet_planes, n=n, r=r, a=cfg["num_actors"],
        m=cfg["member_capacity"], d=cfg["deferred_capacity"],
        base=cfg["fleet"]["base"], novel=cfg["fleet"]["novel"],
        present_p=cfg["fleet"]["present_p"],
        deferred_frac=cfg["fleet"]["deferred_frac"]))


def host_rows(planes, rows) -> tuple:
    """Rows ``rows`` of each plane in ``planes``, copied to the host."""
    import jax.numpy as jnp

    idx = jnp.asarray(np.asarray(rows))
    return tuple(np.asarray(jnp.take(p, idx, axis=0)) for p in planes)


def bytes_per_object(cfg: dict) -> int:
    """Dense bytes of one replica-object at the configured width."""
    a, m, d = cfg["num_actors"], cfg["member_capacity"], \
        cfg["deferred_capacity"]
    cw = cfg["counter_bytes"]
    return a * cw + m * 4 + m * a * cw + d * 4 + d * a * cw


# -- read frames --------------------------------------------------------------


def log_uniform_sizes(count: int, lo: int, hi: int) -> np.ndarray:
    """``count`` sizes spread log-uniformly over ``[lo, hi]`` as a fixed
    quantile grid: every seed gets the same multiset."""
    q = (np.arange(count) + 0.5) / count
    sizes = np.exp(np.log(lo) + q * (np.log(hi + 1) - np.log(lo)))
    return np.clip(np.floor(sizes), lo, hi).astype(np.int64)


def zipf_ranks(rng: np.random.Generator, n: int, s: float, count: int
               ) -> np.ndarray:
    """Zipf(``s``) ranks over ``n`` keys (rank ``k`` with probability
    proportional to ``1 / (k + 1) ** s``)."""
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), s)
    cdf = np.cumsum(w / w.sum())
    u = rng.random(count)
    return np.minimum(np.searchsorted(cdf, u, side="right"), n - 1)
