"""Member-universe sharding — context parallelism for huge sets.

SURVEY.md §5: the structural analogue of sequence/context parallelism in
this domain is scaling the **member axis** of ORSWOT: a set too big for one
device's member table is hash-partitioned across a mesh axis, merged
shard-locally, with the set clock joined globally.  The reference has no
counterpart (its sets are in-memory HashMaps, `/root/reference/src/orswot.rs:26-30`)
— this is a new first-class component the TPU design must supply.

Why shard-local merge is exact (`orswot.rs:89-156` semantics):

* The per-member dot algebra needs only (both sides' dot clocks for that
  member, both sides' **set clocks**).  Members are routed by
  ``member_id % n_shards``, so any member lives on the same shard on both
  sides of a merge — alignment never crosses shards.
* Each shard carries a replicated copy of the full set clock.  A merge
  joins the two replicated clocks identically on every shard, so clock
  coherence is preserved *without* a collective.
* A deferred remove row for member ``m`` routes to ``m``'s shard; replay
  (`orswot.rs:195-243`) compares the (replicated) set clock with the row's
  clock and subtracts from that shard's member table only — shard-local.

The one place a collective IS required: **op application**.  ``Op::Add``
witnesses its dot on the shard holding the member, so the replicated
clocks diverge until :func:`rebroadcast_clock` joins them with an
all-reduce ``pmax`` over the member-shard axis (ICI).  Merges after the
rebroadcast are coherent again.

State layout: the standard 5-tuple with a leading shard axis —
``clock u[S, N, A] (replicated content), ids i32[S, N, Mс], dots
u[S, N, Mс, A], d_ids i32[S, N, Dс], d_clocks u[S, N, Dс, A]`` — sharded
over a mesh axis (default ``"members"``).  ``Mс`` is the per-shard member
capacity; the logical capacity is ``S × Mс``.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..ops import orswot_ops
from ..error import raise_for_overflow
from ..obs.kernels import observed_kernel

EMPTY = orswot_ops.EMPTY


def member_shard(member_ids, n_shards: int):
    """Routing hash: which shard owns each (non-negative) member id."""
    return member_ids % n_shards


def partition_dense(clock, ids, dots, d_ids, d_clocks, n_shards: int,
                    m_cap_shard: int, d_cap_shard: int):
    """Host-side: split dense single-device ORSWOT arrays ``[N, ...]`` into
    member-sharded arrays ``[S, N, ...]`` (numpy).

    Members route by :func:`member_shard`; the set clock is replicated
    into every shard row.  Raises if any shard overflows its capacity —
    by the pigeonhole bound a balanced hash keeps ``≈ M/S`` members per
    shard, so ``m_cap_shard ≥ ceil(m_cap / n_shards)`` plus slack is the
    sizing rule."""
    clock = np.asarray(clock)
    ids = np.asarray(ids)
    dots = np.asarray(dots)
    d_ids = np.asarray(d_ids)
    d_clocks = np.asarray(d_clocks)
    n, a = clock.shape
    s_clock = np.broadcast_to(clock, (n_shards,) + clock.shape).copy()
    s_ids = np.full((n_shards, n, m_cap_shard), EMPTY, dtype=ids.dtype)
    s_dots = np.zeros((n_shards, n, m_cap_shard, a), dtype=dots.dtype)
    s_dids = np.full((n_shards, n, d_cap_shard), EMPTY, dtype=d_ids.dtype)
    s_dclocks = np.zeros((n_shards, n, d_cap_shard, a), dtype=d_clocks.dtype)

    def route(table_ids, payload, out_ids, out_payload, cap, what):
        live_obj, live_slot = np.nonzero(table_ids != EMPTY)
        mids = table_ids[live_obj, live_slot]
        shard = member_shard(mids, n_shards)
        # stable per-(shard, object) slot assignment in input order
        counters = {}
        for k in range(live_obj.size):
            key = (int(shard[k]), int(live_obj[k]))
            slot = counters.get(key, 0)
            if slot >= cap:
                raise ValueError(
                    f"{what}: shard {key[0]} object {key[1]} exceeds "
                    f"per-shard capacity {cap}"
                )
            counters[key] = slot + 1
            out_ids[key[0], key[1], slot] = mids[k]
            out_payload[key[0], key[1], slot] = payload[live_obj[k], live_slot[k]]

    route(ids, dots, s_ids, s_dots, m_cap_shard, "members")
    route(d_ids, d_clocks, s_dids, s_dclocks, d_cap_shard, "deferred")
    return s_clock, s_ids, s_dots, s_dids, s_dclocks


def unpartition_dense(s_clock, s_ids, s_dots, s_dids, s_dclocks,
                      m_cap: int, d_cap: int):
    """Host-side inverse of :func:`partition_dense`: collapse the shard
    axis back into single dense tables in canonical ascending-id order."""
    s_clock = np.asarray(s_clock)
    s_ids = np.asarray(s_ids)
    s_dots = np.asarray(s_dots)
    s_dids = np.asarray(s_dids)
    s_dclocks = np.asarray(s_dclocks)
    n_shards, n, _, a = s_dots.shape
    clock = s_clock.max(axis=0)  # replicated content — max is a no-op join

    ids = np.full((n, m_cap), EMPTY, dtype=s_ids.dtype)
    dots = np.zeros((n, m_cap, a), dtype=s_dots.dtype)
    d_ids = np.full((n, d_cap), EMPTY, dtype=s_dids.dtype)
    d_clocks = np.zeros((n, d_cap, a), dtype=s_dclocks.dtype)

    def collect(src_ids, src_payload, out_ids, out_payload, cap, sort_ids):
        sh, obj, slot = np.nonzero(src_ids != EMPTY)
        mids = src_ids[sh, obj, slot]
        order = np.lexsort((mids, obj)) if sort_ids else np.argsort(obj, kind="stable")
        counters = {}
        for k in order:
            i = int(obj[k])
            pos = counters.get(i, 0)
            if pos >= cap:
                raise ValueError(f"object {i} exceeds capacity {cap} on collect")
            counters[i] = pos + 1
            out_ids[i, pos] = mids[k]
            out_payload[i, pos] = src_payload[sh[k], obj[k], slot[k]]

    collect(s_ids, s_dots, ids, dots, m_cap, sort_ids=True)
    collect(s_dids, s_dclocks, d_ids, d_clocks, d_cap, sort_ids=False)
    return clock, ids, dots, d_ids, d_clocks


def member_sharded_merge(state_a, state_b, mesh: Mesh, axis: str = "members",
                         check: bool = True, impl: str | None = None):
    """Pairwise merge of two member-sharded states — fully shard-local
    (zero collectives): each device runs the standard merge kernel on its
    member partition with the replicated set clocks.  Reuses the cached
    jitted shard-local merge from :mod:`crdt_tpu.parallel.collective`
    (member sharding IS object-axis sharding over the shard dimension —
    the member-specific work is the routing/partition layer around it).

    ``state_a``/``state_b``: 5-tuples of ``[S, N, ...]`` arrays sharded
    over ``axis``.  Returns the merged 5-tuple (same sharding).  With
    ``check=True`` the per-shard overflow bitmap is raised host-side."""
    from .collective import shard_local_merge_fn

    m_cap, d_cap = state_a[1].shape[-1], state_a[3].shape[-1]
    state, overflow = shard_local_merge_fn(mesh, axis, m_cap, d_cap, impl)(
        tuple(state_a), tuple(state_b)
    )
    if check:
        raise_for_overflow(np.asarray(overflow), "member-sharded merge")
    return state


@functools.lru_cache(maxsize=64)
def _clock_join_fn(mesh: Mesh, axis: str):
    spec = P(axis)

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec,),
        out_specs=spec,
        check_vma=False,
    )
    def _join(local_clock):
        # local_clock: [K, N, A] — K shard rows co-located on this device.
        # Join across the co-located rows first, then across devices, and
        # broadcast back so EVERY shard row (not just row-for-row across
        # devices) sees the full clock.
        local = jnp.max(local_clock, axis=0, keepdims=True)
        joined = jax.lax.pmax(local, axis)
        return jnp.broadcast_to(joined, local_clock.shape)

    return observed_kernel("parallel.member_clock_join")(_join)


def rebroadcast_clock(state, mesh: Mesh, axis: str = "members"):
    """Join the per-shard set-clock copies — a max over shard rows
    co-located on each device plus an all-reduce ``pmax`` across the
    member-shard axis, broadcast back to every row.  Required after op
    application (an ``Add`` witnesses its dot only on the owning shard)
    and before the next merge, so every shard again sees the full set
    clock.  This is the 'join clocks globally' collective of the
    member-sharding design; it rides ICI inside a slice."""
    clock, ids, dots, d_ids, d_clocks = state
    return (_clock_join_fn(mesh, axis)(clock), ids, dots, d_ids, d_clocks)


def sharded_apply_add(state, actor_idx, counter, member_id, mesh: Mesh,
                      axis: str = "members"):
    """Batched ``Op::Add`` against a member-sharded state: every shard
    sees the op, only the owning shard (``member_id % S``) applies it;
    the clock rebroadcast then restores coherence.  ``actor_idx`` /
    ``counter`` / ``member_id``: ``[N]`` (one op per object)."""
    n_shards = state[0].shape[0]
    shard_row = jnp.arange(n_shards, dtype=jnp.int32)
    state_out, overflow = _apply_add_fn(mesh, axis, n_shards)(
        tuple(state), shard_row, actor_idx, counter, member_id
    )
    raise_for_overflow(np.asarray(overflow), "member-sharded add")
    return rebroadcast_clock(state_out, mesh, axis)


@functools.lru_cache(maxsize=64)
def _apply_add_fn(mesh: Mesh, axis: str, n_shards: int):
    spec = P(axis)
    rep = P()

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=((spec,) * 5, spec, rep, rep, rep),
        out_specs=((spec,) * 5, spec),
        check_vma=False,
    )
    def _local(s, my_shards, a_idx, cnt, mid):
        # block shapes: state [K, N, ...] (K shards per device), ops [N]
        mine = member_shard(mid, n_shards)[None, :] == my_shards[:, None]
        # non-owners apply a no-op: counter 0 is always already witnessed
        eff_cnt = jnp.where(mine, cnt[None, :], 0)
        k = s[0].shape[0]
        tile = lambda x: jnp.broadcast_to(x[None, :], (k,) + x.shape)
        *new_state, over = orswot_ops.apply_add(*s, tile(a_idx), eff_cnt, tile(mid))
        return tuple(new_state), over

    return observed_kernel("parallel.member_apply_add")(_local)
