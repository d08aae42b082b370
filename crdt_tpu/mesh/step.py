"""The pjit'd anti-entropy step: one kernel launch for the whole fleet.

An unsharded anti-entropy round is three launches (merge, digest,
tree); on an object mesh the whole round fuses into ONE ``shard_map``
program:

* **shard-local joins** — the pairwise ORSWOT lattice merge
  (:func:`crdt_tpu.parallel.collective._orswot_pair_merge`, the exact
  body ``parallel.shard_local_merge`` contracts as pointwise) runs
  unchanged per shard: each device merges only its own object rows,
  zero cross-device bytes.
* **the digest vector** — each shard digests its own rows with the
  SAME traced body the unsharded kernel jits
  (:func:`crdt_tpu.sync.digest.orswot_digest_body`), then the fleet
  vector is ONE ``all_gather`` of shard-local slices — per-object
  digests have no cross-row coupling, so concatenation in device
  order IS the unsharded vector, byte for byte.
* **reduction summaries** — exactly the collectives the reduction
  contracts declare: a ``pmax`` clock join for the fleet version
  vector, a ``psum`` member fold for the live-member count.

With tracing on, the host legs of a step are spans:
``mesh.step.dispatch`` (salts and the program call), ``mesh.step.wait``
(the program finishing, then the overflow check) and
``mesh.step.fetch`` (digests, version vector and member count to the
host).

Dispatch consults the runtime contract gate
(:mod:`crdt_tpu.mesh.contracts`) for every composed kernel, so a
host_only/replicated row can never be placed on the mesh.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from . import contracts
from .state import MESH_AXIS, ShardedBatch

#: manifest names the step composes — consulted at dispatch (per-shard
#: bodies run at mesh size 1 by construction; the step itself runs at
#: the mesh's size)
_SHARD_LOCAL_KERNELS = ("parallel.shard_local_merge",)
_SHARDED_KERNELS = ("sync.digest.orswot", "mesh.step.anti_entropy")


@dataclasses.dataclass(frozen=True)
class MeshStepResult:
    """One anti-entropy round's outputs: the merged sharded fleet, the
    logical digest vector (host u64, unpadded), the fleet version
    vector (pmax clock join) and the fleet live-member count (psum
    fold)."""

    batch: ShardedBatch
    digests: np.ndarray      # uint64[n] — byte-equal to the unsharded path
    version_vector: np.ndarray  # uint64[A]
    live_members: int


@functools.lru_cache(maxsize=32)
def _step_fn(mesh, axis: str, m_cap: int, d_cap: int, use_table: bool,
             impl=None):
    """Cached jitted mesh step (jax.jit caches by function identity; a
    per-call closure would retrace+recompile every call)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..obs.kernels import observed_kernel
    from ..ops import orswot_ops
    from jax import shard_map
    from ..parallel.collective import _orswot_pair_merge
    from ..sync.digest import orswot_digest_body

    digest_body = orswot_digest_body(use_table)
    spec, rep = P(axis), P()
    state = (spec,) * 5
    in_specs = (state, state, rep) + ((rep,) if use_table else ())

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=in_specs,
        out_specs=(state, spec, rep, rep, rep), check_vma=False,
    )
    def _step(sa, sb, asalts, *mtab):
        # shard-local lattice join: the pointwise-contract merge body,
        # per shard — no collective, each device touches only its rows
        merged, overflow = _orswot_pair_merge(sa, sb, m_cap, d_cap, impl)
        # shard-local digest slice (the unsharded kernel's exact body),
        # then the fleet vector as ONE all_gather in device order
        local = digest_body(*merged, asalts, *mtab)
        digests = jax.lax.all_gather(local, axis, axis=0, tiled=True)
        # the declared reduction collectives: pmax clock join + psum
        # member fold — object-axis folds are the reduction contract's
        # whole point, so no pointwise exemption is needed here
        vv = jax.lax.pmax(jnp.max(merged[0], axis=0), axis)
        members = jax.lax.psum(
            jnp.sum(merged[1] != orswot_ops.EMPTY, dtype=jnp.int32), axis)
        return merged, overflow, digests, vv, members

    return observed_kernel("mesh.step.anti_entropy")(_step)


def anti_entropy_step(a: ShardedBatch, b: ShardedBatch, *,
                      check: bool = True, impl=None) -> MeshStepResult:
    """Run one full anti-entropy round — merge + digest + fleet
    summaries — as ONE pjit'd step over the object mesh.

    ``a`` and ``b`` must share a layout and mesh (the same logical
    fleet, two replicas' states).  Raises
    :class:`~crdt_tpu.error.CapacityOverflowError` on slot overflow
    when ``check`` (shard-locally reduced, like every merge path)."""
    import jax

    from ..error import raise_for_overflow
    from ..sync.digest import (_salts_device, actor_salt_table,
                               member_salt_table)
    from ..utils import tracing

    lay = a.layout
    if b.layout != lay or b.mesh != a.mesh:
        raise ValueError(
            "anti_entropy_step needs both fleets on one layout+mesh "
            f"(got {lay} vs {b.layout})")
    size = int(a.mesh.shape[MESH_AXIS])
    for name in _SHARDED_KERNELS:
        contracts.require_shardable(name, size)
    for name in _SHARD_LOCAL_KERNELS:
        # per-shard bodies: the object axis arrives pre-sliced, so they
        # run at mesh size 1 inside the step by construction
        contracts.require_shardable(name, 1)

    da, db = a.device, b.device
    with tracing.span("mesh.step.dispatch"):
        m_cap, d_cap = int(da.ids.shape[-1]), int(da.d_ids.shape[-1])
        asalts = _salts_device(actor_salt_table(
            a.universe, num_actors=int(da.clock.shape[-1])))
        mtable = member_salt_table(a.universe)
        state_a = (da.clock, da.ids, da.dots, da.d_ids, da.d_clocks)
        state_b = (db.clock, db.ids, db.dots, db.d_ids, db.d_clocks)
        fn = _step_fn(a.mesh, MESH_AXIS, m_cap, d_cap, mtable is not None,
                      impl)
        args = (state_a, state_b, asalts) + (
            (_salts_device(mtable),) if mtable is not None else ())
        outputs = fn(*args)
    merged, overflow, digests, vv, members = outputs

    with tracing.span("mesh.step.wait"):
        jax.block_until_ready(outputs)
        if check:
            raise_for_overflow(overflow, "mesh anti_entropy_step")
    with tracing.span("mesh.step.fetch"):
        digests = np.asarray(digests).astype(np.uint64)[:lay.n]
        version_vector = np.asarray(vv).astype(np.uint64)
        live_members = int(np.asarray(members))
    tracing.count("mesh.step.rounds")
    tracing.count("mesh.step.digest_bytes", int(digests.nbytes))
    out = type(da)(clock=merged[0], ids=merged[1], dots=merged[2],
                   d_ids=merged[3], d_clocks=merged[4])
    return MeshStepResult(
        batch=a.replace(out),
        digests=digests,
        version_vector=version_vector,
        live_members=live_members,
    )
