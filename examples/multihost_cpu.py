"""Two-PROCESS distributed lattice join — the multi-host path for real.

The reference simulates replicas in one process
(`/root/reference/test/orswot.rs:37-76`); ``tests/test_sharding.py``
does the same over a virtual device mesh.  This example crosses an
actual process boundary: two OS processes (each holding 4 virtual CPU
devices — stand-ins for two hosts' accelerators) join one
``jax.distributed`` runtime, and the stock collective join runs over
the 2-process global mesh with XLA's cross-process collectives (Gloo on
CPU; ICI/DCN on TPU pods) moving the state.  Nothing in the collective
layer changes — that is the point.

Two topologies, both verified against the scalar N-way oracle:

* ``replicas``  — the 8 replica rows span BOTH processes; the join's
  all-gather itself crosses the process boundary (the comm-backend
  stress case).
* ``hybrid``    — objects partition ACROSS processes (the DCN tier:
  zero cross-process join traffic, each object's merge is independent)
  while each process's 4 replica rows join on its own devices (the
  ICI tier) via ``object_axis=`` — the layout
  ``crdt_tpu.parallel.multihost`` advertises for pods.

Run:  python examples/multihost_cpu.py            # spawns both peers
      python examples/multihost_cpu.py --topology hybrid
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_PROCS = 2
DEVS_PER_PROC = 4


def worker(args) -> int:
    # both env var AND config update: the env must be set before the
    # first backend init; the config update holds even if a site hook
    # imported jax already
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={DEVS_PER_PROC}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from crdt_tpu import Orswot
    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.parallel import (
        allgather_join_orswot,
        initialize,
        local_shard,
        make_multihost_mesh,
    )
    from crdt_tpu.utils.interning import Universe

    topo = initialize(
        coordinator_address=f"127.0.0.1:{args.coordinator_port}",
        num_processes=N_PROCS,
        process_id=args.process_id,
    )
    assert topo["processes"] == N_PROCS, topo
    pid = args.process_id

    # IDENTITY universe: dense index == value.  Cross-host joins mix
    # dense planes built on different hosts, so the interning must be
    # deterministic and shared — per-host insertion-order registries
    # would map DIFFERENT actors to the SAME dense id (see
    # parallel/multihost.py docstring).
    uni = Universe.identity(CrdtConfig(num_actors=8, member_capacity=16,
                                       deferred_capacity=8))
    n_objects = args.objects

    def build_fleet(n_rows, first_actor, obj_slice):
        """Replica rows over the SAME objects; deterministic per seed so
        every process can rebuild any row for the oracle."""
        rows = []
        for r in range(n_rows):
            rng = np.random.RandomState(1000 + first_actor + r)
            row = []
            for i in range(n_objects):
                o = Orswot()
                for _ in range(int(rng.randint(1, 4))):
                    o.apply(o.add(int(rng.randint(0, 12)),
                                  o.value().derive_add_ctx(first_actor + r)))
                row.append(o)
            rows.append(row[obj_slice])
        return rows

    if args.topology == "replicas":
        # 8 replica rows, 4 per process, full object range each; the
        # all-gather crosses the process boundary
        mesh = make_multihost_mesh({"replicas": N_PROCS * DEVS_PER_PROC})
        mine = build_fleet(DEVS_PER_PROC, first_actor=pid * DEVS_PER_PROC,
                           obj_slice=slice(None))
        local = [OrswotBatch.from_scalar(row, uni) for row in mine]
        import jax.numpy as jnp

        local_np = jax.tree_util.tree_map(
            lambda *xs: np.asarray(jnp.stack(xs)), *local
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        stacked = jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                NamedSharding(mesh, P("replicas", *([None] * (x.ndim - 1)))), x
            ),
            local_np,
        )
        joined = allgather_join_orswot(stacked, mesh, axis="replicas")
        # oracle: every process can rebuild all 8 rows deterministically
        expected = [Orswot() for _ in range(n_objects)]
        for p in range(N_PROCS):
            for row in build_fleet(DEVS_PER_PROC, first_actor=p * DEVS_PER_PROC,
                                   obj_slice=slice(None)):
                for e, o in zip(expected, row):
                    e.merge(o)
        want_sets = [sorted(e.value().val) for e in _plunge(expected)]
        # verify every replica row THIS process holds (the collective's
        # postcondition: each row carries the identical global join)
        planes = (joined.clock, joined.ids, joined.dots, joined.d_ids,
                  joined.d_clocks)
        n_local_rows = len(planes[0].addressable_shards)
        assert n_local_rows == DEVS_PER_PROC
        for s in range(n_local_rows):
            shard = OrswotBatch(**dict(zip(
                ("clock", "ids", "dots", "d_ids", "d_clocks"),
                (np.asarray(p.addressable_shards[s].data)[0] for p in planes),
            )))
            plunged = shard.merge(OrswotBatch.zeros(n_objects, uni))
            got_sets = [sorted(o.value().val) for o in plunged.to_scalar(uni)]
            assert got_sets == want_sets, f"proc {pid} shard {s} diverged"
    else:  # hybrid
        # objects split across processes (DCN tier, zero join traffic);
        # 4 replica rows join within each process's devices (ICI tier)
        mesh = make_multihost_mesh(
            {"replicas": DEVS_PER_PROC}, {"objects": N_PROCS}
        )
        my_objs = local_shard(n_objects, N_PROCS, pid)
        mine = build_fleet(DEVS_PER_PROC, first_actor=0, obj_slice=my_objs)
        local = [OrswotBatch.from_scalar(row, uni) for row in mine]
        import jax.numpy as jnp

        local_np = jax.tree_util.tree_map(
            lambda *xs: np.asarray(jnp.stack(xs)), *local
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        stacked = jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                NamedSharding(
                    mesh, P("replicas", "objects", *([None] * (x.ndim - 2)))
                ),
                x,
            ),
            local_np,
        )
        joined = allgather_join_orswot(
            stacked, mesh, axis="replicas", object_axis="objects"
        )
        n_local = local_np.clock.shape[1]
        expected = [Orswot() for _ in range(n_local)]
        for row in mine:
            for e, o in zip(expected, row):
                e.merge(o)
        # each process verifies ITS object partition from its own shards
        shard0 = jax.tree_util.tree_map(
            lambda x: np.asarray(x.addressable_shards[0].data)[0],
            (joined.clock, joined.ids, joined.dots, joined.d_ids,
             joined.d_clocks),
        )
        got = OrswotBatch(
            clock=shard0[0], ids=shard0[1], dots=shard0[2],
            d_ids=shard0[3], d_clocks=shard0[4],
        )
        n_shard = shard0[0].shape[0]
        plunged = got.merge(OrswotBatch.zeros(n_shard, uni))
        got_sets = [sorted(o.value().val) for o in plunged.to_scalar(uni)]
        want = [sorted(e.value().val)
                for e in _plunge(expected)][: n_shard]
        assert got_sets == want, f"proc {pid} hybrid shard diverged"

    print(f"proc {pid}: topology={args.topology} objects={n_objects} "
          f"processes={topo['processes']} MULTIHOST OK", flush=True)
    return 0


def _plunge(states):
    for s in states:
        from crdt_tpu import Orswot

        s.merge(Orswot())
    return states


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--coordinator-port", type=int, default=0)
    ap.add_argument("--objects", type=int, default=8)
    ap.add_argument("--topology", default="replicas",
                    choices=["replicas", "hybrid"])
    args = ap.parse_args()

    if args.process_id is not None:
        return worker(args)

    # demo: spawn both processes
    import socket
    import subprocess

    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]

    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--process-id", str(i), "--coordinator-port", str(port),
             "--objects", str(args.objects), "--topology", args.topology]
        )
        for i in range(N_PROCS)
    ]
    rc = 0
    for p in procs:
        rc |= p.wait()
    print("demo:", "MULTIHOST OK" if rc == 0 else "FAILED")
    return rc


if __name__ == "__main__":
    sys.exit(main())
