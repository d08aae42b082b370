"""The main path's programs, compiled for a v5e chip that is described,
not attached (`on-chip-measurement` guide §2).

Each test lowers a kernel from ``jax.ShapeDtypeStruct``\\ s at the
north-star width (A=64, M=16, D=2, u32) and compiles it with the TPU
compiler installed here, so a program the chip's compiler refuses — a
Mosaic tiling error, a program over 16 GB of HBM, a kernel that cannot
be partitioned — fails tier-1 at no chip time.  Nothing runs.

The topology is described inside a module fixture and never at import:
only one process may load the TPU library, and each xdist worker
imports every test file.  Keep these tests in this one file."""

import os

import pytest

import jax
import jax.numpy as jnp

A, M, D = 64, 16, 2
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        # no escape: where the chip compiler cannot load, these fail
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _fleet(n, sharding, lead=()):
    """One ORSWOT plane set ``(clock, ids, dots, d_ids, d_clocks)``."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(lead + shape, dt, sharding=sharding)

    return (s((n, A), jnp.uint32), s((n, M), jnp.int32),
            s((n, M, A), jnp.uint32), s((n, D), jnp.int32),
            s((n, D, A), jnp.uint32))


def test_unrolled_pairwise_merge_compiles(one_chip):
    import crdt_tpu.batch  # noqa: F401  (x64 on, as on the run path)
    from crdt_tpu.batch.orswot_batch import _merge

    f = _fleet(4096, one_chip)
    compiled = _merge.lower(*f, *f, M, D, "unrolled").compile()
    assert compiled.memory_analysis().argument_size_in_bytes \
        >= 2 * 4096 * 4936


def test_wireloop_fold_kernel_compiles(one_chip, monkeypatch):
    from crdt_tpu.batch.wireloop import _fold_merge_kernel

    # the kernel resolves its merge at trace time from the backend,
    # which here is the CPU: steer it to the TPU default
    monkeypatch.setenv("CRDT_MERGE_IMPL", "unrolled")
    f = _fleet(4104, one_chip)
    compiled = _fold_merge_kernel(M, D).lower(*f, *f).compile()
    assert compiled.memory_analysis().output_size_in_bytes > 0


def test_wireloop_densify_compiles(one_chip):
    """The wire loop's densify at the ★ fleet's shape (15,625 objects,
    ≈ 13 cells each, padded to a power of two): the dense planes it
    writes plus the flat space they are cut from, nothing whole-fleet
    more."""
    import crdt_tpu.batch  # noqa: F401  (x64 on, as on the run path)
    from crdt_tpu.batch.orswot_batch import _densify_cells

    n, k = 15_625, 1 << 18

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = _densify_cells.lower(
        s((n, M), jnp.int32), s((n, D), jnp.int32), s((k,), jnp.int32),
        s((k,), jnp.uint32), a=A, m=M, d=D).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= n * 4936
    assert mem.temp_size_in_bytes < 4 * n * 4936


STAR_N = 1_250_000  # the ★ replica, one chip's whole share


def test_serve_gather_compiles(one_chip):
    """The largest read frame of the ★ replica, gathered from its row
    view: the view is read in place (no whole-view copy) and the program
    plans a few MB of temporaries.  A gather straight from the
    object-minor planes relayouts each whole plane (10.24 GB planned)."""
    import re

    import crdt_tpu.batch  # noqa: F401  (x64 on, as on the run path)
    from crdt_tpu.serve.query import _orswot_kernel, _view_width

    rows = jax.ShapeDtypeStruct((STAR_N, _view_width(A, M)), jnp.uint32,
                                sharding=one_chip)
    obj = jax.ShapeDtypeStruct((4096,), jnp.int64, sharding=one_chip)
    member = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    compiled = _orswot_kernel(A, M).lower(rows, obj, member).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    whole = [ln for ln in compiled.as_text().splitlines()
             if re.search(rf"= \S+\[{STAR_N},[^=]*\bcopy(-start)?\(", ln)]
    assert not whole, whole


def test_serve_view_build_plans_under_one_chip(one_chip):
    """The ★ replica's row view (1,152 lanes a row, 5.76 GB) is built a
    chunk at a time: the planes in, the view out and the temporaries
    plan under 13 GB (built in one piece: 17.0 GB)."""
    import crdt_tpu.batch  # noqa: F401
    from crdt_tpu.serve.query import _view_kernel, _view_width

    assert _view_width(A, M) == 1152
    f = _fleet(STAR_N, one_chip)
    ma = _view_kernel().lower(f[0], f[1], f[2]).compile().memory_analysis()
    assert ma.output_size_in_bytes == STAR_N * 1152 * 4
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < 13 * 10**9, total


def test_mesh_step_compiles_on_four_chips(topo):
    """One anti-entropy round over a 2x2 mesh: the compiled program
    carries exactly the declared collectives — the digest all-gather
    (which XLA:TPU may lower to an all-reduce) plus the pmax clock join
    and the psum member fold — and no others."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from crdt_tpu.mesh.step import _step_fn
    from crdt_tpu.sync.digest import _digest_dtype

    mesh = Mesh(np.asarray(topo.devices[:4]), ("objects",))
    f = _fleet(4 * 4096, NamedSharding(mesh, P("objects")))
    salts = jax.ShapeDtypeStruct((A,), _digest_dtype(),
                                 sharding=NamedSharding(mesh, P()))
    compiled = _step_fn(mesh, "objects", M, D, False, "unrolled") \
        .lower(f, f, salts).compile()
    text = compiled.as_text()
    collectives = [ln for ln in text.splitlines()
                   if re.search(r"= \S+ (all-gather|all-reduce)(-start)?\(",
                                ln)]
    declared = ("all_gather", "pmax", "psum")
    for name in declared:
        assert any(f"/{name}" in ln for ln in collectives), name
    for ln in collectives:
        assert any(f"/{name}" in ln for name in declared), ln
    assert "collective-permute" not in text and "all-to-all" not in text


def test_union_aligned_pallas_fold_compiles(one_chip):
    """At a small width: Mosaic unrolls the kernel over actors, slots
    and replicas, and the north-star shape takes minutes to compile."""
    from crdt_tpu.ops import orswot_fold_aligned

    r, a, m, d = 2, 8, 4, 2
    t = orswot_fold_aligned._tile_size(a, m, d, r, m)
    f = tuple(
        jax.ShapeDtypeStruct((r, t) + shape, dt, sharding=one_chip)
        for shape, dt in (((a,), jnp.uint32), ((m,), jnp.int32),
                          ((m, a), jnp.uint32), ((d,), jnp.int32),
                          ((d, a), jnp.uint32)))
    compiled = orswot_fold_aligned.fold_merge.lower(
        *f, m_cap=m, d_cap=d, u_cap=m, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_smoke_fold_plans_under_one_chip(one_chip):
    """The fold `chip_smoke.py` runs — 8 fleets × 125,000 objects at
    the north-star width, 4.94 GB of inputs — plans inside one chip's
    HBM, with room left for nothing but the inputs it holds."""
    from crdt_tpu.batch.orswot_batch import _fold_tree

    f = _fleet(125_000, one_chip)
    ma = _fold_tree.lower((f,) * 8, M, D, True, "unrolled").compile() \
        .memory_analysis()
    # the inputs, plus at most the device's tile padding
    assert 8 * 125_000 * 4936 <= ma.argument_size_in_bytes \
        < 1.01 * 8 * 125_000 * 4936
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, total
