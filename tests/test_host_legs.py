"""Host-leg spans of the wire, mesh and serve loops.

Each loop names its host legs with ``tracing.span`` inside the library
(so every caller gets them, the benchmark's readers included): on one
thread the legs are flat and sequential, and each runs the expected
number of times per fleet, round, step or frame.  With tracing off no
leg records anything, and tracing never makes a kernel call block.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu import mesh
from crdt_tpu.batch import OrswotBatch
from crdt_tpu.batch.wireloop import PipelinedWireLoop
from crdt_tpu.cluster import ClusterNode
from crdt_tpu.config import CrdtConfig
from crdt_tpu.obs import metrics as obs_metrics
from crdt_tpu.serve import ServeLoop
from crdt_tpu.serve.query import ReadRequest
from crdt_tpu.serve.wire import (decode_read_request, encode_read_request,
                                 encode_result_frame)
from crdt_tpu.utils import tracing
from crdt_tpu.utils.interning import Universe
from crdt_tpu.utils.testdata import anti_entropy_fleets

N, REPLICAS, ROUNDS = 64, 3, 2


def _uni():
    return Universe.identity(CrdtConfig(
        num_actors=8, member_capacity=8, deferred_capacity=4,
        counter_bits=32))


def _fleets(uni, seed, r):
    cfg = uni.config
    reps = anti_entropy_fleets(
        np.random.RandomState(seed), N, cfg.num_actors, cfg.member_capacity,
        cfg.deferred_capacity, r, base=4, novel=1, deferred_frac=0.25,
        dtype=np.uint32)
    return [OrswotBatch(*rep) for rep in reps]


def _wire(uni):
    blobs = [[b.to_wire(uni) for b in _fleets(uni, seed, REPLICAS)]
             for seed in range(ROUNDS)]
    loop = PipelinedWireLoop(uni, fold_path="jnp")

    def run():
        res = loop.run(blobs, collect="none")
        assert res["rounds"] == ROUNDS
    per_fleet, per_round = ROUNDS * REPLICAS, ROUNDS
    # the jnp fold puts every staging set once and dispatches its
    # densify, then r - 1 merges plus the plunger per round
    want = {"wireloop.parse": per_fleet, "wireloop.wait_parsed": per_fleet,
            "wireloop.put": per_fleet, "wireloop.dispatch": 2 * per_fleet,
            "wireloop.wait": per_round, "wireloop.fetch": per_round,
            "wireloop.encode": per_round}
    return run, want


def _mesh(uni):
    a, b = _fleets(uni, 7, 2)
    pair = [mesh.ShardedBatch.shard(x, uni, shards=4, granule=4)
            for x in (a, b)]

    def run():
        for step in range(2):
            res = mesh.anti_entropy_step(pair[step % 2], pair[1 - step % 2])
            assert res.digests.shape == (N,)
    want = {f"mesh.step.{leg}": 2 for leg in ("dispatch", "wait", "fetch")}
    return run, want


def _serve(uni):
    (batch,) = _fleets(uni, 11, 1)
    loop = ServeLoop(ClusterNode("legs", batch, uni))
    frame = encode_read_request(ReadRequest.reads(
        np.arange(0, N, 3), member=np.arange(0, N, 3) % 5 - 1))

    def run():
        out = encode_result_frame(
            loop.serve(decode_read_request(frame, num_objects=N)))
        assert isinstance(out, bytes)
    want = {f"serve.leg.{leg}": 1 for leg in (
        "decode", "admit", "dispatch", "wait", "fetch", "heat", "encode")}
    return run, want


LOOPS = {"wire": _wire, "mesh": _mesh, "serve": _serve}


@pytest.fixture
def global_tracer():
    tracing.enable(False)
    tracing.reset()
    yield tracing.get_tracer()
    tracing.enable(False)
    tracing.reset()


def _legs(tracer, want):
    families = {name.rsplit(".", 1)[0] for name in want}
    return {name: s.count for name, s in tracer.stats.items()
            if name.rsplit(".", 1)[0] in families}


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_host_legs_record_their_spans(loop, global_tracer):
    if loop == "mesh" and len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (the conftest forces 8 host devices)")
    run, want = LOOPS[loop](_uni())
    run()  # warm: compiles stay out of the counted pass
    global_tracer.reset()
    tracing.enable(True)
    run()
    tracing.enable(False)
    assert _legs(global_tracer, want) == want
    if loop == "serve":
        # the per-mode latency histogram is the one serve wall
        hists = obs_metrics.registry().snapshot()["histograms"]
        assert "serve.latency.eventual" in hists
        assert "serve.read_latency" not in hists


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_host_legs_record_nothing_when_tracing_is_off(loop, global_tracer):
    if loop == "mesh" and len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (the conftest forces 8 host devices)")
    run, want = LOOPS[loop](_uni())
    run()
    run()
    assert _legs(global_tracer, want) == {}


def test_tracing_never_blocks_an_observed_kernel(global_tracer, monkeypatch):
    """Spans name the host legs; a kernel call stays an async dispatch
    whether tracing is on or off."""
    from crdt_tpu.batch import vclock_batch

    plane = jnp.zeros((37, 8), dtype=jnp.uint32)
    vclock_batch._merge(plane, plane)  # compile outside the check
    blocked = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(x) or x)
    tracing.enable(True)
    vclock_batch._merge(plane, plane)
    tracing.enable(False)
    assert blocked == []
