"""The control comes out not correct, and so does every fault a cell
can have, planted in the program underneath an otherwise whole run."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from benchmark.tests.control import control

SEED = (1 << 33) + 17


@pytest.mark.parametrize("cell", ["fold.tiny", "wire.tiny", "mesh.tiny",
                                  "reads.tiny"])
def test_control_is_not_correct(run_tiny, cell):
    assert run_tiny(cell, SEED)["correct"]
    with control():
        out = run_tiny(cell, SEED)
    assert not out["correct"]
    assert max(c["value"] for c in out["checks"].values()) > 0


def _fold_faults():
    from crdt_tpu.batch import OrswotBatch

    join = OrswotBatch.join_fleet.__func__

    def unchanged(cls, fleets, **kw):
        return fleets[0]

    def half(cls, fleets, **kw):
        return join(cls, fleets[:len(fleets) // 2], **kw)

    def altered(cls, fleets, **kw):
        out = join(cls, fleets, **kw)
        return dataclasses.replace(out, clock=out.clock.at[:, 0].add(1))

    return {k: (OrswotBatch, "join_fleet", classmethod(f)) for k, f in
            (("unchanged", unchanged), ("half", half),
             ("altered", altered))}


def _wire_faults():
    from crdt_tpu.batch import PipelinedWireLoop

    run = PipelinedWireLoop.run

    def unchanged(self, rounds, *, on_round=None, **kw):
        for i, rnd in enumerate(rounds if on_round else ()):
            on_round(i, list(rnd[0]))
        return run(self, rounds, **kw)

    def half(self, rounds, **kw):
        return run(self, [rnd[:len(rnd) // 2] for rnd in rounds], **kw)

    def altered(self, rounds, *, on_round=None, **kw):
        def flip(i, blobs):
            on_round(i, [b[:-1] + bytes([b[-1] ^ 1]) for b in blobs])
        return run(self, rounds, on_round=flip if on_round else None, **kw)

    return {k: (PipelinedWireLoop, "run", f) for k, f in
            (("unchanged", unchanged), ("half", half),
             ("altered", altered))}


def _mesh_faults():
    from crdt_tpu import mesh
    from crdt_tpu.mesh import step as step_mod

    step = step_mod.anti_entropy_step

    def unchanged(a, b, **kw):
        out = step(a, b, **kw)
        return dataclasses.replace(out, batch=a)

    def no_exchange(a, b, **kw):
        # each chip keeps only its own digest slice: the all-gather left out
        out = step(a, b, **kw)
        d = np.zeros_like(out.digests)
        lo, hi = a.layout.ranges()[0]
        d[lo:hi] = out.digests[lo:hi]
        return dataclasses.replace(out, digests=d)

    def altered(a, b, **kw):
        out = step(a, b, **kw)
        vv = out.version_vector.copy()
        vv[0] += 1
        return dataclasses.replace(out, version_vector=vv)

    return {k: (mesh, "anti_entropy_step", f) for k, f in
            (("unchanged", unchanged), ("no_exchange", no_exchange),
             ("altered", altered))}


def _reads_faults():
    from crdt_tpu.serve import ServeLoop

    serve = ServeLoop.serve

    def half(self, req):
        frame = serve(self, req)
        keep = max(1, len(frame) // 2)
        for name in ("obj", "kind", "member", "status", "val",
                     "add_clock", "rm_clock"):
            setattr(frame, name, getattr(frame, name)[:keep])
        return frame

    def altered(self, req):
        frame = serve(self, req)
        frame.val = frame.val.copy()
        frame.val[0] += 1
        return frame

    return {k: (ServeLoop, "serve", f) for k, f in
            (("half", half), ("altered", altered))}


FAULTS = {"fold.tiny": _fold_faults, "wire.tiny": _wire_faults,
          "mesh.tiny": _mesh_faults, "reads.tiny": _reads_faults}
CASES = [("fold.tiny", f) for f in ("unchanged", "half", "altered")] \
    + [("wire.tiny", f) for f in ("unchanged", "half", "altered")] \
    + [("mesh.tiny", f) for f in ("unchanged", "no_exchange", "altered")] \
    + [("reads.tiny", f) for f in ("half", "altered")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(run_tiny, monkeypatch, cell, fault):
    owner, name, broken = FAULTS[cell]()[fault]
    monkeypatch.setattr(owner, name, broken)
    out = run_tiny(cell, SEED)
    assert not out["correct"], (fault, out["checks"])
