"""The named wire cell (``wire.named``), cut small, runs end to end on
the CPU and comes out correct with no blob on the Python codec; the
named decoder refuses non-canonical blobs; and a control output with
one object changed is counted wrong.

The tiny root is ``conftest.make_root``'s, with the named cell added
beside the others the way a new cell is added: a configuration file, a
traffic file and manifest entries.
"""

from __future__ import annotations

import json
import os

import pytest

from benchmark.tests.conftest import ROOT

SEED = (1 << 34) + 5
CELL = "named.tiny"


def _add_named_cell(root: str) -> str:
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    cell = next(w for w in real["workloads"] if w["name"] == "wire.named")
    conf = next(c for c in real["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "configs", "named_tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(bench, "traffic", "named_tiny.json"), "w") as f:
        json.dump(dict(traffic, slice_objects=64, check_objects=32), f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(dict(conf, name="named_tiny",
                                    file="benchmark/configs/named_tiny.json",
                                    reduced=["objects"]))
    manifest["workloads"].append(dict(cell, name=CELL, config="named_tiny",
                                      traffic="named_tiny"))
    real_lists = {m["name"]: m.get("workloads", [])
                  for m in real["end_to_end"] + real["per_layer"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "wire.named" in real_lists.get(m["name"], []):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


@pytest.fixture(scope="module")
def named_root(tmp_path_factory):
    from benchmark.tests.conftest import make_root

    return _add_named_cell(make_root(tmp_path_factory.mktemp("named")))


def _run(root, seed=SEED, trace=False):
    from benchmark import run

    return run.run_cell(run.resolve(CELL, root), seed, 0.3, trace,
                        require_tpu=False, root=root)


def test_named_cell_runs_and_is_correct(named_root):
    from benchmark import run

    out = _run(named_root)
    assert out["correct"], out["checks"]
    assert out["checks"]["objects_wrong"]["value"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in run.resolve(CELL, named_root).end_to_end}
    assert want == {"wire_objs_per_s", "setup_s"}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_traced_named_cell_reads_no_fallback(named_root):
    out = _run(named_root, seed=SEED + 1, trace=True)
    assert out["correct"]
    assert out["metrics"]["fallback_pct.named"]["value"] == 0.0
    assert {"parse_ms.wire", "egress_ms.wire"} <= set(out["metrics"])


def test_fallback_reader_reads_the_counter_deltas():
    from benchmark import run

    reader = run.load_module(os.path.join(
        ROOT, "benchmark", "metrics", "fallback_pct.named.py"), "m")

    def view(stats):
        return run.LayerView(trace=None, stats=stats, traffic={},
                             device_kind="cpu", chips=1)

    assert reader.read(view({"rounds": 2, "wire_blobs": 40,
                             "wire_fallback": 10})) == 25.0
    # a driver that counts no blobs (the other wire cells) reads nothing
    assert reader.read(view({"rounds": 2})) is None


def test_names_are_ycsb_key_names_and_vnode_ids():
    import numpy as np

    from benchmark import named

    ns = np.array([0, 1, 7, 1 << 20, (1 << 24) - 1, 123456789])
    assert named.member_names(ns) == [named.member_name(int(n)) for n in ns]
    assert all(5 <= len(x) <= 23 and x.startswith("user")
               for x in named.member_names(np.arange(5000)))
    # key 0: FNV-1a 64 over eight zero octets is the offset basis times
    # the prime eight times, then Math.abs of the signed long
    h = named.FNV_OFFSET_BASIS_64 * named.FNV_PRIME_64 ** 8 % (1 << 64)
    assert named.member_name(0) == f"user{abs(h - (h >> 63 << 64))}"
    assert named.actor_name(SEED, 3) == named.actor_name(SEED, 3)
    assert len(named.actor_name(SEED, 3)) == 8
    assert named.actor_name(SEED, 3) != named.actor_name(SEED + 1, 3)


def _blob(pairs, entries):
    """An ORSWOT blob written by hand: every clock's pairs and the
    entries in the order given."""
    from crdt_tpu.utils.serde import to_binary

    def body(pairs):
        return bytes([len(pairs)]) + b"".join(
            to_binary(a) + to_binary(c) for a, c in pairs)

    out = bytes([0x26]) + body(pairs) + bytes([len(entries)])
    out += b"".join(to_binary(m) + bytes([0x20]) + body(d)
                    for m, d in entries)
    return out + b"\x00"


def test_named_decoder_refuses_non_canonical_blobs():
    from benchmark import named
    from benchmark.reference import Malformed

    a, b = b"\x01" * 8, b"\x02" * 8
    ok = _blob([(a, 3), (b, 1)],
               [("user1", [(a, 3)]), ("user2", [(a, 2), (b, 1)])])
    clock, entries, _ = named.decode_named_blob(ok)
    assert clock == {a: 3, b: 1} and entries == {"user1": {a: 3},
                                                 "user2": {a: 2, b: 1}}
    for bad in (
        _blob([(a, 3), (b, 1)], [("user2", [(b, 1)]), ("user1", [(a, 3)])]),
        _blob([(b, 1), (a, 3)], [("user1", [(a, 3)])]),
        _blob([(a, 3)], [("user1", [(b, 1), (a, 3)])]),
        _blob([(a, 3)], [("user1", [(a, 3)]), ("user1", [(a, 3)])]),
    ):
        with pytest.raises(Malformed):
            named.decode_named_blob(bad)
    with pytest.raises(Malformed):
        named.decode_named_blob(ok + b"\x00")


def test_one_object_changed_is_counted_wrong(named_root, monkeypatch):
    """The control: the loop's output with one object's set clock
    advanced, re-encoded canonically, put in the program's place."""
    from crdt_tpu.batch import PipelinedWireLoop
    from crdt_tpu.utils.serde import from_binary, to_binary

    assert _run(named_root)["correct"]
    run_loop = PipelinedWireLoop.run

    def altered(self, rounds, *, on_round=None, **kw):
        def change(i, blobs):
            blobs = list(blobs)
            for j, blob in enumerate(blobs):
                s = from_binary(blob)
                if s.clock.dots:
                    actor = next(iter(s.clock.dots))
                    s.clock.dots[actor] += 1
                    blobs[j] = to_binary(s)
                    break
            on_round(i, blobs)
        return run_loop(self, rounds, on_round=change if on_round else None,
                        **kw)

    monkeypatch.setattr(PipelinedWireLoop, "run", altered)
    out = _run(named_root)
    assert not out["correct"]
    assert out["checks"]["objects_wrong"]["value"] >= 1


def test_control_is_not_correct_on_the_named_cell(named_root):
    """The reference with buffered removes never replayed, put in the
    program's place, reads as not correct here too."""
    from benchmark.tests.control import control

    with control():
        out = _run(named_root)
    assert not out["correct"]
    assert out["checks"]["objects_wrong"]["value"] > 0
