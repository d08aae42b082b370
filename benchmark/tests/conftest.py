"""Benchmark tests run on the CPU at tiny sizes, with four host devices
for the mesh loop.  ``tiny_root`` builds a checkout root whose cells are
the real cells cut small: new configuration and traffic files and a
manifest, beside the real drivers and metric readers, which is all a
new cell ever needs.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# CPU programs need no cache, and one written by another host only warns
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the real cells, each cut small: tiny cell -> (real cell,
#: configuration changes, traffic changes)
TINY = {
    "fold.tiny": ("fold.star", {"objects": 192, "deployment_chips": 2},
                  {"check_objects": 64}),
    "wire.tiny": ("wire.star", {}, {"slice_objects": 64,
                                    "check_objects": 32}),
    "mesh.tiny": ("mesh.star4", {"objects": 1024}, {"check_objects": 64}),
    "reads.tiny": ("reads.star", {"objects": 4096},
                   {"frame_max": 64, "frames": 96}),
}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def make_root(path) -> str:
    """A checkout root holding the tiny cells; returns its path."""
    bench = os.path.join(path, "benchmark")
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(bench, d), exist_ok=True)
    for d in ("drivers", "metrics"):
        os.symlink(os.path.join(BENCH, d), os.path.join(bench, d))
    real = _load("BENCHMARK.json")
    cells = {w["name"]: w for w in real["workloads"]}
    files = {c["name"]: c["file"] for c in real["configs"]}
    manifest = dict(real, configs=[], workloads=[])
    for tiny, (name, cchange, tchange) in TINY.items():
        w = cells[name]
        stem = tiny.replace(".", "_")
        with open(os.path.join(bench, "configs", stem + ".json"), "w") as f:
            json.dump(dict(_load(files[w["config"]]), **cchange), f)
        with open(os.path.join(bench, "traffic", stem + ".json"), "w") as f:
            json.dump(dict(_load("benchmark", "traffic",
                                 w["traffic"] + ".json"), **tchange), f)
        manifest["configs"].append({
            "name": stem, "source": "test", "reduced": ["objects"],
            "file": f"benchmark/configs/{stem}.json", "why": "test"})
        manifest["workloads"].append(dict(
            w, name=tiny, config=stem, traffic=stem))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for t, (name, _, _) in TINY.items()
                              if name in m["workloads"]]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return str(path)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def run_tiny(tiny_root):
    """``run_tiny(cell, seed, seconds=0.3, trace=False)`` -> the result
    object of one run on the CPU."""
    from benchmark import run

    def go(cell, seed, seconds=0.3, trace=False):
        return run.run_cell(run.resolve(cell, tiny_root), seed, seconds,
                            trace, require_tpu=False, root=tiny_root)

    return go
