"""Each cell's loop, cut small, runs end to end on the CPU and comes out
correct; a new cell is only new files and a manifest entry; and the
entry point itself refuses a host without a TPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.tests.conftest import ROOT, TINY

SEED = (1 << 35) + 11


@pytest.mark.parametrize("cell", list(TINY))
def test_cell_runs_and_is_correct(run_tiny, tiny_root, cell):
    from benchmark import run

    out = run_tiny(cell, SEED)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in run.resolve(cell, tiny_root).end_to_end}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == (4 if cell == "mesh.tiny" else 1)


def test_traced_run_reports_layer_metrics(run_tiny):
    out = run_tiny("wire.tiny", SEED, trace=True)
    assert out["correct"]
    # the host legs come from the program's stage clocks; the CPU trace
    # has no device plane, so the device metrics stay out of the line
    assert {"parse_ms.wire", "egress_ms.wire"} <= set(out["metrics"])
    assert "idle_pct.wire" not in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_new_cell_needs_only_files_and_a_manifest_entry(tmp_path):
    """A three-replica fold (Riak's n_val 3): one configuration file,
    one traffic file, one manifest entry."""
    from benchmark import run
    from benchmark.tests.conftest import make_root

    root = make_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "fold_tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "configs", "n3.json"), "w") as f:
        json.dump(dict(cfg, replicas=3), f)
    shutil.copy(os.path.join(bench, "traffic", "fold_tiny.json"),
                os.path.join(bench, "traffic", "n3_rounds.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "n3", "source": "test",
                                "file": "benchmark/configs/n3.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "fold.n3", "config": "n3",
                                  "traffic": "n3_rounds", "chips": 1,
                                  "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "merges_per_s":
            m["workloads"].append("fold.n3")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    out = run.run_cell(run.resolve("fold.n3", root), SEED, 0.2, False,
                       require_tpu=False, root=root)
    assert out["correct"] and out["metrics"]["merges_per_s"]["value"] > 0


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "fold.star",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_the_cpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_entry_point_needs_the_program(tmp_path):
    """A directory with the manifest and the benchmark but not the store
    exits non-zero with no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp_path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert "{" not in proc.stdout
