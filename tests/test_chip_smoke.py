"""CPU rehearsal of `chip_smoke.py`: every phase at a tiny size, its
parity verdict asserted, and the refusal to run without a TPU.

The smoke itself runs on the chip (`python chip_smoke.py`); these tests
keep its phases honest on every tier-1 run at no chip time."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def folded():
    return chip_smoke.fold_phase(512, 8, seed=0, sample=32)


def test_fold_phase_parity(folded):
    batch, rec = folded
    assert rec["parity"] == "ok"
    assert int(batch.clock.shape[0]) == 512
    # 8 fleets at the north-star width: 4,936 B per replica-object
    assert rec["resident_bytes"] == 8 * 512 * 4936


def test_replicate_phase_parity():
    rec = chip_smoke.replicate_phase(512, 4, seed=0, sample=32)
    assert rec["parity"] == "ok"
    assert rec["ingest_native_fraction"] == 1.0


def test_sync_phase_parity(folded):
    rec = chip_smoke.sync_phase(folded[0], 0.01, seed=0, timeout_s=120.0)
    assert rec["parity"] == "ok"
    assert rec["planted"] == 5


def test_serve_phase_parity(folded):
    rec = chip_smoke.serve_phase(folded[0], 512, seed=0)
    assert rec["parity"] == "ok"


@pytest.mark.mesh
def test_mesh_phase_parity():
    rec = chip_smoke.mesh_phase(1024, 4, seed=0)
    assert rec["parity"] == "ok" and rec["devices"] == 4


_HUNG_SYNC = r"""
import atexit, sys, threading
sys.path.insert(0, {repo!r})
import chip_smoke
from crdt_tpu.sync import SyncSession

gate = threading.Event()
real = SyncSession.sync

def sync(self, transport):
    if self.peer == "a":  # side b never answers
        gate.wait()
    return real(self, transport)

SyncSession.sync = sync
# a teardown that waits for the hung session, as the chip's did
atexit.register(gate.wait)

def main(argv=None):
    folded, _ = chip_smoke.fold_phase(64, 2, 0, sample=4)
    chip_smoke.sync_phase(folded, 0.05, 0, timeout_s=2.0)
    print('{{"ok": true}}')
    return 0

chip_smoke.main = main
chip_smoke._run_cli([])
"""


def test_hung_sync_ends_the_process():
    """One session never answers: `sync_phase` gives up at its join
    limit, and the process exits 1 at once even though an exit hook
    would wait for the hung thread for ever."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-c", _HUNG_SYNC.format(repo=REPO)],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "sync: a session hung" in proc.stderr
    assert '"ok": true' not in proc.stdout


def test_parity_mismatch_raises():
    with pytest.raises(chip_smoke.ParityError, match="replicate"):
        chip_smoke._check(False, "replicate: planted mismatch")


def test_main_refuses_the_cpu(capsys):
    """No TPU: a non-zero exit and never an ok line."""
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    for line in out.splitlines():
        assert json.loads(line).get("ok") is not True


def test_compile_cache_home(monkeypatch, tmp_path):
    """`JAX_COMPILATION_CACHE_DIR` wins and nothing is set; otherwise
    the cache lives at the fixed `<checkout>/.jax_cache`."""
    import jax

    from crdt_tpu.config import use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        home = os.path.join(REPO, ".jax_cache")
        assert use_compile_cache() == home
        assert jax.config.jax_compilation_cache_dir == home
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
