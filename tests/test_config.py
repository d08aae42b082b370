"""`crdt_tpu.config`: where the persistent compilation cache lives."""

import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
