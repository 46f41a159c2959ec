"""Where the launchers put JAX's persistent compilation cache."""

import os
import subprocess
import sys

import jax

from repro.launch.compile_cache import DEFAULT_DIR, configure_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_is_one_fixed_directory_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
        assert DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
        assert configure_compile_cache() == DEFAULT_DIR  # same every call
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_directory_is_left_to_jax_and_written_only_there(tmp_path):
    """In a fresh process: the cache lands in the directory the
    environment names, and the helper sets no other."""
    cache = tmp_path / "cache"
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import configure_compile_cache\n"
        "d = configure_compile_cache()\n"
        "assert d == jax.config.jax_compilation_cache_dir, d\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n"
        "print(d)\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(cache)
    assert any(cache.iterdir())
