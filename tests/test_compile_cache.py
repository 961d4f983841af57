"""Where the persistent compilation cache goes: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads it; the helper sets nothing), else the fixed
``<checkout>/.jax_cache``."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache as CC

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_dir_receives_the_entries(tmp_path):
    """A fresh process compiles, and its entry lands in the env's dir."""
    code = (
        "from repro.launch.compile_cache import place_compile_cache\n"
        "place_compile_cache()\n"
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_default_dir_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = CC.place_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert CC.place_compile_cache() == path      # same on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
