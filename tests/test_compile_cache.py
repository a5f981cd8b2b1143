"""The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` wins when
set, otherwise one fixed directory inside the checkout."""
import os
import subprocess
import sys

import jax

from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_wins_and_is_written(tmp_path):
    """With the variable set, nothing is set in code and compiled
    programs land in that directory (child process: the cache config is
    process-global)."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import setup_compile_cache\n"
        "assert setup_compile_cache() == jax.config.jax_compilation_cache_dir\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=300)
    assert any(name.endswith("-cache") for name in os.listdir(tmp_path))


def test_default_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.setup_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
