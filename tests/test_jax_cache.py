"""Placement of JAX's persistent compilation cache (``repro.jax_cache``)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro import jax_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_cache_is_in_the_checkout(monkeypatch):
    monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert jax_cache.configure_compile_cache() == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_holds_the_entries(tmp_path):
    """With the variable set, the code sets no directory of its own and a
    compiled entry lands in the variable's directory."""
    body = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.jax_cache import configure_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(configure_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
    """)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env[jax_cache.ENV_VAR] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir()), "no compiled entry was written"
