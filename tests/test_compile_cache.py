"""The persistent compilation cache goes where `repro.compile_cache` says."""

from __future__ import annotations

import os

from conftest import run_in_subprocess

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE = """
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print("dir=" + enable_compile_cache())
    print("config=" + str(jax.config.jax_compilation_cache_dir))
"""


def test_cache_env_wins_and_entries_land_there(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = run_in_subprocess(_COMPILE + """
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
    """, devices=1)
    assert f"dir={tmp_path}" in out and f"config={tmp_path}" in out
    assert os.listdir(tmp_path), "no cache entry was written"


def test_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    out = run_in_subprocess(_COMPILE, devices=1)
    expected = os.path.join(ROOT, ".jax_cache")
    assert f"dir={expected}" in out and f"config={expected}" in out
