"""Start-of-run setup of the entry points: compile-cache placement and the
device line."""
import os
import subprocess
import sys

import jax

from repro.launch import device

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_compile_cache_follows_env(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing, and the
    compile cache is written where the variable says."""
    cache = tmp_path / "cache"
    code = r"""
import jax, jax.numpy as jnp
from repro.launch import device
print("DIR", device.enable_compile_cache())
print("CFG", jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 2).lower(jnp.ones(8)).compile()
"""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert f"DIR {cache}" in out.stdout and f"CFG {cache}" in out.stdout
    assert any(cache.iterdir()), "nothing was written to the cache dir"


def test_compile_cache_default_is_fixed_repo_path(monkeypatch):
    """Without the variable the cache is <repo>/.jax_cache on every call."""
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = device.enable_compile_cache()
        second = device.enable_compile_cache()
        repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        assert first == second == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_device_line_names_platform_kind_count():
    devs = jax.devices()
    assert device.device_line() == (
        f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
