"""Persistent XLA compilation cache (utils/compile_cache.py)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from roc_tpu.utils.compile_cache import (DEFAULT_DIR, ENV_VAR,
                                         enable_compile_cache,
                                         resolve_cache_dir)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_populates_and_is_honored(tmp_path):
    d = str(tmp_path / "xla")
    got = enable_compile_cache(d, min_compile_secs=0.0)
    assert got == d and os.path.isdir(d)
    assert jax.config.jax_compilation_cache_dir == d
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum() + 41.0)
    f(jnp.ones((256, 256))).block_until_ready()
    assert os.listdir(d), "compilation cache stayed empty"


def test_env_var_beats_explicit_argument(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is the operator's placement: the code
    sets no other directory, whatever a caller or --cache-dir passes."""
    d = str(tmp_path / "envcache")
    monkeypatch.setenv(ENV_VAR, d)
    assert enable_compile_cache() == d
    assert enable_compile_cache(str(tmp_path / "explicit")) == d
    assert jax.config.jax_compilation_cache_dir == d
    assert not (tmp_path / "explicit").exists()


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    """Unset, the directory is <repo>/.jax_cache — the path is part of
    the cache key, so two processes must resolve the identical one."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert DEFAULT_DIR == os.path.join(_REPO, ".jax_cache")
    assert resolve_cache_dir() == DEFAULT_DIR
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    code = ("from roc_tpu.utils.compile_cache import resolve_cache_dir;"
            "print(resolve_cache_dir())")
    # a different cwd per process: the default must not depend on it
    got = [subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=120,
                          check=True).stdout.strip()
           for cwd in (_REPO, "/")]
    assert got == [DEFAULT_DIR, DEFAULT_DIR]


def test_uncreatable_dir_raises(tmp_path):
    # a path under a regular FILE can never be created (works even as
    # root, unlike a permissions-based setup)
    f = tmp_path / "plainfile"
    f.write_text("x")
    with pytest.raises(OSError):
        enable_compile_cache(str(f / "sub"))
