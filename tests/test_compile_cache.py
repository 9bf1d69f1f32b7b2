"""The persistent compile cache is placed from outside: the environment's
directory when set, else the fixed ``<checkout>/.jax_cache``."""
import os
import random
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]

#: a fresh process: enable the cache, compile one program, block on it
_COMPILE_ONE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
jax.jit(lambda x: x * {scale!r} + 1.0)(jnp.ones(8)).block_until_ready()
"""


@pytest.fixture
def updates(monkeypatch):
    """Record jax config updates instead of applying them (the tests must
    not turn the cache on in this worker)."""
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_environment_dir_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                                      updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)] * 2


def _compile_in_child(env_dir):
    """Run one compile in a child pinned to the CPU, with
    ``JAX_COMPILATION_CACHE_DIR`` set to ``env_dir`` (unset when None).
    The program's constant is random, so its cache entry is new."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.pathsep.join(
                   [str(CHECKOUT / "src"), env.get("PYTHONPATH", "")]))
    script = _COMPILE_ONE.format(scale=random.uniform(2.0, 3.0))
    subprocess.run([sys.executable, "-c", script], env=env, check=True,
                   timeout=120)


def _entries(path):
    return set(path.iterdir()) if path.is_dir() else set()


@pytest.mark.parametrize("env_set", [False, True])
def test_a_real_compile_lands_in_the_chosen_dir(tmp_path, env_set):
    default = CHECKOUT / ".jax_cache"
    before = _entries(default)
    _compile_in_child(tmp_path if env_set else None)
    new = _entries(default) - before
    try:
        if env_set:
            assert _entries(tmp_path), "no entry in JAX_COMPILATION_CACHE_DIR"
            assert not new, f"entries also appeared in {default}: {new}"
        else:
            assert new, f"no entry appeared in {default}"
    finally:
        for p in new:
            p.unlink(missing_ok=True)
