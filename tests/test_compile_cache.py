"""The persistent compile cache lands where `use_compile_cache` says.

Each case compiles in a fresh CPU-only interpreter: the cache directory is
fixed by the first compile of a process, which in this one has happened.
"""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.runtime import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

_CHILD = """
import jax, jax.numpy as jnp
from repro.runtime.compile_cache import use_compile_cache
print(use_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sort(x) * 7 + {salt})(jnp.arange(29)).block_until_ready()
"""


def _entries(d: pathlib.Path) -> set:
    return set(os.listdir(d)) if d.is_dir() else set()


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_location(tmp_path, env_set):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV and k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    want = tmp_path / "cache" if env_set else compile_cache.DEFAULT_DIR
    if env_set:
        env[compile_cache.ENV] = str(want)
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    before = _entries(want)
    default_before = _entries(compile_cache.DEFAULT_DIR)
    salt = abs(hash((str(tmp_path), env_set))) % 10**6
    proc = subprocess.run([sys.executable, "-c", _CHILD.format(salt=salt)],
                          env=env, cwd=tmp_path, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == str(want)
    assert _entries(want) - before, "no cache entry was written"
    if env_set:   # nothing went to the default directory or the cwd
        assert _entries(compile_cache.DEFAULT_DIR) == default_before
        assert _entries(tmp_path) == {"cache"}
