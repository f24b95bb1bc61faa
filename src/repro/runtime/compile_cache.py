"""Where jax's persistent compilation cache lives.

Entry points that compile for the chip (`chip_smoke.py`, the HTTP front
end, the benchmark runner) call `use_compile_cache()` before their first
compile. If `JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and
this sets nothing. Otherwise the cache goes to `<checkout>/.jax_cache`: a
fixed path, because the path is part of what a later run must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
