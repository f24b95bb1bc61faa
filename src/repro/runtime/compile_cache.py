"""Where jax's persistent compilation cache lives, and how often jax
compiles.

Entry points that compile for the chip (`chip_smoke.py`, the HTTP front
end, the benchmark runner) call `use_compile_cache()` before their first
compile. If `JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and
this sets nothing. Otherwise the cache goes to `<checkout>/.jax_cache`: a
fixed path, because the path is part of what a later run must find again.

`compile_count()` counts backend compiles since this module was imported
(`repro.sort` imports it): a `jax.monitoring` listener, registered on
import, on jax's backend-compile event, which fires for
every program handed to the backend, the eager ops' too, whether the
persistent cache then serves it or XLA compiles it. It sees what the
executable cache's `misses` cannot: a compile on a warm path. Each
compile also marks its end in a profiler trace (`spans.COMPILE`), so a
trace counts the compiles inside its window.
"""
from __future__ import annotations

import os
import pathlib
import threading

import jax

from repro.runtime import spans

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

#: jax's event for one backend compile (jax/_src/dispatch.py, jax 0.9).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_compiles = 0


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


def compile_count() -> int:
    """Backend compiles in this process so far."""
    with _lock:
        return _compiles


def _on_event_duration(event: str, duration_s: float, **kwargs) -> None:
    global _compiles
    if event != BACKEND_COMPILE_EVENT:
        return
    with spans.span(spans.COMPILE, fun=str(kwargs.get("fun_name", ""))):
        with _lock:
            _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
