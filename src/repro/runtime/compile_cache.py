"""JAX's persistent compilation cache, and a meter for what compiling costs.

The cache directory is part of what a later run has to find again, so it
never comes from a temporary name, a process id or the clock:

* ``JAX_COMPILATION_CACHE_DIR``, when set, names it (JAX reads the variable
  itself; nothing else is set);
* otherwise it is the fixed ``<checkout>/.jax_cache``, which git ignores.

Call :func:`enable_compile_cache` once, before the first compile.
"""
from __future__ import annotations

import os
import pathlib
from typing import Dict

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/runtime/ -> the checkout's root.
CHECKOUT_CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
                      / ".jax_cache")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HITS = "/jax/compilation_cache/cache_hits"
_CACHE_MISSES = "/jax/compilation_cache/cache_misses"


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives for this process."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir`; return it."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileMeter:
    """Accumulates XLA compile seconds and persistent-cache hits/misses.

    Reads JAX's own monitoring events, so it sees every compile in the
    process (a cache hit records its retrieval time as the compile time).
    Take :meth:`snapshot` before and after a phase and subtract.
    """

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HITS:
            self.cache_hits += 1
        elif event == _CACHE_MISSES:
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.compile_s, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
