"""Persistent XLA compile cache placement + compile accounting.

One helper every entry point calls (``chip_smoke.py``,
``benchmarks/drivers/``, ``scripts/profile_*.py``, ``tests/conftest.py``)
so the cache directory is decided in exactly one place:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself; the
  helper touches no directory setting and only lowers the size/time
  thresholds so every program is cached;
- otherwise: ``<checkout>/.jax_cache`` (git-ignored). The path is part
  of nothing volatile — no pid, no time, no tempdir — because a cache
  directory that moves never hits.
"""

from __future__ import annotations

import os
from typing import Dict

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the in-checkout default, used only when the environment names none
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use. Call before the first compile."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return env_dir or DEFAULT_CACHE_DIR


class CompileWatch:
    """Counts XLA backend compiles, their wall seconds and persistent
    cache hits/misses from ``jax.monitoring`` events — every compile the
    process performs, not only the ones a caller remembered to count.
    Listeners cannot be unregistered, so build ONE per process and read
    deltas with :meth:`snapshot`."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.cache_hits = 0
        self.cache_misses = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self.cache_hits += 1
        elif event == self._MISS:
            self.cache_misses += 1

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_seconds += duration

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles,
                "compile_seconds": round(self.compile_seconds, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
