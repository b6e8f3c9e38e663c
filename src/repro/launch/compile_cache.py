"""Where entry points keep JAX's persistent compilation cache.

Library imports never touch the cache; a script calls ``use_compile_cache``
once, before its first compile.  The cache's path is part of every entry's
key, so it must not move between runs: when ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it and nothing here overrides it, otherwise the cache is
the fixed directory ``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CompileCounter", "REPO_CACHE_DIR", "use_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on at its fixed place; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


class CompileCounter:
    """Counts persistent-cache hits and misses from JAX's monitoring events.

    A miss is a cold compile of a program eligible for the cache; programs
    that compile faster than ``jax_persistent_cache_min_compile_time_secs``
    are neither cached nor counted.  Listeners cannot be unregistered, so
    make one counter per process.
    """

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax.monitoring
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1
