"""JAX's persistent compilation cache, placed from outside.

The cache's directory is part of its key, so a directory that moves
never hits: the entry scripts (``chip_smoke.py``, ``bench.py``'s chip
children) call `configure` once before their first compile and the
library itself never touches the setting. Where the machine sets
``JAX_COMPILATION_CACHE_DIR`` JAX already reads it and no directory is
set in code; otherwise one fixed directory inside the checkout is used.
"""

from __future__ import annotations

import os

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"


class CompileCache:
    """The directory in use, and what it served since `configure`:
    ``requests`` compiles consulted the cache, ``hits`` of them were
    read from it."""

    def __init__(self, path: str):
        self.path = path
        self.requests = 0
        self.hits = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == _REQUESTS:
            self.requests += 1
        elif event == _HITS:
            self.hits += 1


def configure() -> CompileCache:
    """Turn the persistent cache on for this process."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: a warm start should skip the small ones too
    # (the default keeps only compiles that took over a second).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache = CompileCache(path)
    jax.monitoring.register_event_listener(cache._on_event)
    return cache
