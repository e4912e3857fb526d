"""Keyed build-once caches shared across the core/launch layers.

:class:`CompileCache` started life as :class:`repro.core.plan.GossipPlan`'s
executable cache and is re-exported from :mod:`repro.core.plan` for
backwards compatibility; it lives here so leaf modules that ``plan``
itself imports (e.g. :mod:`repro.core.flatbuf`'s layout cache) can use the
same LRU without an import cycle.

:func:`enable_persistent_cache` points JAX's on-disk compilation cache at
one fixed directory, so a second process of the same program loads its
executables instead of compiling them again.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from typing import Any, Callable

__all__ = ["CompileCache", "enable_persistent_cache"]

# <checkout>/.jax_cache: this file is <checkout>/src/repro/core/cache.py
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    must find again, so it never comes from a temp name, a pid or the time.
    Entry points call this before their first compile, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCache:
    """Keyed build-once cache (typically: hashable key -> jitted fn).

    ``max_entries`` bounds the cache with least-recently-used eviction --
    an aperiodic Matching stream (random_match) visits a fresh pairing
    every step, and a long multi-model process visits a fresh flat-buffer
    layout per tree structure, so without a bound the dict would grow for
    the whole process lifetime.  Periodic schedules / steady-state servers
    never evict (their working set is tiny).
    """

    def __init__(self, max_entries: int | None = None):
        self._cache: "OrderedDict" = OrderedDict()
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key, build: Callable[[], Any]):
        if key in self._cache:
            self.hits += 1
            self._cache.move_to_end(key)
            return self._cache[key]
        self.misses += 1
        val = self._cache[key] = build()
        if self.max_entries is not None and len(self._cache) > self.max_entries:
            self._cache.popitem(last=False)
            self.evictions += 1
        return val

    def stats(self) -> dict:
        """Hit/miss/eviction counters + current size.  A serving loop whose
        bucketed shapes are working: misses stop growing after warmup."""
        return {"entries": len(self._cache), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions}

    def __len__(self) -> int:
        return len(self._cache)

    def __contains__(self, key) -> bool:
        return key in self._cache
