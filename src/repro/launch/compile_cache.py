"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at one fixed path
inside the checkout (``<repo>/.jax_cache``, gitignored): the directory is
part of each entry's key, so a path taken from a temporary name, a pid or
the time would never hit.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Persistent-cache hits and misses, and seconds spent in backend
    compilation (a cache hit counts its read time), from construction on.

    JAX's monitoring listeners live as long as the process, so make one
    counter per process, at its entry point.
    """

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration_secs

    def __str__(self) -> str:
        return (f"{self.hits} persistent-cache hits, {self.misses} misses, "
                f"{self.compile_s:.2f}s backend compile (host wall clock)")
