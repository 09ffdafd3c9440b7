"""JAX's persistent compilation cache, set up in one place.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) calls :func:`enable_compile_cache`
before it compiles anything, so a second run of the same program on the
same chip loads its kernels and steps instead of compiling them again.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself; nothing here overrides it), otherwise the fixed
directory ``.jax_compile_cache`` at the root of the checkout (listed in
``.gitignore``).  The path never depends on a temporary name, a process id
or the time: it is part of what makes a later run find the entries.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = [
    "CACHE_ENV",
    "CacheEvents",
    "compile_cache_dir",
    "enable_compile_cache",
]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else the checkout's directory."""
    return os.environ.get(CACHE_ENV) or str(_CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and cache
    every compiled program however quick its compile.  Returns the path."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CacheEvents:
    """Persistent-cache hits and misses counted from construction on (a
    listener on JAX's monitoring events, for the life of the process)."""

    _NAMES = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self) -> None:
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        name = self._NAMES.get(event)
        if name is not None:
            setattr(self, name, getattr(self, name) + 1)

    def __repr__(self) -> str:
        return f"CacheEvents(hits={self.hits}, misses={self.misses})"
