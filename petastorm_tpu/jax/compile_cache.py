"""JAX's persistent compilation cache for the repository's entry points.

The library sets nothing when it is imported: a program that compiles on
the chip (``chip_smoke.py``, the ImageNet example) calls
:func:`enable_compile_cache` once, before its first compile.  The cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says when it is set, and otherwise
at one fixed path inside the checkout - never a temp, pid or time name, since
the directory is part of what a later run must find again.
"""

from __future__ import annotations

import os

import jax

#: the fixed in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache")

_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"
_counts = {"hits": 0, "misses": 0}
_listening = False


def _count(event: str, **_) -> None:
    if event == _HITS:
        _counts["hits"] += 1
    elif event == _MISSES:
        _counts["misses"] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on (every compile is cached, however
    quick) and count its hits and misses; returns the cache directory."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not _listening:
        jax.monitoring.register_event_listener(_count)
        _listening = True
    return path


def cache_counts() -> dict:
    """``{"hits": n, "misses": m}`` of persistent-cache lookups so far; the
    difference of two snapshots says whether the compiles between them
    were served from the cache."""
    return dict(_counts)
