"""The one place that turns JAX's persistent compilation cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the directory
from the environment and this module sets none; otherwise the cache
lives at ``<checkout>/.cache/jax`` (git-ignored).  The path is part of
the cache key, so it is fixed: no home directory, temp name, pid or
time.  ``chip_smoke.py``, ``bench.py``, ``benchmarks/*`` and
``tests/conftest.py`` all come through here.
"""

from __future__ import annotations

import os
from typing import Dict

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_stats: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _stats[key] += 1


def enable_compile_cache(min_compile_secs: float = 0.0) -> str:
    """Turn the persistent cache on and return its directory.  Programs
    that compiled faster than ``min_compile_secs`` are not stored."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".cache", "jax")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def compile_cache_stats() -> Dict[str, int]:
    """Persistent-cache hits and misses of this process since
    :func:`enable_compile_cache`."""
    return dict(_stats)
