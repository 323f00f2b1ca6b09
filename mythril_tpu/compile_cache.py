"""One persistent XLA compile cache, placed from outside.

Every process that owns an engine calls :func:`enable` before its first
compile: in-process ``analyze``, the engine worker, the cache-probe
child and the tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
reads it itself and nothing here sets a directory; where it
is not, the cache is ``<checkout>/.jax_cache`` — a fixed path, because
the path is part of the cache key and a directory that moves never
hits. A supervisor never calls this: it has no engine to compile.

JAX (0.9.0) does not persist an executable that holds a host callback,
so ``sym_run`` with the precompile callbacks compiles once per process
whatever this module does; every other program is served from here.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

ENV = "JAX_COMPILATION_CACHE_DIR"
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_lock = threading.Lock()
_enabled = False
#: backend compiles this process has paid for, and what the persistent
#: cache saved it (jax.monitoring events; see :func:`stats`)
_stats = {"xla_compiles": 0, "xla_compile_sec": 0.0, "cache_hits": 0}


def cache_dir() -> str:
    """Where the persistent cache lives: ``$JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or os.path.join(_ROOT, ".jax_cache")


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        with _lock:
            _stats["xla_compiles"] += 1
            _stats["xla_compile_sec"] += float(secs)


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _stats["cache_hits"] += 1


def enable() -> str:
    """Turn the persistent cache on for this process (first call wins)
    and start counting its compiles. Returns the directory in force."""
    global _enabled
    with _lock:
        first, _enabled = not _enabled, True
    if first:
        import jax

        if not os.environ.get(ENV):
            jax.config.update("jax_compilation_cache_dir", cache_dir())
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    return cache_dir()


def stats() -> Dict:
    """``xla_compiles`` counts backend compile requests, cache hits
    included; ``xla_compiles - cache_hits`` were paid in full."""
    with _lock:
        out = dict(_stats)
    out["xla_compile_sec"] = round(out["xla_compile_sec"], 3)
    return out
