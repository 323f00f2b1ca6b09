"""Let JAX's persistent compilation cache keep a TPU executable that
holds host callbacks.

JAX 0.9.0 refuses to *write* such an executable (``compiler._cache_write``)
and reads one back without its callbacks
(``compilation_cache.get_executable_and_time``). On a TPU neither is
forced by the runtime: ``pure_callback`` lowers to host send/recv on
channels numbered in program order, nothing of the process is baked
into the HLO (so the cache key is the same in every process), and
``Client.deserialize_executable`` takes the callbacks to attach. The
program under test compiles ``sym_run`` for ~450 s in every process for
this reason alone (PERF.md); the benchmark's contract has only the first
run in a checkout compile. :func:`install` closes the gap from outside
the program: the callbacks still run, on the host, at every call. So
the set-up of a non-first run is not the product's, whose every process
pays the cold one (PERF.md section 2); both drivers install this always.

On a CPU the callback is a pointer inside the HLO and JAX hands the
compiler no callback objects, so nothing here changes what it does.
"""

from __future__ import annotations

import threading

_tls = threading.local()
_installed = False


def install() -> None:
    """Patch this process's JAX (idempotent). Call before the first
    compile."""
    global _installed
    if _installed:
        return
    _installed = True
    from jax._src import compilation_cache as cc
    from jax._src import compiler

    compile_or_get_cached = compiler.compile_or_get_cached
    cache_write = compiler._cache_write

    def _compile_or_get_cached(backend, computation, devices,
                               compile_options, host_callbacks,
                               *args, **kw):
        _tls.host_callbacks = list(host_callbacks)
        try:
            return compile_or_get_cached(backend, computation, devices,
                                         compile_options, host_callbacks,
                                         *args, **kw)
        finally:
            _tls.host_callbacks = None

    def _get_executable_and_time(cache_key, compile_options, backend,
                                 executable_devices):
        cache = cc._get_cache(backend)
        blob = cache.get(cache_key) if cache is not None else None
        if blob is None:
            return None, None
        serialized, compile_time = cc.extract_executable_and_time(
            cc.decompress_executable(blob))
        callbacks = getattr(_tls, "host_callbacks", None)
        if callbacks:
            exe = backend.deserialize_executable(
                serialized, executable_devices, compile_options, callbacks)
        else:
            exe = backend.deserialize_executable(
                serialized, executable_devices, compile_options)
        return exe, compile_time

    def _cache_write(cache_key, compile_time_secs, module_name, backend,
                     executable, host_callbacks):
        return cache_write(cache_key, compile_time_secs, module_name,
                           backend, executable, ())

    compiler.compile_or_get_cached = _compile_or_get_cached
    compiler._cache_write = _cache_write
    cc.get_executable_and_time = _get_executable_and_time
