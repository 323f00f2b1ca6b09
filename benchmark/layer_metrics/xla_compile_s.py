"""Seconds inside XLA's compile-or-load requests at the end of set-up:
the engine report's ``xla_compile_sec`` (compile_cache.py counts
``backend_compile_duration``, cache loads included). Layer: compile
cache. Moves ``setup_s``."""


def read(obs: dict):
    eng = obs.get("engine_setup") or {}
    val = eng.get("xla_compile_sec")
    return None if val is None else float(val)
