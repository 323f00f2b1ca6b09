"""Test harness: force JAX onto CPU with 8 virtual devices.

Mirrors the reference's "no chain needed" test philosophy (SURVEY.md §4):
the reference tests LASER with hand-built fixtures and mocked RPC; we test
the TPU framework on a virtual 8-device CPU mesh so CI needs no TPU.
``tests/test_sharding.py`` shards the symbolic engine's lane axis over
this mesh and asserts bit-equivalence with the unsharded run; the other
suites run single-device.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mythril_tpu  # noqa: E402,F401  (enables x64)

import jax  # noqa: E402

# Persistent compilation cache: the superstep graph is large and this box has
# one core — cache compiled executables across test runs. A crashed writer
# can leave a corrupt entry that segfaults later readers; wipe .jax_cache
# or set MYTHRIL_NO_JAX_CACHE=1 if the suite dies inside jax compile/cache
# frames.
if os.environ.get("MYTHRIL_NO_JAX_CACHE") != "1":
    # per-xdist-worker cache dir: concurrent workers must not race writes
    # into one cache (worker ids are stable, so reuse across runs holds)
    from mythril_tpu import compile_cache

    _CACHE_DIR = compile_cache.enable(
        sub=os.environ.get("PYTEST_XDIST_WORKER", "gw0"))
    # engine-worker SUBPROCESSES (mythril_tpu/engine_worker.py) inherit
    # the environment, not jax.config: exporting the directory in force
    # makes them share this worker's cache instead of compiling cold
    os.environ.setdefault(compile_cache.ENV, _CACHE_DIR)
