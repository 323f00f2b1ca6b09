"""Test harness: force JAX onto CPU with 8 virtual devices.

Mirrors the reference's "no chain needed" test philosophy (SURVEY.md §4):
the reference tests LASER with hand-built fixtures and mocked RPC; we test
the TPU framework on a virtual 8-device CPU mesh so CI needs no TPU.
``tests/test_sharding.py`` shards the symbolic engine's lane axis over
this mesh and asserts bit-equivalence with the unsharded run; the other
suites run single-device.
"""

import gc
import os
import sys
import threading

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mythril_tpu  # noqa: E402,F401  (enables x64)

import jax  # noqa: E402
import pytest  # noqa: E402

# Persistent compilation cache: ``sym_run`` compiles for 40-70 s at the
# tests' shapes and every worker meets most of them, so the executables
# are kept across workers and runs. All xdist workers share ONE
# directory (a replacement for a crashed worker starts warm too), which
# takes three things JAX (0.9.0) does not do by itself:
#
# - an entry appears whole or not at all (``_atomic_put``): JAX writes
#   in place, and a reader that meets a half-written entry, or one a
#   killed run left behind, aborts inside the deserialiser;
# - EVERY test process keeps and loads executables WITH their host
#   callbacks (``benchmark/hostcb_cache.py``, which some tests under
#   ``tests/benchmark`` install anyway): JAX persists no program that
#   holds one, so ``sym_run`` was compiled anew in every process, and a
#   worker without the shim that loaded what a worker with it had
#   written segfaulted at the first precompile call;
# - the children the tests start (engine workers, the CLI, daemons) run
#   the product as it is, without the shim, so they get a directory of
#   their own through the environment, where nothing with a callback is
#   ever written.
#
# Set MYTHRIL_NO_JAX_CACHE=1 to run without any of it.
if os.environ.get("MYTHRIL_NO_JAX_CACHE") != "1":
    from mythril_tpu import compile_cache

    # the xdist controller imports this file first and its workers
    # inherit the environment: the root is fixed there, once
    _ROOT_ENV = "MYTHRIL_TEST_CACHE_ROOT"
    _CACHE_ROOT = os.environ.setdefault(_ROOT_ENV, compile_cache.cache_dir())
    os.environ[compile_cache.ENV] = os.path.join(_CACHE_ROOT, "plain")
    compile_cache.enable()
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(_CACHE_ROOT, "hostcb"))

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    try:
        import hostcb_cache
    finally:
        sys.path.pop(0)
    hostcb_cache.install()

    from jax._src import lru_cache as _lru

    _put = _lru.LRUCache.put

    def _atomic_put(self, key, val):
        if self.eviction_enabled:       # not the tests' configuration
            return _put(self, key, val)
        path = self.path / (key + _lru._CACHE_SUFFIX)
        if path.exists():
            return
        tmp = self.path / (".%s.%d-%d.tmp" % (key, os.getpid(),
                                               threading.get_ident()))
        tmp.write_bytes(val)
        os.replace(tmp, path)

    _lru.LRUCache.put = _atomic_put


# A CPU executable of ``sym_run``'s size holds ~4,700 memory mappings
# (one JIT section each) for as long as it lives, and a process may hold
# ``vm.max_map_count`` of them (65,530): the fourteenth such program a
# worker keeps makes the next mmap fail, and whatever asked for memory
# dies inside XLA or LLVM (compile, serialize, deserialize, "Cannot
# allocate memory"; docs/xla-cpu-segfault.md). JAX keeps every program
# it has run. So before a test starts, drop them where the process is
# on its way there: early where a new file starts, which seldom runs
# its predecessor's programs, and late inside a file, whose tests share
# theirs. A dropped program that is needed again is traced anew and
# loaded from the cache above; nothing is dropped while a test runs.
def _mappings() -> int:
    try:
        with open("/proc/self/maps", "rb") as fh:
            return sum(1 for _ in fh)
    except OSError:         # no procfs: nothing to count, nothing to do
        return 0


def _max_mappings() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as fh:
            return int(fh.read())
    except (OSError, ValueError):
        return 65530


_MAX_MAPPINGS = _max_mappings()
_last_file = None


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    global _last_file
    path = item.nodeid.split("::", 1)[0]
    new_file, _last_file = path != _last_file, path
    if _mappings() > _MAX_MAPPINGS * (0.3 if new_file else 0.6):
        jax.clear_caches()
        gc.collect()
