"""Loaded at start-up by every Python process the ``serve`` driver
starts (``PYTHONPATH`` puts this directory first): the daemon, which
never touches JAX's backend, and its engine worker, which holds the chip.

- install ``benchmark/hostcb_cache.py`` so that the worker's
  ``sym_run`` is kept by the persistent compile cache.
- ``BENCH_PROFILE_DIR=<dir>``: in the process that has loaded the
  engine, record a ``jax.profiler`` trace into ``<dir>`` from the moment
  ``<dir>.start`` exists until ``<dir>.stop`` does. Only the process
  that holds the chip can trace it, and the harness is outside it.
"""

import os
import sys
import threading
import time


def _install_cache() -> None:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, here)
    try:
        import hostcb_cache

        hostcb_cache.install()
    finally:
        sys.path.remove(here)


def _profile_on_request(out: str) -> None:
    def loop() -> None:
        while not os.path.exists(out + ".start"):
            if os.path.exists(out + ".stop"):
                return
            time.sleep(0.05)
        if "mythril_tpu.symbolic.engine" not in sys.modules:
            return          # a supervisor: it has no device to trace
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        with jax.profiler.TraceAnnotation(
                "bench_clock_sync", mono_ns=int(time.monotonic() * 1e9)):
            pass
        while not os.path.exists(out + ".stop"):
            time.sleep(0.05)
        jax.profiler.stop_trace()
        with open(out + ".done", "w") as fh:
            fh.write(str(os.getpid()))

    threading.Thread(target=loop, name="bench-profile", daemon=True).start()


_install_cache()
if os.environ.get("BENCH_PROFILE_DIR"):
    _profile_on_request(os.environ["BENCH_PROFILE_DIR"])
