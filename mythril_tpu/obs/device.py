"""Where the host meets the device: every read of a device array on the
host goes through :func:`fetch`, and the campaign's phase spans say how
much of their wall clock was such reads and how much was CPU.

A read (``np.asarray`` / ``jax.device_get`` of a device array) blocks
until the array is there. A plain copy of a leaf that already exists
is quick even while the chip runs a program; an expression that first
dispatches a small kernel (``leaf[lane]``) queues behind whatever runs
there, and on a busy chip is a wait for the device, not a copy
(PERF.md §5). So the host phase copies a leaf whole, once a frontier,
and indexes the copy (:class:`HostLeaves`), and dispatches nothing.
``fetch`` does what those calls did, no caching and no extra sync, and
always times the blocking call (clock reads only, like
``trace.timer``):

- ``device_fetches_total`` / ``device_fetch_seconds_total`` in
  ``REGISTRY`` count every read of the process, and
  ``device_kernel_reads_total`` those handed over as a callable (the
  ``kernel:`` form: 0 in a host phase);
- a per-thread tally (:func:`tally`) lets a span difference the reads
  made on its own thread: :func:`phase_timer` spans (``device_phase``,
  ``host_phase``) gain ``device_fetches``, ``device_wait_s`` and
  ``cpu_s`` (``time.thread_time()`` outside the reads) at their end,
  so that ``dur = cpu_s + device_wait_s + rest`` and ``rest`` is
  waiting for a lock, a pool or another thread; and ``proc_cpu_s``
  (``time.process_time()`` differenced: every thread of the process),
  which says what the rest of the process did meanwhile:
  ``proc_cpu_s / dur`` near 1 while two threads had work is one lock
  letting one of them run at a time, near 2 they ran side by side
  (the runtime's own threads count too: read it beside the two
  threads' ``cpu_s``, which add up to the wall clock under one lock);
- only when ``trace.active()``, each read is a ``device_fetch`` span
  with ``what`` (the leaf's name), ``bytes`` and ``mono`` at its start.

What ``device_wait_s`` really is: the blocking read *and* the wait to
get the interpreter lock back once the array is there. Beside a busy
thread it over-reads by that wait (PERF.md §5).

What the tally misses: work the phase hands to other threads (the
module pool of ``--solver-workers`` > 1, the watchdog thread of
``--batch-timeout``) lands on those threads' tallies and CPU clocks, so
the phase's own ``device_fetches`` / ``cpu_s`` read low and ``rest``
grows by it; the registry's totals still hold every read.

No top-level jax (or numpy) import: supervisors load ``obs`` without a
backend (tests/test_light_imports.py).
"""

from __future__ import annotations

import threading
import time
from operator import attrgetter
from typing import Tuple

from . import metrics as obs_metrics
from . import trace as obs_trace

_TALLY = threading.local()


def tally() -> Tuple[int, float, float]:
    """``(reads, seconds blocked in them, CPU seconds burnt inside
    them)`` of the calling thread since it started."""
    return (getattr(_TALLY, "n", 0), getattr(_TALLY, "seconds", 0.0),
            getattr(_TALLY, "cpu", 0.0))


def fetch(x, what: str):
    """Read device array ``x`` on the host: ``np.asarray(x)`` for one
    array, ``jax.device_get(x)`` for a tuple or list of them. ``x`` may
    be a callable that returns either: an expression that dispatches a
    small kernel and reads its result (``lambda: leaf[lane]``) is then
    timed whole, and ``what`` says so (``"kernel:..."``)."""
    c0 = time.thread_time()
    t0 = time.monotonic()
    reg = obs_metrics.REGISTRY
    if callable(x):
        reg.counter("device_kernel_reads_total",
                    help="reads that first dispatch a program on the "
                         "device (fetch handed a callable)").inc()
        x = x()
    if isinstance(x, (tuple, list)):
        import jax

        out = jax.device_get(x)
        nbytes = sum(getattr(a, "nbytes", 0) for a in out)
    else:
        import numpy as np

        out = np.asarray(x)
        nbytes = out.nbytes
    dt = time.monotonic() - t0
    # the copy itself burns CPU inside the wait: kept apart (the CPU
    # clock is read outside the wall clock's interval), so that a
    # phase's ``cpu_s`` and ``device_wait_s`` never count a second twice
    cpu = time.thread_time() - c0
    n, seconds, cpu0 = tally()
    _TALLY.n, _TALLY.seconds, _TALLY.cpu = n + 1, seconds + dt, cpu0 + cpu
    reg.counter("device_fetches_total",
                help="host reads of device arrays (obs.device."
                     "fetch)").inc()
    reg.counter("device_fetch_seconds_total",
                help="seconds the host stood blocked in those "
                     "reads").inc(dt)
    if obs_trace.active():
        obs_trace.complete("device_fetch", dt, mono=t0, what=what,
                           bytes=int(nbytes))
    return out


class HostLeaves:
    """The leaves of one finished frontier that are on the host: the
    first request for a name copies the whole leaf (``fetch(leaf,
    name)``), every later one gets the same NumPy array, and callers
    index that. Names are attribute paths from the frontier
    (``"st_val_sym"``, ``"base.active"``). The arrays are what
    ``np.asarray`` of a device array gives (read-only): a caller that
    mutates copies first. ``host_leaf_reads_total{result}`` counts
    copies and hits. Threads that race for one leaf copy it twice and
    keep either; the memo is one dict assignment."""

    __slots__ = ("sf", "_memo")

    def __init__(self, sf):
        self.sf = sf
        self._memo: dict = {}

    def __call__(self, name: str):
        out = self._memo.get(name)
        hit = out is not None
        if not hit:
            out = self._memo[name] = fetch(attrgetter(name)(self.sf), name)
        obs_metrics.REGISTRY.counter(
            "host_leaf_reads_total",
            help="requests for a frontier leaf on the host: copied "
                 "from the device, or served from the context's copy",
            labels={"result": "hit" if hit else "copy"}).inc()
        return out


class PhaseSpan(obs_trace.Span):
    """An always-measuring span (``trace.timer``) that at its end also
    carries what its own thread did meanwhile: ``device_fetches``,
    ``device_wait_s`` (the tally's differences) and ``cpu_s`` (the
    thread's CPU seconds outside the reads), so that
    ``cpu_s + device_wait_s <= dur``; and ``proc_cpu_s``, the CPU
    seconds of the whole process over the span."""

    __slots__ = ("_n0", "_w0", "_c0", "_p0")

    def __enter__(self) -> "PhaseSpan":
        super().__enter__()
        self._n0, self._w0, in_reads = tally()
        self._c0 = time.thread_time() - in_reads
        self._p0 = time.process_time()
        return self

    start = __enter__

    def __exit__(self, *exc) -> bool:
        n1, w1, in_reads = tally()
        proc = time.process_time() - self._p0
        cpu = time.thread_time() - in_reads - self._c0
        wait = round(w1 - self._w0, 6)
        # both clocks were read inside the span, so the sum cannot pass
        # its duration; rounding to the microsecond must not either
        so_far = round(self.elapsed, 6)
        cpu = round(max(0.0, min(cpu, so_far - wait)), 6)
        if cpu + wait > so_far:
            cpu = max(0.0, round(cpu - 1e-6, 6))
        self.attrs.update(device_fetches=n1 - self._n0,
                          device_wait_s=wait, cpu_s=cpu,
                          proc_cpu_s=round(proc, 6))
        return super().__exit__(*exc)


def phase_timer(name: str, **attrs) -> PhaseSpan:
    """``trace.timer`` for a campaign phase: start and stop it on the
    thread that does the phase's work."""
    return PhaseSpan(obs_trace.get_tracer(), name, attrs)


__all__ = ["HostLeaves", "PhaseSpan", "fetch", "phase_timer", "tally"]
