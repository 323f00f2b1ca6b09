"""Fault-isolated execution: watchdogs, fault injection, backend management.

The pod-scale north star (10k contracts in minutes, ROADMAP) is only as
strong as its weakest failure mode, and this repo has hit every one of
them on real hardware:

- a wedged TPU runtime hangs ``jax.devices()`` forever;
- a hung XLA compile can exceed any outer budget;
- one pathological contract can stall or crash a whole campaign batch,
  and ``CorpusCampaign.run`` only checked its deadline *between*
  batches.

This module is the shared answer (DTVM's fault-contained-execution
property, PAPERS.md; EVMx assumes a host-side supervisor that survives
device faults):

- :func:`run_with_watchdog` — run a callable under a hard wall-clock
  deadline in a worker thread; expiry raises :class:`BatchTimeout`
  instead of stalling the supervisor (the stuck thread is abandoned,
  as an unkillable D-state probe child is).
- :class:`FaultInjector` — deterministic, env/constructor-driven fault
  injection (hang / raise / device-lost / kill at a batch index or on a
  contract name) so every recovery path is testable on CPU.
- :class:`BackendManager` — subprocess-isolated backend probe with a
  timeout, bounded re-init attempts with backoff, and an explicit CPU
  fallback, all recorded as structured events for the campaign report.

IMPORTANT: nothing in this module may touch a JAX backend at import or
probe time — the whole point is to stay alive when the backend is the
thing that is wedged. The probe runs ``jax.devices()`` in a *child*
process only.
"""

from __future__ import annotations

import collections
import os
import select
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import compile_cache
from .backend import (TIER_ORDER, TIER_RUNG, TIER_RUNG_ALIAS,
                      default_oom_ladder, profile as tier_profile,
                      pinned_tier, probe_tier, terminal_tier,
                      tier_of_platform, tiers_below)


class ResilienceError(RuntimeError):
    """Base for supervisor-level failures."""


class BatchTimeout(ResilienceError):
    """A watchdogged unit of work exceeded its wall-clock budget."""


class DeviceLostError(ResilienceError):
    """The accelerator went away mid-run (injected or detected)."""


class ResourceExhausted(ResilienceError):
    """Device/host memory exhaustion (injected or classified from a
    backend error). The campaign answers this with the degradation
    ladder — shrink the work, don't abort the run."""


class WorkerDied(ResilienceError):
    """The supervised engine worker subprocess died (segfault, OOM
    kill, torn IPC reply, init failure). The batch it was running is
    NOT lost — the supervisor restarts the worker and the campaign's
    retry→ladder→bisect machinery replays the batch."""


class WorkerError(ResilienceError):
    """An exception raised INSIDE the engine worker, rehydrated on the
    parent side. The message carries the original type name + text so
    :func:`classify_backend_error`'s string triage still applies."""


class WorkerCrashLoop(ResilienceError):
    """The crash-loop circuit breaker is open: N worker deaths within
    the window. The supervisor refuses to spawn until the cooldown
    lapses; the campaign answers by pinning the batch to the in-process
    CPU path (the trusted fallback the accelerator crash loop cannot
    reach)."""


class InjectedKill(BaseException):
    """Simulates SIGKILL mid-batch for kill/resume testing.

    Deliberately a ``BaseException``: the campaign's retry/bisect
    machinery catches ``Exception`` — a simulated kill must blow through
    it uncheckpointed, exactly like a real SIGKILL would.
    """


# --- watchdog ---------------------------------------------------------


def run_with_watchdog(fn: Callable, timeout: Optional[float],
                      label: str = "work"):
    """Run ``fn()`` under a hard wall-clock deadline.

    ``timeout=None`` runs inline (no thread). Otherwise the work runs in
    a daemon thread; if it has not finished after ``timeout`` seconds a
    :class:`BatchTimeout` is raised and the thread is ABANDONED — a hung
    XLA compile or wedged device call cannot be interrupted from Python,
    so the supervisor walks away from it (the abandoned thread dies with
    the process; an injected hang just sleeps). Exceptions from ``fn``
    (including ``BaseException``s like :class:`InjectedKill`) re-raise
    in the caller.
    """
    if timeout is None:
        return fn()
    box: Dict[str, object] = {}
    done = threading.Event()

    def work():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=work, daemon=True,
                         name=f"watchdog:{label}")
    t.start()
    if not done.wait(timeout):
        raise BatchTimeout(
            f"{label} exceeded {timeout:.1f}s wall-clock budget")
    if "error" in box:
        raise box["error"]  # type: ignore[misc]
    return box.get("value")


# --- backend-error classification -------------------------------------

# message fragments (lowercased) that identify device/host memory
# exhaustion in XLA/JAX runtime errors across backends: TPU and GPU
# allocators raise XlaRuntimeError with a RESOURCE_EXHAUSTED status,
# CPU-side failures surface as MemoryError or "out of memory" strings
_OOM_MARKERS = ("resource_exhausted", "resource exhausted",
                "out of memory", "oom ", "allocation failure",
                "failed to allocate")
_DEVICE_LOST_MARKERS = ("device_lost", "device lost", "data_loss",
                        "failed_precondition: device",
                        "unavailable: device", "device or resource busy",
                        "device not found")
_COMPILE_MARKERS = ("compilation failure", "compile failed",
                    "xla compilation", "error during compilation",
                    "unimplemented:", "mlir")


def classify_backend_error(e: BaseException) -> Optional[str]:
    """Best-effort triage of a batch failure into the recovery path
    that can actually cure it: ``"oom"`` (degradation ladder),
    ``"device-lost"`` (backend re-probe), ``"compile"`` (no point
    retrying the identical shape — bisect immediately), or ``None``
    (unclassified: the generic retry → bisect path).

    Matches by type first (:class:`ResourceExhausted`,
    :class:`DeviceLostError`, ``MemoryError``), then by message
    fragments of ``XlaRuntimeError``-family exceptions — jaxlib does not
    export stable subclasses per status code, so the status string in
    the message is the only portable discriminator."""
    if isinstance(e, ResourceExhausted) or isinstance(e, MemoryError):
        return "oom"
    if isinstance(e, DeviceLostError):
        return "device-lost"
    text = f"{type(e).__name__}: {e}".lower()
    if any(m in text for m in _OOM_MARKERS):
        return "oom"
    if any(m in text for m in _DEVICE_LOST_MARKERS):
        return "device-lost"
    if any(m in text for m in _COMPILE_MARKERS):
        return "compile"
    return None


# --- degradation ladder ----------------------------------------------

#: the rungs a campaign batch walks on RESOURCE_EXHAUSTED, in order and
#: cumulatively: halve the per-contract frontier lanes (displaced forks
#: park and spill through the engine's defer/rebalance machinery), then
#: additionally halve the batch width (two half-width sub-batches), then
#: additionally demote execution to the next available backend tier
#: (host RAM >> HBM on the floor). The ladder shape is owned by the
#: BackendProfile registry; the terminal rung keeps its historical name
#: ``"cpu"`` but is resolved against the tier ladder at walk time.
DEGRADE_RUNGS = default_oom_ladder()


def parse_ladder(text: Optional[str]) -> Tuple[str, ...]:
    """``--oom-ladder`` parser: comma-separated rung names in walk
    order; ``"none"`` (or empty) disables degradation entirely. The
    terminal rung accepts both its historical spelling (``cpu``) and
    ``next-tier``; both mean "demote to the next available tier"."""
    if text is None:
        return DEGRADE_RUNGS
    rungs = tuple(TIER_RUNG if r.strip() == TIER_RUNG_ALIAS else r.strip()
                  for r in text.split(",") if r.strip())
    if rungs in ((), ("none",)):
        return ()
    for r in rungs:
        if r not in DEGRADE_RUNGS:
            raise ValueError(
                f"oom ladder rung {r!r}: must be of {DEGRADE_RUNGS}")
    return rungs


# --- fault injection --------------------------------------------------

FAULT_MODES = ("hang", "raise", "device-lost", "kill", "oom",
               "worker-kill", "worker-segv", "flap")

#: fault modes handled by the WorkerSupervisor (a signal is delivered
#: to the engine worker SUBPROCESS) rather than raised in-process by
#: :meth:`FaultInjector.fire`
_WORKER_FAULT_SIGNALS = {"worker-kill": signal.SIGKILL,
                         "worker-segv": signal.SIGSEGV}

#: how long an injected hang sleeps per check; the watchdog is expected
#: to fire long before the total (a daemon thread naps harmlessly after)
_HANG_TOTAL_S = 3600.0


@dataclass
class FaultSpec:
    """One trigger: ``mode`` fires when the batch index and/or contract
    name matches, at most ``times`` times (None = every time — a
    persistent poison; ``times=1`` models a transient fault the
    retry-once policy cures). ``nth=N`` instead fires on the Nth
    matching attempt seen by THIS process (1-based) — worker-LOCAL
    ordering, for fleet tests where global batch indices are claimed
    nondeterministically across racing workers (docs/fleet.md).

    ``flap`` models an oscillating backend: odd matching attempts lose
    the device (:class:`DeviceLostError`, which demotes the campaign's
    backend tier), even attempts pass — so demote, repromote, demote
    alternate deterministically until flap damping holds the tier
    (docs/resilience.md "Backend tiers"). ``times`` bounds the number
    of down-phases; only down-phases count as fires."""

    mode: str
    batch: Optional[int] = None
    contract: Optional[str] = None
    times: Optional[int] = None
    nth: Optional[int] = None
    fired: int = 0
    calls: int = 0
    flap_calls: int = 0

    def matches(self, batch: Optional[int],
                contracts: Sequence[str]) -> bool:
        if self.times is not None and self.fired >= self.times:
            return False
        if self.batch is not None and batch != self.batch:
            return False
        if self.contract is not None and self.contract not in contracts:
            return False
        if self.nth is not None:
            self.calls += 1
            if self.calls != self.nth:
                return False
        return True

    @classmethod
    def parse(cls, text: str) -> "FaultSpec":
        """``mode[:key=value]*`` — e.g. ``raise:contract=c002``,
        ``hang:batch=1``, ``raise:batch=0:times=1``, ``kill:batch=2``,
        ``kill:nth=2`` (this worker's 2nd attempt, wherever it lands)."""
        parts = [p for p in text.strip().split(":") if p]
        if not parts or parts[0] not in FAULT_MODES:
            raise ValueError(
                f"fault spec {text!r}: mode must be one of {FAULT_MODES}")
        spec = cls(mode=parts[0])
        for kv in parts[1:]:
            if "=" not in kv:
                raise ValueError(f"fault spec {text!r}: expected key=value, "
                                 f"got {kv!r}")
            k, v = kv.split("=", 1)
            if k == "batch":
                spec.batch = int(v)
            elif k == "contract":
                spec.contract = v
            elif k == "times":
                spec.times = int(v)
            elif k == "nth":
                spec.nth = int(v)
                if spec.nth < 1:
                    raise ValueError(
                        f"fault spec {text!r}: nth is 1-based")
            else:
                raise ValueError(f"fault spec {text!r}: unknown key {k!r}")
        if spec.batch is None and spec.contract is None \
                and spec.nth is None and spec.mode != "flap":
            # flap is exempt: its down/up alternation IS its bound —
            # every even attempt passes, so it cannot poison a batch
            raise ValueError(
                f"fault spec {text!r}: need batch=, contract= and/or "
                "nth= (an unconditional fault would poison every batch)")
        return spec


class FaultInjector:
    """Deterministic fault source, checked at the top of every guarded
    batch attempt. Specs parse from a ``;``-separated string — the
    ``MYTHRIL_FAULT_INJECT`` env var or ``--fault-inject`` — or are
    built directly. The log of fires is kept for test assertions."""

    def __init__(self, specs: Sequence[FaultSpec] = ()):
        self.specs = list(specs)
        self.log: List[Dict] = []

    @classmethod
    def from_string(cls, text: Optional[str]) -> Optional["FaultInjector"]:
        if not text:
            return None
        return cls([FaultSpec.parse(p)
                    for p in text.split(";") if p.strip()])

    @classmethod
    def from_env(cls) -> Optional["FaultInjector"]:
        return cls.from_string(os.environ.get("MYTHRIL_FAULT_INJECT"))

    def fire(self, batch: Optional[int] = None,
             contracts: Sequence[str] = ()) -> None:
        """Raise/hang per the first matching spec (called INSIDE the
        watchdog, so a hang surfaces as :class:`BatchTimeout`).
        ``worker-*`` specs are skipped — they are the supervisor's to
        deliver (:meth:`worker_signal`), not in-process raises."""
        for spec in self.specs:
            if spec.mode in _WORKER_FAULT_SIGNALS:
                continue
            if not spec.matches(batch, contracts):
                continue
            if spec.mode == "flap":
                # oscillation: odd matching attempts are the down-phase
                # (device lost), even attempts the up-phase (clean pass
                # — and other specs still get their look)
                spec.flap_calls += 1
                if spec.flap_calls % 2 == 0:
                    continue
                spec.fired += 1
                self.log.append({"mode": "flap", "batch": batch,
                                 "contracts": list(contracts)})
                raise DeviceLostError(
                    f"injected flapping backend: device lost "
                    f"(batch={batch}, down-phase {spec.fired})")
            spec.fired += 1
            self.log.append({"mode": spec.mode, "batch": batch,
                             "contracts": list(contracts)})
            if spec.mode == "hang":
                t0 = time.monotonic()
                while time.monotonic() - t0 < _HANG_TOTAL_S:
                    time.sleep(0.05)
                return
            if spec.mode == "raise":
                raise ResilienceError(
                    f"injected fault (batch={batch}, "
                    f"contracts={list(contracts)})")
            if spec.mode == "device-lost":
                raise DeviceLostError(
                    f"injected device loss (batch={batch})")
            if spec.mode == "kill":
                raise InjectedKill(
                    f"injected kill (batch={batch})")
            if spec.mode == "oom":
                # message mirrors a real XLA allocator failure so the
                # classifier exercises the same string path it would on
                # hardware; ``times=N`` models pressure that clears
                # after N ladder steps shrink the working set
                raise ResourceExhausted(
                    f"injected RESOURCE_EXHAUSTED: out of memory "
                    f"(batch={batch})")

    def worker_signal(self, batch: Optional[int] = None,
                      contracts: Sequence[str] = ()) -> Optional[int]:
        """Signal number of the first matching ``worker-kill`` /
        ``worker-segv`` spec (the supervisor delivers it to the engine
        worker subprocess right before dispatching the batch, so the
        batch attempt observes an externally-killed worker), or None.
        ``worker-kill:nth=K`` counts THIS process's worker-batch
        dispatches — K specs with nth=1..K model a crash loop. EVERY
        worker spec sees every dispatch (no early return), so stacked
        nth counters stay aligned."""
        hit: Optional[int] = None
        for spec in self.specs:
            sig = _WORKER_FAULT_SIGNALS.get(spec.mode)
            if sig is None:
                continue
            if not spec.matches(batch, contracts):
                continue
            if hit is None:
                spec.fired += 1
                self.log.append({"mode": spec.mode, "batch": batch,
                                 "contracts": list(contracts)})
                hit = sig
        return hit


# --- backend management ----------------------------------------------


class BackendManager:
    """Probe/recover the JAX backend without ever letting a wedge reach
    this process: the probe child runs ``jax.devices()`` and is
    abandoned (not waited on) if it hangs — a child wedged in an
    uninterruptible driver call survives SIGKILL (round-3/5 evidence).

    ``probe_fn`` swaps the subprocess probe for a callable
    ``(timeout_s) -> (ok, diag)`` in tests. Every attempt, backoff, and
    fallback lands in ``events`` (list of dicts) so campaign reports
    and bench records carry the full backend story.
    """

    def __init__(self, init_timeout: float = 75.0, max_attempts: int = 2,
                 backoff: float = 5.0,
                 probe_fn: Optional[Callable[[float], Tuple[bool, str]]] = None):
        self.init_timeout = init_timeout
        self.max_attempts = max(1, int(max_attempts))
        self.backoff = backoff
        self.probe_fn = probe_fn
        self.events: List[Dict] = []

    def _event(self, kind: str, detail: str = "", attempt: int = 0) -> None:
        self.events.append({"kind": kind, "detail": detail[:300],
                            "attempt": attempt,
                            "t": round(time.time(), 3)})

    def _subprocess_probe(self, timeout_s: float) -> Tuple[bool, str]:
        """One isolated backend init. Returns (ok, diagnosis)."""
        import tempfile

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with tempfile.TemporaryFile(mode="w+") as out:
            p = subprocess.Popen(
                [sys.executable, "-c",
                 "import sys; sys.path.insert(0, %r); " % root
                 + "import mythril_tpu, jax; d = jax.devices(); "
                   "print('OK', jax.default_backend(), len(d))"],
                stdout=out, stderr=subprocess.STDOUT,
            )
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if p.poll() is not None:
                    break
                time.sleep(0.2)
            if p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    pass  # unkillable (D-state): abandon it
                return False, f"backend init hung >{timeout_s:.0f}s"
            out.seek(0)
            text = out.read()
            if p.returncode == 0 and "OK" in text:
                return True, text.strip().splitlines()[-1]
            return False, "backend init failed (rc=%s): %s" % (
                p.returncode, text.strip()[-300:])

    def probe(self) -> Tuple[bool, str]:
        """Bounded re-init attempts with backoff between them."""
        probe = self.probe_fn or self._subprocess_probe
        diag = "no probe attempt made"
        for attempt in range(1, self.max_attempts + 1):
            ok, diag = probe(self.init_timeout)
            self._event("probe_ok" if ok else "probe_fail", diag, attempt)
            if ok:
                return True, diag
            if attempt < self.max_attempts and self.backoff > 0:
                # linear backoff: a wedged runtime sometimes clears after
                # the stuck client's grpc deadline lapses
                time.sleep(self.backoff * attempt)
        return False, diag

    def ensure_or_fallback(self, tiers: Optional[Sequence[str]] = None
                           ) -> Tuple[bool, str]:
        """Probe the configured tier; on failure walk DOWN the ranked
        tier ladder (mythril_tpu.backend) probing each lower tier once,
        and pin this process — via JAX_PLATFORMS, so heavy engine
        imports must not have run yet — to the first tier that answers.
        The floor tier (host CPU) needs no probe and is where the walk
        always terminates; landing there records the historical
        ``cpu_fallback`` event kind, landing on an intermediate tier
        records ``tier_fallback``. Returns (backend_ok, diagnosis) for
        the *configured* backend."""
        ok, diag = self.probe()
        if ok:
            return True, diag
        configured = self._configured_tier()
        landed = None
        for tier in tiers_below(configured, tiers):
            if tier == terminal_tier():
                break  # the floor is trusted, not probed
            if self.probe_fn is not None:
                tok, tdiag = self.probe_fn(tier_profile(tier).probe_timeout)
            else:
                tok, tdiag = probe_tier(tier)
            self._event("probe_ok" if tok else "probe_fail",
                        f"tier {tier}: {tdiag}")
            if tok:
                landed = tier
                break
        if landed is None:
            landed = terminal_tier()
        os.environ["JAX_PLATFORMS"] = tier_profile(landed).jax_platform
        kind = ("cpu_fallback" if landed == terminal_tier()
                else "tier_fallback")
        self._event(kind,
                    f"configured backend ({configured}) unreachable; "
                    f"demoted to the {landed} tier "
                    f"(JAX_PLATFORMS={tier_profile(landed).jax_platform})")
        return False, diag

    @staticmethod
    def _configured_tier() -> str:
        """The tier this process was asked to run on: a pinned
        JAX_PLATFORMS if it names a known tier, else the best rank
        (an unpinned process is assumed to want the best hardware)."""
        return pinned_tier() or TIER_ORDER[0]

    def recover(self, reason: str = "device-lost") -> bool:
        """After a device loss mid-campaign: record it, re-probe with the
        usual bounded attempts. Returns whether the backend answered."""
        self._event("device_lost", reason)
        ok, _ = self.probe()
        return ok


# --- supervised engine worker (docs/resilience.md) ---------------------


class WorkerSupervisor:
    """Parent-side supervisor of ONE engine-worker subprocess
    (mythril_tpu/engine_worker.py): the worker owns the JAX backend and
    runs device batches; this class owns the worker.

    The isolation contract: a libtpu segfault, an OOM kill, or a hard
    hang inside the worker surfaces HERE as :class:`WorkerDied` /
    :class:`BatchTimeout` — a recoverable event the campaign's
    retry→ladder→bisect machinery already knows how to replay — never
    as parent-process death. Three layers:

    - **per-batch deadline, enforced from the parent** — the reply is
      awaited with ``select`` on the raw pipe fd; expiry SIGKILLs the
      worker (a wedged libtpu call cannot be interrupted any other
      way) and raises :class:`BatchTimeout`;
    - **restart with capped exponential backoff** — consecutive deaths
      double the respawn delay up to ``backoff_cap``, so a dying
      backend is probed, not hammered;
    - **crash-loop circuit breaker** — ``breaker_threshold`` deaths
      within ``breaker_window`` seconds open the breaker:
      :meth:`run_batch` raises :class:`WorkerCrashLoop` (the campaign
      pins the batch to the in-process CPU path) until
      ``breaker_cooldown`` lapses, then ONE half-open attempt decides
      whether to close (success) or re-open (another death).

    Every transition lands as a ``worker_spawn`` / ``worker_death`` /
    ``worker_restart`` / ``breaker_open`` / ``breaker_close`` event
    (via ``on_event`` — the campaign routes them into
    ``backend_events`` + the trace bus) and on the metrics registry
    (``engine_worker_{spawns,deaths,restarts}_total``,
    ``engine_worker_rss_bytes``, ``engine_worker_breaker_open``).

    ``stub=True`` spawns the protocol-only worker (no engine import) —
    the fast path for supervision-machinery tests; the child process,
    pipes, signals and deaths are all real either way.
    """

    def __init__(self, config: Optional[Dict] = None, *,
                 stub: bool = False,
                 batch_timeout: Optional[float] = None,
                 spawn_timeout: float = 300.0,
                 backoff_base: float = 0.5, backoff_cap: float = 30.0,
                 breaker_threshold: int = 3,
                 breaker_window: float = 60.0,
                 breaker_cooldown: float = 30.0,
                 fault_injector: Optional[FaultInjector] = None,
                 on_event: Optional[Callable] = None,
                 worker_env: Optional[Dict[str, str]] = None,
                 expect_tier: Optional[str] = None):
        self.config = dict(config or {})
        self.stub = bool(stub)
        self.batch_timeout = batch_timeout
        self.spawn_timeout = float(spawn_timeout)
        self.backoff_base = max(0.0, float(backoff_base))
        self.backoff_cap = max(0.0, float(backoff_cap))
        self.breaker_threshold = max(1, int(breaker_threshold))
        self.breaker_window = max(0.01, float(breaker_window))
        self.breaker_cooldown = max(0.0, float(breaker_cooldown))
        self.fault_injector = fault_injector
        self.on_event = on_event
        self.worker_env = dict(worker_env or {})
        #: the tier this worker is spawned for: an engine worker whose
        #: init reply names a device of another tier is a failed spawn
        #: (None = unpinned, whatever JAX picks is what was asked for)
        self.expect_tier = expect_tier
        #: ``{platform, kind, count, id}`` from the live worker's init
        #: reply (None for a stub, or before the first spawn)
        self.device: Optional[Dict] = None
        self.proc: Optional[subprocess.Popen] = None
        self.events: List[Dict] = []
        self.restarts = 0
        self.spawns = 0
        self.rss_bytes = 0
        #: clock handshake result: ``parent_mono - child_mono``, set at
        #: init and refreshed per batch reply — added to worker-side
        #: ``mono`` readings so both processes share one timeline
        self.mono_offset: Optional[float] = None
        self._deaths: "collections.deque[float]" = collections.deque()
        self._consecutive = 0
        self._breaker_opened: Optional[float] = None
        self._lock = threading.RLock()

    # --- events / metrics ----------------------------------------------
    def _event(self, kind: str, detail: str = "", **kw) -> None:
        e = {"kind": kind, "detail": detail[:300],
             "t": round(time.time(), 3)}
        e.update(kw)
        self.events.append(e)
        if self.on_event is not None:
            self.on_event(kind, detail=detail[:300], **kw)
        else:
            from .obs import trace as obs_trace

            obs_trace.event(kind, **{k: v for k, v in e.items()
                                     if k != "kind"})

    def _counter(self, name: str, help: str = ""):
        from .obs import metrics as obs_metrics

        return obs_metrics.REGISTRY.counter(name, help=help)

    def _gauge(self, name: str, help: str = ""):
        from .obs import metrics as obs_metrics

        return obs_metrics.REGISTRY.gauge(name, help=help)

    # --- breaker --------------------------------------------------------
    def breaker_state(self) -> str:
        """``closed`` | ``open`` | ``half-open`` (cooldown lapsed; the
        next :meth:`run_batch` probes the worker once)."""
        if self._breaker_opened is None:
            return "closed"
        if time.monotonic() - self._breaker_opened < self.breaker_cooldown:
            return "open"
        return "half-open"

    def status(self) -> Dict:
        with self._lock:
            return {"alive": self.alive(),
                    "pid": self.proc.pid if self.proc else None,
                    "stub": self.stub,
                    "spawns": self.spawns,
                    "restarts": self.restarts,
                    "deaths_in_window": len(self._deaths),
                    "breaker": self.breaker_state(),
                    "rss_bytes": self.rss_bytes,
                    "device": self.device}

    def _check_breaker(self) -> None:
        state = self.breaker_state()
        if state == "open":
            raise WorkerCrashLoop(
                f"engine-worker breaker open ({len(self._deaths)} "
                f"deaths within {self.breaker_window:.0f}s); work is "
                f"pinned to the in-process CPU path for "
                f"{self.breaker_cooldown:.0f}s")
        if state == "half-open":
            self._event("breaker_half_open",
                        detail="cooldown lapsed; probing the worker "
                               "with one live batch")

    def _record_death(self, detail: str) -> None:
        now = time.monotonic()
        self._deaths.append(now)
        while self._deaths and now - self._deaths[0] > self.breaker_window:
            self._deaths.popleft()
        self._consecutive += 1
        rc = self.proc.poll() if self.proc is not None else None
        self._counter("engine_worker_deaths_total",
                      help="engine-worker subprocess deaths observed "
                           "by the supervisor").inc()
        self._event("worker_death", detail=detail, rc=rc,
                    deaths_in_window=len(self._deaths))
        self._flag_cache_dirty()
        self._reap()
        if self._breaker_opened is not None:
            # the half-open probe died: re-open for a fresh cooldown
            self._breaker_opened = now
            self._event("breaker_open",
                        detail="half-open probe died; breaker re-opened")
            self._gauge("engine_worker_breaker_open",
                        help="1 while the crash-loop breaker is open").set(1)
        elif len(self._deaths) >= self.breaker_threshold:
            self._breaker_opened = now
            self._counter("engine_worker_breaker_opens_total",
                          help="crash-loop breaker open transitions").inc()
            self._event("breaker_open",
                        detail=f"{len(self._deaths)} worker deaths "
                               f"within {self.breaker_window:.0f}s; "
                               "pinning work to the in-process CPU "
                               "path")
            self._gauge("engine_worker_breaker_open",
                        help="1 while the crash-loop breaker is open").set(1)

    def _flag_cache_dirty(self) -> None:
        """Drop the ``.dirty`` marker into the shared XLA cache dir (if
        one is configured): this worker died uncleanly, so it may have
        left a torn cache entry behind — the NEXT engine spawn probes
        the cache before trusting it (engine_worker._maybe_probe_cache)
        instead of segfaulting on a poisoned read. Best-effort: a
        missing marker just means no probe, which was the status quo."""
        cache = (self.worker_env.get(compile_cache.ENV)
                 or compile_cache.cache_dir())
        if not os.path.isdir(cache):
            return
        from .engine_worker import CACHE_DIRTY_MARKER

        try:
            with open(os.path.join(cache, CACHE_DIRTY_MARKER), "w") as fh:
                fh.write(f"pid={os.getpid()} t={time.time():.3f}\n")
        except OSError:
            pass

    def _note_success(self) -> None:
        self._consecutive = 0
        if self._breaker_opened is not None:
            self._breaker_opened = None
            self._deaths.clear()
            self._event("breaker_close",
                        detail="half-open probe succeeded; worker path "
                               "restored")
            self._gauge("engine_worker_breaker_open",
                        help="1 while the crash-loop breaker is open").set(0)

    # --- process lifecycle ---------------------------------------------
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def _exit_code(self) -> Optional[int]:
        """The worker's exit code right after an EOF: the pipe closes a
        beat before the process is waitable, so give it a moment —
        ``-11`` vs ``-9`` in the death event is real diagnostic
        signal."""
        if self.proc is None:
            return None
        try:
            return self.proc.wait(timeout=2)
        except (subprocess.TimeoutExpired, OSError):
            return self.proc.poll()

    def _reap(self) -> None:
        if self.proc is None:
            return
        try:
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait(timeout=5)
        except (OSError, subprocess.TimeoutExpired):
            pass  # unkillable (D-state): abandon, like the probe child
        for stream in (self.proc.stdin, self.proc.stdout):
            try:
                if stream is not None:
                    stream.close()
            except OSError:
                pass
        self.proc = None

    def _spawn_and_init(self) -> None:
        """Spawn + init-handshake one worker, honoring the restart
        backoff. Raises :class:`WorkerDied` when the worker cannot come
        up (counted as a death — a failing init IS the crash loop)."""
        if self._consecutive > 0:
            delay = min(self.backoff_cap,
                        self.backoff_base * (2 ** (self._consecutive - 1)))
            if delay > 0:
                time.sleep(delay)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env.update(self.worker_env)
        self.proc = subprocess.Popen(
            [sys.executable, "-c",
             "import sys; sys.path.insert(0, %r); "
             "from mythril_tpu.engine_worker import worker_main; "
             "raise SystemExit(worker_main())" % root],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        self.spawns += 1
        self._counter("engine_worker_spawns_total",
                      help="engine-worker subprocesses spawned").inc()
        if self.spawns > 1:
            self.restarts += 1
            self._counter("engine_worker_restarts_total",
                          help="engine-worker respawns after a "
                               "death").inc()
            self._event("worker_restart", pid=self.proc.pid,
                        attempt=self.spawns,
                        detail=f"respawn #{self.restarts}")
        from .obs import trace as obs_trace

        def spawned(device=None):
            # one event per spawn, emitted once the init handshake has
            # settled so it can say which device the engine got
            self._event("worker_spawn", pid=self.proc.pid,
                        detail="stub" if self.stub else "engine",
                        device=device)

        self.device = None
        try:
            self._send({"op": "init", "stub": self.stub,
                        "trace": obs_trace.active(),
                        "config": self.config})
            rep = self._read_frame(time.monotonic() + self.spawn_timeout)
        except TimeoutError:
            spawned()
            self._record_death(
                f"worker init exceeded {self.spawn_timeout:.0f}s; "
                "killed")
            raise WorkerDied(
                f"engine worker init hung >{self.spawn_timeout:.0f}s "
                "(killed)") from None
        except (EOFError, OSError):
            rc = self._exit_code()
            spawned()
            self._record_death(f"worker died during init (rc={rc})")
            raise WorkerDied(
                f"engine worker died during init (rc={rc})") from None
        if not rep.get("ok"):
            # the worker is alive but could not build its engine (bad
            # config, missing dep): not a crash, but not usable either
            spawned()
            self._reap()
            raise self._rehydrate(rep)
        self.device = (rep.get("value") or {}).get("device")
        spawned(self.device)
        got = tier_of_platform((self.device or {}).get("platform"))
        if (not self.stub and self.expect_tier is not None
                and got != self.expect_tier):
            # e.g. a TPU init that failed inside the child and left it
            # on the CPU: a quiet CPU worker would be reported as the
            # tier's capacity, so it counts as a death instead
            detail = (f"worker spawned for the {self.expect_tier} tier "
                      f"came up on {self.device!r}")
            self._record_death(detail)
            raise WorkerDied(detail)
        child_mono = (rep.get("value") or {}).get("mono")
        if isinstance(child_mono, (int, float)):
            self.mono_offset = time.monotonic() - float(child_mono)

    def ensure_alive(self) -> None:
        """Spawn + init the worker now if none is running (same
        breaker and backoff discipline as a batch dispatch)."""
        with self._lock:
            self._check_breaker()
            if not self.alive():
                self._spawn_and_init()

    def close(self) -> None:
        """Orderly shutdown: ask the worker to exit, then reap."""
        with self._lock:
            if self.alive():
                try:
                    self._send({"op": "exit"})
                    self.proc.wait(timeout=5)
                except (OSError, subprocess.TimeoutExpired, TimeoutError):
                    pass
            self._reap()

    # --- framed IPC (length-prefixed pickle over the pipes) -------------
    def _send(self, msg: Dict) -> None:
        from .engine_worker import pack_frame

        self.proc.stdin.write(pack_frame(msg))
        self.proc.stdin.flush()

    def _read_frame(self, deadline: Optional[float]) -> Dict:
        """One reply frame from the worker, or TimeoutError (deadline)
        / EOFError (worker death, incl. a torn mid-reply frame)."""
        import pickle

        from .engine_worker import FRAME_HEADER

        hdr = self._read_exact(FRAME_HEADER.size, deadline)
        (n,) = FRAME_HEADER.unpack(hdr)
        return pickle.loads(self._read_exact(n, deadline))

    def _read_exact(self, n: int, deadline: Optional[float]) -> bytes:
        fd = self.proc.stdout.fileno()
        buf = b""
        while len(buf) < n:
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError()
                wait = min(remaining, 0.5)
            else:
                wait = 0.5
            ready, _, _ = select.select([fd], [], [], wait)
            if not ready:
                if self.proc.poll() is not None:
                    raise EOFError()
                continue
            chunk = os.read(fd, n - len(buf))
            if not chunk:
                raise EOFError()
            buf += chunk
        return buf

    def _rehydrate(self, rep: Dict) -> BaseException:
        """Parent-side exception for a worker error reply, typed so the
        existing recovery paths (ladder / re-probe / bisect) classify
        it exactly like an in-process failure."""
        msg = f"{rep.get('etype', 'Error')}: {rep.get('emsg', '')}"[:500]
        kind = rep.get("classify")
        if kind == "oom":
            return ResourceExhausted(msg)
        if kind == "device-lost":
            return DeviceLostError(msg)
        return WorkerError(msg)

    # --- telemetry backhaul (docs/observability.md "Distributed
    # --- tracing") ------------------------------------------------------
    def _absorb_telemetry(self, tel, bi: int) -> None:
        """Land one batch reply's worker-side telemetry in this
        process: refresh the clock offset from the reply's fresh child
        ``mono`` reading, re-emit the drained spans/events offset-
        corrected (tagged ``proc="worker"``), and fold the metric
        delta into the parent registry."""
        if not isinstance(tel, dict):
            return
        from .obs import metrics as obs_metrics
        from .obs import trace as obs_trace

        child_mono = tel.get("mono")
        if isinstance(child_mono, (int, float)):
            self.mono_offset = time.monotonic() - float(child_mono)
        off = self.mono_offset or 0.0
        recs = tel.get("records") or ()
        if recs:
            obs_trace.reemit_records(
                recs, mono_offset=off, proc="worker",
                wpid=self.proc.pid if self.proc else None)
        obs_metrics.apply_delta(tel.get("metrics"))

    def _telemetry_lost(self, bi: int, detail: str) -> None:
        """The worker died with undelivered telemetry (its buffered
        spans/events die with the process): declare the loss instead of
        dropping it silently — an invisible device phase is exactly the
        blind spot this machinery exists to close."""
        from .obs import trace as obs_trace

        if not obs_trace.active():
            return  # worker was never tracing: nothing was lost
        self._counter(
            "engine_worker_telemetry_lost_total",
            help="batches whose worker-side spans/events died with "
                 "the worker before backhaul").inc()
        self._event("worker_telemetry_lost", detail=detail, batch=bi)

    def _update_rss(self) -> None:
        try:
            with open(f"/proc/{self.proc.pid}/statm") as fh:
                pages = int(fh.read().split()[1])
            self.rss_bytes = pages * os.sysconf("SC_PAGE_SIZE")
        except (OSError, ValueError, IndexError, AttributeError):
            return
        self._gauge("engine_worker_rss_bytes",
                    help="resident set size of the engine worker "
                         "subprocess").set(self.rss_bytes)

    # --- the one entry point -------------------------------------------
    def run_batch(self, bi: int, names: Sequence[str],
                  codes: Sequence[bytes],
                  lanes: Optional[int] = None,
                  width: Optional[int] = None,
                  on_cpu: bool = False,
                  on_tier: Optional[str] = None,
                  creations: Optional[Sequence[Optional[bytes]]] = None
                  ) -> Dict:
        """Run one batch in the worker under the parent-side deadline
        (``creations``: the contracts' creation codes when the batch
        deploys, so that a replayed batch deploys again).
        Raises :class:`WorkerCrashLoop` (breaker open),
        :class:`BatchTimeout` (deadline; worker killed),
        :class:`WorkerDied` (crash mid-batch), or the rehydrated typed
        error the worker reported. Returns the batch's partial-result
        dict (``issues``/``paths``/``dropped``/``iprof``)."""
        with self._lock:
            self.ensure_alive()
            if self.fault_injector is not None:
                sig = self.fault_injector.worker_signal(
                    batch=bi, contracts=names)
                if sig is not None:
                    try:
                        os.kill(self.proc.pid, sig)
                    except OSError:
                        pass
            deadline = (time.monotonic() + self.batch_timeout
                        if self.batch_timeout is not None else None)
            from .obs import trace as obs_trace

            try:
                self._send({"op": "batch", "bi": int(bi),
                            "names": [str(x) for x in names],
                            "codes": [bytes(c) for c in codes],
                            "creations": (
                                None if creations is None else
                                [None if k is None else bytes(k)
                                 for k in creations]),
                            "lanes": lanes, "width": width,
                            "on_cpu": bool(on_cpu or on_tier == "cpu"),
                            "on_tier": on_tier,
                            "trace": obs_trace.context_snapshot()})
                rep = self._read_frame(deadline)
            except TimeoutError:
                self._telemetry_lost(
                    bi, f"batch {bi} deadline; worker killed with "
                        "its span buffer")
                self._record_death(
                    f"batch {bi} exceeded {self.batch_timeout:.1f}s; "
                    "worker killed")
                raise BatchTimeout(
                    f"batch {bi} exceeded {self.batch_timeout:.1f}s "
                    "wall-clock budget in the engine worker (worker "
                    "killed)") from None
            except (EOFError, OSError):
                rc = self._exit_code()
                self._telemetry_lost(
                    bi, f"worker died mid-batch {bi} (rc={rc}); span "
                        "buffer lost with it")
                self._record_death(f"worker died mid-batch {bi} (rc={rc})")
                raise WorkerDied(
                    f"engine worker died mid-batch {bi} (rc={rc})"
                ) from None
            if not rep.get("ok"):
                # an error REPLY means the worker survived: the fault
                # was contained inside the engine, not the process
                self._note_success()
                self._update_rss()
                raise self._rehydrate(rep)
            self._note_success()
            self._update_rss()
            value = rep["value"]
            if isinstance(value, dict):
                self._absorb_telemetry(value.pop("telemetry", None), bi)
            return value

    def prewarm(self, buckets: Sequence[Dict],
                on_tier: Optional[str] = None) -> Dict:
        """AOT-prewarm a list of shape buckets in the worker (the
        compile-store recovery path, docs/serving.md "Compile artifacts
        & prewarm"). Same lifecycle discipline as :meth:`run_batch` —
        breaker check, spawn-on-demand, parent-side deadline (the spawn
        timeout: a prewarm is all compile, which is exactly what that
        budget was sized for), death accounting — so a wedged prewarm
        can never outlive its budget and a crashy one trips the same
        breaker live batches do. Returns the worker's ``{done, total}``
        reply; raises the same typed errors as ``run_batch``."""
        buckets = [dict(b) for b in buckets]
        with self._lock:
            self.ensure_alive()
            deadline = time.monotonic() + self.spawn_timeout
            from .obs import trace as obs_trace

            try:
                self._send({"op": "prewarm", "buckets": buckets,
                            "on_tier": on_tier,
                            "trace": obs_trace.context_snapshot()})
                rep = self._read_frame(deadline)
            except TimeoutError:
                self._record_death(
                    f"prewarm ({len(buckets)} buckets) exceeded "
                    f"{self.spawn_timeout:.0f}s; worker killed")
                raise BatchTimeout(
                    f"prewarm exceeded {self.spawn_timeout:.0f}s "
                    "wall-clock budget in the engine worker (worker "
                    "killed)") from None
            except (EOFError, OSError):
                rc = self._exit_code()
                self._record_death(f"worker died mid-prewarm (rc={rc})")
                raise WorkerDied(
                    f"engine worker died mid-prewarm (rc={rc})"
                ) from None
            if not rep.get("ok"):
                self._note_success()
                raise self._rehydrate(rep)
            self._note_success()
            self._update_rss()
            value = rep["value"]
            if isinstance(value, dict):
                self._absorb_telemetry(value.pop("telemetry", None), -1)
            return value


__all__ = [
    "BackendManager", "BatchTimeout", "DEGRADE_RUNGS", "DeviceLostError",
    "FaultInjector", "FaultSpec", "InjectedKill", "ResilienceError",
    "ResourceExhausted", "WorkerCrashLoop", "WorkerDied", "WorkerError",
    "WorkerSupervisor", "classify_backend_error", "parse_ladder",
    "run_with_watchdog",
]
