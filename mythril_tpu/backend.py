"""Backend tiers: one registry of platform profiles and the
demote-and-repromote failover ladder built on top of it.

Before this module, platform knowledge was smeared across the tree as
``JAX_PLATFORMS=cpu`` literals: the startup probe's fallback pinned the
process to CPU (resilience.py), the OOM ladder's terminal rung was the
string ``"cpu"`` (config.py) — and nothing ever *lifted* any of those
pins, so a transient TPU wedge demoted the process for its whole
lifetime.

This module replaces all of that with two pieces:

- :class:`BackendProfile` — a frozen record per platform (tpu/gpu/cpu)
  owning the constants the rest of the tree used to hard-code: tier
  rank, default lane width, padding multiple, probe timeout, the OOM
  ladder shape, and the ``pure_callback`` dispatch strategy.

- :class:`TierManager` — the ranked failover ladder. Failures demote
  to the *next* tier (not straight to CPU); a background prober
  re-checks the better tier with the same subprocess-isolation
  contract as the startup probe and climbs back when it passes. A
  sticky demotion window plus flap damping (a bounded count of
  transitions per rolling window) keep an oscillating backend from
  thrashing warm compiles.

Import cost matters: config.py imports this module, and the engine
worker imports config before JAX — so this file is stdlib-only at
import time.
"""

from __future__ import annotations

import collections
import os
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "BackendProfile", "PROFILES", "TIER_ORDER", "TIER_RUNG",
    "profile", "terminal_tier", "default_oom_ladder", "parse_tiers",
    "detect_tiers", "tiers_below", "tier_of_platform", "probe_tier",
    "available_tiers", "TierManager", "device_record", "engine_report",
    "engine_counters", "pinned_tier",
]


@dataclass(frozen=True)
class BackendProfile:
    """Everything the rest of the tree needs to know about one
    platform, so no caller has to special-case ``if platform == "tpu"``
    again. ``rank`` orders the failover ladder (0 is best)."""

    name: str
    rank: int
    #: value written to ``JAX_PLATFORMS`` to pin a process here
    jax_platform: str
    #: default interpreter lane width (SIMD batch of contract paths)
    default_lanes: int
    #: pad batch dims to this multiple (MXU/VPU tiling on TPU; warp
    #: width on GPU; no constraint worth paying for on host CPU)
    pad_multiple: int
    #: subprocess probe budget — how long ``jax.devices()`` may take
    #: before the tier is declared wedged (TPU init is the slowest)
    probe_timeout: float
    #: degradation ladder walked on RESOURCE_EXHAUSTED at this tier
    oom_ladder: Tuple[str, ...]
    #: host-callback strategy: "threaded" platforms tolerate blocking
    #: io_callback bodies; "inline" runs them on the dispatch thread
    pure_callback: str
    description: str = ""


#: historical name of the terminal OOM-ladder rung. It predates tiers
#: ("cpu" literally meant pin-to-CPU); it now means "demote to the
#: next available tier" and is resolved against the tier list at walk
#: time. Config strings keep accepting both spellings.
TIER_RUNG = "cpu"
#: accepted alias in ``--oom-ladder`` strings for the terminal rung
TIER_RUNG_ALIAS = "next-tier"

PROFILES: Dict[str, BackendProfile] = {
    "tpu": BackendProfile(
        name="tpu", rank=0, jax_platform="tpu",
        default_lanes=8, pad_multiple=8, probe_timeout=75.0,
        oom_ladder=("halve-lanes", "halve-batch", TIER_RUNG),
        pure_callback="threaded",
        description="TPU via PJRT; slow init, fast lanes"),
    "gpu": BackendProfile(
        name="gpu", rank=1, jax_platform="cuda",
        default_lanes=8, pad_multiple=4, probe_timeout=30.0,
        oom_ladder=("halve-lanes", "halve-batch", TIER_RUNG),
        pure_callback="threaded",
        description="CUDA/ROCm lanes; a first-class tier, not a "
                    "second CPU"),
    "cpu": BackendProfile(
        name="cpu", rank=2, jax_platform="cpu",
        default_lanes=8, pad_multiple=1, probe_timeout=20.0,
        # on the floor tier the terminal rung is a no-op (there is no
        # tier below the host), so the floor's ladder ends at batching
        oom_ladder=("halve-lanes", "halve-batch"),
        pure_callback="inline",
        description="host CPU; always present, never probed away"),
}

#: ladder order, best first — the single source of tier rank
TIER_ORDER: Tuple[str, ...] = tuple(
    sorted(PROFILES, key=lambda n: PROFILES[n].rank))


def profile(name: str) -> BackendProfile:
    try:
        return PROFILES[name]
    except KeyError:
        raise ValueError(
            f"unknown backend tier {name!r} (known: {', '.join(TIER_ORDER)})"
        ) from None


def terminal_tier() -> str:
    """The floor of the ladder — the tier that needs no probe because
    losing it means losing the host itself."""
    return TIER_ORDER[-1]


def default_oom_ladder() -> Tuple[str, ...]:
    """The degradation ladder of the best-ranked tier: what a campaign
    walks on RESOURCE_EXHAUSTED before demoting off the tier."""
    return PROFILES[TIER_ORDER[0]].oom_ladder


def parse_tiers(value) -> Tuple[str, ...]:
    """Normalize a tier list (comma string or sequence) into a ranked,
    deduplicated tuple. Rejects unknown names; always keeps the
    terminal tier at the end so the ladder has a floor."""
    if value is None:
        return detect_tiers()
    if isinstance(value, str):
        names = [t.strip() for t in value.split(",") if t.strip()]
    else:
        names = [str(t) for t in value]
    for n in names:
        profile(n)  # raises ValueError on unknown tiers
    ranked = tuple(sorted(set(names), key=lambda n: PROFILES[n].rank))
    if not ranked:
        return (terminal_tier(),)
    if ranked[-1] != terminal_tier():
        ranked = ranked + (terminal_tier(),)
    return ranked


def detect_tiers() -> Tuple[str, ...]:
    """The ranked tier list this process should consider, without
    probing anything: ``MYTHRIL_BACKEND_TIERS`` wins, else a pinned
    ``JAX_PLATFORMS`` restricts the ladder to that platform (plus the
    floor), else the full ladder."""
    env = os.environ.get("MYTHRIL_BACKEND_TIERS")
    if env:
        return parse_tiers(env)
    pinned = os.environ.get("JAX_PLATFORMS")
    if pinned:
        known = [t for t in (p.strip() for p in pinned.split(","))
                 if t in PROFILES]
        if known:
            return parse_tiers(known)
    return TIER_ORDER


def tiers_below(name: str, tiers: Optional[Sequence[str]] = None
                ) -> Tuple[str, ...]:
    """Tiers ranked strictly worse than ``name``, best first."""
    ladder = parse_tiers(tiers) if tiers is not None else TIER_ORDER
    rank = profile(name).rank
    return tuple(t for t in ladder if PROFILES[t].rank > rank)


def tier_of_platform(platform) -> Optional[str]:
    """Map a platform label (``jax.default_backend()`` output, a bench
    ``platform`` field like ``"cpu-fallback"``, or a profile name) back
    to its tier name; None when unrecognizable."""
    if not platform:
        return None
    label = str(platform).lower()
    for name, prof in PROFILES.items():
        if label == name or label == prof.jax_platform:
            return name
        if label.startswith(name + "-") or label.startswith(
                prof.jax_platform + "-"):
            return name
    return None


def pinned_tier(env: Optional[Dict[str, str]] = None) -> Optional[str]:
    """The tier ``JAX_PLATFORMS`` (of ``env``, default this process's)
    pins a process to: the first entry that names a known tier, or
    None for an unpinned process, which takes what JAX picks."""
    value = (os.environ if env is None else env).get("JAX_PLATFORMS", "")
    for part in value.split(","):
        tier = tier_of_platform(part.strip())
        if tier:
            return tier
    return None


def device_record() -> Dict:
    """The device this process's engine runs on, as JAX reports it.
    Initializes the backend: only the process that owns the engine
    calls this (the worker at init, an in-process run after a batch)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "id": devs[0].id}


def engine_counters() -> Dict:
    """Compile counters of this process: engine chunk shapes compiled
    (``engine_compiles_total``) and JAX's own backend compiles."""
    from . import compile_cache
    from .obs import metrics as obs_metrics

    return {"engine_compiles": int(obs_metrics.REGISTRY.counter(
                "engine_compiles_total").value),
            **compile_cache.stats()}


def engine_report() -> Dict:
    """What the engine's own process knows about how it is running:
    the device it got, whether host callbacks work there, whether the
    native tape evaluator is loaded, what it compiled and the device
    memory peak. Carried on every batch result so a run on the wrong
    device, or on a slower fallback, is on record instead of silent."""
    import jax

    from . import compile_cache, native
    from .ops import callbacks

    mem = jax.devices()[0].memory_stats() or {}
    return {
        "device": device_record(),
        "host_callbacks": callbacks._CB_OK,   # None = never asked
        "native_tape_eval": native.status(),
        **engine_counters(),
        "compile_cache": compile_cache.cache_dir(),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
    }


# ---------------------------------------------------------------------------
# subprocess probe — the PR 10 isolation contract: the child does the
# dangerous device init; a wedged child is abandoned, never joined.


def _probe_child(env: Dict[str, str], timeout_s: float) -> Tuple[bool, str]:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import mythril_tpu, jax; d = jax.devices(); "
            "print('OK', jax.default_backend(), len(d))")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            cwd=root, env=env, text=True)
    except OSError as e:  # pragma: no cover - spawn failure
        return False, f"probe spawn failed: {e}"
    deadline = time.monotonic() + timeout_s
    while proc.poll() is None:
        if time.monotonic() >= deadline:
            # abandon, don't join: a D-state child wedged in device
            # init survives SIGKILL and a .wait() would hang us too
            try:
                proc.kill()
            except OSError:
                pass
            return False, f"probe timed out after {timeout_s:.0f}s"
        time.sleep(0.05)
    out = (proc.stdout.read() if proc.stdout else "") or ""
    err = (proc.stderr.read() if proc.stderr else "") or ""
    if proc.returncode == 0 and out.startswith("OK"):
        return True, out.strip()
    tail = (err.strip().splitlines() or ["no stderr"])[-1]
    return False, f"probe exited rc={proc.returncode}: {tail[:200]}"


def probe_tier(tier: str, timeout_s: Optional[float] = None
               ) -> Tuple[bool, str]:
    """Health-check one tier in a subprocess pinned to that platform.
    The floor tier always passes without spawning anything — the host
    CPU being gone is not a state this process can observe."""
    prof = profile(tier)
    if tier == terminal_tier():
        return True, "terminal tier (host CPU), no probe needed"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = prof.jax_platform
    env.pop("MYTHRIL_WORKER_FAULT", None)
    return _probe_child(
        env, prof.probe_timeout if timeout_s is None else timeout_s)


def available_tiers(tiers: Optional[Sequence[str]] = None,
                    probe_fn: Optional[Callable] = None,
                    timeout_s: Optional[float] = None) -> Tuple[str, ...]:
    """Probe each candidate tier and return the ranked subset that
    answers. The floor tier is always included."""
    probe = probe_fn or probe_tier
    out: List[str] = []
    for tier in parse_tiers(tiers) if tiers is not None else detect_tiers():
        ok, _ = probe(tier, timeout_s)
        if ok:
            out.append(tier)
    if terminal_tier() not in out:
        out.append(terminal_tier())
    return tuple(out)


# ---------------------------------------------------------------------------
# metrics — lazy import like resilience.py so backend.py stays cheap
# for the engine worker's import path


def _counter(name: str, help_: str = ""):
    try:
        from .obs import metrics as obs_metrics
        return obs_metrics.REGISTRY.counter(name, help=help_)
    except Exception:  # pragma: no cover - obs must never break tiers
        return None


def _gauge(name: str, help_: str = ""):
    try:
        from .obs import metrics as obs_metrics
        return obs_metrics.REGISTRY.gauge(name, help=help_)
    except Exception:  # pragma: no cover
        return None


class TierManager:
    """The demote-and-repromote ladder over a ranked tier list.

    State machine (docs/resilience.md "Backend tiers")::

        preferred --demote(crash-loop / device-lost)--> demoted
        demoted   --probe passes, sticky window over--> repromoted
        demoted   --window full of transitions--------> flap-damped

    Thread model: ``demote``/``tick`` are called from the campaign
    thread; the optional background prober calls ``tick`` from its own
    daemon thread. All state mutations hold ``_lock``; the campaign
    folds transitions into its own state (warm-marker invalidation,
    worker respawn) by watching ``generation`` — the prober itself
    never touches campaign state.

    ``env_pin`` controls whether :meth:`platform_env` pins spawned
    engine workers with ``JAX_PLATFORMS``; tests running synthetic
    ladders (e.g. a pretend "tpu" tier on a CPU-only box) set it False
    so the tier is an accounting state while execution stays on host.
    """

    def __init__(self,
                 tiers: Optional[Sequence[str]] = None,
                 probe_fn: Optional[Callable[[str, Optional[float]],
                                             Tuple[bool, str]]] = None,
                 sticky_window: float = 20.0,
                 flap_window: float = 120.0,
                 flap_max: int = 4,
                 probe_every: float = 30.0,
                 env_pin: bool = True,
                 auto_prober: bool = True,
                 on_event: Optional[Callable] = None):
        self.tiers: Tuple[str, ...] = parse_tiers(tiers)
        self.probe_fn = probe_fn or probe_tier
        self.sticky_window = float(sticky_window)
        self.flap_window = float(flap_window)
        self.flap_max = int(flap_max)
        self.probe_every = float(probe_every)
        self.env_pin = bool(env_pin)
        self.auto_prober = bool(auto_prober)
        self.on_event = on_event
        self.events: List[Dict] = []
        self.demotions = 0
        self.repromotions = 0
        self.probe_failures = 0
        #: bumped on every applied transition; campaigns compare it to
        #: fold warm-invalidation + worker respawn at a safe point
        self.generation = 0
        self._idx = 0
        self._lock = threading.RLock()
        self._transitions: Deque[float] = collections.deque()
        self._demoted_at: Optional[float] = None
        self._last_probe: Optional[float] = None
        self._damped_emitted = False
        self._prober: Optional[threading.Thread] = None
        self._stop = threading.Event()
        _gauge("engine_backend_tier",
               "rank of the current backend tier (0 = best)"
               ).set(profile(self.current).rank)

    # -- introspection ----------------------------------------------------

    @property
    def current(self) -> str:
        return self.tiers[self._idx]

    @property
    def preferred(self) -> str:
        return self.tiers[0]

    def demoted(self) -> bool:
        return self._idx > 0

    def current_profile(self) -> BackendProfile:
        return profile(self.current)

    def platform_env(self) -> Dict[str, str]:
        """Env overlay for spawned engine workers: pin them to the
        tier this manager currently holds (empty when env pinning is
        disabled for synthetic-ladder tests)."""
        if not self.env_pin:
            return {}
        return {"JAX_PLATFORMS": self.current_profile().jax_platform}

    def status(self) -> Dict:
        with self._lock:
            return {
                "tiers": list(self.tiers),
                "current": self.current,
                "preferred": self.preferred,
                "demoted": self.demoted(),
                "demotions": self.demotions,
                "repromotions": self.repromotions,
                "probe_failures": self.probe_failures,
                "transitions_in_window": len(self._transitions),
                "flap_damped": self._damped_emitted,
                "generation": self.generation,
            }

    # -- events -----------------------------------------------------------

    def _event(self, kind: str, detail: str = "", **kw) -> None:
        rec = {"kind": kind, "detail": detail, "t": time.time()}
        rec.update(kw)
        self.events.append(rec)
        if self.on_event is not None:
            try:
                self.on_event(kind, detail=detail, **kw)
            except Exception:  # pragma: no cover - observer must not kill us
                pass
        else:
            try:
                from .obs import trace as obs_trace
                obs_trace.event("tier_" + kind if not kind.startswith("tier")
                                else kind, detail=detail, **kw)
            except Exception:  # pragma: no cover
                pass

    def _note_transition(self, now: float) -> None:
        self._transitions.append(now)
        self._trim_window(now)
        self.generation += 1
        _gauge("engine_backend_tier").set(self.current_profile().rank)

    def _trim_window(self, now: float) -> None:
        while self._transitions and now - self._transitions[0] > self.flap_window:
            self._transitions.popleft()
        if len(self._transitions) + 2 <= self.flap_max:
            # window drained enough for a round trip again — the next
            # damping episode gets its own event
            self._damped_emitted = False

    # -- transitions ------------------------------------------------------

    def demote(self, reason: str = "", failed: Optional[str] = None) -> str:
        """Step down one tier because ``failed`` (default: the current
        tier) just proved unhealthy. No-op when we already sit below
        the failed tier (a stale report must not double-demote) or on
        the floor. Returns the tier now held."""
        with self._lock:
            failed = failed or self.current
            if profile(self.current).rank > profile(failed).rank:
                return self.current
            if self._idx + 1 >= len(self.tiers):
                # the floor: nothing below to demote to; stay pinned
                # and let the prober (if any) climb back later
                return self.current
            src = self.current
            self._idx += 1
            self.demotions += 1
            now = time.monotonic()
            self._demoted_at = now
            self._note_transition(now)
            c = _counter("engine_tier_demotions_total",
                         "backend tier demotions")
            if c is not None:
                c.inc()
            self._event("tier_demoted", detail=reason[:200],
                        src=src, dst=self.current)
            if self.auto_prober and self.probe_every > 0:
                self.start_prober()
            return self.current

    def maybe_repromote(self) -> bool:
        """Try to climb one tier back up. Gated by the sticky demotion
        window (fresh demotions hold), flap damping (no headroom for a
        demote+repromote round trip in the rolling window), and a live
        probe of the better tier. Returns True when a climb applied."""
        with self._lock:
            if self._idx == 0:
                return False
            now = time.monotonic()
            if (self._demoted_at is not None
                    and now - self._demoted_at < self.sticky_window):
                return False
            self._trim_window(now)
            if len(self._transitions) + 2 > self.flap_max:
                if not self._damped_emitted:
                    self._damped_emitted = True
                    self._event(
                        "tier_flap_damped",
                        detail=(f"{len(self._transitions)} transitions in "
                                f"{self.flap_window:.0f}s window; holding "
                                f"{self.current} (flap_max={self.flap_max})"),
                        held=self.current)
                return False
            target = self.tiers[self._idx - 1]
            self._last_probe = now
            try:
                ok, diag = self.probe_fn(target, profile(target).probe_timeout)
            except Exception as e:  # pragma: no cover - probe must not kill us
                ok, diag = False, f"probe raised: {e}"
            if not ok:
                self.probe_failures += 1
                c = _counter("engine_tier_probe_failures_total",
                             "failed re-promotion probes")
                if c is not None:
                    c.inc()
                self._event("tier_probe_failed", detail=str(diag)[:200],
                            target=target)
                return False
            self._idx -= 1
            self.repromotions += 1
            self._note_transition(time.monotonic())
            c = _counter("engine_tier_repromotions_total",
                         "backend tier re-promotions")
            if c is not None:
                c.inc()
            self._event("tier_repromoted", detail=str(diag)[:200],
                        dst=self.current)
            return True

    def tick(self) -> bool:
        """Periodic driver: attempt a re-promotion when one is due.
        Called by campaigns at batch boundaries (so transitions land at
        accounting-safe points) and by the background prober."""
        with self._lock:
            if self._idx == 0:
                return False
            if (self.probe_every > 0 and self._last_probe is not None
                    and time.monotonic() - self._last_probe < self.probe_every):
                return False
        return self.maybe_repromote()

    # -- background prober ------------------------------------------------

    def start_prober(self) -> None:
        """Start the background re-promotion prober (idempotent). It
        retires itself once the preferred tier is regained; a later
        demotion starts a fresh one."""
        with self._lock:
            if self._prober is not None and self._prober.is_alive():
                return
            self._stop.clear()
            self._prober = threading.Thread(
                target=self._probe_loop, name="tier-prober", daemon=True)
            self._prober.start()

    def stop_prober(self) -> None:
        self._stop.set()
        t = self._prober
        if t is not None and t.is_alive():
            t.join(timeout=2.0)

    def _probe_loop(self) -> None:
        pause = max(0.02, min(1.0, self.probe_every / 4.0
                              if self.probe_every > 0 else 0.05))
        while not self._stop.is_set():
            with self._lock:
                if self._idx == 0:
                    return  # climbed all the way back; prober retires
            try:
                self.tick()
            except Exception:  # pragma: no cover - prober must not die loudly
                pass
            self._stop.wait(pause)
