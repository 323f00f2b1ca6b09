"""Supervised engine worker: subprocess-isolated device execution.

The child half of the process-isolation boundary
(docs/resilience.md "Process isolation & supervision"): this process
OWNS the JAX backend and runs device batches on behalf of a parent
``WorkerSupervisor`` (mythril_tpu/resilience.py), speaking a
length-prefixed pickle protocol over its stdin/stdout pipes. The
division of labor:

- a libtpu segfault, an OOM kill, or a wedged XLA compile happens
  HERE — the parent observes pipe EOF (death) or a missed deadline
  (hang) and restarts this process, feeding the failed batch back
  through the campaign's retry→ladder→bisect machinery;
- an engine EXCEPTION (solver error, RESOURCE_EXHAUSTED, a poison
  contract) is caught, classified with
  :func:`mythril_tpu.resilience.classify_backend_error`, and returned
  as an error reply — the worker survives, and the parent rehydrates
  the same typed error its in-process path would have seen.

Protocol (every frame = 8-byte big-endian length + pickle):

- ``{"op": "init", "stub": bool, "config": {...}}`` → builds the
  resident engine (or nothing, in stub mode) and replies
  ``{"ok": True, "value": {"pid": ...}}``. ``config`` carries the
  parent campaign's engine knobs (shapes, limits, spec, solver
  budget); the worker builds its own corpus-less ``CorpusCampaign``
  and serves batches through its ``_explore_batch``/``_harvest_batch``
  seam, so batch semantics (padding, warm shapes, pad filtering) are
  the campaign's own code, not a re-implementation.
- ``{"op": "batch", "bi", "names", "codes", "creations", "lanes",
  "width", "on_cpu"}`` (``creations``: the contracts' creation codes,
  None where one has none, or None for a batch that does not deploy)
  → ``{"ok": True, "value": {issues/paths/dropped/iprof}}``
  or ``{"ok": False, "etype", "emsg", "classify"}``.
- ``{"op": "ping"}`` → rss diagnostics; ``{"op": "exit"}`` → clean 0.

Stdout is the protocol channel: the REAL fd is duplicated away at
startup and fd 1 is re-pointed at stderr, so engine prints and jax
warnings can never corrupt a frame. EOF on stdin (parent death) exits
the worker — an orphaned worker never outlives its supervisor.

Deterministic chaos (tools/chaos_campaign.py): the
``MYTHRIL_WORKER_FAULT`` env var — ``sig:point:nth[:once=PATH]`` with
``sig`` ∈ kill|segv and ``point`` ∈ mid-compile|mid-superstep|
mid-reply — makes the worker deliver a REAL signal to itself at the
named point of its ``nth`` batch request (``once=PATH`` is a cookie
file so the fault fires exactly once across restarts). ``mid-reply``
writes a torn half-frame first, so the parent also exercises the
truncated-IPC path.

Stub mode (``init`` with ``stub=True``) skips every engine import and
answers batches with deterministic counts — the fast worker for
supervision-machinery tests; pipes, signals and process death are just
as real. A stub batch whose names include ``__hang__`` sleeps forever
(the parent-deadline fixture).
"""

from __future__ import annotations

import contextlib
import os
import pickle
import signal
import struct
import sys
import time
from typing import BinaryIO, Dict, List, Optional

from . import compile_cache
from .obs import device as obs_device
from .obs import metrics as obs_metrics
from .obs import trace as obs_trace

#: frame header: one 8-byte big-endian payload length
FRAME_HEADER = struct.Struct(">Q")

PROTOCOL_VERSION = 1

_FAULT_SIGNALS = {"kill": signal.SIGKILL, "segv": signal.SIGSEGV}
_FAULT_POINTS = ("mid-compile", "mid-superstep", "mid-reply")


def pack_frame(obj) -> bytes:
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    return FRAME_HEADER.pack(len(data)) + data


def read_frame(stream: BinaryIO):
    """One frame from a blocking stream, or None on EOF (the child's
    read side; the parent reads with a deadline instead — see
    ``WorkerSupervisor._read_frame``)."""
    hdr = b""
    while len(hdr) < FRAME_HEADER.size:
        chunk = stream.read(FRAME_HEADER.size - len(hdr))
        if not chunk:
            return None
        hdr += chunk
    (n,) = FRAME_HEADER.unpack(hdr)
    buf = b""
    while len(buf) < n:
        chunk = stream.read(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return pickle.loads(buf)


class ChildFault:
    """Parsed ``MYTHRIL_WORKER_FAULT`` spec (see module docstring)."""

    def __init__(self, sig: int, point: str, nth: int,
                 once: Optional[str] = None):
        self.sig = sig
        self.point = point
        self.nth = nth
        self.once = once

    @classmethod
    def from_env(cls) -> Optional["ChildFault"]:
        text = os.environ.get("MYTHRIL_WORKER_FAULT")
        if not text:
            return None
        parts = text.strip().split(":")
        if len(parts) < 3 or parts[0] not in _FAULT_SIGNALS \
                or parts[1] not in _FAULT_POINTS:
            raise ValueError(
                f"MYTHRIL_WORKER_FAULT {text!r}: expected "
                f"sig:point:nth[:once=PATH] with sig of "
                f"{tuple(_FAULT_SIGNALS)} and point of {_FAULT_POINTS}")
        once = None
        for extra in parts[3:]:
            if extra.startswith("once="):
                once = extra[len("once="):]
            else:
                raise ValueError(
                    f"MYTHRIL_WORKER_FAULT {text!r}: unknown option "
                    f"{extra!r}")
        return cls(_FAULT_SIGNALS[parts[0]], parts[1], int(parts[2]),
                   once)

    def _take(self) -> bool:
        """Claim the fault. With ``once=PATH`` the cookie file is the
        cross-restart memory: the first taker creates it and fires,
        every later (restarted) worker sees it and stays healthy."""
        if self.once is None:
            return True
        try:
            fd = os.open(self.once, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            return False
        except OSError:
            return True  # unwritable cookie dir: still fire (visible)
        os.close(fd)
        return True

    def should(self, point: str, nth: int) -> bool:
        return (point == self.point and nth == self.nth
                and self._take())

    def fire(self, point: str, nth: int) -> None:
        """Deliver the REAL signal to this process at a named point —
        a genuine SIGSEGV/SIGKILL death, not a Python exception."""
        if self.should(point, nth):
            os.kill(os.getpid(), self.sig)
            time.sleep(5)  # SIGKILL delivery is async; don't race on


def _rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError, AttributeError):
        return 0


#: marker a supervisor drops into the shared XLA cache dir when a
#: worker dies uncleanly mid-batch: the NEXT spawn must probe the cache
#: before trusting it (a killed writer can leave a torn entry that
#: segfaults later readers — tests/conftest.py documents the original
#: incident)
CACHE_DIRTY_MARKER = ".dirty"

# the probe body: a minimal jit through the suspect cache dir, run in
# a THROWAWAY subprocess (PR-13 pattern: a poisoned cache segfaults the
# probe child, never this worker). MYTHRIL_CACHE_PROBE_FAULT=segv|hang
# is the deterministic-chaos hook standing in for a real torn entry.
_PROBE_SRC = """\
import os, signal, sys, time
f = os.environ.get("MYTHRIL_CACHE_PROBE_FAULT")
if f == "segv":
    os.kill(os.getpid(), signal.SIGSEGV); time.sleep(5)
if f == "hang":
    time.sleep(3600)
import jax
import jax.numpy as jnp
jax.jit(lambda x: x + 1)(jnp.zeros((8,), jnp.int32)).block_until_ready()
"""


def probe_cache(cache: str, timeout: Optional[float] = None) -> bool:
    """Whether a probe compile through ``cache`` survives. Best-effort
    by construction (a torn entry only fires when ITS key is read; the
    probe catches index/deserializer-level poison), but the failure
    mode is contained: the probe child dies, not the engine."""
    import subprocess

    if timeout is None:
        timeout = float(os.environ.get(
            "MYTHRIL_CACHE_PROBE_TIMEOUT", "180"))
    # the child is pointed at the suspect dir from outside, the way
    # every process of this repo is (mythril_tpu/compile_cache.py)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env[compile_cache.ENV] = cache
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    try:
        r = subprocess.run([sys.executable, "-c", _PROBE_SRC],
                           capture_output=True, timeout=timeout,
                           env=env)
        return r.returncode == 0
    except (subprocess.TimeoutExpired, OSError):
        return False


def _maybe_probe_cache(cache: str) -> str:
    """Corrupt-persistent-cache resilience: when the supervisor flagged
    the cache ``.dirty`` (a worker died uncleanly) or the operator
    forces it (``MYTHRIL_CACHE_PROBE=1``), probe-compile in a subprocess
    before the engine touches a single entry. A failed probe sets the
    WHOLE dir aside as ``<cache>.corrupt`` (evidence preserved — never
    a silent wipe) and continues cold on a fresh dir with a loud
    ``compile_cache_quarantined`` event; a clean probe clears the
    marker. Returns the cache dir the engine should use."""
    marker = os.path.join(cache, CACHE_DIRTY_MARKER)
    forced = os.environ.get("MYTHRIL_CACHE_PROBE") == "1"
    if not (forced or os.path.exists(marker)):
        return cache
    if probe_cache(cache):
        try:
            os.unlink(marker)
        except OSError:
            pass
        return cache
    dest = cache + ".corrupt"
    if os.path.exists(dest):
        dest = f"{cache}.corrupt.{os.getpid()}"
    try:
        os.replace(cache, dest)
    except OSError:
        dest = None  # couldn't set aside; still never serve it as-is
    os.makedirs(cache, exist_ok=True)
    obs_trace.event("compile_cache_quarantined", cache=cache,
                    quarantined_to=dest or "")
    obs_metrics.REGISTRY.counter(
        "compile_cache_quarantined_total",
        help="poisoned XLA cache dirs set aside .corrupt").inc()
    print(f"[worker] XLA cache {cache} failed its probe compile; "
          f"quarantined to {dest}, continuing cold", file=sys.stderr,
          flush=True)
    return cache


def _build_campaign(config: Dict):
    """The worker's resident engine: a corpus-less CorpusCampaign with
    the parent's knobs. Heavy imports happen here, under the parent's
    spawn deadline — a wedged backend init is a killed worker, not a
    wedged fleet."""
    import mythril_tpu  # noqa: F401  (enables x64)

    _maybe_probe_cache(compile_cache.cache_dir())
    compile_cache.enable()
    if config.get("solver_store"):
        from .smt import portfolio as smt_portfolio

        smt_portfolio.set_store(config["solver_store"])
    from .mythril.campaign import CorpusCampaign

    return CorpusCampaign(
        [],
        batch_size=int(config.get("batch_size", 32)),
        lanes_per_contract=int(config.get("lanes_per_contract", 32)),
        limits=config["limits"],
        spec=config.get("spec"),
        max_steps=int(config.get("max_steps", 256)),
        transaction_count=int(config.get("transaction_count", 1)),
        modules=config.get("modules"),
        solver_timeout=config.get("solver_timeout"),
        solver_iters=int(config.get("solver_iters", 400)),
        parallel_solving=bool(config.get("parallel_solving", False)),
        solver_workers=int(config.get("solver_workers", 1)),
        enable_iprof=bool(config.get("enable_iprof", False)),
        batch_timeout=None,         # the PARENT enforces the deadline
        worker_isolation="off",     # no recursive workers
        solver_store=None,          # installed above, process-global
    )


def _run_batch(camp, stub: bool, msg: Dict,
               fault: Optional[ChildFault], nth: int) -> Dict:
    bi = int(msg["bi"])
    names = list(msg["names"])
    codes = list(msg["codes"])
    creations = msg.get("creations")
    lanes = msg.get("lanes")
    width = msg.get("width")
    # re-enter the parent's request trace scope: every span/event this
    # batch emits (device_phase, superstep, solver stages) carries the
    # same trace_id the HTTP submit minted, two processes away
    with obs_trace.apply_context(msg.get("trace")):
        if fault is not None:
            fault.fire("mid-compile", nth)
        if stub:
            if "__hang__" in names:
                time.sleep(3600)
            with obs_device.phase_timer("device_phase", bi=bi,
                                        n=len(names)) as dv:
                if fault is not None:
                    fault.fire("mid-superstep", nth)
            return {"issues": [], "paths": len(names), "dropped": 0,
                    "iprof": {},
                    "phases": {"device": dv.dur or 0.0, "host": 0.0}}
        # tier pin: honor the explicit tier label when present (a
        # demoted parent pins degraded batches to its tier), else the
        # historical on_cpu bool from older supervisors
        tier = (msg.get("on_tier")
                or ("cpu" if msg.get("on_cpu") else None))
        cm = camp._tier_device(tier) if tier else None
        with (cm if cm is not None else contextlib.nullcontext()):
            with obs_device.phase_timer("device_phase", bi=bi,
                                        n=len(names)) as dv:
                sym = camp._explore_batch(bi, names, codes, lanes,
                                          width, creations)
                if fault is not None:
                    # after the device work ran, before the host
                    # harvest: the closest honest stand-in for
                    # "mid-superstep" a process boundary allows
                    fault.fire("mid-superstep", nth)
            with obs_device.phase_timer("host_phase", bi=bi) as hp:
                out = camp._harvest_batch(bi, sym)
        out["phases"] = {"device": dv.dur or 0.0, "host": hp.dur or 0.0}
        # the chunk step-counts this worker has compiled through the
        # shared persistent cache: the parent folds them into its
        # compile-store bucket so a RESTARTED daemon's prewarm can seed
        # them and keep engine_compiles_total flat across the restart
        out["warm_chunks"] = sorted(
            {int(c) for c in camp._warm_set(lanes, width,
                                            creations is not None)
             if not isinstance(c, tuple)})
        return out


def _run_prewarm(camp, stub: bool, msg: Dict) -> Dict:
    """AOT prewarm verb: compile a list of shape buckets ahead of
    traffic. Each bucket is a shape SKELETON — ``{lanes, width,
    tier?}`` — compiled by running ``_explore_batch`` over an all-pad
    STOP-stub corpus (shape, not content, keys the jaxpr: the
    ShapeDtypeStruct idea from tools/scaling_report.py without needing
    AOT export plumbing; the persistent cache makes the artifact
    durable). One bucket per frame-roundtrip would be cleaner but
    slower; instead the whole list rides one verb and the reply carries
    how far it got. Stub mode validates shapes and counts — the
    supervision-machinery tests' fast path."""
    buckets = list(msg.get("buckets") or [])
    done = 0
    warm_chunks: List[List[int]] = []
    for b in buckets:
        lanes = int(b.get("lanes") or 0)
        width = int(b.get("width") or 0)
        if lanes <= 0 or width <= 0:
            raise ValueError(
                f"prewarm bucket {b!r}: non-positive shape")
        if stub:
            done += 1
            warm_chunks.append([])
            continue
        # the bucket's recorded chunks are warm FLEET-wide (their
        # executables live in the shared persistent cache), so mark
        # them before exploring: the compile counter must read this
        # pass as cache traffic, not fresh compilation
        deploys = bool(b.get("deploys"))
        warm = camp._warm_set(lanes, width, deploys)
        warm.update(int(c) for c in b.get("chunks") or ())
        tier = b.get("tier") or msg.get("on_tier")
        cm = camp._tier_device(tier) if tier else None
        with (cm if cm is not None else contextlib.nullcontext()):
            with obs_trace.timer("prewarm_compile", lanes=lanes,
                                 width=width, tier=tier or ""):
                sym = camp._explore_batch(
                    -1, [], [], lanes, width,
                    creations=[] if deploys else None)
                # the wrapper compiles lazily as chunks run; touching
                # the exploration result forces every chunk through
                camp._harvest_batch(-1, sym)
        warm_chunks.append(sorted(
            {int(c) for c in warm if not isinstance(c, tuple)}))
        done += 1
    return {"done": done, "total": len(buckets), "stub": stub,
            "warm_chunks": warm_chunks}


def _drain_telemetry(msnap: Optional[Dict]) -> Optional[Dict]:
    """The per-reply telemetry payload: buffered spans/events, a fresh
    child ``monotonic()`` reading (the parent refreshes its clock
    offset against it), and the metric delta since the last reply.
    ``None`` when the parent didn't ask for tracing at init."""
    tracer = obs_trace.get_tracer()
    if tracer is None or tracer.buffer_records is None:
        return None
    after = obs_metrics.REGISTRY.snapshot()
    return {"records": tracer.drain_buffer(),
            "mono": time.monotonic(),
            "metrics": obs_metrics.snapshot_delta(after, msnap or {}),
            "_after": after}


def worker_main() -> int:
    # claim the protocol channel, then point fd 1 at stderr so engine
    # prints / jax warnings cannot corrupt a frame
    inp = os.fdopen(os.dup(sys.stdin.fileno()), "rb", buffering=0)
    out = os.fdopen(os.dup(sys.stdout.fileno()), "wb", buffering=0)
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    sys.stdout = sys.stderr
    fault = ChildFault.from_env()
    camp = None
    stub = False
    nbatch = 0
    msnap: Optional[Dict] = None
    while True:
        msg = read_frame(inp)
        if msg is None:
            return 0  # parent closed the pipe (or died): exit with it
        op = msg.get("op")
        tear = False
        try:
            if op == "init":
                stub = bool(msg.get("stub"))
                if msg.get("trace"):
                    # parent is tracing: buffer spans/events locally
                    # and ship them back with each batch reply
                    obs_trace.configure(buffer=True)
                    msnap = obs_metrics.REGISTRY.snapshot()
                device = None
                if not stub:
                    camp = _build_campaign(msg.get("config") or {})
                    # build the engine now (its tables take the
                    # backend) and say which device that was: the
                    # supervisor refuses a worker that came up on
                    # another tier than it was spawned for
                    import mythril_tpu.symbolic.engine  # noqa: F401
                    from .backend import device_record

                    device = device_record()
                # the child monotonic reading is half of the clock
                # handshake: the parent computes
                # offset = parent_mono - child_mono for span stitching
                reply = {"ok": True,
                         "value": {"pid": os.getpid(), "stub": stub,
                                   "protocol": PROTOCOL_VERSION,
                                   "mono": time.monotonic(),
                                   "device": device}}
            elif op == "ping":
                reply = {"ok": True, "value": {"pid": os.getpid(),
                                               "rss": _rss_bytes()}}
            elif op == "batch":
                nbatch += 1
                value = _run_batch(camp, stub, msg, fault, nbatch)
                tel = _drain_telemetry(msnap)
                if tel is not None:
                    msnap = tel.pop("_after")
                    value["telemetry"] = tel
                reply = {"ok": True, "value": value}
                tear = (fault is not None
                        and fault.should("mid-reply", nbatch))
            elif op == "prewarm":
                value = _run_prewarm(camp, stub, msg)
                tel = _drain_telemetry(msnap)
                if tel is not None:
                    msnap = tel.pop("_after")
                    value["telemetry"] = tel
                reply = {"ok": True, "value": value}
            elif op == "exit":
                try:
                    out.write(pack_frame({"ok": True, "value": None}))
                    out.flush()
                except OSError:
                    pass
                return 0
            else:
                reply = {"ok": False, "etype": "ValueError",
                         "emsg": f"unknown op {op!r}", "classify": None}
        except BaseException as e:  # noqa: BLE001 — relayed typed
            from .resilience import classify_backend_error

            reply = {"ok": False, "etype": type(e).__name__,
                     "emsg": str(e)[:2000],
                     "classify": classify_backend_error(e)}
        frame = pack_frame(reply)
        try:
            if tear:
                # torn mid-reply: half a frame on the wire, then a real
                # signal — the parent must treat it as worker death
                out.write(frame[:max(1, len(frame) // 2)])
                out.flush()
                os.kill(os.getpid(), fault.sig)
                time.sleep(5)
            out.write(frame)
            out.flush()
        except OSError:
            return 0  # parent went away mid-reply


if __name__ == "__main__":
    raise SystemExit(worker_main())
