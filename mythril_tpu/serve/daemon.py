"""Daemon lifecycle: wiring, signal handling, graceful drain.

:class:`AnalysisDaemon` composes the serve layer (docs/serving.md):
one results store, one admission queue, one scheduler thread, one
threaded HTTP server. The lifecycle contract:

- **start** — scheduler + HTTP come up; the engine itself loads lazily
  on the first batch that actually needs lanes, so a daemon fronting a
  pure-dedupe workload never initializes a backend;
- **SIGTERM / SIGINT** (or :meth:`shutdown`) — DRAIN: new submissions
  get HTTP 503 immediately, the in-flight batch finishes and its
  verdicts persist to the store (fleet mode: already-fed units get up
  to ``drain_timeout`` for their workers to commit, then the feed is
  closed), every still-queued entry resolves with an error so no
  long-poller hangs, and the process exits;
- **restart** — completed verdicts are durable files keyed on
  ``(bytecode_hash, config_hash)``, so resubmitting after a kill
  serves finished work from the dedupe store and re-analyzes only what
  never committed: exactly-once results without any WAL. This is the
  serve-layer face of the PR 4/5 kill+resume guarantees (the soak's
  ``serve`` leg kills a daemon mid-batch and asserts it).

A second signal while draining aborts the drain (fleet pending included)
— the operator's escalation path when a batch is wedged.
"""

from __future__ import annotations

import logging
import os
import signal
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .http import ServeHTTPServer
from .queue import AdmissionQueue, ShedPolicy, TenantQuota
from .scheduler import Scheduler, StoreOnlyScheduler
from .store import ResultsStore

log = logging.getLogger(__name__)


@dataclass
class ServeOptions:
    """Daemon-level analysis configuration — the baseline every
    submission's effective config derives from. ``OVERRIDABLE`` names
    the per-request knobs; everything else is fixed at daemon start so
    one tenant cannot stampede the compile cache with exotic shapes."""

    batch_size: int = 8
    lanes_per_contract: int = 32
    max_steps: int = 256
    transaction_count: int = 1
    modules: Optional[List[str]] = None
    limits_profile: str = "default"
    solver_iters: int = 400
    solver_timeout: Optional[float] = None
    solver_workers: int = 1
    batch_timeout: Optional[float] = None
    max_batch_retries: int = 1
    oom_ladder: Optional[Sequence[str]] = None
    fault_inject: Optional[str] = None
    concrete_storage: bool = False
    #: engine-worker process isolation (docs/resilience.md): "auto"
    #: resolves to ON under serve — backend death must be a worker
    #: restart, not daemon death. Operational (excluded from the
    #: dedupe config hash): flipping it must not split the verdict
    #: cache.
    worker_isolation: str = "auto"
    #: ranked backend-tier ladder for resident campaigns (comma string
    #: or sequence; None = detect — docs/resilience.md "Backend
    #: tiers"). Operational like worker_isolation: which tier served a
    #: batch must not split the verdict cache — the issues in the
    #: bytecode don't depend on the silicon that found them.
    backend_tiers: Optional[Sequence[str]] = None
    #: per-request overrides accepted in the submit body's ``options``
    OVERRIDABLE = ("max_steps", "transaction_count", "modules")

    def effective(self, overrides: Dict) -> Dict:
        """The config dict that keys dedupe (``config_hash``) and
        shape-class bucketing. Unknown / non-overridable option keys
        raise — silently ignoring them would dedupe two analyses the
        client believes are different."""
        bad = [k for k in overrides if k not in self.OVERRIDABLE]
        if bad:
            raise ValueError(
                f"options {sorted(bad)} are not overridable per "
                f"request (allowed: {list(self.OVERRIDABLE)})")
        cfg = {
            "batch_size": self.batch_size,
            "lanes_per_contract": self.lanes_per_contract,
            "max_steps": int(overrides.get("max_steps",
                                           self.max_steps)),
            "transaction_count": int(
                overrides.get("transaction_count",
                              self.transaction_count)),
            "modules": (list(overrides["modules"])
                        if overrides.get("modules") is not None
                        else (list(self.modules)
                              if self.modules else None)),
            "limits_profile": self.limits_profile,
            "solver_iters": self.solver_iters,
            "solver_timeout": self.solver_timeout,
            "solver_workers": self.solver_workers,
            "batch_timeout": self.batch_timeout,
            "max_batch_retries": self.max_batch_retries,
            "oom_ladder": (tuple(self.oom_ladder)
                           if self.oom_ladder is not None else None),
            "fault_inject": self.fault_inject,
            "concrete_storage": self.concrete_storage,
            "worker_isolation": self.worker_isolation,
            "backend_tiers": (tuple(self.backend_tiers)
                              if isinstance(self.backend_tiers,
                                            (list, tuple))
                              else self.backend_tiers),
        }
        return cfg


class AnalysisDaemon:
    def __init__(self, options: Optional[ServeOptions] = None,
                 data_dir: str = "serve_data",
                 host: str = "127.0.0.1", port: int = 8780,
                 dedupe: bool = True, max_queue: int = 4096,
                 drain_timeout: float = 30.0,
                 fleet_dir: Optional[str] = None,
                 campaign_factory=None,
                 solver_store: Optional[str] = "auto",
                 quotas: Optional[Dict[str, TenantQuota]] = None,
                 default_quota: Optional[TenantQuota] = None,
                 shed: Optional[ShedPolicy] = "auto",
                 follow_uri: Optional[str] = None,
                 follow_poll: float = 2.0,
                 backfill_uri: Optional[str] = None,
                 backfill_window: int = 64,
                 backfill_poll: float = 2.0,
                 compact_every: Optional[float] = None,
                 store_only: bool = False,
                 store_refresh: float = 2.0,
                 compile_store: Optional[str] = "auto",
                 prewarm: bool = True):
        if store_only:
            # an edge replica has no engine: it cannot host a fleet,
            # tail the chain, backfill history, or serve without the
            # store it exists to serve from
            bad = [n for n, v in (("--fleet", fleet_dir),
                                  ("--follow", follow_uri),
                                  ("--backfill", backfill_uri),
                                  ("--compact-every", compact_every))
                   if v]
            if bad:
                raise ValueError(
                    f"--store-only is incompatible with "
                    f"{', '.join(bad)}")
            if not dedupe:
                raise ValueError(
                    "--store-only needs the dedupe store "
                    "(--no-dedupe makes no sense here)")
        self.options = options or ServeOptions()
        self.data_dir = data_dir
        os.makedirs(data_dir, exist_ok=True)
        self.store = ResultsStore(os.path.join(data_dir, "store"))
        # overload protection defaults ON (docs/serving.md "Overload &
        # multi-replica serving"): the default thresholds only engage
        # when the queue is nearly full or entries have sat for tens
        # of seconds — an unloaded daemon never sheds. None disables.
        if shed == "auto":
            shed = ShedPolicy()
        # per-QUERY solver verdict store (docs/solver.md) beside the
        # per-CONTRACT dedupe store: the daemon's solver work survives
        # restarts and is shared with any fleet workers it fronts.
        # "auto" = <data-dir>/solver_store; None disables.
        if solver_store == "auto":
            solver_store = os.path.join(data_dir, "solver_store")
        if store_only:
            solver_store = None  # no solver work will ever run here
        self.solver_store = solver_store
        self.store_only = bool(store_only)
        self.queue = AdmissionQueue(
            store=self.store, dedupe=dedupe, max_depth=max_queue,
            config_fn=self.options.effective, quotas=quotas,
            default_quota=default_quota, shed=shed,
            store_only=store_only)
        self.follow_uri = follow_uri
        self.follow_poll = float(follow_poll)
        self.follower = None
        self.backfill_uri = backfill_uri
        self.backfill_window = int(backfill_window)
        self.backfill_poll = float(backfill_poll)
        self.backfill = None
        self.compact_every = compact_every
        self.store_refresh = max(0.05, float(store_refresh))
        self._bg_stop = threading.Event()
        self._bg_threads: List[threading.Thread] = []
        # fleet compile-artifact store + AOT prewarm (docs/serving.md
        # "Compile artifacts & prewarm"): "auto" puts the registry +
        # shared XLA cache under the data dir so sibling/restarted
        # replicas share it; None disables. A store-only replica has
        # no engine and therefore nothing to compile. Created lazily
        # in start() — the compilestore import chain reaches jax, and
        # the daemon constructor stays backend-free.
        if compile_store == "auto":
            compile_store = (None if store_only
                             else os.path.join(data_dir, "compile_store"))
        self.compile_store_dir = compile_store
        self.compile_store = None
        self.prewarm = bool(prewarm) and compile_store is not None
        self._prewarm_doc: Optional[Dict] = None
        if store_only:
            self.scheduler = StoreOnlyScheduler()
        else:
            self.scheduler = Scheduler(
                self.queue, store=self.store,
                batch_size=self.options.batch_size,
                fleet_dir=fleet_dir, campaign_factory=campaign_factory)
        self.host = host
        self._port = port
        self.drain_timeout = float(drain_timeout)
        self.httpd: Optional[ServeHTTPServer] = None
        self._http_thread: Optional[threading.Thread] = None
        self.state = "starting"
        self.t_start = time.monotonic()
        self._done = threading.Event()
        self._shutdown_lock = threading.Lock()
        self._signals = 0

    # --- surface the HTTP layer routes through --------------------------
    def submit(self, contracts: Sequence[Tuple[str, bytes]], **kw):
        if self.state != "serving":
            from .queue import QueueClosed

            raise QueueClosed(f"daemon is {self.state}")
        return self.queue.submit(contracts, **kw)

    def health(self) -> Dict:
        if self.store_only:
            # the smt package import chain reaches JAX — a store-only
            # replica's healthz must stay backend-free (there is no
            # solver store here anyway)
            vstore = None
        else:
            from ..smt import portfolio as smt_portfolio

            vstore = smt_portfolio.get_store()
        qstats = self.queue.stats()
        doc = {
            "ok": True,
            "state": self.state,
            "queue_depth": qstats["queue_depth"],
            "oldest_entry_age_sec": qstats["oldest_entry_age_sec"],
            "shed_state": qstats["shed_state"],
            "tenants": qstats["tenants"],
            "batches_run": self.scheduler.batches_run,
            "fleet_units_pending": self.scheduler.pending_fleet_units(),
            "store_verdicts": self.store.count(),
            "solver_verdicts": vstore.count() if vstore else 0,
            "uptime_sec": round(time.monotonic() - self.t_start, 3),
            "pid": os.getpid(),
            "engine_worker_restarts": self.scheduler.worker_restarts(),
        }
        # a dead scheduler loop degrades the whole daemon (requests
        # would never schedule); an OPEN crash-loop breaker degrades
        # one config (its batches run pinned to in-process CPU) while
        # everything else serves normally — orchestrators see both
        if self.scheduler.crashed:
            doc["ok"] = False
            doc["state"] = "degraded"
            doc["error"] = f"scheduler loop died: {self.scheduler.crashed}"
        degraded = self.scheduler.degraded_configs()
        if degraded:
            doc["degraded_configs"] = degraded
        # backend-tier capacity classes (docs/resilience.md "Backend
        # tiers"): per-config ladder state, present once any resident
        # campaign has needed a ladder
        tiers = self.scheduler.tier_status()
        if tiers:
            doc["backend_tiers"] = tiers
        # which device each config's engine actually got (a worker that
        # came up on the CPU must not look like accelerator capacity)
        engines = self.scheduler.engine_status()
        if engines:
            doc["engines"] = engines
            doc["device"] = engines[0].get("device")
        # compile-artifact prewarm state (docs/serving.md "Compile
        # artifacts & prewarm"): what the background pass did / is
        # doing, so an orchestrator can tell "came back warm" from
        # "still compiling lazily"
        if self.compile_store_dir and not self.store_only:
            doc["prewarm"] = (dict(self._prewarm_doc)
                              if self._prewarm_doc is not None
                              else {"state": ("pending" if self.prewarm
                                              else "disabled"),
                                    "done": 0, "total": 0,
                                    "last_error": None})
        if self.follower is not None:
            doc["follower"] = self.follower.status()
        if self.backfill is not None:
            doc["backfill"] = self.backfill.status()
        doc["store_generation"] = self.store.generation()
        if self.store_only:
            doc["store_only"] = True
        return doc

    @property
    def port(self) -> int:
        """The BOUND port (``--port 0`` asks the OS for a free one)."""
        if self.httpd is not None:
            return self.httpd.server_address[1]
        return self._port

    # --- lifecycle ------------------------------------------------------
    def start(self) -> None:
        obs_metrics.REGISTRY.enabled = True  # /metrics is always on
        # serve is always-traced (docs/observability.md "Distributed
        # tracing"): without an operator-installed tracer (--trace),
        # install one on the data dir so /v1/trace, per-result timings
        # and worker span backhaul work out of the box. Size rotation
        # bounds the JSONL log for long-lived daemons.
        self._own_tracer = None
        if not obs_trace.active():
            self._own_tracer = obs_trace.configure(
                os.path.join(self.data_dir, "trace.json"))
        if self.solver_store:
            # resident campaigns run with solver_store=None, so the
            # daemon-installed store stays in force for every batch;
            # /metrics exposes the portfolio ladder from the first
            # scrape (register_metrics inside set_store). The previous
            # store is restored on shutdown — in-process daemons
            # (tests) must not leak their store into later work.
            from ..smt import portfolio as smt_portfolio

            self._prev_solver_store = smt_portfolio.set_store(
                self.solver_store)
        if self.compile_store_dir and not self.store_only:
            from ..compilestore import CompileStore

            self.compile_store = CompileStore(self.compile_store_dir)
            self.scheduler.compile_store = self.compile_store
        self.scheduler.start()
        self.httpd = ServeHTTPServer((self.host, self._port), self)
        self._http_thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True,
            name="serve-http")
        self._http_thread.start()
        self.state = "serving"
        if self.follow_uri:
            from ..utils.loader import rpc_client_from_uri
            from .follower import ChainFollower

            self.follower = ChainFollower(
                self, rpc_client_from_uri(self.follow_uri),
                poll=self.follow_poll)
            self.follower.start()
        if self.backfill_uri:
            from ..utils.loader import rpc_client_from_uri
            from .backfill import ChainBackfill

            self.backfill = ChainBackfill(
                self, rpc_client_from_uri(self.backfill_uri),
                window=self.backfill_window, poll=self.backfill_poll)
            self.backfill.start()
        if self.compact_every and not self.store_only:
            t = threading.Thread(target=self._compact_loop,
                                 daemon=True, name="serve-compactor")
            t.start()
            self._bg_threads.append(t)
        if self.store_only:
            t = threading.Thread(target=self._refresh_loop,
                                 daemon=True, name="serve-refresher")
            t.start()
            self._bg_threads.append(t)
        if self.prewarm and self.compile_store is not None:
            t = threading.Thread(target=self._prewarm_loop,
                                 daemon=True, name="serve-prewarm")
            t.start()
            self._bg_threads.append(t)
        obs_trace.event("serve_started", host=self.host, port=self.port,
                        data_dir=self.data_dir)
        log.info("serving on %s:%d (data dir %s)", self.host, self.port,
                 self.data_dir)

    def _compact_loop(self) -> None:
        """Background compactor (``--compact-every``): periodically
        fold settled loose verdicts into the segment tier. ONE replica
        per data dir runs this (docs/serving.md deployment contract);
        a failed pass is logged and retried next period — the loose
        files it would have folded are still fully servable."""
        while not self._bg_stop.wait(self.compact_every):
            try:
                stats = self.store.compact()
                if stats.get("folded") or stats.get("dupes"):
                    log.info("compacted store: %s", stats)
            except Exception as e:  # noqa: BLE001 — keep the daemon up
                obs_metrics.REGISTRY.counter(
                    "serve_store_compaction_errors_total",
                    help="background compaction passes that failed "
                         "(retried next period)").inc()
                log.warning("compaction failed: %s: %s",
                            type(e).__name__, str(e)[:200])

    def _prewarm_loop(self) -> None:
        """Background AOT prewarm (docs/serving.md "Compile artifacts
        & prewarm"): on daemon start, materialize the baseline config's
        resident campaign and replay the registry's hottest buckets for
        its tier; afterwards, poll for recovery events — a worker
        respawn or a tier re-promotion flags ``_prewarm_pending`` on
        its campaign — and re-prewarm. Strictly subordinate to live
        traffic: the pass yields between buckets whenever the queue has
        work (or the daemon is draining), so a submitted request is
        scheduled without waiting for prewarm completion. Every failure
        here degrades to lazy compile — this thread may never take the
        daemon down."""

        def busy() -> bool:
            if self._bg_stop.is_set():
                return True
            try:
                return self.queue.stats()["queue_depth"] > 0
            except Exception:  # noqa: BLE001 — err on yielding
                return True

        try:
            from .store import config_hash

            cfg = self.options.effective({})
            camp = self.scheduler.campaign_for_config(cfg,
                                                      config_hash(cfg))
        except Exception as e:  # noqa: BLE001 — degrade to lazy compile
            self._prewarm_doc = {"state": "failed", "done": 0,
                                 "total": 0,
                                 "last_error": f"{type(e).__name__}: "
                                               f"{str(e)[:200]}"}
            log.warning("prewarm: baseline campaign unavailable: %s", e)
            return
        first = True
        while not self._bg_stop.is_set():
            for camp in list(self.scheduler._campaigns.values()):
                if self._bg_stop.is_set():
                    break
                # a factory-injected stand-in campaign (tests, custom
                # embedders) may not speak the prewarm protocol
                if not hasattr(camp, "prewarm_from_store"):
                    continue
                if not (first or getattr(camp, "_prewarm_pending",
                                         False)):
                    continue
                try:
                    self._prewarm_doc = camp.prewarm_from_store(
                        should_stop=busy)
                except Exception as e:  # noqa: BLE001 — lazy compile
                    self._prewarm_doc = {
                        "state": "failed", "done": 0, "total": 0,
                        "last_error": f"{type(e).__name__}: "
                                      f"{str(e)[:200]}"}
                    log.warning("prewarm pass failed: %s", e)
            first = False
            self._bg_stop.wait(0.25)

    def _refresh_loop(self) -> None:
        """Store-only replica poll: pick up manifest generations
        committed by the analysis fleet on the shared/snapshotted data
        dir."""
        while not self._bg_stop.wait(self.store_refresh):
            try:
                self.store.refresh()
            except Exception as e:  # noqa: BLE001 — keep serving
                log.warning("manifest refresh failed: %s: %s",
                            type(e).__name__, str(e)[:200])

    def shutdown(self, reason: str = "shutdown") -> None:
        """Graceful drain; idempotent and safe from any thread (the
        signal path runs it on a helper thread so the handler itself
        stays async-signal-trivial)."""
        with self._shutdown_lock:
            if self.state in ("draining", "stopped"):
                return
            self.state = "draining"
        obs_trace.event("serve_draining", reason=reason)
        log.info("draining (%s): rejecting new submissions, finishing "
                 "the in-flight batch", reason)
        self._bg_stop.set()
        if self.follower is not None:
            # the follower stops BEFORE the queue closes, so its last
            # block either submitted fully or will be retried from the
            # durable cursor on restart — never half-ingested
            self.follower.stop()
        if self.backfill is not None:
            # same ordering argument: a window interrupted before its
            # cursor advanced is simply re-scanned on restart, and the
            # dedupe store makes the overlap free
            self.backfill.stop()
        self.queue.close()
        self.scheduler.request_stop()
        if not self.scheduler.join(self.drain_timeout):
            # the in-flight batch (or a fleet worker) is wedged past
            # the budget: abandon it — its entries resolve as errors,
            # its verdicts simply never land (re-analyzed on restart)
            log.warning("drain timeout (%.1fs): abandoning the "
                        "in-flight work", self.drain_timeout)
            self.scheduler.abort()
            self.scheduler.join(2.0)
        failed = self.queue.fail_pending(
            "daemon shut down before this entry was scheduled; "
            "resubmit — completed contracts will be served from the "
            "dedupe store")
        if failed:
            log.info("failed %d still-queued entries", failed)
        if self.httpd is not None:
            self.httpd.shutdown()
            self.httpd.server_close()
        if self.solver_store and hasattr(self, "_prev_solver_store"):
            from ..smt import portfolio as smt_portfolio

            smt_portfolio.set_store(self._prev_solver_store)
        self.state = "stopped"
        obs_trace.event("serve_stopped", reason=reason,
                        queued_failed=failed)
        if (getattr(self, "_own_tracer", None) is not None
                and obs_trace.get_tracer() is self._own_tracer):
            obs_trace.close()
            self._own_tracer = None
        self._done.set()

    def handle_signal(self, signum, frame=None) -> None:
        """SIGTERM/SIGINT: first one drains, a second one escalates to
        abort (the wedged-batch escape hatch)."""
        self._signals += 1
        if self._signals >= 2:
            self.scheduler.abort()
        name = signal.Signals(signum).name
        threading.Thread(target=self.shutdown, args=(name,),
                         daemon=True,
                         name="serve-shutdown").start()

    def install_signal_handlers(self) -> None:
        signal.signal(signal.SIGTERM, self.handle_signal)
        signal.signal(signal.SIGINT, self.handle_signal)

    def serve_forever(self) -> None:
        """Start, then block until a signal (or another thread's
        :meth:`shutdown`) completes the drain."""
        self.start()
        self._done.wait()

    def wait_stopped(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)


__all__ = ["AnalysisDaemon", "ServeOptions"]
