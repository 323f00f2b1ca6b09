"""Scheduler: drains the admission queue into resident campaigns.

The loop that makes the daemon WARM (docs/serving.md): it pops
same-config batches from the queue and runs them through one
long-lived :class:`CorpusCampaign` per effective config — the full PR
1/2 resilience machinery (watchdog / OOM ladder / retry / bisect)
applies per batch, and because all campaigns share ONE warm-shape
registry (keyed by the engine shape class: batch width x lanes x step
budget x tx count), the second batch of any shape replays ``sym_run``'s
process-wide XLA cache instead of recompiling
(``serve_warm_compile_hits_total``). Verdicts are persisted to the
results store as each batch commits, so completed work survives a
daemon kill and is served from dedupe after restart — exactly once.

With a ``fleet_dir`` the scheduler FRONTS a fleet instead of running
locally (docs/fleet.md): admitted batches are appended to a FEED ledger
as self-contained work units (bytecode rides the unit descriptor),
remote ``--fleet-follow`` workers claim/heartbeat/commit them, and this
loop polls committed unit results back into the same entry-resolution
path. Dedupe and queue semantics are identical — the fleet only
replaces WHERE lanes run.

Single scheduler thread; entry resolution goes through the queue's one
condition, so HTTP waiters wake exactly when their results commit.

Replica safety (docs/serving.md "Overload & multi-replica serving"):
N daemons may point at ONE ``--data-dir``. Verdict persistence is
first-wins (``ResultsStore.put`` via ``exclusive_write``), so two
replicas racing the same ``(bytecode, config)`` commit exactly one
file and the loser's copy is dropped (equal by construction) — each
replica still resolves its own waiters from its own batch result. The
in-flight dedupe index is deliberately process-local: cross-replica
dedupe happens through the shared store the moment the first replica
commits. The warm-shape registry is process-local too (warmth is an
XLA-cache property of one process), but since PR 20 it is no longer a
process-local *accident*: with a compile store attached
(mythril_tpu/compilestore.py), warm observations are recorded durably
per (tier, shape-class, config-hash) bucket and replayed by the
daemon's prewarm thread, so a restarted or sibling replica re-acquires
warmth from the shared persistent cache instead of recompiling
(docs/serving.md "Compile artifacts & prewarm").
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, List, Optional

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .queue import AdmissionQueue, Entry
from .store import ResultsStore

log = logging.getLogger(__name__)


def default_campaign_factory(config: Dict):
    """Build the resident engine for one effective config. Loads the
    engine lazily — the daemon stays backend-free until the first
    non-dedupe submission actually needs lanes."""
    from ..config import DEFAULT_LIMITS, TEST_LIMITS
    from ..mythril.campaign import CorpusCampaign
    from ..resilience import FaultInjector

    limits = (TEST_LIMITS if config.get("limits_profile") == "test"
              else DEFAULT_LIMITS)
    spec = None
    if config.get("concrete_storage"):
        from ..symbolic import SymSpec

        spec = SymSpec(storage=False)
    # worker isolation (docs/resilience.md): "auto" means ON under
    # serve — an always-on daemon is exactly where a libtpu segfault
    # must be a worker restart, not daemon death
    isolation = config.get("worker_isolation", "auto")
    if isolation == "auto":
        isolation = "on"
    return CorpusCampaign(
        [],
        batch_size=int(config.get("batch_size", 8)),
        lanes_per_contract=int(config.get("lanes_per_contract", 32)),
        limits=limits,
        spec=spec,
        max_steps=int(config.get("max_steps", 256)),
        transaction_count=int(config.get("transaction_count", 1)),
        modules=config.get("modules"),
        solver_timeout=config.get("solver_timeout"),
        solver_iters=int(config.get("solver_iters", 400)),
        batch_timeout=config.get("batch_timeout"),
        max_batch_retries=int(config.get("max_batch_retries", 1)),
        fault_injector=FaultInjector.from_string(
            config.get("fault_inject")),
        oom_ladder=config.get("oom_ladder"),
        solver_workers=int(config.get("solver_workers", 1)),
        worker_isolation=isolation,
        # backend tiers (docs/resilience.md "Backend tiers"): each
        # resident campaign is placed on whatever tier its worker
        # currently holds — a crash-looping accelerator demotes just
        # this config's capacity class, and the ladder's prober climbs
        # back without a daemon restart
        backend_tiers=config.get("backend_tiers"),
    )


class Scheduler:
    def __init__(self, queue: AdmissionQueue,
                 store: Optional[ResultsStore] = None,
                 batch_size: int = 8,
                 poll: float = 0.25,
                 fleet_dir: Optional[str] = None,
                 campaign_factory: Optional[Callable] = None,
                 compile_store=None):
        self.queue = queue
        self.store = store
        self.batch_size = max(1, int(batch_size))
        self.poll = max(0.02, float(poll))
        self.fleet_dir = fleet_dir
        self.campaign_factory = campaign_factory or default_campaign_factory
        #: fleet compile-artifact store (mythril_tpu/compilestore.py):
        #: when set, every resident campaign records its warm shapes
        #: durably and the daemon's prewarm thread can replay them —
        #: this is what retires the "warmth is process-local" caveat
        #: in the module docstring for RECOVERY (in-process warmth is
        #: still per-process; the registry + shared persistent cache
        #: make re-acquiring it cheap)
        self.compile_store = compile_store
        #: one resident campaign per effective config (cfh); all share
        #: the warm-shape registry below, so config variants of one
        #: ENGINE shape class (same width/lanes/steps/tx, e.g. a
        #: different module list) still count as warm
        self._campaigns: Dict[str, object] = {}
        #: guards campaign get-or-create: the daemon's prewarm thread
        #: may materialize the baseline campaign while the loop creates
        #: one for the first request — exactly one instance must win
        self._camp_lock = threading.Lock()
        self._warm_shapes: Dict[tuple, set] = {}
        self._ledger = None
        #: fleet mode: fed-but-uncommitted units -> their entries
        self._pending: Dict[str, List[Entry]] = {}
        self._stop = threading.Event()     # drain: finish in-flight
        self._abort = threading.Event()    # give up on fleet pending
        self._thread: Optional[threading.Thread] = None
        self.batches_run = 0
        #: set to "<Type>: <msg>" if the loop thread dies of an
        #: unhandled error — /healthz flips to "degraded" and every
        #: pending request fails immediately instead of hanging until
        #: its deadline
        self.crashed: Optional[str] = None
        self._reg = obs_metrics.REGISTRY
        #: commit accounting for the serve_contracts_per_min gauge: one
        #: (monotonic time, n_contracts) sample per committed batch,
        #: pruned to the trailing window. The headline end-to-end rate
        #: (ROADMAP "contracts/min") as production sees it — fed by
        #: verdict commits, not engine internals, so fleet-committed and
        #: resident batches count the same way.
        self._commit_log: List[tuple] = []
        self._commit_window = 300.0

    # --- lifecycle ------------------------------------------------------
    def start(self) -> None:
        if self.fleet_dir is not None:
            from ..fleet import WorkLedger

            self._ledger = WorkLedger(self.fleet_dir)
            self._ledger.ensure_feed()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serve-scheduler")
        self._thread.start()

    def request_stop(self) -> None:
        """Begin draining: the in-flight batch (and, fleet mode,
        already-fed units) completes; nothing new is popped. Pair with
        ``queue.close()`` so nothing new is admitted either."""
        self._stop.set()

    def abort(self) -> None:
        """Hard stop: also abandon fed-but-uncommitted fleet units
        (their entries resolve as errors so no waiter hangs)."""
        self._abort.set()
        self._stop.set()

    def join(self, timeout: Optional[float] = None) -> bool:
        if self._thread is None:
            return True
        self._thread.join(timeout)
        return not self._thread.is_alive()

    # --- the loop -------------------------------------------------------
    def _loop(self) -> None:
        """Crash containment around the real loop: if the scheduler
        thread dies of an unhandled error, pending requests used to
        hang until their deadlines — now they FAIL immediately with
        the error string, the queue closes (new submissions get 503),
        and ``/healthz`` reports ``degraded``. The daemon keeps
        serving reads (results, metrics, health) — dying quietly is
        the one thing the loop may not do."""
        try:
            self._loop_inner()
        except Exception as e:  # noqa: BLE001 — the containment seam
            self.crashed = f"{type(e).__name__}: {str(e)[:300]}"
            log.exception("serve scheduler loop died")
            self._reg.counter(
                "serve_scheduler_crashes_total",
                help="unhandled errors that killed the scheduler "
                     "loop").inc()
            obs_trace.event("scheduler_crashed", detail=self.crashed)
            try:
                self.queue.close()
                self.queue.fail_pending(
                    f"scheduler loop died ({self.crashed}); restart "
                    "the daemon — completed contracts will be served "
                    "from the dedupe store")
            except Exception:  # noqa: BLE001 — best-effort unblock
                log.exception("failing pending entries after "
                              "scheduler crash")
            for uid, entries in list(self._pending.items()):
                for en in entries:
                    self.queue.resolve(
                        en, {"status": "error",
                             "error": f"scheduler loop died before "
                                      f"fleet unit {uid} committed "
                                      f"({self.crashed})"})
            self._pending.clear()
        finally:
            for camp in list(self._campaigns.values()):
                close = getattr(camp, "close_worker", None)
                if callable(close):
                    try:
                        close()
                    except Exception:  # noqa: BLE001 — exit path
                        log.exception("closing engine worker")
            if self._ledger is not None:
                # tell --fleet-follow workers the feed is complete so
                # they drain and exit instead of polling a dead
                # daemon's ledger
                try:
                    self._ledger.feed_close()
                except OSError:
                    pass

    def _loop_inner(self) -> None:
        while True:
            if self._ledger is not None:
                self._poll_fleet()
            if self._stop.is_set():
                if self._ledger is None or not self._pending \
                        or self._abort.is_set():
                    break
                # draining a fleet: the fed units are on remote
                # workers; keep polling for their commits
                time.sleep(min(self.poll, 0.1))
                continue
            entries = self.queue.pop_batch(self.batch_size,
                                           timeout=self.poll)
            if not entries:
                continue
            try:
                if self._ledger is not None:
                    self._feed_batch(entries)
                else:
                    self._run_batch(entries)
            except Exception as e:  # noqa: BLE001 — no waiter may hang
                log.exception("serve batch failed")
                self._reg.counter(
                    "serve_batch_errors_total",
                    help="scheduler batches that raised").inc()
                for en in entries:
                    self.queue.resolve(
                        en, {"status": "error",
                             "error": f"{type(e).__name__}: "
                                      f"{str(e)[:200]}"})
        if self._abort.is_set() and self._pending:
            for uid, entries in list(self._pending.items()):
                for en in entries:
                    self.queue.resolve(
                        en, {"status": "error",
                             "error": "daemon exited before fleet "
                                      f"unit {uid} committed"})
            self._pending.clear()

    # --- local (resident-campaign) execution ----------------------------
    def campaign_for_config(self, config: Dict, cfh: str):
        """Get-or-create the resident campaign for one effective
        config. Public so the daemon's background prewarm thread can
        materialize (and warm) the baseline config's campaign before
        the first request ever arrives."""
        with self._camp_lock:
            camp = self._campaigns.get(cfh)
            if camp is None:
                camp = self.campaign_factory(config)
                # one warm-shape registry across every resident
                # campaign: sym_run's XLA cache is process-wide, so
                # warmth is a process property, not a per-config one
                if hasattr(camp, "_warm_shapes"):
                    camp._warm_shapes = self._warm_shapes
                if (self.compile_store is not None
                        and hasattr(camp, "attach_compile_store")):
                    camp.attach_compile_store(self.compile_store,
                                              cfh=cfh)
                self._campaigns[cfh] = camp
            return camp

    def _campaign_for(self, e: Entry):
        return self.campaign_for_config(e.config, e.cfh)

    def _run_batch(self, entries: List[Entry]) -> None:
        camp = self._campaign_for(entries[0])
        warm = False
        if hasattr(camp, "shape_is_warm"):
            warm = bool(camp.shape_is_warm())
        items = [(e.uname, e.code) for e in entries]
        tenants = sorted({e.submission.tenant for e in entries})
        # one batch may serve several requests: the first entry's
        # trace_id leads the scope, the rest ride as link ids — every
        # span below (campaign, worker, solver) indexes under ALL of
        # them, so each request's /v1/trace view is complete
        ids: List[str] = []
        for e in entries:
            if e.trace_id and e.trace_id not in ids:
                ids.append(e.trace_id)
        ids = ids or [obs_trace.new_trace_id()]
        with obs_trace.trace_context(ids[0], link_ids=ids[1:]), \
                obs_trace.span("schedule", n=len(entries),
                               cfh=entries[0].cfh, warm=warm,
                               tenants=tenants):
            out = camp.run_external_batch(items)
        # stage attribution: each entry waited through the whole batch
        # device + host phases, so the batch totals ARE its stage costs
        ph = out.get("phases") if isinstance(out, dict) else None
        if isinstance(ph, dict):
            for e in entries:
                for k in ("device", "host"):
                    v = float(ph.get(k) or 0.0)
                    if v:
                        e.timings[k] = v
        self.batches_run += 1
        self._reg.counter(
            "serve_batches_total",
            help="batches the scheduler ran through resident "
                 "campaigns").inc()
        if warm:
            self._reg.counter(
                "serve_warm_compile_hits_total",
                help="batches that reused an already-compiled engine "
                     "shape class (no XLA recompile)").inc()
        self._bind_results(entries, out.get("issues") or [],
                           out.get("quarantined") or [],
                           batch=out.get("batch"),
                           batch_status=str(out.get("status", "ok")))

    def _note_commits(self, n: int) -> None:
        """Record ``n`` contract verdicts committed now and refresh the
        ``serve_contracts_per_min`` gauge over the trailing window."""
        now = time.monotonic()
        self._commit_log.append((now, n))
        cut = now - self._commit_window
        while self._commit_log and self._commit_log[0][0] < cut:
            self._commit_log.pop(0)
        total = sum(c for _, c in self._commit_log)
        # rate over the observed span (first sample to now), floored at
        # one second so a burst of early commits cannot print as an
        # absurd rate; a single sample reports over the full window
        span = max(1.0, now - self._commit_log[0][0]) \
            if len(self._commit_log) > 1 else self._commit_window
        self._reg.gauge(
            "serve_contracts_per_min",
            help="contract verdicts committed per minute "
                 "(trailing window)").set(round(total * 60.0 / span, 2))

    def _bind_results(self, entries: List[Entry], issues: List[Dict],
                      quarantined: List[Dict],
                      batch=None, batch_status: str = "ok") -> None:
        """Map a batch's engine output back onto its entries (issues
        and quarantine records name the per-entry ``uname``), persist
        fresh verdicts, resolve every entry + its dedupe followers."""
        by_uname: Dict[str, List[Dict]] = {}
        for i in issues:
            by_uname.setdefault(str(i.get("contract")), []).append(i)
        quar = {str(q.get("name")): q for q in quarantined}
        for e in entries:
            if e.uname in quar:
                # a poison contract's verdict is an error, not a
                # finding — do NOT cache it (the quarantine reason may
                # be environmental: a wedged device, an OOM'd rung)
                self.queue.resolve(
                    e, {"status": "quarantined",
                        "error": str(quar[e.uname].get("reason",
                                                       ""))[:300],
                        "issues": [], "batch": batch})
                continue
            my = []
            for i in by_uname.get(e.uname, []):
                i = dict(i)
                i["contract"] = e.name
                my.append(i)
            verdict = {"status": "ok", "issues": my,
                       "batch_status": batch_status}
            if e.trace_id:
                # provenance: the stored verdict names the request
                # trace that computed it (dedupe-served copies keep it)
                verdict["trace_id"] = e.trace_id
            if self.store is not None and self.queue.dedupe:
                t0 = time.monotonic()
                self.store.put(e.bch, e.cfh, verdict)
                e.timings["commit"] = time.monotonic() - t0
                obs_trace.event("verdict_commit", eid=e.eid,
                                bch=e.bch, trace_id=e.trace_id,
                                dur=round(e.timings["commit"], 6))
            res = dict(verdict)
            res["batch"] = batch
            self.queue.resolve(e, res)
        self._note_commits(len(entries))

    # --- fleet-fed execution (docs/fleet.md) ----------------------------
    def _feed_batch(self, entries: List[Entry]) -> None:
        # the unit config carries the requests' trace ids across the
        # ledger: the claiming worker re-enters the same trace scope
        # (campaign._run_unit), so remote spans join these requests
        cfg = dict(entries[0].config)
        ids: List[str] = []
        for e in entries:
            if e.trace_id and e.trace_id not in ids:
                ids.append(e.trace_id)
        if ids:
            cfg["trace"] = {"ids": ids}
        uid = self._ledger.feed_unit(
            [(e.uname, e.code) for e in entries], config=cfg)
        self._pending[uid] = entries
        self._reg.counter(
            "serve_fleet_units_fed_total",
            help="admitted batches appended to the feed ledger").inc()
        self._reg.gauge(
            "serve_fleet_units_pending",
            help="fed units awaiting a worker commit").set(
            len(self._pending))

    def _poll_fleet(self) -> None:
        for uid, entries in list(self._pending.items()):
            rec = self._ledger.result_record(uid)
            if rec is not None:
                self._bind_results(
                    entries, rec.get("issues") or [],
                    rec.get("quarantined") or [],
                    batch=uid,
                    batch_status=";".join(rec.get("batch_status")
                                          or []) or "ok")
                del self._pending[uid]
                self.batches_run += 1
                self._reg.counter("serve_batches_total").inc()
                continue
            if self._ledger.unit_lost(uid):
                for e in entries:
                    self.queue.resolve(
                        e, {"status": "error",
                            "error": f"fleet unit {uid} lost (re-lease "
                                     "cap exhausted)"})
                del self._pending[uid]
        self._reg.gauge("serve_fleet_units_pending").set(
            len(self._pending))

    def pending_fleet_units(self) -> int:
        return len(self._pending)

    # --- worker supervision surface (docs/resilience.md) ----------------
    def degraded_configs(self) -> List[Dict]:
        """Configs whose engine-worker crash-loop breaker is not
        closed — ``/healthz`` reports them so an orchestrator can see
        "this daemon serves, but config X runs pinned to CPU"."""
        out: List[Dict] = []
        for cfh, camp in list(self._campaigns.items()):
            status = getattr(camp, "worker_status", None)
            st = status() if callable(status) else None
            if st is not None and st.get("breaker") != "closed":
                out.append({"config": cfh, "breaker": st["breaker"],
                            "deaths_in_window": st.get(
                                "deaths_in_window"),
                            "restarts": st.get("restarts")})
        return out

    def worker_restarts(self) -> int:
        """Total engine-worker respawns across resident campaigns."""
        n = 0
        for camp in list(self._campaigns.values()):
            status = getattr(camp, "worker_status", None)
            st = status() if callable(status) else None
            if st is not None:
                n += int(st.get("restarts", 0))
        return n

    def warm_counts(self) -> tuple:
        """``(warm shape classes in this process, registry buckets)``
        for the serve heartbeat's ``warm a/b`` token. Prefers a
        resident campaign's tier-scoped count (its registry view is
        filtered to the tier it holds); a campaign-less daemon falls
        back to the store-wide bucket count. ``None`` second element =
        no compile store attached."""
        a = sum(1 for s in self._warm_shapes.values() if s)
        if self.compile_store is None:
            return a, None
        for camp in list(self._campaigns.values()):
            wc = getattr(camp, "warm_counts", None)
            if callable(wc):
                try:
                    return wc()
                except Exception:  # noqa: BLE001 — heartbeat decoration
                    break
        try:
            return a, len(self.compile_store.buckets())
        except Exception:  # noqa: BLE001 — registry scan is best-effort
            return a, 0

    # --- backend-tier surface (docs/resilience.md "Backend tiers") ------
    def tier_status(self) -> List[Dict]:
        """Per-config backend-tier ladder state: which capacity class
        each resident campaign currently holds, plus its demotion /
        re-promotion / flap-damping accounting. ``/healthz`` reports it
        so an orchestrator can see "config X runs demoted on cpu, the
        prober is climbing" without grepping logs."""
        out: List[Dict] = []
        for cfh, camp in list(self._campaigns.items()):
            status = getattr(camp, "tier_status", None)
            st = status() if callable(status) else None
            if st is not None:
                st["config"] = cfh
                out.append(st)
        return out


    def engine_status(self) -> List[Dict]:
        """Per-config engine report (``/healthz`` ``engines``): the
        device each resident campaign's engine got — from its worker's
        init reply, or read in-process after the first batch — plus
        host-callback support, native evaluator and compile counters.
        Empty until a campaign has an engine."""
        out: List[Dict] = []
        for cfh, camp in list(self._campaigns.items()):
            status = getattr(camp, "engine_status", None)
            st = status() if callable(status) else None
            if st:
                st["config"] = cfh
                out.append(st)
        return out


class StoreOnlyScheduler:
    """The null scheduler behind ``serve --store-only`` (docs/serving.md
    "Verdict segments & edge replicas"): an edge replica has NO engine
    — every answer comes from the dedupe store at admission time, so
    nothing ever reaches a scheduler. This stub keeps the daemon's
    lifecycle and ``/healthz`` surfaces working without importing any
    engine/JAX code (the light-imports contract the store-only mode is
    built on)."""

    batches_run = 0
    crashed = None

    def start(self) -> None:
        pass

    def request_stop(self) -> None:
        pass

    def abort(self) -> None:
        pass

    def join(self, timeout: Optional[float] = None) -> bool:
        return True

    def pending_fleet_units(self) -> int:
        return 0

    def degraded_configs(self) -> List[Dict]:
        return []

    def worker_restarts(self) -> int:
        return 0

    def tier_status(self) -> List[Dict]:
        return []

    def engine_status(self) -> List[Dict]:
        return []

    def warm_counts(self) -> tuple:
        return 0, None


__all__ = ["Scheduler", "StoreOnlyScheduler", "default_campaign_factory"]
