"""Corpus-scale analysis campaign (BASELINE configs 2-3).

The north star is 10k contracts through the full SWC suite in minutes —
nothing like the reference exists for this (users shell-script one
``myth`` process per contract, SURVEY §2.3); the frontier engine instead
streams fixed-shape BATCHES of contracts through ONE compiled program:

- every batch has exactly ``batch_size`` contracts x ``lanes_per_contract``
  lanes (short batches pad with a STOP stub), so XLA compiles once and
  every subsequent batch replays the cached executable;
- a durable JSON checkpoint (issues + batch cursor; checksummed,
  rotated — docs/checkpointing.md) lands every ``checkpoint_every``
  batches (default: every batch); resume verifies it, falls back to
  the rotated copy if the newest write was torn, and skips completed
  batches — a killed 10k-contract run loses at most one cadence of
  work even when the kill lands mid-checkpoint-write;
- the campaign report carries the BASELINE metrics: contracts/sec,
  paths/sec, issues, solver statistics, per-batch wall times;
- execution is fault-isolated (docs/resilience.md): each batch runs
  under an optional wall-clock watchdog, a RESOURCE_EXHAUSTED batch
  walks the degradation ladder (halve lanes → halve batch width → CPU)
  instead of failing, any other failure is retried then BISECTED so
  poison contracts are quarantined individually, and backend loss
  degrades through bounded re-probes to an explicit CPU fallback — a
  10k campaign loses at most the poison contracts;
- with ``pipeline=True`` (the CLI default; docs/performance.md) batch
  *i*'s HOST phase (detection modules, witness search, report merge)
  runs on a worker thread while batch *i+1*'s DEVICE phase (corpus
  packing + sym_run) runs on the main thread, and checkpoint
  serialization+fsync moves to a background writer — the device never
  idles waiting for the solver. Results are byte-identical to the
  serial path (commits stay in batch order; one host phase in flight);
  ANY fault drains the pipeline back to the serial
  retry/degrade/bisect machinery above, so PR 1/2 semantics hold
  unchanged;
- with ``fleet_dir`` set (``--fleet``; docs/fleet.md) the campaign is
  ELASTIC across hosts: workers claim leased work units from a shared
  filesystem ledger, heartbeat them while running, reclaim a dead
  host's stale leases, and commit per-unit results exactly once —
  ``merge_campaigns`` then closes a coverage manifest over
  analyzed/quarantined/lost. The static ``num_hosts/host_index``
  strided split stays as the zero-coordination fast path.

CLI: ``python -m mythril_tpu analyze --corpus DIR`` (see interfaces/cli).
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

if TYPE_CHECKING:  # import is heavy at runtime (engine); lazy below
    from ..symbolic import SymSpec

from ..config import DEFAULT_LIMITS, DEFAULT_RESILIENCE, LimitsConfig
from ..fleet import contract_record, corpus_fingerprint
from ..obs import device as obs_device
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..resilience import (BackendManager, BatchTimeout, DeviceLostError,
                          FaultInjector, WorkerCrashLoop,
                          classify_backend_error, run_with_watchdog)
from ..utils.checkpoint import (BackgroundCheckpointWriter,
                                load_json_checkpoint_resilient,
                                save_json_checkpoint)

# NOTE: no engine imports at module level — ``campaign-merge`` (pure
# dict math over per-host JSONs) must be runnable without initializing a
# JAX backend: importing the symbolic package builds jnp tables, which
# on a wedged TPU runtime hangs the process before main() ever runs.
# SymSpec loads lazily inside CorpusCampaign.__init__.

log = logging.getLogger(__name__)

#: pad contract for short batches: plain STOP (no paths beyond the seed,
#: no issues, negligible lane cost)
_PAD_BYTECODE = b"\x00"

#: constructor of a contract that came without creation code in a batch
#: that deploys: plain STOP (it writes nothing), as single-contract
#: ``analyze`` fills in (mythril/orchestration.py)
_PAD_CREATION = b"\x00"

#: warm-shape marker for worker-isolated batches: the ENGINE WORKER's
#: process-wide XLA cache is warm for the shape class, not this
#: process's — the token is discarded when the worker dies (a fresh
#: worker recompiles), keeping serve's warm-compile accounting honest
_WORKER_WARM = ("worker-resident",)


#: a corpus directory's manifest of one linked system ends in this
SYSTEM_MANIFEST = ".system.json"


def load_corpus_dir(path: str,
                    max_members: Optional[int] = None) -> List[tuple]:
    """The corpus under ``path``, sorted for a stable batch order: one
    ``(name, runtime bytecode)`` pair for every *.hex / *.bin /
    *.bin-runtime file (hex-encoded, 0x prefix optional), except that
    ``X.bin`` beside ``X.bin-runtime`` (what ``solc --bin --bin-runtime``
    writes) is ONE contract, ``(X, runtime, creation bytecode)``: the
    campaign runs its constructor and starts the message calls from
    the storage it left.

    A ``S.system.json`` file is the manifest of a linked system::

        {"system": "S", "members": [{"name": "X", "address": "0x.."}, ..]}

    Its members (files of the directory, named without their suffix) are
    contracts that call each other at those addresses; the order is the
    order they deploy in. Each comes back as ``(X, runtime, creation or
    None, {"system": "S", "address": int})``, the members of a system
    together and in the manifest's order, where its first member sorts.
    The campaign keeps a system whole: one batch, one world, one item of
    retry, bisection and quarantine. ``max_members`` (``max_accounts -
    2``: attacker, creator, then the members) refuses a larger system
    here. The manifest's presence is the switch: a directory without one
    reads as it always did."""
    import json

    from ..disassembler.disassembly import _to_bytes

    def read(fn):
        with open(os.path.join(path, fn)) as fh:
            return fh.read().strip()

    files = sorted(os.listdir(path))
    # X.bin-runtime -> the X.bin beside it
    creation_of = {fn: fn[:-len("-runtime")] for fn in files
                   if fn.endswith(".bin-runtime")
                   and fn[:-len("-runtime")] in files}
    link_of: Dict[str, dict] = {}
    members_of: Dict[str, List[str]] = {}
    for fn in files:
        if not fn.endswith(SYSTEM_MANIFEST):
            continue
        doc = json.loads(read(fn))
        system = str(doc.get("system") or fn[:-len(SYSTEM_MANIFEST)])
        members = doc.get("members") or []
        if max_members is not None and len(members) > max_members:
            raise ValueError(
                f"{fn}: a system of {len(members)} members does not fit: "
                f"a lane's account table holds attacker, creator and at "
                f"most {max_members} members (max_accounts - 2)")
        if system in members_of or not members:
            raise ValueError(f"{fn}: system {system!r} is empty or has a "
                             f"second manifest")
        addrs = set()
        for m in members:
            name, addr = str(m["name"]), int(str(m["address"]), 16)
            if name in link_of or addr in addrs:
                raise ValueError(f"{fn}: member {name!r} or its address "
                                 f"appears twice")
            addrs.add(addr)
            link_of[name] = {"system": system, "address": addr}
        members_of[system] = [str(m["name"]) for m in members]
    ordered: List[tuple] = []
    for fn in files:
        if not fn.endswith((".hex", ".bin", ".bin-runtime")) \
                or fn in creation_of.values():
            continue
        text = read(fn)
        if not text:
            continue
        rec = (fn.rsplit(".", 1)[0], _to_bytes(text))
        creation = read(creation_of[fn]) if fn in creation_of else ""
        ordered.append(rec + (_to_bytes(creation),) if creation else rec)
    recs = {rec[0]: rec for rec in ordered}
    missing = sorted(set(link_of) - set(recs))
    if missing:
        raise ValueError(f"manifest member(s) without a code file under "
                         f"{path}: {', '.join(missing)}")
    out, placed = [], set()
    for rec in ordered:
        name = rec[0]
        if name not in link_of:
            out.append(rec)
            continue
        system = link_of[name]["system"]
        if system in placed:
            continue
        placed.add(system)
        for member in members_of[system]:
            n, code, *rest = recs[member]
            out.append((n, code, rest[0] if rest else None,
                        link_of[member]))
    if not out:
        raise ValueError(f"no *.hex / *.bin corpus files under {path}")
    return out


def record_link(item: Sequence) -> Optional[dict]:
    """The system a corpus record belongs to (``{"system", "address"}``),
    or None for a contract that is alone in its world."""
    return item[3] if len(item) > 3 and item[3] else None


def corpus_units(items: Sequence[tuple]) -> List[List[tuple]]:
    """``items`` cut into the campaign's units: the members of one
    linked system, which stand together, are ONE unit; every other
    contract is a unit of its own."""
    units: List[List[tuple]] = []
    last = None
    for it in items:
        ln = record_link(it)
        if ln is not None and last == ln["system"]:
            units[-1].append(it)
        else:
            units.append([it])
        last = ln["system"] if ln is not None else None
    return units


def _split_records(items: Sequence[tuple]):
    """``(names, runtime codes, creation codes, links)`` of a batch; the
    creation codes are None when no contract of it has any, the links
    (:func:`record_link` of each) when no contract belongs to a system."""
    recs = [contract_record(i) for i in items]
    creations = [k for _, _, k in recs]
    links = [record_link(i) for i in items]
    return ([n for n, _, _ in recs], [c for _, c, _ in recs],
            creations if any(k is not None for k in creations) else None,
            links if any(links) else None)


class _HostPhaseStart:
    """When one pipelined host phase starts. ``release(after)`` opens
    it, once, for whichever comes first (``_run_pipelined`` says what
    can); ``wait()`` blocks the worker until then and returns that
    first ``after`` (None: the run is going down, do no work)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._open = threading.Event()
        self._after: Optional[str] = None

    def release(self, after: Optional[str]) -> None:
        with self._lock:
            if not self._open.is_set():
                self._after = after
                self._open.set()

    def wait(self) -> Optional[str]:
        self._open.wait()
        return self._after


@dataclass
class CampaignResult:
    contracts: int = 0
    batches: int = 0
    issues: List[Dict] = field(default_factory=list)
    wall_sec: float = 0.0
    compile_sec: float = 0.0   # first batch (compile-dominated)
    paths_total: int = 0
    dropped_forks: int = 0
    solver: Dict = field(default_factory=dict)
    batch_wall: List[float] = field(default_factory=list)
    iprof: Dict[str, int] = field(default_factory=dict)  # opcode -> count
    # fault isolation (resilience layer): poison contracts the campaign
    # lost, batch-level retry count, per-batch outcome markers, and the
    # BackendManager's probe/fallback/recovery event log
    quarantined: List[Dict] = field(default_factory=list)
    retries: int = 0
    batch_status: List[str] = field(default_factory=list)
    backend_events: List[Dict] = field(default_factory=list)
    # fleet mode (docs/fleet.md): this worker's committed unit records,
    # the ledger's lost list, and the manifest merge_campaigns needs for
    # exactly-once accounting + the coverage manifest
    fleet: Dict = field(default_factory=dict)
    # staged solver-portfolio session delta (docs/solver.md): per-stage
    # attempts/hits/latency + the Z3-avoided headline
    solver_portfolio: Dict = field(default_factory=dict)
    # backend.engine_report() of the process that ran the engine (this
    # one, or the engine worker), as of the last batch: the device it
    # got, host-callback support, native evaluator, compile counters
    engine: Dict = field(default_factory=dict)

    def as_dict(self) -> Dict:
        # rates derive from the per-batch wall times, which the
        # checkpoint persists — a resumed run must not divide an
        # all-batches numerator by a one-session denominator
        total = sum(self.batch_wall)
        steady = self.batch_wall[1:] or self.batch_wall
        per_batch = self.contracts / self.batches if self.batches else 0.0
        steady_rate = (
            round(per_batch * len(steady) / sum(steady), 3)
            if steady and sum(steady) > 0 else 0.0
        )
        return {
            "contracts": self.contracts,
            "batches": self.batches,
            "issues": len(self.issues),
            "wall_sec": round(total, 3),
            "wall_sec_this_session": round(self.wall_sec, 3),
            "contracts_per_sec": round(
                self.contracts / total, 3) if total else 0.0,
            "contracts_per_sec_steady": steady_rate,
            # the headline end-to-end metric (ROADMAP "contracts/min"):
            # same ratio, operator-scale units — benches, heartbeats and
            # serve /metrics all quote this one
            "contracts_per_min": round(
                self.contracts / total * 60.0, 2) if total else 0.0,
            "paths_total": self.paths_total,
            "paths_per_sec": round(
                self.paths_total / total, 1) if total else 0.0,
            "dropped_forks": self.dropped_forks,
            "solver": self.solver,
            # headline observable for the silent-false-negative channel:
            # share of solver queries that returned neither sat nor unsat
            "solver_unknown_rate": (
                round(self.solver.get("unknown", 0)
                      / self.solver["attempts"], 4)
                if self.solver.get("attempts") else 0.0
            ),
            "quarantined": self.quarantined,
            "retries": self.retries,
            "batch_status": self.batch_status,
            "backend_events": self.backend_events,
            "device": self.engine.get("device"),
            "engine": self.engine,
            **({"iprof": self.iprof} if self.iprof else {}),
            **({"fleet": self.fleet} if self.fleet else {}),
            **({"solver_portfolio": self.solver_portfolio}
               if self.solver_portfolio else {}),
        }


class CorpusCampaign:
    """Stream a contract corpus through the analysis pipeline in
    constant-shape batches with checkpoint/resume."""

    def __init__(
        self,
        contracts: Sequence[tuple],  # (name, runtime bytecode[, creation])
        batch_size: int = 32,
        lanes_per_contract: int = 32,
        limits: LimitsConfig = DEFAULT_LIMITS,
        spec: Optional["SymSpec"] = None,  # None = SymSpec() (lazy import)
        max_steps: int = 256,
        transaction_count: int = 1,
        modules: Optional[Sequence[str]] = None,
        checkpoint_dir: Optional[str] = None,
        execution_timeout: Optional[float] = None,
        plugins: Sequence = (),
        enable_iprof: bool = False,
        num_hosts: int = 1,
        host_index: int = 0,
        solver_timeout: Optional[float] = None,
        solver_iters: int = 400,
        parallel_solving: bool = False,
        batch_timeout: Optional[float] = DEFAULT_RESILIENCE.batch_timeout,
        max_batch_retries: int = DEFAULT_RESILIENCE.max_batch_retries,
        fault_injector: Optional[FaultInjector] = None,
        backend: Optional[BackendManager] = None,
        batch_runner=None,
        oom_ladder: Optional[Sequence[str]] = None,
        checkpoint_every: int = DEFAULT_RESILIENCE.checkpoint_every,
        heartbeat_every: Optional[float] = None,
        pipeline: bool = False,
        solver_workers: int = 1,
        fleet_dir: Optional[str] = None,
        lease_ttl: float = 60.0,
        unit_size: Optional[int] = None,
        max_unit_leases: int = 3,
        worker_id: Optional[str] = None,
        fleet_follow: bool = False,
        solver_store: Optional[str] = "auto",
        worker_isolation: str = "off",
        worker_supervisor=None,
        tier_manager=None,
        backend_tiers: Optional[Sequence[str]] = None,
    ):
        # multi-host corpus sharding (SURVEY §5.8: "host-side DCN ... only
        # for corpus sharding"): each host takes a deterministic strided
        # slice — no coordination needed beyond the (num_hosts, host_index)
        # pair, which jax.distributed provides as
        # (process_count, process_index) on a real pod. Strided (not
        # contiguous) so a sorted corpus's size gradient spreads evenly.
        # Checkpoints are per-host files, so one shared checkpoint dir
        # (NFS/GCS) serves the whole fleet; merge_campaigns() combines
        # the per-host results into corpus-level metrics.
        if not (0 <= host_index < num_hosts):
            raise ValueError(f"host_index {host_index} not in [0, {num_hosts})")
        if fleet_dir is not None and num_hosts > 1:
            # the ledger IS the work distribution — layering a static
            # strided split under it would hand each worker a different
            # corpus view and break the shared manifest
            raise ValueError("--fleet replaces --num-hosts/--host-index: "
                             "every worker sees the whole corpus and "
                             "claims units from the shared ledger")
        self.num_hosts = num_hosts
        self.host_index = host_index
        contracts = list(contracts)
        if num_hosts > 1:
            contracts = contracts[host_index::num_hosts]
        self.contracts = contracts
        # linked systems (``load_corpus_dir``: a manifest's members) are
        # units of a batch: never split, so the batches are cut on unit
        # boundaries. Without any the cut is every ``batch_size``
        # contracts, by arithmetic, as it always was (``_cuts`` None)
        self._cuts: Optional[List[Tuple[int, int]]] = None
        if any(record_link(c) for c in contracts):
            if num_hosts > 1 or fleet_dir is not None or fleet_follow:
                raise ValueError(
                    "a corpus with linked systems runs as one campaign: "
                    "--num-hosts and --fleet cut a corpus by position "
                    "and would split a system")
            room = limits.max_accounts - 2
            self._cuts, start, n = [], 0, 0
            for unit in corpus_units(contracts):
                if len(unit) > min(room, batch_size):
                    raise ValueError(
                        f"system {record_link(unit[0])['system']!r} has "
                        f"{len(unit)} members: a lane's account table "
                        f"holds attacker, creator and at most {room} "
                        f"members (max_accounts - 2 = {room}) and a "
                        f"batch {batch_size} contracts")
                if n + len(unit) > batch_size:
                    self._cuts.append((start, start + n))
                    start, n = start + n, 0
                n += len(unit)
            if n:
                self._cuts.append((start, start + n))
        # content identity of THIS host's slice: stamped into campaign
        # checkpoints (a resumed run must prove it is analyzing the same
        # contracts, not just the same count) and the fleet manifest
        self._corpus_fp = corpus_fingerprint(contracts)
        self.batch_size = batch_size
        self.lanes_per_contract = lanes_per_contract
        self.limits = limits
        if spec is None:
            from ..symbolic import SymSpec

            spec = SymSpec()
        self.spec = spec
        self.max_steps = max_steps
        self.transaction_count = transaction_count
        self.modules = list(modules) if modules else None
        self.checkpoint_dir = checkpoint_dir
        self.execution_timeout = execution_timeout
        self.plugins = list(plugins)
        self.enable_iprof = enable_iprof
        self.solver_timeout = solver_timeout
        self.solver_iters = solver_iters
        self.parallel_solving = parallel_solving
        # resilience layer (see mythril_tpu/resilience.py): a hard
        # per-batch wall-clock watchdog, bounded retry, and poison
        # bisection keep one bad contract (or one wedged compile) from
        # taking down a 10k-contract run. ``batch_runner`` swaps the
        # engine pass for a stub in fault-machinery tests.
        self.batch_timeout = batch_timeout
        self.max_batch_retries = max(0, int(max_batch_retries))
        self.fault_injector = (fault_injector
                               if fault_injector is not None
                               else FaultInjector.from_env())
        self.backend = backend
        self._batch_runner = batch_runner
        # a stub runner that doesn't understand degraded capacity still
        # exercises the ladder's control flow (events, statuses); only
        # runners declaring lanes/width actually shrink the work
        self._runner_degradable = True
        if batch_runner is not None:
            import inspect

            try:
                params = inspect.signature(batch_runner).parameters
                self._runner_degradable = (
                    "lanes" in params or "width" in params
                    or any(p.kind is inspect.Parameter.VAR_KEYWORD
                           for p in params.values()))
            except (TypeError, ValueError):
                self._runner_degradable = False
        # RESOURCE_EXHAUSTED degradation ladder (docs/resilience.md):
        # rung names from resilience.DEGRADE_RUNGS, walked in order,
        # cumulatively; () disables (an OOM then falls to retry/bisect)
        self.oom_ladder = tuple(DEFAULT_RESILIENCE.oom_ladder
                                if oom_ladder is None else oom_ladder)
        self.checkpoint_every = max(1, int(checkpoint_every))
        # campaign-level structured events (degradation steps, checkpoint
        # recoveries) — merged with the BackendManager's into the report.
        # Every event carries BOTH clocks plus a session token: wall time
        # (`t`) is comparable across resumed sessions but can step;
        # monotonic (`mono`) orders within a session; `session` lets
        # merge_campaigns keep per-session streams contiguous.
        self._events: List[Dict] = []
        self._event_kinds: Dict[str, int] = {}   # kind -> count so far
        self._session = f"{os.getpid():x}-{int(time.time() * 1000):x}"
        # telemetry spine (docs/observability.md): events are re-emitted
        # onto the obs.trace bus (when one is configured), batches get
        # spans, and --heartbeat N prints a one-line progress pulse at
        # most every N seconds
        self.heartbeat_every = heartbeat_every
        self._backend_emitted = 0   # backend.events already re-emitted
        self._last_ckpt_mono: Optional[float] = None
        self._last_beat: Optional[float] = None
        # depth-1 batch pipeline (docs/performance.md): overlap batch
        # i's host phase with batch i+1's device phase; checkpoints go
        # through a background writer. Off = the PR 1/2 serial path.
        self.pipeline = bool(pipeline)
        self.solver_workers = max(1, int(solver_workers))
        self._ckpt_writer: Optional[BackgroundCheckpointWriter] = None
        # cumulative overlap accounting for the pipeline_occupancy gauge
        self._pipe_host_sec = 0.0
        self._pipe_hidden_sec = 0.0
        # elastic fleet mode (docs/fleet.md): when set, run() claims
        # leased work units from the shared ledger instead of walking a
        # static slice; durability is per-unit result files (the
        # per-host JSON checkpoint is not used). Unit size rounds up to
        # a whole number of batches so global batch indices stay
        # deterministic across workers (fault specs, trace correlation).
        self.fleet_dir = fleet_dir
        self.lease_ttl = float(lease_ttl)
        self.max_unit_leases = int(max_unit_leases)
        self.worker_id = worker_id
        us = unit_size if unit_size else batch_size
        self.unit_size = ((max(1, int(us)) + batch_size - 1)
                          // batch_size) * batch_size
        # follow mode (docs/serving.md): with fleet_follow the ledger is
        # a FEED — units (with their bytecode) arrive over time from a
        # serve daemon instead of being cut from a local corpus
        self.fleet_follow = bool(fleet_follow)
        # staged solver portfolio (docs/solver.md): a shared per-QUERY
        # verdict-store directory. "auto" = on by default under a fleet
        # ledger (every worker shares <fleet_dir>/solver_store — solver
        # work crosses hosts like unit results do); otherwise off
        # unless --solver-store names a dir. The run scopes the
        # process-global store and restores the previous one on exit,
        # so back-to-back campaigns (tests, the serve scheduler's
        # resident instances) never leak stores into each other.
        if solver_store == "auto":
            solver_store = (os.path.join(fleet_dir, "solver_store")
                            if fleet_dir is not None else None)
        self.solver_store = solver_store
        # cross-batch warm-compile accounting: one chunk-shape set per
        # ENGINE shape class (batch width, lanes, step budget, tx
        # count), shared by every SymExecWrapper of that class — batch
        # N>0 of a campaign (or request N>0 of a serve daemon) rides
        # sym_run's process-wide XLA cache, and with a shared set the
        # compile counter / cold spans / pacing stop re-counting it
        self._warm_shapes: Dict[tuple, set] = {}
        self._extern_batches = 0
        # fleet-wide compile-artifact store (mythril_tpu/compilestore.py,
        # docs/serving.md "Compile artifacts & prewarm"): attached by
        # the serve scheduler / daemon via attach_compile_store(); when
        # present, every warm observation is also recorded durably and
        # prewarm_from_store() can bring a fresh process back warm.
        # _prewarm_pending flags recovery events (tier re-promotion,
        # worker respawn) for the daemon's background prewarm thread.
        # the engine's own account of itself (backend.engine_report),
        # refreshed by every finished batch attempt
        self._engine: Dict = {}
        self._recent_batches: "collections.deque" = collections.deque(
            maxlen=8)
        self._compile_store = None
        self._store_cfh: Optional[str] = None
        self._prewarm_pending = False
        self._prewarm_state: Dict = {"state": "idle", "done": 0,
                                     "total": 0, "last_error": None}
        # portfolio-stats baseline for this run's deltas (heartbeat
        # Z3-avoided %, per-batch solver_portfolio events, the report)
        self._pstats0: Optional[Dict] = None
        # supervised engine worker (docs/resilience.md "Process
        # isolation & supervision"): with isolation on, device batches
        # run in a restartable SUBPROCESS that owns the JAX backend —
        # libtpu segfaults / OOM kills / hard hangs become worker
        # deaths the retry→ladder→bisect machinery replays, never
        # parent death. "auto" = on under a fleet ledger (a dead
        # worker there also wedges lease turnover); serve resolves its
        # own auto in the campaign factory. Plugins and sharded specs
        # can't cross the pickle boundary — isolation quietly stays
        # off for them.
        if isinstance(worker_isolation, bool):
            isolate = worker_isolation
        elif worker_isolation == "auto":
            isolate = fleet_dir is not None or fleet_follow
        elif worker_isolation in ("on", "off"):
            isolate = worker_isolation == "on"
        else:
            raise ValueError(
                f"worker_isolation {worker_isolation!r}: must be "
                "'on', 'off' or 'auto'")
        if isolate and (self.plugins or self._cuts is not None
                        or getattr(self.spec, "mesh", None) is not None):
            log.warning("worker isolation disabled: plugins / sharded "
                        "specs / linked systems do not cross the worker "
                        "process boundary")
            isolate = False
        self.worker_isolation = isolate
        self._supervisor = worker_supervisor
        if worker_supervisor is not None \
                and worker_supervisor.on_event is None:
            worker_supervisor.on_event = self._worker_event
        # backend tiers (mythril_tpu/backend.py, docs/resilience.md
        # "Backend tiers"): the demote-and-repromote failover ladder.
        # Lazy — no TierManager exists until the first demotion-capable
        # failure (crash-loop breaker, device loss), so tier-free runs
        # pay nothing; an EXPLICIT ladder (backend_tiers / injected
        # manager) is created eagerly so the tier shows up as a
        # capacity class (serve /healthz, heartbeat) while healthy. An
        # injected manager may be shared across campaigns (the serve
        # scheduler, soak); only an owned one has its prober stopped
        # at run end.
        self._tm = tier_manager
        self._tm_owned = tier_manager is None
        self._backend_tiers = backend_tiers
        self._tier_gen_seen = (tier_manager.generation
                               if tier_manager is not None else 0)
        if tier_manager is not None and tier_manager.on_event is None:
            tier_manager.on_event = self._tier_event
        elif tier_manager is None and backend_tiers is not None:
            self._tier_manager()

    # --- batches -------------------------------------------------------
    @property
    def n_batches(self) -> int:
        if self._cuts is not None:
            return len(self._cuts)
        return (len(self.contracts) + self.batch_size - 1) // self.batch_size

    def _batch_items(self, bi: int) -> List[tuple]:
        """The contracts of batch ``bi``: every ``batch_size`` by
        position, or, with linked systems, whole units up to it."""
        lo, hi = (self._cuts[bi] if self._cuts is not None else
                  (bi * self.batch_size, (bi + 1) * self.batch_size))
        return self.contracts[lo:hi]

    def _contracts_done(self, batches: int) -> int:
        if self._cuts is not None:
            return self._cuts[batches - 1][1] if batches else 0
        return min(batches * self.batch_size, len(self.contracts))

    # --- checkpointing -------------------------------------------------
    @property
    def _ckpt_path(self) -> Optional[str]:
        if self.checkpoint_dir is None:
            return None
        # the name embeds BOTH shard coordinates: host 1 of a 4-wide
        # fleet and host 1 of an 8-wide fleet must not collide on one
        # file in the shared checkpoint dir (pre-fleet runs named only
        # the index — see MIGRATING.md)
        name = ("campaign.json" if self.num_hosts == 1
                else f"campaign_host{self.host_index}"
                     f"of{self.num_hosts}.json")
        return os.path.join(self.checkpoint_dir, name)

    @property
    def _shard_stamp(self) -> List:
        """Identity of this host's slice as persisted in the campaign
        checkpoint: fleet width, host index, slice length, and the
        slice's CONTENT fingerprint — a count alone cannot tell "same
        corpus" from "same size", and resuming a cursor over different
        contracts silently skips/double-attributes work."""
        return [self.num_hosts, self.host_index, len(self.contracts),
                self._corpus_fp]

    def _event(self, kind: str, detail: str = "", **kw) -> None:
        # both clocks on purpose: wall (`t`) survives the checkpoint
        # boundary so resumed sessions' events sort globally; monotonic
        # (`mono`) is step-free within a session; `session` disambiguates
        # when wall clocks of two sessions overlap or run backwards
        e = {"kind": kind, "detail": detail[:300],
             "t": round(time.time(), 3),
             "mono": round(time.monotonic(), 3),
             "session": self._session}
        e.update(kw)
        self._events.append(e)
        self._event_kinds[kind] = self._event_kinds.get(kind, 0) + 1
        obs_trace.event(kind, **{k: v for k, v in e.items() if k != "kind"})
        obs_metrics.REGISTRY.counter(f"campaign_{kind}_total").inc()

    def _emit_backend_events(self) -> None:
        """Re-emit BackendManager events (probe/fallback/device-lost)
        newly appended since the last call onto the trace bus, so the
        one stream carries the backend story too. The report's
        ``backend_events`` field is built from the original lists —
        this is a mirror, not a move."""
        if self.backend is None or not obs_trace.active():
            return
        new = self.backend.events[self._backend_emitted:]
        self._backend_emitted += len(new)
        for e in new:
            obs_trace.event(e.get("kind", "backend"),
                            **{k: v for k, v in e.items() if k != "kind"})

    def _load_ckpt(self) -> Dict:
        p = self._ckpt_path
        state = None
        if p is not None:
            # verified load with fallback: a torn newest file (kill -9
            # mid-write) degrades to the rotated last-known-good copy —
            # costing at most the batches since that copy, never the run
            state, src = load_json_checkpoint_resilient(p)
            if state is not None and src != p:
                self._event("checkpoint_recovered", detail=src)
            elif state is None and os.path.exists(p + ".corrupt"):
                # newest corrupt (quarantined aside) and nothing
                # rotated: the torn file was the first checkpoint ever,
                # so no completed batch was durably recorded — a fresh
                # start replays only batch 0
                self._event("checkpoint_reset", detail=p)
        if state is not None:
            # a checkpoint taken under a different sharding (or corpus)
            # indexes a DIFFERENT contract slice — resuming it would
            # silently skip contracts and double-attribute issues.
            # REFUSE the resume: set the stale file aside (so the next
            # save's rotation can't clobber evidence) and start fresh,
            # with the decision on the event record. Pre-fingerprint
            # checkpoints stamped only [num_hosts, host_index, count];
            # they keep resuming when those three still match.
            shard = state.get("shard")
            want = self._shard_stamp
            ok = (shard is None or shard == want
                  or (isinstance(shard, list) and len(shard) == 3
                      and shard == want[:3]))
            if not ok:
                self._event(
                    "checkpoint_reset",
                    detail=f"{p}: shard config changed (checkpoint "
                           f"{shard}, current {want}); refusing to "
                           "resume a different corpus slice — starting "
                           "fresh")
                for stale in (p, p + ".1"):
                    if os.path.exists(stale):
                        try:
                            os.replace(stale, stale + ".stale")
                        except OSError:
                            pass
                state = None
            else:
                # resilience fields arrived after the first checkpoint
                # schema; an old (or hand-rewound) file resumes cleanly
                for k, v in (("quarantined", []), ("retries", 0),
                             ("batch_status", []), ("backend_events", [])):
                    state.setdefault(k, v)
                return state
        return {"next_batch": 0, "issues": [], "batch_wall": [],
                "paths_total": 0, "dropped_forks": 0, "iprof": {},
                "solver": {},
                "quarantined": [], "retries": 0, "batch_status": [],
                "backend_events": [],
                "shard": self._shard_stamp}

    @staticmethod
    def _snapshot_state(state: Dict) -> Dict:
        """Shallow-copy the mutable containers so the background writer
        serializes a frozen view while the campaign keeps appending to
        the live ``res`` lists. One level suffices: list/dict ELEMENTS
        (issue dicts, event dicts, iprof counts) are append-only — never
        mutated after they land in the state."""
        return {k: (list(v) if isinstance(v, list)
                    else dict(v) if isinstance(v, dict) else v)
                for k, v in state.items()}

    def _save_ckpt(self, state: Dict) -> None:
        p = self._ckpt_path
        if p is None:
            return
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        if self._ckpt_writer is not None:
            # pipelined: serialization + fsync move off the commit path.
            # The durability CONTRACT is unchanged (the writer uses the
            # same tmp+fsync+rotate+rename writer); only the guarantee's
            # timing shifts — _last_ckpt_mono is stamped when the rename
            # actually lands, so the heartbeat's ckpt-age stays honest.
            def _durable() -> None:
                self._last_ckpt_mono = time.monotonic()

            self._ckpt_writer.submit(self._snapshot_state(state),
                                     on_durable=_durable)
            return
        # checksummed + fsynced + rotated: a crash never corrupts the
        # cursor, and even a torn rename leaves <p>.1 loadable
        save_json_checkpoint(p, state)
        self._last_ckpt_mono = time.monotonic()

    # --- one engine pass -----------------------------------------------
    def _padded(self, names: Sequence[str], codes: Sequence[bytes],
                width: Optional[int] = None,
                creations: Optional[Sequence[Optional[bytes]]] = None,
                links: Optional[Sequence[Optional[dict]]] = None):
        """A batch's records padded to ``width`` (default
        ``batch_size``), the constant compiled shape: short batches get
        STOP stubs, a contract without creation code a stub
        constructor."""
        width = self.batch_size if width is None else width
        names = list(names)
        codes = list(codes)
        while len(codes) < width:
            names.append(f"_pad_{len(codes)}")
            codes.append(_PAD_BYTECODE)
        if creations is not None:
            creations = [k if k is not None else _PAD_CREATION
                         for k in creations]
            creations += [_PAD_CREATION] * (width - len(creations))
        if links is not None:
            links = list(links) + [None] * (width - len(links))
        return names, codes, creations, links

    def _build_batch(self, items: Sequence[tuple], tctx=None):
        """The look-ahead's job, on the pipeline's worker thread: pack
        ``items`` as :meth:`_explore_batch` would at the default rung
        and return the bundle its wrapper starts from. ``tctx``
        re-enters the submitting thread's trace scope."""
        from ..analysis import symbolic

        names, codes, creations, links = _split_records(items)
        names, codes, creations, links = self._padded(
            names, codes, creations=creations, links=links)
        with obs_trace.apply_context(tctx):
            return symbolic.build_batch(
                codes, contract_names=names, limits=self.limits,
                lanes_per_contract=self.lanes_per_contract,
                creation_bytecodes=creations,
                enable_iprof=self.enable_iprof, links=links)

    def _explore_batch(self, bi: int, names: List[str],
                       codes: List[bytes],
                       lanes: Optional[int] = None,
                       width: Optional[int] = None,
                       creations: Optional[List[Optional[bytes]]] = None,
                       on_first_call=None,
                       links: Optional[List[Optional[dict]]] = None,
                       build=None):
        """DEVICE phase of one batch: pad to the compiled width and run
        the exploration (SymExecWrapper packs the corpus and drives the
        ``sym_run`` chunks — the dispatches are async under JAX; only
        the per-tx harvest syncs ride this thread). Always padded to
        ``width`` (default ``batch_size``) so every attempt at a given
        rung replays ONE compiled engine. ``lanes``/``width`` below
        their defaults are the degradation ladder shrinking the working
        set: a smaller shape is a new (cheaper) compile, and the
        tighter fork capacity is absorbed by the engine's park/spill
        machinery (``defer_starved`` + rebalance) instead of dropping
        paths. With ``creations`` (the batch's creation codes, None
        where a contract has none) the batch DEPLOYS: every lane first
        runs its contract's constructor from ``CREATOR_ADDRESS`` and the
        message calls start from the storage it left; the corpus then
        holds ``2 x width`` images, an engine shape class of its own,
        explored as every other batch is (one lane pool, chunks, spill,
        drain). ``on_first_call`` is the wrapper's hook of that name: it
        fires once, when the first ``sym_run`` call is enqueued. With
        ``links`` (:func:`record_link` of each contract) the batch holds
        linked systems: the wrapper gives a system's lanes its members
        at their addresses and joins its constructors' end states into
        one world. ``build`` is this batch already packed
        (:meth:`_build_batch` of the same records at the default rung,
        by the pipeline's look-ahead): the wrapper starts from it.
        Returns the finished wrapper for :meth:`_harvest_batch`."""
        from ..analysis import SymExecWrapper

        width = self.batch_size if width is None else width
        lanes = self.lanes_per_contract if lanes is None else lanes
        names, codes, creations, links = self._padded(
            names, codes, width, creations, links)
        if creations is not None:
            obs_metrics.REGISTRY.counter(
                "campaign_contracts_deployed_total",
                help="contracts whose constructor a campaign batch "
                     "ran").inc(len(names) - sum(
                         n.startswith("_pad_") for n in names))
        sym = SymExecWrapper(
            codes, contract_names=names, limits=self.limits,
            spec=self.spec,
            lanes_per_contract=lanes,
            max_steps=self.max_steps,
            solver_iters=self.solver_iters,
            solver_timeout=self.solver_timeout,
            transaction_count=self.transaction_count,
            creation_bytecodes=creations,
            plugins=self.plugins,
            enable_iprof=self.enable_iprof,
            warm_shapes=self._warm_set(lanes, width,
                                       creations is not None),
            on_first_call=on_first_call,
            **({} if links is None else {"links": links}),
            build=build,
        )
        # compile counters as of the END of this device phase: device
        # phases never overlap each other, so a batch that compiled no
        # engine shape leaves ``engine_compiles`` exactly flat (the
        # pipelined host phase of the batch before may still be
        # compiling solver kernels, which is why it is sampled here)
        from ..backend import engine_counters

        sym.engine_counters = engine_counters()
        return sym

    def _shape_key(self, lanes: Optional[int] = None,
                   width: Optional[int] = None,
                   deploys: bool = False) -> tuple:
        """Identity of one compiled engine shape class: every batch with
        this key replays the same sym_run executables (the corpus is
        padded to ``width`` contracts x ``lanes`` lanes, and max_steps /
        transaction_count are static jit args). Degrade rungs shrink
        lanes/width and thus land in their own (cheaper) class. A batch
        that deploys runs over ``2 x width`` images (creation + runtime)
        and is a class of its own: a fifth field, 1; a batch of pairs
        keeps the four it always had."""
        key = (self.batch_size if width is None else width,
               self.lanes_per_contract if lanes is None else lanes,
               self.max_steps, self.transaction_count)
        return key + (1,) if deploys else key

    def _warm_set(self, lanes: Optional[int] = None,
                  width: Optional[int] = None,
                  deploys: bool = False) -> set:
        return self._warm_shapes.setdefault(
            self._shape_key(lanes, width, deploys), set())

    def shape_is_warm(self, lanes: Optional[int] = None,
                      width: Optional[int] = None,
                      deploys: bool = False) -> bool:
        """Whether this campaign has already compiled (some chunk of)
        the given engine shape class — the serve scheduler's
        warm-compile-hit predicate (docs/serving.md)."""
        return bool(self._warm_shapes.get(
            self._shape_key(lanes, width, deploys)))

    # --- fleet compile-artifact store (docs/serving.md "Compile
    # --- artifacts & prewarm") ------------------------------------------
    def attach_compile_store(self, store, cfh: Optional[str] = None) -> None:
        """Wire a :class:`~mythril_tpu.compilestore.CompileStore` into
        this campaign: warm observations are recorded durably per
        ``(tier, shape-class, semantic-config-hash)`` bucket, and
        :meth:`prewarm_from_store` can replay the registry to bring a
        fresh process back warm. ``cfh`` defaults to
        :meth:`semantic_hash` (serve passes its own config hash so the
        bucket key space matches the request dedupe key space)."""
        self._compile_store = store
        self._store_cfh = cfh or self.semantic_hash()

    def semantic_hash(self) -> str:
        """Semantic-config hash of this campaign's compiled behavior:
        the worker config minus purely operational knobs, so two
        processes with the same engine semantics land in the same
        compile-store buckets."""
        from ..compilestore import semantic_config_hash

        cfg = self._worker_config()
        for k in ("solver_store", "solver_workers", "parallel_solving"):
            cfg.pop(k, None)
        # a spec/plugin object's repr embeds its address — hash the
        # TYPE, which is what actually forks the compiled engine
        cfg["spec"] = (type(self.spec).__name__
                       if self.spec is not None else None)
        return semantic_config_hash(cfg)

    def _active_tier(self) -> str:
        """The tier label compile-store buckets are keyed under: the
        ladder's current tier when one exists, else the process's
        default jax backend (what an unladdered campaign compiles on)."""
        if self._tm is not None:
            return self._tm.current
        from ..backend import detect_tiers, pinned_tier, tier_of_platform

        if self._worker_enabled():
            # a supervisor never asks JAX (that would take the
            # accelerator its worker needs): the tier is the one this
            # process is pinned to, else the one its worker came up on
            # — spawned now if need be, the prewarm that asks wants it
            pinned = pinned_tier()
            if pinned:
                return pinned
            try:
                sup = self._ensure_supervisor()
                sup.ensure_alive()
                got = tier_of_platform((sup.device or {}).get("platform"))
            except Exception:  # noqa: BLE001 — no worker: best guess
                got = None
            return got or detect_tiers()[0]
        try:
            import jax

            return jax.default_backend()
        except Exception:  # noqa: BLE001 — no backend at all
            return "cpu"

    def _store_record(self, lanes: Optional[int] = None,
                      width: Optional[int] = None,
                      deploys: bool = False) -> None:
        """Durably record one warm observation (hit count + the chunk
        step-counts now warm) for this shape class. Never raises — a
        full disk or torn registry must not fail the batch that just
        succeeded."""
        store = self._compile_store
        if store is None:
            return
        try:
            chunks = [c for c in self._warm_set(lanes, width, deploys)
                      if isinstance(c, int)]
            store.record(self._active_tier(),
                         self._shape_key(lanes, width, deploys),
                         self._store_cfh or self.semantic_hash(),
                         chunks=chunks)
        except Exception as e:  # noqa: BLE001 — recording is best-effort
            log.warning("compile-store record failed: %s", e)

    def warm_counts(self) -> tuple:
        """``(warm shape classes in this process, registry buckets for
        the active tier)`` — the heartbeat's ``warm a/b`` token.
        The second element is ``None`` without an attached store."""
        a = sum(1 for s in self._warm_shapes.values() if s)
        if self._compile_store is None:
            return a, None
        try:
            b = len(self._compile_store.buckets(
                tier=self._active_tier(), cfh=self._store_cfh))
        except Exception:  # noqa: BLE001 — registry scan is best-effort
            b = 0
        return a, b

    def prewarm_bucket(self, bucket: Dict) -> None:
        """AOT-prewarm one registry bucket: seed the warm-shape set
        with the bucket's recorded chunk step-counts (they are warm
        FLEET-wide — the shared persistent cache holds their
        executables, so compiling them again is a cache hit, and the
        compile counter must not re-count it), then drive the compile —
        through the supervised worker when isolation is on, in-process
        otherwise. A stub batch-runner has no engine to warm: seeding
        is the whole effect. Buckets from another engine shape config
        (different max_steps / tx count) are skipped — their compiled
        functions could never be replayed here."""
        shape = [int(d) for d in bucket.get("shape") or ()]
        if len(shape) not in (4, 5):
            raise ValueError(f"prewarm bucket shape {shape!r}")
        width, lanes, max_steps, txc = shape[:4]
        deploys = shape[4:] == [1]      # _shape_key's fifth field
        if max_steps != self.max_steps or txc != self.transaction_count:
            return
        chunks = [int(c) for c in bucket.get("chunks") or ()]
        warm = self._warm_set(lanes, width, deploys)
        warm.update(chunks)
        tier = self._tm.current if self._tm is not None else None
        if self._worker_enabled():
            sup = self._ensure_supervisor()
            val = sup.prewarm([{"lanes": lanes, "width": width,
                                "tier": tier, "chunks": chunks,
                                "deploys": deploys}],
                              on_tier=tier)
            for wc in (val or {}).get("warm_chunks") or ():
                warm.update(int(c) for c in wc or ())
            warm.add(_WORKER_WARM)
        elif self._batch_runner is None:
            cm = self._tier_device(tier) if tier else None
            with (cm if cm is not None else contextlib.nullcontext()):
                sym = self._explore_batch(
                    -1, [], [], lanes, width,
                    creations=[] if deploys else None)
                self._harvest_batch(-1, sym)
        self._event("prewarm_bucket", tier=tier or "",
                    width=width, lanes=lanes, chunks=len(chunks))
        self._store_record(lanes, width, deploys)

    def prewarm_from_store(self, limit: Optional[int] = None,
                           should_stop=None) -> Dict:
        """Replay the registry's hottest buckets for the active tier
        ahead of traffic (daemon start, worker respawn, tier
        re-promotion). Strictly subordinate to live work: the caller's
        ``should_stop`` is consulted between buckets, and a stop leaves
        ``_prewarm_pending`` set so the background loop resumes later.
        A single bucket failure degrades to lazy compile for that
        bucket (loud ``prewarm_failed`` event, never an abort); a
        crash-looping worker (breaker open) stops the whole pass —
        hammering a broken backend with compile work helps nobody.
        Returns (and stores, for ``/healthz``) the status doc."""
        st = self._prewarm_state
        store = self._compile_store
        if store is None:
            return dict(st)
        self._prewarm_pending = False
        tier = self._active_tier()
        buckets = store.buckets(tier=tier, cfh=self._store_cfh)
        if limit is not None:
            buckets = buckets[:limit]
        st.update({"state": "running", "done": 0, "total": len(buckets),
                   "last_error": None, "tier": tier})
        if buckets:
            self._event("prewarm_started", tier=tier,
                        buckets=len(buckets))
        stopped = False
        for b in buckets:
            if should_stop is not None and should_stop():
                self._prewarm_pending = True  # resume when idle again
                stopped = True
                break
            try:
                self.prewarm_bucket(b)
                st["done"] += 1
                obs_metrics.REGISTRY.counter(
                    "prewarm_buckets_total",
                    help="registry buckets AOT-prewarmed").inc()
            except WorkerCrashLoop as e:
                st["last_error"] = str(e)[:300]
                self._event("prewarm_failed", detail=str(e)[:300],
                            tier=tier, terminal=True)
                obs_metrics.REGISTRY.counter(
                    "prewarm_failures_total",
                    help="prewarm buckets that degraded to lazy "
                         "compile").inc()
                break
            except Exception as e:  # noqa: BLE001 — degrade to lazy compile
                st["last_error"] = str(e)[:300]
                self._event("prewarm_failed", detail=str(e)[:300],
                            tier=tier, terminal=False)
                obs_metrics.REGISTRY.counter(
                    "prewarm_failures_total",
                    help="prewarm buckets that degraded to lazy "
                         "compile").inc()
        st["state"] = ("yielded" if stopped else
                       "failed" if st["last_error"] else "done")
        if buckets and not stopped:
            self._event("prewarm_done", tier=tier, done=st["done"],
                        total=st["total"])
        return dict(st)

    def prewarm_status(self) -> Dict:
        """The ``/healthz`` ``prewarm`` doc: state, buckets done/total,
        last error."""
        return dict(self._prewarm_state)

    def _harvest_batch(self, bi: int, sym) -> Dict:
        """HOST phase of one batch: detection modules + witness search +
        report merge over a finished exploration. NOT pure host work:
        the wrapper's per-tx harvest pulled only the trap codes, so the
        modules, the tape extraction and the coverage summary read the
        leaves of each transaction's frontier from the device here:
        each leaf whole and once a context (``AnalysisContext.host``),
        every index on the host copy. The pipelined campaign runs this
        on a worker thread while the NEXT batch explores on the device;
        on one chip a read that dispatches a program (a device leaf
        sliced per lane) would queue behind the running ``sym_run``
        call, a plain copy does not. The phase's span says how long it
        waited (``device_wait_s``) beside its CPU seconds (``cpu_s``)."""
        from ..analysis import fire_lasers

        report = fire_lasers(
            sym, white_list=self.modules,
            parallel=self.parallel_solving or self.solver_workers > 1,
            workers=(self.solver_workers
                     if self.solver_workers > 1 else None))
        cov = sym.coverage
        issues = []
        for issue in report.issues:
            if issue.contract.startswith("_pad_"):
                continue
            d = issue.as_dict()
            d["batch"] = bi
            issues.append(d)
        from ..backend import engine_report

        for failed in getattr(sym, "failed_deployments", ()):
            self._event("system_deploy_failed", batch=bi,
                        detail=failed["reason"], system=failed["system"])
        return {
            "issues": issues,
            "paths": int(cov.get("surviving_paths", 0)),
            "dropped": int(cov.get("dropped_forks", 0)),
            "iprof": dict(sym.iprof) if self.enable_iprof else {},
            # read here, in the process that ran the engine: across the
            # worker boundary it rides the batch reply
            "engine": {**engine_report(),
                       **getattr(sym, "engine_counters", {})},
        }

    def _note_engine(self, bi: int, engine: Optional[Dict]) -> None:
        """Keep the newest engine report and put its counters on the
        event stream, one ``engine_batch`` per finished attempt: a
        batch that compiled nothing leaves them flat."""
        if not engine:
            return  # stub runner: no engine ran
        self._engine = engine
        self._recent_batches.append(
            {"batch": bi, "engine_compiles": engine.get("engine_compiles")})
        self._event("engine_batch", batch=bi,
                    platform=(engine.get("device") or {}).get("platform"),
                    **{k: engine.get(k) for k in
                       ("engine_compiles", "xla_compiles", "cache_hits",
                        "xla_compile_sec")})

    def engine_status(self) -> Dict:
        """The newest engine report, or — before the first batch of a
        worker-isolated campaign — just the device the live worker's
        init reply named (``serve`` ``/healthz``)."""
        sup = self._supervisor
        if self._engine:
            doc = dict(self._engine)
        elif sup is not None and getattr(sup, "device", None):
            doc = {"device": sup.device}
        else:
            return {}
        # a resident campaign has no end-of-run report: the event
        # kinds so far and the newest per-batch counters stand in
        doc["event_kinds"] = dict(self._event_kinds)
        doc["recent_batches"] = list(self._recent_batches)
        return doc

    def _exec_batch(self, bi: int, names: List[str], codes: List[bytes],
                    lanes: Optional[int] = None,
                    width: Optional[int] = None,
                    creations: Optional[List[Optional[bytes]]] = None,
                    links: Optional[List[Optional[dict]]] = None
                    ) -> Dict:
        """Analyze one (padded) batch; returns the batch's partial
        results. Serial composition of the device + host phases — the
        unit of work the watchdog guards and the bisection replays on
        sub-batches. Each phase runs inside its own span, and the
        durations feed the per-request stage attribution
        (docs/observability.md "Per-stage latency")."""
        with obs_device.phase_timer("device_phase", bi=bi,
                                    n=len(names)) as dv:
            sym = self._explore_batch(bi, names, codes, lanes, width,
                                      creations, links=links)
        with obs_device.phase_timer("host_phase", bi=bi) as hp:
            out = self._harvest_batch(bi, sym)
        acc = getattr(self, "_phase_acc", None)
        if acc is not None:
            acc["device"] += dv.dur or 0.0
            acc["host"] += hp.dur or 0.0
        self._store_record(lanes, width, creations is not None)
        return out

    # --- supervised engine worker (docs/resilience.md) ------------------
    def _worker_enabled(self) -> bool:
        """Whether this batch goes through the engine-worker boundary:
        isolation on AND the real engine is the runner (a stub
        ``batch_runner`` has nothing to isolate — it runs in-process,
        so fault-machinery tests keep their exact semantics)."""
        return self.worker_isolation and self._batch_runner is None

    def _worker_event(self, kind: str, detail: str = "", **kw) -> None:
        """Supervisor events routed onto the campaign's event stream
        (report ``backend_events`` + trace bus + counters). A worker
        death also drops the worker-resident warm-shape markers: the
        replacement process recompiles, and serve's warm-compile
        accounting must say so."""
        if kind == "worker_death":
            for s in self._warm_shapes.values():
                s.discard(_WORKER_WARM)
        if kind == "worker_restart":
            # a fresh worker process compiles cold (modulo the shared
            # persistent cache): flag the background prewarm loop
            self._prewarm_pending = True
        self._event(kind, detail=detail, **kw)

    def _worker_config(self) -> Dict:
        """The engine knobs the worker needs to mirror this campaign
        (pickled across the spawn; see engine_worker._build_campaign)."""
        return {
            "batch_size": self.batch_size,
            "lanes_per_contract": self.lanes_per_contract,
            "limits": self.limits,
            "spec": self.spec,
            "max_steps": self.max_steps,
            "transaction_count": self.transaction_count,
            "modules": self.modules,
            "solver_timeout": self.solver_timeout,
            "solver_iters": self.solver_iters,
            "parallel_solving": self.parallel_solving,
            "solver_workers": self.solver_workers,
            "enable_iprof": self.enable_iprof,
            "solver_store": self.solver_store,
        }

    def _ensure_supervisor(self):
        if self._supervisor is None:
            from ..resilience import WorkerSupervisor

            # spawn the worker pinned to the tier this campaign holds
            # (empty overlay when no ladder is active or env pinning is
            # off): the worker is the tier's capacity, so a demoted
            # campaign's replacement worker must come up on the demoted
            # platform, not re-wedge on the failed one
            worker_env = (self._tm.platform_env()
                          if self._tm is not None else {})
            # the tier the child is pinned to — by the ladder, or by
            # this process's own JAX_PLATFORMS, which it inherits — is
            # the tier its init reply must name; an unpinned child
            # takes what JAX picks and only puts it on record
            from ..backend import pinned_tier

            self._supervisor = WorkerSupervisor(
                config=self._worker_config(),
                batch_timeout=self.batch_timeout,
                fault_injector=self.fault_injector,
                on_event=self._worker_event,
                worker_env=worker_env,
                expect_tier=pinned_tier({**os.environ, **worker_env}))
        return self._supervisor

    def _worker_run(self, bi: int, names: List[str], codes: List[bytes],
                    lanes: Optional[int], width: Optional[int],
                    on_tier: Optional[str],
                    creations: Optional[List[Optional[bytes]]] = None
                    ) -> Dict:
        """One batch through the supervisor (which enforces the
        per-batch deadline parent-side — no extra watchdog thread).
        Success marks the shape class worker-warm. The reply's
        child-measured ``phases`` feed the stage attribution: host time
        is the child's own reading; device time is parent wall minus
        it, so spawn + IPC cost lands on the device side (it stalls the
        same pipeline slot device work does)."""
        sup = self._ensure_supervisor()
        t0 = time.monotonic()
        try:
            out = sup.run_batch(bi, names, codes, lanes=lanes,
                                width=width, on_cpu=(on_tier == "cpu"),
                                on_tier=on_tier, creations=creations)
        except BaseException:
            # a failed attempt (worker death, deadline) stalled the
            # pipeline slot too: charge its wall to the device stage so
            # per-request timings still sum to the request wall
            acc = getattr(self, "_phase_acc", None)
            if acc is not None:
                acc["device"] += max(0.0, time.monotonic() - t0)
            raise
        wall = time.monotonic() - t0
        ph = out.pop("phases", None) if isinstance(out, dict) else None
        acc = getattr(self, "_phase_acc", None)
        if acc is not None:
            h = float((ph or {}).get("host") or 0.0)
            acc["host"] += h
            acc["device"] += max(0.0, wall - h)
        # chunk ints the worker compiled through the shared persistent
        # cache: fleet-warm (they outlive the worker process), so they
        # join the shape class's warm set and the registry bucket
        wc = out.pop("warm_chunks", None) if isinstance(out, dict) \
            else None
        warm = self._warm_set(lanes, width, creations is not None)
        warm.update(int(c) for c in wc or ())
        warm.add(_WORKER_WARM)
        self._store_record(lanes, width, creations is not None)
        return out

    def worker_status(self) -> Optional[Dict]:
        """Supervisor diagnostics (breaker state, restarts, rss) for
        ``serve`` ``/healthz`` and the heartbeat line; None when no
        worker has been needed yet."""
        if self._supervisor is None:
            return None
        return self._supervisor.status()

    def tier_status(self) -> Optional[Dict]:
        """Backend-tier ladder state (current/preferred tier, demotion
        and re-promotion counts, flap damping) for ``serve``
        ``/healthz``; None while no ladder has been needed."""
        if self._tm is None:
            return None
        return self._tm.status()

    def close_worker(self) -> None:
        """Shut the engine worker down (run() exit, serve drain). The
        supervisor object is dropped, so a later batch respawns."""
        if self._supervisor is not None:
            try:
                self._supervisor.close()
            finally:
                self._supervisor = None

    # --- resident mode (docs/serving.md) --------------------------------
    def run_external_batch(self, items: Sequence[tuple],
                           bi: Optional[int] = None) -> Dict:
        """Resident-mode entry: analyze one externally-fed batch of
        ``(name, bytecode)`` pairs (or records with creation code,
        which deploy) through the FULL resilient machinery
        (watchdog / OOM ladder / retry / bisect-to-quarantine) and
        return its partial-result dict (``issues`` / ``paths`` /
        ``dropped`` / ``iprof`` / ``quarantined`` / ``retries`` /
        ``status``).

        This is what turns the batch campaign into a service substrate
        (ROADMAP open item #3): the serve scheduler keeps ONE campaign
        instance per engine shape class alive across requests, so every
        batch after the first replays sym_run's cached executables (the
        shared warm-shape set keeps the compile accounting honest) and
        nothing recompiles on entry. No checkpoint is written — the
        caller owns durability (the serve results store; a fleet feed
        ledger commits per unit). Batch indices default to a private
        monotone counter so fault specs (``raise:batch=N``) and trace
        correlation keep meaning one thing for the daemon's lifetime."""
        if bi is None:
            bi = self._extern_batches
        self._extern_batches = max(self._extern_batches, bi) + 1
        items = list(items)
        # per-batch device/host attribution accumulator: filled by
        # _exec_batch (in-process) or _worker_run (isolation on) across
        # every retry/degrade/bisect attempt this batch takes
        self._phase_acc = {"device": 0.0, "host": 0.0}
        with obs_trace.timer("batch", bi=bi, n=len(items),
                             resident=True) as sp:
            out = self._run_batch_resilient(bi, items)
        self._emit_backend_events()
        obs_trace.event("batch_status", bi=bi, status=out["status"],
                        dur=round(sp.elapsed, 6))
        reg = obs_metrics.REGISTRY
        reg.counter("batches_total").inc()
        reg.histogram("batch_seconds",
                      help="per-batch wall time").observe(sp.elapsed)
        reg.counter("batch_retries_total").inc(out["retries"])
        reg.counter("contracts_quarantined_total").inc(
            len(out["quarantined"]))
        from ..smt.solver import SOLVER_STATS

        self._portfolio_event(SOLVER_STATS.as_dict())
        out["wall_sec"] = sp.elapsed
        out["batch"] = bi
        out["phases"] = dict(self._phase_acc)
        return out

    # --- fault isolation ----------------------------------------------
    @staticmethod
    def _tier_device(platform: str = "cpu"):
        """``jax.default_device`` context pinning execution to the
        given tier's platform, or None when no such device is available
        (then the pin degenerates to a plain replay). Imported lazily —
        the campaign must stay importable without initializing a
        backend."""
        try:
            import jax

            from ..backend import profile as _tier_profile

            try:
                platform = _tier_profile(platform).jax_platform
            except ValueError:
                pass  # raw jax platform label (e.g. "cuda") — use as is
            return jax.default_device(jax.devices(platform)[0])
        except Exception:  # noqa: BLE001 — no backend / no such plugin
            return None

    @classmethod
    def _cpu_device(cls):
        """Historical name for the floor-tier pin (kept for the engine
        worker's compat path)."""
        return cls._tier_device("cpu")

    # --- backend tiers (docs/resilience.md "Backend tiers") -------------
    def _tier_event(self, kind: str, detail: str = "", **kw) -> None:
        """TierManager events routed onto the campaign's event stream
        (report ``backend_events`` + trace bus + counters)."""
        self._event(kind, detail=detail, **kw)

    def _tier_manager(self):
        """Get-or-create the tier ladder. Created on the first
        demotion-capable failure with knobs from DEFAULT_RESILIENCE;
        the detected tier list on a pinned process is just the pinned
        platform plus the floor, so a CPU-only run's ladder is
        ``("cpu",)`` and every demotion is a silent floor no-op."""
        if self._tm is None:
            from ..backend import TierManager

            self._tm = TierManager(
                tiers=self._backend_tiers,
                sticky_window=DEFAULT_RESILIENCE.tier_sticky_window,
                flap_window=DEFAULT_RESILIENCE.tier_flap_window,
                flap_max=DEFAULT_RESILIENCE.tier_flap_max,
                probe_every=DEFAULT_RESILIENCE.tier_probe_every,
                on_event=self._tier_event)
            self._tier_gen_seen = self._tm.generation
        return self._tm

    def _tier_sync(self) -> Optional[str]:
        """Fold tier transitions — possibly applied by the background
        prober thread — into campaign state at a batch-attempt
        boundary, the one place it is safe: every warm-shape marker is
        invalidated (the cached executables belong to the previous
        backend) and the engine worker is closed so the next dispatch
        respawns it pinned to the new tier (fresh process, fresh
        crash-loop breaker — the crash evidence belonged to the old
        tier). Returns the tier to pin this attempt to, or None while
        the preferred tier holds."""
        tm = self._tm
        if tm is None:
            return None
        if tm.generation != self._tier_gen_seen:
            self._tier_gen_seen = tm.generation
            for s in self._warm_shapes.values():
                s.clear()
            self.close_worker()
            self._event("tier_applied", tier=tm.current,
                        generation=tm.generation)
            # the tier the campaign now holds compiles cold by design
            # (the invalidation above is correct — those executables
            # belonged to the previous backend); the compile store can
            # make the recovery cheap, so flag the prewarm loop
            self._prewarm_pending = True
        return tm.current if tm.demoted() else None

    def _floor_tier(self) -> str:
        """The tier the terminal OOM-ladder rung lands on: the worst
        rung of this campaign's ladder (the floor — host CPU — when no
        ladder exists yet)."""
        if self._tm is not None:
            return self._tm.tiers[-1]
        from ..backend import terminal_tier

        return terminal_tier()

    def _guarded_batch(self, bi: int, items: Sequence[tuple],
                       lanes: Optional[int] = None,
                       width: Optional[int] = None,
                       on_cpu: bool = False,
                       on_tier: Optional[str] = None) -> Dict:
        """One attempt: fault-injection check + engine pass, under the
        wall-clock watchdog. A hung compile / wedged device call
        surfaces as BatchTimeout here instead of stalling the run.
        ``lanes``/``width``/``on_tier`` carry the degradation rung
        (``on_cpu`` is the rung's historical spelling: the floor tier).

        With worker isolation on, the pass runs in the supervised
        engine-worker subprocess instead: the supervisor enforces the
        same ``batch_timeout`` from the parent side (so no watchdog
        thread is layered on top), a worker death raises
        ``WorkerDied`` into the same retry→ladder→bisect tail, and an
        open crash-loop breaker DEMOTES the backend tier — the attempt
        falls through to the in-process path on the demoted tier, and
        the tier manager's prober climbs back when the better tier
        probes healthy again (no permanent pin)."""
        names, codes, creations, links = _split_records(items)

        # batch boundaries are where tier transitions land: give a due
        # re-promotion its chance, then fold any transition (from here
        # or the background prober) into campaign state
        if self._tm is not None:
            self._tm.tick()
        pin = self._tier_sync()
        if on_cpu and on_tier is None:
            on_tier = self._floor_tier()
        if on_tier is None and pin is not None:
            on_tier = pin

        injected = False
        if self._worker_enabled():
            if self.fault_injector is not None:
                # parent-side injected faults (hang/raise/kill/oom)
                # keep their exact semantics: fired under the watchdog
                # like a serial attempt, BEFORE the worker dispatch
                run_with_watchdog(
                    lambda: self.fault_injector.fire(batch=bi,
                                                     contracts=names),
                    self.batch_timeout, label=f"batch {bi} inject")
                injected = True
            try:
                return self._worker_run(bi, names, codes, lanes, width,
                                        on_tier, creations)
            except WorkerCrashLoop as e:
                tm = self._tier_manager()
                on_tier = tm.demote(
                    reason=f"worker crash-loop: {str(e)[:160]}")
                self._event("worker_breaker_pinned", batch=bi,
                            tier=on_tier, detail=str(e)[:200])
                # consume the transition now (close the dead worker,
                # drop warm markers) and finish this attempt in-process
                # on the demoted tier
                self._tier_sync()

        def call_runner():
            # a stub runner has no engine to deploy with: it is handed
            # names and runtime codes, as ever
            if self._batch_runner is None:
                kw = {} if creations is None else {"creations": creations}
                if links is not None:
                    kw["links"] = links
                return self._exec_batch(bi, names, codes, lanes=lanes,
                                        width=width, **kw)
            if not self._runner_degradable:
                return self._batch_runner(bi, names, codes)
            return self._batch_runner(bi, names, codes, lanes=lanes,
                                      width=width)

        def work():
            if self.fault_injector is not None and not injected:
                self.fault_injector.fire(batch=bi, contracts=names)
            if on_tier is not None:
                cm = self._tier_device(on_tier)
                if cm is not None:
                    with cm:
                        return call_runner()
            return call_runner()

        return run_with_watchdog(work, self.batch_timeout,
                                 label=f"batch {bi}")

    # --- pipelined phases (docs/performance.md) ------------------------
    def _device_phase(self, bi: int, items: Sequence[tuple],
                      on_first_call=None, prebuilt=None,
                      note: Optional[Dict] = None):
        """Pipelined attempt, first half: fault-injection check + corpus
        packing + exploration, under the watchdog (a hung compile
        surfaces as BatchTimeout instead of stalling BOTH pipeline
        stages). Returns an opaque handle for :meth:`_host_phase_work`.
        ``on_first_call`` fires when the exploration has enqueued its
        first ``sym_run`` call; a phase that makes none (below, or one
        that fails first) never fires it. ``prebuilt`` is the future of
        this batch's :meth:`_build_batch`, if the look-ahead submitted
        one: an exploration takes it (:meth:`_take_prebuilt`, which
        leaves what became of it in ``note``) after the injector has
        fired, so a fault lands where it does without a look-ahead.
        A custom ``batch_runner`` has no device/host seam — the runner
        IS the whole attempt, so its finished result rides the handle
        and the host phase degenerates to a pass-through (same code
        path, no overlap). The same holds for a worker-isolated batch:
        the SymExecWrapper cannot cross the process boundary, so the
        whole attempt runs in the worker (supervisor deadline, breaker
        fallback — all of :meth:`_guarded_batch`'s worker semantics)
        and the host phase passes the finished result through."""
        if self._worker_enabled():
            return ("out", self._guarded_batch(bi, items))
        names, codes, creations, links = _split_records(items)

        def work():
            if self.fault_injector is not None:
                self.fault_injector.fire(batch=bi, contracts=names)
            if self._batch_runner is not None:
                if not self._runner_degradable:
                    return ("out", self._batch_runner(bi, names, codes))
                return ("out", self._batch_runner(bi, names, codes,
                                                  lanes=None, width=None))
            return ("sym", self._explore_batch(
                bi, names, codes, creations=creations,
                on_first_call=on_first_call, links=links,
                build=self._take_prebuilt(prebuilt, note)))

        return run_with_watchdog(work, self.batch_timeout,
                                 label=f"batch {bi} device")

    @staticmethod
    def _take_prebuilt(prebuilt, note: Optional[Dict] = None):
        """The bundle of the look-ahead's build, on the thread that
        explores the batch: inside a ``batch_build`` span
        ``stage="prebuilt"`` whose ``dur`` is the wait for it (about 0
        when the build is done) and which says where the build ran
        (``built_tid``, ``built_mono``, ``built_dur``: the worker's
        three stage spans lie there). None where no build was submitted
        or the build raised: what it raised is dropped, the phase builds
        for itself and fails where it fails without a look-ahead.
        ``pipeline_prebuilt_total{used}`` and ``note["prebuilt"]`` say
        which: ``taken`` (done when asked for), ``waited``, ``failed``,
        ``inline`` (none submitted)."""
        build, used = None, "inline"
        if prebuilt is not None:
            with obs_device.phase_timer("batch_build",
                                        stage="prebuilt") as sp:
                used = "taken" if prebuilt.done() else "waited"
                try:
                    build = prebuilt.result()
                except Exception as e:  # noqa: BLE001 — built inline
                    used = "failed"
                    log.debug("look-ahead build failed (%s): building "
                              "inline", e)
                else:
                    sp.attrs.update(built_tid=build.tid,
                                    built_mono=round(build.mono, 6),
                                    built_dur=round(build.dur, 6))
                sp.attrs["used"] = used
        obs_metrics.REGISTRY.counter(
            "pipeline_prebuilt_total",
            help="pipelined explorations by what became of the "
                 "look-ahead's build of their batch: taken (done when "
                 "asked for), waited, failed (raised; built inline), "
                 "inline (none was submitted)",
            labels={"used": used}).inc()
        if note is not None:
            note["prebuilt"] = used
        return build

    def _host_phase_work(self, bi: int, handle) -> Dict:
        """Pipelined attempt, second half: modules + solver + merge,
        under its own watchdog budget (a wedged witness search must not
        stall the device side forever)."""
        kind, payload = handle
        if kind == "out":
            return payload
        return run_with_watchdog(lambda: self._harvest_batch(bi, payload),
                                 self.batch_timeout,
                                 label=f"batch {bi} host")

    def _host_phase_job(self, bi: int, handle, tctx,
                        start: _HostPhaseStart,
                        idle_since: Optional[float]):
        """Worker-thread entry: wait for ``start``, then run the
        host phase inside a span and return ``(out, host_dur,
        done_mono)`` so the commit side can account overlap (hidden
        host seconds) and worker idle. The wait is outside the span: it
        is the worker's idle time since ``idle_since`` (the end of the
        host phase before), the ``host-waits-device`` stall. A start
        that was abandoned returns None with no work done. ``tctx``
        re-enters the submitting thread's trace scope (contextvars
        don't cross the pool boundary on their own)."""
        after = start.wait()
        if after is None:
            return None
        reg = obs_metrics.REGISTRY
        with obs_trace.apply_context(tctx):
            if idle_since is not None:
                idle = max(0.0, time.monotonic() - idle_since)
                obs_trace.complete("pipeline_stall", idle,
                                   wait="host-waits-device", bi=bi)
                reg.counter(
                    "pipeline_host_waits_device_seconds_total",
                    help="worker idle between host phases").inc(idle)
            reg.counter(
                "pipeline_host_phase_starts_total",
                help="pipelined host phases by what released their "
                     "start: the next batch's first sym_run call, the "
                     "end of its device phase, or no next phase",
                labels={"after": after}).inc()
            sp = obs_device.phase_timer("host_phase", bi=bi,
                                        after=after).start()
            try:
                out = self._host_phase_work(bi, handle)
            finally:
                sp.stop()
        return out, sp.dur or 0.0, time.monotonic()

    @staticmethod
    def _fault_reason(e: BaseException) -> str:
        if isinstance(e, BatchTimeout):
            return f"timeout: {e}"
        if isinstance(e, DeviceLostError):
            return f"device-lost: {e}"
        return f"{type(e).__name__}: {str(e)[:200]}"

    def _note_failure(self, e: BaseException) -> None:
        # a device loss gets a bounded backend re-probe (with backoff)
        # before the batch retries; the events land in the report
        if isinstance(e, DeviceLostError):
            if self.backend is not None:
                self.backend.recover(reason=str(e)[:200])
            # losing the device is the tier's failure: when a ladder is
            # active, demote so the retry runs on the next tier (a
            # CPU-only ladder makes this a silent floor no-op)
            if self._tm is not None:
                self._tm.demote(reason=f"device-lost: {str(e)[:160]}")

    def _degrade_batch(self, bi: int, items: Sequence[tuple],
                       first_err: BaseException) -> Tuple[Dict, str]:
        """Walk the RESOURCE_EXHAUSTED ladder until the batch fits.

        Rungs apply cumulatively — halve the per-contract lanes, then
        additionally halve the batch width (the batch replays as
        half-width sub-batches, each padded to the new shape), then
        additionally demote execution to the next available backend
        tier (host CPU on the floor). Every step lands
        in the report's ``backend_events``; a rung that fails with a
        NON-OOM error re-raises immediately (that failure belongs to
        the retry/bisect machinery, not the ladder). Partial sub-batch
        results are discarded on a failed rung so nothing is counted
        twice when the next rung replays the whole batch. Returns
        ``(results, rung)`` of the first rung that completed; raises the
        last OOM when the ladder is exhausted."""
        lanes = self.lanes_per_contract
        width = self.batch_size
        on_tier: Optional[str] = None
        err = first_err
        for rung in self.oom_ladder:
            if rung == "halve-lanes":
                lanes = max(1, lanes // 2)
            elif rung == "halve-batch":
                width = max(1, width // 2)
            elif rung == "cpu":
                # the terminal rung's historical name: demote this
                # batch to the ladder's floor tier (host CPU when no
                # lower accelerator tier is configured)
                on_tier = self._floor_tier()
            self._event("degrade", detail=self._fault_reason(err),
                        batch=bi, step=rung, lanes=lanes, width=width)
            try:
                out = {"issues": [], "paths": 0, "dropped": 0, "iprof": {}}
                for sub in self._sub_batches(items, width):
                    r = self._guarded_batch(bi, sub,
                                            lanes=lanes, width=width,
                                            on_tier=on_tier)
                    out["issues"].extend(r["issues"])
                    out["paths"] += r["paths"]
                    out["dropped"] += r["dropped"]
                    for op, n in r["iprof"].items():
                        out["iprof"][op] = out["iprof"].get(op, 0) + n
                self._event("degrade_ok", batch=bi, step=rung)
                return out, rung
            except Exception as e:  # noqa: BLE001 — triage below
                err = e
                if classify_backend_error(e) != "oom":
                    raise
                log.warning("batch %d still RESOURCE_EXHAUSTED after "
                            "%s (%s)", bi, rung, self._fault_reason(e))
        raise err

    @staticmethod
    def _sub_batches(items: Sequence[tuple], width: int) -> List[list]:
        """``items`` in sub-batches of at most ``width`` contracts, cut
        on unit boundaries (a linked system stays whole; one larger than
        ``width`` is a sub-batch of its own, which the narrower shape
        then refuses: the rung fails and the ladder goes on)."""
        out: List[list] = [[]]
        for unit in corpus_units(items):
            if out[-1] and len(out[-1]) + len(unit) > width:
                out.append([])
            out[-1].extend(unit)
        return [b for b in out if b]

    def _run_batch_resilient(self, bi: int,
                             items: Sequence[tuple],
                             first_err: Optional[BaseException] = None
                             ) -> Dict:
        """Full batch → degrade (OOM) / retry → bisect to the poison
        contract(s).

        ``first_err`` is the pipeline's drain entry: the pipelined
        device+host attempt already WAS the first attempt (it fired the
        fault injector exactly once, like a serial first attempt), so
        on its failure the pipeline hands the error here and this
        method skips straight to the degrade/retry/bisect tail —
        attempt counts, events, statuses and quarantine decisions stay
        byte-identical to a serial run hitting the same fault.

        A 10k campaign must lose at most the poison contracts, never the
        run. A failure classified as RESOURCE_EXHAUSTED first walks the
        degradation ladder (shrink lanes, then batch width, then fall
        to CPU) — capacity pressure is absorbed by the scheduler, not
        answered with an abort. Any other failure (timeout, crash,
        device error) is retried ``max_batch_retries`` times — except a
        classified compile failure, where replaying the identical shape
        cannot succeed — then the batch is bisected, each half
        replaying through the same compiled shape, until the offending
        contract(s) are isolated and quarantined with a reason.
        InjectedKill (and real signals) still blow through
        uncheckpointed, which is what the resume path is for."""
        out = {"issues": [], "paths": 0, "dropped": 0, "iprof": {},
               "quarantined": [], "retries": 0, "status": "ok"}

        def merge(r: Dict) -> None:
            self._note_engine(bi, r.get("engine"))
            out["issues"].extend(r["issues"])
            out["paths"] += r["paths"]
            out["dropped"] += r["dropped"]
            for k, v in r["iprof"].items():
                out["iprof"][k] = out["iprof"].get(k, 0) + v

        if first_err is None:
            try:
                merge(self._guarded_batch(bi, items))
                return out
            except Exception as e:  # noqa: BLE001 — isolate, don't die
                err = e
                log.warning("batch %d failed (%s)", bi,
                            self._fault_reason(e))
        else:
            err = first_err
            log.warning("batch %d failed pipelined (%s); draining to the "
                        "serial path", bi, self._fault_reason(err))
        self._note_failure(err)
        kind = classify_backend_error(err)
        if kind == "oom" and self.oom_ladder:
            try:
                degraded, rung = self._degrade_batch(bi, items, err)
                merge(degraded)
                out["status"] = f"ok-degraded:{rung}"
                return out
            except Exception as e:  # noqa: BLE001 — ladder exhausted
                err = e
                self._note_failure(e)
                log.warning("batch %d degradation exhausted (%s); "
                            "falling back to retry/bisect", bi,
                            self._fault_reason(e))
        # a classified compile failure deterministically reproduces on
        # an identical replay — skip straight to bisection
        retry_budget = 0 if kind == "compile" else self.max_batch_retries
        for _ in range(retry_budget):
            out["retries"] += 1
            try:
                merge(self._guarded_batch(bi, items))
                out["status"] = "ok-retry"
                return out
            except Exception as e:  # noqa: BLE001
                err = e
                self._note_failure(e)
        # bisect: a failing group splits in half; a failing singleton is
        # the poison — quarantine it and keep going. The unit is what
        # ``corpus_units`` says: a linked system is one item (its
        # members are never analysed apart, so a poisoned member
        # quarantines its system, and every record says which)
        groups = [corpus_units(items)]
        while groups:
            g = groups.pop()
            try:
                merge(self._guarded_batch(bi, [c for u in g for c in u]))
            except Exception as e:  # noqa: BLE001
                self._note_failure(e)
                if len(g) == 1:
                    link = record_link(g[0][0])
                    for member in g[0]:
                        out["quarantined"].append({
                            "name": member[0],
                            "reason": self._fault_reason(e),
                            "batch": bi,
                            **({} if link is None
                               else {"system": link["system"]}),
                        })
                else:
                    mid = len(g) // 2
                    groups.append(g[mid:])
                    groups.append(g[:mid])
        out["status"] = f"quarantined:{len(out['quarantined'])}"
        return out

    def _portfolio_delta(self) -> Dict:
        """This run's solver-portfolio delta (daemon-lifetime totals
        when no run() baseline exists, e.g. resident serve batches)."""
        from ..smt import portfolio as smt_portfolio

        return smt_portfolio.stats_delta(
            smt_portfolio.PORTFOLIO_STATS.snapshot(), self._pstats0)

    def _portfolio_event(self, solver_totals: Optional[Dict]) -> None:
        """Emit the cumulative per-stage solver-portfolio counters as
        one trace event (batch-commit cadence — trace_report sections
        7/8 read the LAST one, so cumulative beats per-batch deltas)."""
        if not obs_trace.active():
            return
        d = self._portfolio_delta()
        t = solver_totals or {}
        obs_trace.event("solver_portfolio",
                        queries=d["queries"],
                        z3_avoided_pct=d["z3_avoided_pct"],
                        witness_mismatch=d["witness_mismatch"],
                        stages=d["stages"],
                        attempts=t.get("attempts", 0),
                        sat=t.get("sat", 0), unsat=t.get("unsat", 0),
                        unknown=t.get("unknown", 0))

    def _heartbeat(self, done: int, total: int, res: "CampaignResult",
                   last_out: Dict) -> None:
        """One line of live progress on stderr (plus a ``heartbeat``
        event on the trace bus): contracts done, paths/s, frontier
        occupancy, current rung, Z3-avoided %% (the share of solver
        queries the portfolio resolved before the witness search —
        docs/solver.md), last-checkpoint age. The 10k-campaign
        operator's 'is it still making progress, and at what cost'
        pulse — without grepping four channels."""
        wall = sum(res.batch_wall)
        contracts = self._contracts_done(done)
        pps = res.paths_total / wall if wall else 0.0
        # contracts/min: the end-to-end headline rate (ROADMAP "Kill the
        # P-scaling cliff" makes it the number next to lane-steps/s) —
        # published as a gauge too, so serve /metrics and the heartbeat
        # quote the same figure
        cpm = contracts / wall * 60.0 if wall else 0.0
        obs_metrics.REGISTRY.gauge(
            "campaign_contracts_per_min",
            help="end-to-end analyzed contracts per minute "
                 "(batch walls, campaign scope)").set(round(cpm, 2))
        # occupancy: the engine gauge when telemetry collected it this
        # chunk, else a lane-capacity estimate from the last batch
        occ = obs_metrics.REGISTRY.gauge("frontier_occupancy").value
        if not occ:
            cap = max(1, self.batch_size * self.lanes_per_contract)
            occ = min(1.0, last_out.get("paths", 0) / cap)
        rung = res.batch_status[-1] if res.batch_status else "-"
        z3av = self._portfolio_delta()["z3_avoided_pct"]
        age = (time.monotonic() - self._last_ckpt_mono
               if self._last_ckpt_mono is not None else None)
        age_s = f"{age:.1f}s" if age is not None else "never"
        # engine-worker token (docs/resilience.md): restarts so far,
        # plus the breaker state when it isn't closed — the operator's
        # one-glance "is the backend crash-looping" signal
        wst = self.worker_status()
        wk = ""
        if wst is not None:
            wk = f" wkr r{wst['restarts']}"
            if wst["breaker"] != "closed":
                wk += f"/breaker-{wst['breaker']}"
        # backend-tier token: which capacity class this campaign holds
        # right now ("tier=cpu!" marks a demotion in one glance)
        tier = self._tm.current if self._tm is not None else None
        tk = ""
        if tier is not None:
            tk = f" tier={tier}" + ("!" if self._tm.demoted() else "")
        # compile-warmth token (docs/serving.md "Compile artifacts &
        # prewarm"): shape classes warm in THIS process / registry
        # buckets recorded for the active tier ("warm 2/5" = three
        # buckets would still compile cold here)
        warm_a, warm_b = self.warm_counts()
        wa = ""
        if warm_a or warm_b:
            wa = f" warm {warm_a}/" + ("-" if warm_b is None
                                       else str(warm_b))
        # serving token: end-to-end request latency percentiles from
        # the serve_request_seconds histogram — SLO drift on the same
        # line the operator already watches, no /metrics scrape needed
        rq = ""
        req_p50 = req_p95 = None
        rh = obs_metrics.REGISTRY.histogram(
            "serve_request_seconds",
            help="end-to-end request latency (submit to resolve)")
        if rh.count:
            req_p50, req_p95 = rh.quantile(0.5), rh.quantile(0.95)
            rq = f" req p50 {req_p50:.2f}s/p95 {req_p95:.2f}s"
        print(f"heartbeat: batch {done}/{total} contracts {contracts}/"
              f"{len(self.contracts)} c/min {cpm:.1f} paths/s {pps:.1f} "
              f"frontier {100.0 * occ:.0f}% rung {rung} "
              f"z3-avoid {z3av:.0f}% "
              f"ckpt-age {age_s}{wk}{tk}{wa}{rq}",
              file=sys.stderr, flush=True)
        obs_trace.event("heartbeat", batch=done, batches_total=total,
                        contracts=contracts,
                        contracts_per_min=round(cpm, 2),
                        paths_per_sec=round(pps, 1),
                        occupancy=round(occ, 4), rung=rung,
                        z3_avoided_pct=z3av,
                        ckpt_age=(round(age, 3) if age is not None
                                  else None),
                        worker_restarts=(wst["restarts"]
                                         if wst is not None else None),
                        worker_breaker=(wst["breaker"]
                                        if wst is not None else None),
                        tier=tier,
                        warm_shapes=warm_a, warm_buckets=warm_b,
                        req_p50=(round(req_p50, 4)
                                 if req_p50 is not None else None),
                        req_p95=(round(req_p95, 4)
                                 if req_p95 is not None else None))

    # --- the pipelined loop --------------------------------------------
    def _run_pipelined(self, start_batch: int, n_batches: int,
                       deadline: Optional[float], commit) -> None:
        """Depth-1 batch pipeline: batch *i*'s host phase (worker
        thread) overlaps batch *i+1*'s device phase (this thread).

        Batch *i*'s host phase is submitted when its device phase has
        ended and STARTS when batch *i+1*'s device phase has enqueued
        its first ``sym_run`` call (``SymExecWrapper``'s
        ``on_first_call``): until then that phase builds its batch on
        the interpreter lock the host phase would share, with the
        device waiting; from then on it blocks in reads with the lock
        released. Each start is released exactly once
        (:class:`_HostPhaseStart`), by whichever comes first: that hook
        (``after="first_call"``); the end of device phase *i+1* however
        it ends, a handle that made no call included
        (``"phase_end"``); the loop leaving with no further device
        phase (``"no_next_phase"``); this function going down with an
        exception the loop does not drain (the start is abandoned: the
        worker returns without working and no thread is left waiting).
        The ``host_phase`` span carries ``after`` and
        ``pipeline_host_phase_starts_total{after}`` counts them.

        The look-ahead: the same hook submits the BUILD of batch *i+2*
        (:meth:`_build_batch`: images, corpus, seeded frontier; nothing
        of it depends on an earlier batch) to the same one-worker pool,
        behind host phase *i*, so both run under phase *i+1*'s
        ``sym_run`` calls, and phase *i+2* starts from the finished
        bundle (:meth:`_take_prebuilt`) instead of building in its
        lead-in with the device idle. A phase builds for itself where
        there is no bundle of its batch: the window's first, one after
        a drain (the bundle is dropped) or after a phase that made no
        call, and one whose build raised (the error is dropped and the
        phase's own build raises it where it always did).
        ``pipeline_prebuilt_total{used}`` and ``prebuilt`` on the
        ``device_phase`` span say which.

        Invariants that keep results byte-identical to the serial loop:

        - at most ONE host phase is in flight, and ``commit`` runs
          strictly in batch order (batch *i* commits before *i+1*'s
          host phase is even submitted);
        - the fault injector fires once per pipelined attempt, in the
          device phase — the same cadence as a serial first attempt
          (a build never fires it);
        - a bundle is what the phase's own build would have made, and
          none is built for a batch past the last, under worker
          isolation or a custom ``batch_runner`` (neither fires the
          hook); leaving the loop cancels a build that has not started
          and drops one that has;
        - ANY phase failure drains: the outstanding host phase commits
          first, then the failed batch re-enters
          ``_run_batch_resilient`` with ``first_err`` set, so degrade/
          retry/bisect/quarantine decisions replay the serial machinery
          exactly (``ok-degraded:<rung>``, retry counts, statuses);
        - an ``InjectedKill`` (or real signal) blows through
          uncommitted, exactly like the serial loop — the resume path
          replays what was never durably recorded, nothing twice.

        Stall telemetry (docs/performance.md): ``pipeline_stall`` spans
        with ``wait=device-waits-host`` (this loop blocked on an
        unfinished host phase — the device sat idle) and
        ``wait=host-waits-device`` (the worker sat idle between host
        phases, up to the release of the next one's start; the attr is
        ``wait``, not ``kind`` — ``kind`` is the JSONL schema's reserved
        record-type field and a colliding span attr is dropped), plus a
        ``pipeline_occupancy`` gauge = fraction of
        host-phase seconds hidden behind device execution: those that
        passed while a ``sym_run`` call of the device phase beside the
        host phase was in flight (``SymExecWrapper.sym_run_calls``). A
        host second spent while no call was in flight (between two
        calls, or after a start released at the phase's end) is not
        hidden: the device waited through it. The per-batch
        ``batch`` span/wall is ``device_dur + commit_stall`` — the
        batch's contribution to campaign wall-clock — so the trace
        report's batch stall table sums to (about) the campaign wall,
        and a pipelined run's total reads strictly below a serial run's
        whenever any host time was hidden."""
        from concurrent.futures import ThreadPoolExecutor
        from functools import partial

        reg = obs_metrics.REGISTRY
        pool = ThreadPoolExecutor(max_workers=1,
                                  thread_name_prefix="host-phase")
        inflight: Optional[Dict] = None
        host_idle_since: Optional[float] = None
        # the look-ahead: ``{"bi", "future"}`` of the one batch built
        # ahead of its phase, if any
        ahead: Optional[Dict] = None

        def first_call(bi: int, start: Optional[_HostPhaseStart]) -> None:
            """Phase ``bi`` has enqueued its first ``sym_run`` call:
            from here on its thread blocks in reads with the interpreter
            lock released, so the worker gets the previous batch's host
            phase and, behind it in the pool's queue, the build of the
            next batch."""
            nonlocal ahead
            if start is not None:
                start.release("first_call")
            if bi + 1 < n_batches:
                ahead = {"bi": bi + 1, "future": pool.submit(
                    self._build_batch, self._batch_items(bi + 1),
                    obs_trace.context_snapshot())}

        def drop_ahead() -> None:
            """Give up the look-ahead's build: one that has not started
            never runs, one that has is dropped when it ends."""
            nonlocal ahead
            if ahead is not None:
                ahead["future"].cancel()
                ahead = None

        def account_overlap(host_dur: float, done_mono: float,
                            beside) -> float:
            """``hidden``: the overlap of the host phase that ended at
            ``done_mono`` with the ``sym_run`` calls of ``beside``, the
            handle of the device phase that ran meanwhile (None: there
            was none; a handle without a wrapper made no call)."""
            calls = (beside[1].sym_run_calls
                     if beside is not None and beside[0] == "sym" else ())
            start = done_mono - host_dur
            hidden = sum(max(0.0, min(done_mono, c1) - max(start, c0))
                         for c0, c1 in calls)
            self._pipe_host_sec += host_dur
            self._pipe_hidden_sec += hidden
            reg.counter(
                "pipeline_host_hidden_seconds_total",
                help="host-phase seconds that passed while a sym_run "
                     "call was in flight").inc(hidden)
            reg.gauge(
                "pipeline_occupancy",
                help="fraction of host-phase seconds that passed while "
                     "a sym_run call was in flight").set(
                self._pipe_hidden_sec / self._pipe_host_sec
                if self._pipe_host_sec else 0.0)
            return hidden

        def drain_serial(bi: int, items: Sequence[tuple], err,
                         dev_dur: float, t_wall: float, t_mono: float,
                         stall: float = 0.0) -> None:
            """Pipelined attempt failed: replay the serial machinery
            (skipping the already-paid first attempt) and commit."""
            drop_ahead()
            rec = obs_trace.timer("batch_drain", bi=bi).start()
            out = self._run_batch_resilient(bi, items, first_err=err)
            rec.stop()
            dt = dev_dur + stall + (rec.dur or 0.0)
            obs_trace.complete("batch", dt, t_wall=t_wall, mono=t_mono,
                               bi=bi, n=len(items), pipelined=True,
                               drained=True)
            commit(bi, out, dt)

        def commit_inflight(fl: Dict, beside=None) -> None:
            nonlocal host_idle_since
            bi = fl["bi"]
            wait_sp = obs_trace.timer("pipeline_stall",
                                      wait="device-waits-host",
                                      bi=bi).start()
            try:
                out, host_dur, done_mono = fl["future"].result()
            except Exception as e:  # noqa: BLE001 — drain to serial
                wait_sp.stop()
                host_idle_since = time.monotonic()
                drain_serial(bi, fl["items"], e, fl["dev_dur"],
                             fl["t_wall"], fl["mono"],
                             stall=wait_sp.dur or 0.0)
                return
            stall = wait_sp.stop()
            host_idle_since = done_mono
            # a clean pipelined attempt is a clean first attempt: same
            # resilience envelope _run_batch_resilient gives its own
            # first-try success (no retries, nothing quarantined)
            self._note_engine(bi, out.get("engine"))
            out = {"issues": out["issues"], "paths": out["paths"],
                   "dropped": out["dropped"], "iprof": out["iprof"],
                   "quarantined": [], "retries": 0, "status": "ok"}
            reg.counter(
                "pipeline_device_waits_host_seconds_total",
                help="device idle: loop blocked on an unfinished host "
                     "phase").inc(stall)
            hidden = account_overlap(host_dur, done_mono, beside)
            dt = fl["dev_dur"] + stall
            obs_trace.complete("batch", dt, t_wall=fl["t_wall"],
                               mono=fl["mono"], bi=bi, n=fl["n"],
                               pipelined=True,
                               device_dur=round(fl["dev_dur"], 6),
                               host_dur=round(host_dur, 6),
                               stall=round(stall, 6),
                               hidden=round(hidden, 6))
            commit(bi, out, dt)

        try:
            for bi in range(start_batch, n_batches):
                if deadline is not None and time.monotonic() >= deadline:
                    break
                items = self._batch_items(bi)
                t_wall, t_mono = time.time(), time.monotonic()
                dev_sp = obs_device.phase_timer(
                    "device_phase", bi=bi, n=len(items)).start()
                handle = None
                first_err: Optional[BaseException] = None
                # only the build of THIS batch will do (a phase that an
                # expired watchdog walked away from may fire its hook
                # late)
                if ahead is not None and ahead["bi"] != bi:
                    drop_ahead()
                mine = ahead
                try:
                    # the PREVIOUS batch's host phase starts at this
                    # phase's first ``sym_run`` call, and the NEXT
                    # batch's build behind it
                    handle = self._device_phase(
                        bi, items, on_first_call=partial(
                            first_call, bi,
                            inflight and inflight["start"]),
                        prebuilt=mine and mine["future"],
                        note=dev_sp.attrs)
                except Exception as e:  # noqa: BLE001 — drained below
                    first_err = e
                dev_dur = dev_sp.stop()
                if ahead is mine:
                    # no first call replaced it: nothing is built ahead
                    drop_ahead()
                # commit the PREVIOUS batch only now: its host phase ran
                # concurrently with the device phase that just finished
                # (all of it after that phase, if it made no call)
                if inflight is not None:
                    inflight["start"].release("phase_end")
                    commit_inflight(inflight, handle)
                    inflight = None
                if first_err is not None:
                    drain_serial(bi, items, first_err, dev_dur,
                                 t_wall, t_mono)
                    continue
                start = _HostPhaseStart()
                inflight = {"bi": bi, "items": items, "n": len(items),
                            "dev_dur": dev_dur, "t_wall": t_wall,
                            "mono": t_mono, "start": start,
                            "future": pool.submit(
                                self._host_phase_job, bi, handle,
                                obs_trace.context_snapshot(), start,
                                host_idle_since)}
            if inflight is not None:
                inflight["start"].release("no_next_phase")
                commit_inflight(inflight)
                inflight = None
        finally:
            # no blocking wait: on the kill path a host phase that was
            # running finishes harmlessly, one that was still waiting
            # for its start is told to do nothing, and the pool reaps
            # its thread either way
            if inflight is not None:
                inflight["start"].release(None)
            drop_ahead()
            pool.shutdown(wait=False)

    # --- elastic fleet mode (docs/fleet.md) -----------------------------
    def _run_unit(self, ledger, unit,
                  deadline: Optional[float] = None,
                  items: Optional[Sequence[tuple]] = None,
                  trace: Optional[Dict] = None) -> Optional[Dict]:
        """Analyze one claimed work unit: its contracts stream through
        the same resilient batch machinery as a static run (retry /
        degrade / bisect / quarantine all apply within the unit), under
        a background lease heartbeat. Batch indices are GLOBAL
        (``unit.start // batch_size`` + offset) so fault specs and trace
        correlation mean the same thing on every worker. Returns the
        self-contained unit record the ledger commits — the durable,
        merge-ready account of exactly these contracts — or ``None``
        when the deadline expired mid-unit (the lease is released so
        another worker picks the unit up without burning a re-lease
        grant)."""
        from ..smt import portfolio as smt_portfolio
        from ..smt.solver import SOLVER_STATS

        stats0 = SOLVER_STATS.snapshot()
        pstats0 = smt_portfolio.PORTFOLIO_STATS.snapshot()
        rec: Dict = {"unit": unit.uid, "attempt": unit.attempt,
                     "worker": ledger.worker, "corpus": ledger.corpus,
                     "contracts": list(unit.names),
                     "issues": [], "paths_total": 0, "dropped_forks": 0,
                     "batches": 0, "batch_wall": [], "batch_status": [],
                     "quarantined": [], "retries": 0, "iprof": {}}
        # static ledgers index the local corpus; feed units (follow
        # mode) carry their own bytecode — the caller hands it in
        items = (list(items) if items is not None
                 else self.contracts[unit.start:unit.start
                                     + len(unit.names)])
        base_bi = unit.start // self.batch_size
        reg = obs_metrics.REGISTRY
        # trace ingestion point (fleet claim): continue the trace the
        # feeder stamped into the unit config, or mint one here — every
        # span/event this unit emits carries it either way
        ids = (list((trace or {}).get("ids") or ())
               or [obs_trace.new_trace_id()])
        with obs_trace.trace_context(ids[0], link_ids=ids[1:]), \
                ledger.renewer(unit):
            for j in range(0, len(items), self.batch_size):
                if deadline is not None and time.monotonic() >= deadline:
                    ledger.release(unit)
                    return None
                bi = base_bi + j // self.batch_size
                batch = items[j:j + self.batch_size]
                with obs_trace.timer("batch", bi=bi, n=len(batch),
                                     unit=unit.uid) as sp:
                    out = self._run_batch_resilient(bi, batch)
                self._emit_backend_events()
                obs_trace.event("batch_status", bi=bi, unit=unit.uid,
                                status=out["status"],
                                dur=round(sp.elapsed, 6))
                reg.counter("batches_total").inc()
                reg.histogram("batch_seconds",
                              help="per-batch wall time").observe(
                    sp.elapsed)
                reg.counter("batch_retries_total").inc(out["retries"])
                reg.counter("contracts_quarantined_total").inc(
                    len(out["quarantined"]))
                for i in out["issues"]:
                    i["unit"] = unit.uid
                for q in out["quarantined"]:
                    q["unit"] = unit.uid
                rec["issues"].extend(out["issues"])
                rec["paths_total"] += out["paths"]
                rec["dropped_forks"] += out["dropped"]
                rec["batches"] += 1
                rec["batch_wall"].append(round(sp.elapsed, 6))
                rec["batch_status"].append(out["status"])
                rec["quarantined"].extend(out["quarantined"])
                rec["retries"] += out["retries"]
                for k, v in out["iprof"].items():
                    rec["iprof"][k] = rec["iprof"].get(k, 0) + v
        rec["solver"] = {k: round(v, 3)
                         for k, v in SOLVER_STATS.delta(stats0).items()}
        # the unit record carries its portfolio delta too (numeric-only
        # merge arithmetic skips the nested dict; it rides for audit)
        from ..smt import portfolio as smt_portfolio

        rec["solver_portfolio"] = smt_portfolio.stats_delta(
            smt_portfolio.PORTFOLIO_STATS.snapshot(), pstats0)
        self._portfolio_event(rec["solver"])
        return rec

    def _fleet_absorb(self, res: CampaignResult, rec: Dict) -> None:
        """Fold one committed unit record into this worker's result."""
        res.issues.extend(rec["issues"])
        res.paths_total += rec["paths_total"]
        res.dropped_forks += rec["dropped_forks"]
        res.batch_wall.extend(rec["batch_wall"])
        res.batch_status.extend(rec["batch_status"])
        res.quarantined.extend(rec["quarantined"])
        res.retries += rec["retries"]
        for k, v in rec["iprof"].items():
            res.iprof[k] = res.iprof.get(k, 0) + v
        res.fleet["units"].append(rec)

    def _fleet_beat(self, res: CampaignResult, rec: Dict) -> None:
        if self.heartbeat_every is None:
            return
        now = time.monotonic()
        if (self._last_beat is not None
                and now - self._last_beat < self.heartbeat_every):
            return
        self._last_beat = now
        wall = sum(res.batch_wall)
        pps = res.paths_total / wall if wall else 0.0
        z3av = self._portfolio_delta()["z3_avoided_pct"]
        print(f"heartbeat: unit {rec['unit']} committed "
              f"({len(res.fleet['units'])} by this worker), "
              f"paths/s {pps:.1f} z3-avoid {z3av:.0f}%",
              file=sys.stderr, flush=True)
        obs_trace.event("heartbeat", unit=rec["unit"],
                        units_committed=len(res.fleet["units"]),
                        paths_per_sec=round(pps, 1),
                        z3_avoided_pct=z3av)

    def _run_fleet(self, progress=None) -> CampaignResult:
        """Claim→run→commit loop against the shared work ledger
        (docs/fleet.md). Durability is the per-unit result files — the
        per-host JSON checkpoint is not written (a dead worker's units
        are re-leased whole, so there is no mid-unit cursor to
        persist). The loop ends when every unit is committed or lost;
        while other workers still hold live leases this worker polls,
        ready to reclaim if their heartbeats go stale. An
        ``InjectedKill`` (or real signal) blows through uncommitted,
        leaving our lease to expire — exactly the contract the
        reclaim path is built on.

        With ``fleet_follow`` the ledger is a FEED (docs/serving.md): a
        serve daemon appends units — each carrying its own bytecode —
        over time, so instead of cutting the local corpus this worker
        polls for newly fed units and exits only when the feeder has
        CLOSED the feed and every unit is committed or lost (or the
        ``execution_timeout`` deadline lapses)."""
        from ..fleet import WorkLedger
        from ..smt.solver import SOLVER_STATS

        t_start = time.monotonic()
        deadline = (None if self.execution_timeout is None
                    else t_start + self.execution_timeout)
        stats_at_start = SOLVER_STATS.snapshot()
        ledger = WorkLedger(self.fleet_dir, ttl=self.lease_ttl,
                            max_leases=self.max_unit_leases,
                            worker=self.worker_id, on_event=self._event)
        if self.fleet_follow:
            ledger.attach_feed()
        else:
            ledger.ensure(self.contracts, unit_size=self.unit_size)
        res = CampaignResult()
        res.fleet = {"worker": ledger.worker,
                     "manifest": ledger.manifest_summary(),
                     "units": [], "lost": []}
        poll = max(0.05, min(self.lease_ttl / 4.0, 2.0))
        done_units = 0
        while True:
            if deadline is not None and time.monotonic() >= deadline:
                break
            if self.fleet_follow:
                ledger.refresh()
            unit = ledger.claim_next()
            if unit is None:
                if self.fleet_follow:
                    if ledger.feed_closed() and not ledger.pending():
                        break
                elif not ledger.pending():
                    break
                # someone else holds live leases (or the feeder has
                # more work coming): poll — stale heartbeats become
                # reclaimable, fed units become claimable
                time.sleep(poll)
                continue
            items = None
            ucfg: Dict = {}
            if self.fleet_follow:
                items, ucfg = ledger.read_unit_items(unit.uid)
                ucfg = ucfg if isinstance(ucfg, dict) else {}
            rec = self._run_unit(ledger, unit, deadline, items=items,
                                 trace=ucfg.get("trace"))
            if rec is None:
                break  # deadline mid-unit; lease already released
            if ledger.commit(unit, rec):
                self._fleet_absorb(res, rec)
                # the manifest in the report must cover the units this
                # worker saw — a feed manifest grows after attach
                if self.fleet_follow:
                    res.fleet["manifest"] = ledger.manifest_summary()
            # a failed commit (duplicate) already landed its event via
            # the ledger; the record is DROPPED so nothing counts twice
            done_units += 1
            if progress is not None:
                progress(done_units, ledger.n_units,
                         sum(rec["batch_wall"]), len(res.issues))
            self._fleet_beat(res, rec)
        res.fleet["lost"] = ledger.lost_units()
        res.batches = len(res.batch_wall)
        res.contracts = sum(len(u["contracts"])
                            for u in res.fleet["units"])
        res.wall_sec = time.monotonic() - t_start
        res.compile_sec = res.batch_wall[0] if res.batch_wall else 0.0
        res.backend_events = ((list(self.backend.events)
                               if self.backend is not None else [])
                              + list(self._events))
        res.solver = {k: round(v, 3)
                      for k, v in SOLVER_STATS.delta(stats_at_start).items()}
        return res

    # --- the campaign --------------------------------------------------
    def run(self, progress=None) -> CampaignResult:
        """Run the campaign (static slice or fleet loop), with the
        solver-portfolio store scoped to the run: the configured store
        directory becomes the process-global verdict store for the
        duration and the previous one is restored afterwards (even
        across a simulated kill), so concurrent owners — a serve
        daemon's data-dir store, another test's tmp dir — are never
        clobbered."""
        from ..smt import portfolio as smt_portfolio

        prev_store = (smt_portfolio.set_store(self.solver_store)
                      if self.solver_store else None)
        self._pstats0 = smt_portfolio.PORTFOLIO_STATS.snapshot()
        try:
            res = (self._run_fleet(progress)
                   if self.fleet_dir is not None
                   else self._run_static(progress))
        finally:
            if self.solver_store:
                smt_portfolio.set_store(prev_store)
            # the engine worker must not outlive the run (a real
            # SIGKILL of this process closes the pipes instead, and
            # the worker exits on stdin EOF)
            self.close_worker()
            # an OWNED tier ladder's prober dies with the run; an
            # injected (shared) one keeps probing — the serve
            # scheduler / soak harness owns its lifecycle
            if self._tm is not None and self._tm_owned:
                self._tm.stop_prober()
        res.solver_portfolio = smt_portfolio.stats_delta(
            smt_portfolio.PORTFOLIO_STATS.snapshot(), self._pstats0)
        res.engine = dict(self._engine)
        return res

    def _run_static(self, progress=None) -> CampaignResult:
        from ..smt.solver import SOLVER_STATS

        t_start = time.monotonic()
        deadline = (None if self.execution_timeout is None
                    else t_start + self.execution_timeout)
        state = self._load_ckpt()
        state.setdefault("shard", self._shard_stamp)
        res = CampaignResult()
        res.issues = list(state["issues"])
        res.batch_wall = list(state["batch_wall"])
        res.paths_total = int(state["paths_total"])
        res.dropped_forks = int(state["dropped_forks"])
        res.iprof = dict(state.get("iprof", {}))
        res.quarantined = list(state.get("quarantined", []))
        res.retries = int(state.get("retries", 0))
        res.batch_status = list(state.get("batch_status", []))
        # backend events accumulate like solver stats: prior sessions'
        # events come from the checkpoint, this session's from the live
        # BackendManager (snapshotted fresh at every save)
        events_prior = list(state.get("backend_events", []))
        # solver stats accumulate ACROSS sessions: the checkpoint carries
        # the totals from prior (killed/resumed) sessions, this session's
        # delta is added per batch — so the final report's sat/unsat/
        # unknown split covers the whole campaign, not just the last
        # session (the miss rate must be observable)
        solver_prior = dict(state.get("solver", {}))
        stats_at_start = SOLVER_STATS.snapshot()

        def session_events() -> List[Dict]:
            return (events_prior
                    + (list(self.backend.events)
                       if self.backend is not None else [])
                    + list(self._events))

        n_batches = self.n_batches
        dirty = [False]  # mutable: commit() below flips it
        start_batch = int(state["next_batch"])
        reg = obs_metrics.REGISTRY

        def commit(bi: int, out: Dict, dt: float) -> None:
            """Merge one finished batch into the result + checkpoint
            state. BOTH loops (serial below, pipelined) call this
            strictly in batch order — it is the single accounting
            point, which is what makes a pipelined run's results
            byte-identical to a serial run's."""
            self._emit_backend_events()
            obs_trace.event("batch_status", bi=bi, status=out["status"],
                            dur=round(dt, 6))
            reg.counter("batches_total").inc()
            reg.histogram("batch_seconds",
                          help="per-batch wall time").observe(dt)
            reg.counter("batch_retries_total").inc(out["retries"])
            reg.counter("contracts_quarantined_total").inc(
                len(out["quarantined"]))
            res.issues.extend(out["issues"])
            res.batch_wall.append(dt)
            res.paths_total += out["paths"]
            res.dropped_forks += out["dropped"]
            for name, n in out["iprof"].items():
                res.iprof[name] = res.iprof.get(name, 0) + n
            res.quarantined.extend(out["quarantined"])
            res.retries += out["retries"]
            res.batch_status.append(out["status"])
            # safe to read here even in pipelined mode: solver queries
            # only run in host phases, which are committed in order and
            # never concurrently with this call
            sess = SOLVER_STATS.delta(stats_at_start)
            state.update(next_batch=bi + 1, issues=res.issues,
                         batch_wall=res.batch_wall,
                         paths_total=res.paths_total,
                         dropped_forks=res.dropped_forks,
                         iprof=res.iprof,
                         quarantined=res.quarantined,
                         retries=res.retries,
                         batch_status=res.batch_status,
                         backend_events=session_events(),
                         solver={k: round(solver_prior.get(k, 0) + v, 3)
                                 for k, v in sess.items()})
            # --checkpoint-every N: durable write every N batches (and
            # always after the last); a kill between writes replays at
            # most N batches whose results were never persisted — no
            # contract is ever counted twice
            if (bi + 1 - start_batch) % self.checkpoint_every == 0 \
                    or bi + 1 == n_batches:
                self._save_ckpt(state)
                dirty[0] = False
            else:
                dirty[0] = True
            # solver gauges mirror the accumulated campaign totals —
            # a scrape mid-run sees the whole-campaign split, like the
            # final report will
            for k, v in state["solver"].items():
                if isinstance(v, (int, float)):
                    reg.gauge(f"solver_{k}").set(v)
            # cumulative portfolio ladder on the trace bus (section 8
            # of trace_report reads the last of these)
            self._portfolio_event(state["solver"])
            if progress is not None:
                progress(bi + 1, n_batches, dt, len(res.issues))
            if self.heartbeat_every is not None:
                now = time.monotonic()
                if (self._last_beat is None
                        or now - self._last_beat >= self.heartbeat_every):
                    self._last_beat = now
                    self._heartbeat(bi + 1, n_batches, res, out)

        self._ckpt_writer = None
        if self.pipeline and self._ckpt_path is not None:
            os.makedirs(self.checkpoint_dir, exist_ok=True)
            self._ckpt_writer = BackgroundCheckpointWriter(self._ckpt_path)
        try:
            if self.pipeline:
                self._run_pipelined(start_batch, n_batches, deadline,
                                    commit)
            else:
                for bi in range(start_batch, n_batches):
                    if (deadline is not None
                            and time.monotonic() >= deadline):
                        break
                    batch = self._batch_items(bi)
                    with obs_trace.timer("batch", bi=bi,
                                         n=len(batch)) as sp:
                        out = self._run_batch_resilient(bi, batch)
                    commit(bi, out, sp.elapsed)
            if dirty[0]:
                # deadline (or loop-exit) with unpersisted batches:
                # flush so the paid work survives the session
                self._save_ckpt(state)
            if self._ckpt_writer is not None:
                # the last submitted snapshot must be durable before the
                # result is reported — close() flushes, then joins
                self._ckpt_writer.close()
                self._ckpt_writer = None
        except BaseException:
            # a (simulated) kill or unhandled fault must NOT flush the
            # queued checkpoint snapshot: a real SIGKILL would not have,
            # and the kill/resume no-double-count guard is tested
            # against exactly that contract. An already-started write
            # completes (or tears — the loaders' checksum + rotation
            # fallback covers both).
            if self._ckpt_writer is not None:
                self._ckpt_writer.close(discard_pending=True)
                self._ckpt_writer = None
            raise

        res.batches = len(res.batch_wall)
        res.contracts = self._contracts_done(res.batches)
        res.wall_sec = time.monotonic() - t_start
        res.compile_sec = res.batch_wall[0] if res.batch_wall else 0.0
        res.backend_events = session_events()
        sess = SOLVER_STATS.delta(stats_at_start)
        res.solver = {k: round(solver_prior.get(k, 0) + v, 3)
                      for k, v in sess.items()}
        return res


def merge_campaigns(results: Sequence[Dict]) -> Dict:
    """Combine per-host campaign result dicts (``as_dict()`` shape, with
    optional ``issues_detail``) into corpus-level metrics. Hosts run
    CONCURRENTLY on a pod, so merged wall-clock is the slowest host, while
    throughput is the corpus total over that wall-clock.

    Fleet results (docs/fleet.md) get EXACTLY-ONCE accounting: a result
    carrying a ``fleet.units`` list contributes through its unit
    records, keyed by unit id — the first committed record of a unit
    wins, any later copy (the same result file merged twice, or a
    split-brain double account) is dropped with a ``unit_duplicate``
    event in the merged ``backend_events``. A result ALL of whose units
    were already merged is discarded wholesale (its events/solver would
    otherwise double too). The merged report then gains a top-level
    ``coverage`` manifest built from the ledger manifest: every contract
    ends in exactly one of ``analyzed`` / ``quarantined`` / ``lost``,
    with anything else counted ``unaccounted`` — and ``full`` is only
    True when lost and unaccounted are both zero (the
    ``campaign-merge --strict-coverage`` gate)."""
    seen_units: set = set()
    dup_units: List[Dict] = []
    manifests: List[Dict] = []
    unit_rows: List[Dict] = []
    # (result, fresh-units-or-None); None = legacy per-host result that
    # contributes through its top-level fields
    kept: List[tuple] = []
    for r in results:
        fl = r.get("fleet") or {}
        units = fl.get("units")
        if not isinstance(units, list):
            kept.append((r, None))
            continue
        if isinstance(fl.get("manifest"), dict):
            manifests.append(fl["manifest"])
        # a ledger-synthesized pseudo-host (campaign-merge given the
        # --fleet DIR itself) overlaps worker reports BY CONSTRUCTION —
        # its copies dedupe silently; only genuine anomalies (the same
        # result file twice, a split-brain double account) are flagged
        is_ledger = str(fl.get("worker", "")).startswith("ledger:")
        fresh = []
        for u in units:
            uid = str(u.get("unit"))
            if uid in seen_units:
                if not is_ledger:
                    dup_units.append(
                        {"unit": uid,
                         "worker": str(u.get("worker",
                                             fl.get("worker", "?")))})
                continue
            seen_units.add(uid)
            fresh.append(u)
        if units and not fresh:
            # every unit already merged: the same result file twice —
            # drop the whole host so its events aren't re-counted either
            continue
        unit_rows.extend(fresh)
        kept.append((r, fresh))

    legacy = [r for r, fresh in kept if fresh is None]
    merged: Dict = {
        "hosts": len(kept),
        "contracts": (sum(r.get("contracts", 0) for r in legacy)
                      + sum(len(u.get("contracts") or [])
                            for u in unit_rows)),
        "batches": (sum(r.get("batches", 0) for r in legacy)
                    + sum(u.get("batches", 0) for u in unit_rows)),
        "issues": (sum(r.get("issues", 0) for r in legacy)
                   + sum(len(u.get("issues") or []) for u in unit_rows)),
        "wall_sec": max((r.get("wall_sec", 0.0) for r, _ in kept),
                        default=0.0),
        "paths_total": (sum(r.get("paths_total", 0) for r in legacy)
                        + sum(u.get("paths_total", 0)
                              for u in unit_rows)),
        "dropped_forks": (sum(r.get("dropped_forks", 0) for r in legacy)
                          + sum(u.get("dropped_forks", 0)
                                for u in unit_rows)),
        # resilience fields: quarantine entries already carry their host's
        # batch index (and, for fleet results, their unit id);
        # concatenation in input order keeps them auditable
        "quarantined": ([q for r in legacy
                         for q in (r.get("quarantined") or [])]
                        + [q for u in unit_rows
                           for q in (u.get("quarantined") or [])]),
        "retries": (sum(r.get("retries", 0) for r in legacy)
                    + sum(u.get("retries", 0) for u in unit_rows)),
        "batch_status": ([s for r in legacy
                          for s in (r.get("batch_status") or [])]
                         + [s for u in unit_rows
                            for s in (u.get("batch_status") or [])]),
        # per-session event ordering preserved: a plain concatenation
        # interleaves resumed sessions' streams arbitrarily (host A's
        # resume can carry events older than host B's first session).
        # sorted() is stable, so events WITHIN one session keep their
        # emission order even where timestamps tie or are missing;
        # legacy events without session/t sort first as one group.
        "backend_events": sorted(
            (e for r, _ in kept
             for e in (r.get("backend_events") or [])),
            key=lambda e: (str(e.get("session", "")),
                           float(e.get("t", 0.0))
                           if isinstance(e.get("t", 0.0), (int, float))
                           else 0.0)),
    }
    # the duplicate-drop decisions are part of the merged audit trail
    merged["backend_events"] += [
        {"kind": "unit_duplicate", "unit": d["unit"],
         "worker": d["worker"],
         "detail": "unit already merged; duplicate copy dropped"}
        for d in dup_units]
    wall = merged["wall_sec"]
    merged["contracts_per_sec"] = (
        round(merged["contracts"] / wall, 3) if wall else 0.0)
    merged["paths_per_sec"] = (
        round(merged["paths_total"] / wall, 1) if wall else 0.0)
    solver: Dict = {}
    for src in legacy + unit_rows:
        for k, v in (src.get("solver") or {}).items():
            if isinstance(v, (int, float)):
                solver[k] = solver.get(k, 0) + v
    merged["solver"] = solver
    merged["solver_unknown_rate"] = (
        round(solver.get("unknown", 0) / solver["attempts"], 4)
        if solver.get("attempts") else 0.0)
    iprof: Dict[str, int] = {}
    for src in legacy + unit_rows:
        for k, v in (src.get("iprof") or {}).items():
            iprof[k] = iprof.get(k, 0) + v
    if iprof:
        merged["iprof"] = iprof
    detail = ([i for r in legacy for i in r.get("issues_detail", [])]
              + [i for u in unit_rows for i in (u.get("issues") or [])])
    if detail:
        merged["issues_detail"] = detail
    if manifests:
        merged["coverage"] = _fleet_coverage(manifests, unit_rows,
                                             dup_units, kept)
    return merged


def _fleet_coverage(manifests: Sequence[Dict], unit_rows: Sequence[Dict],
                    dup_units: Sequence[Dict], kept: Sequence[tuple]
                    ) -> Dict:
    """The merged coverage manifest: classify every manifest contract as
    analyzed / quarantined / lost / unaccounted from the unique unit
    records. ``lost`` takes the ledgers' re-lease-cap markers (a unit
    that was ALSO committed counts as committed — results win);
    ``unaccounted`` is whatever no record speaks for (a worker's result
    file missing from the merge, a unit still leased when the fleet
    stopped, a corrupt unit result)."""
    # a FEED manifest (docs/serving.md) grows while workers run, so
    # snapshots taken at different commit times legitimately differ in
    # length: take the largest as truth and call it mixed only when an
    # earlier snapshot is not a prefix of it. Static manifests must
    # match exactly, as before.
    man = max(manifests, key=lambda m: int(m.get("units") or 0))
    names = list(man.get("names") or [])
    if any(m.get("mode") == "feed" for m in manifests):
        mixed = any(
            m.get("corpus") != man.get("corpus")
            or list(m.get("names") or []) != names[:len(m.get("names")
                                                        or [])]
            for m in manifests)
    else:
        mixed = any(m.get("corpus") != man.get("corpus")
                    or m.get("names") != man.get("names")
                    for m in manifests)
    us = max(1, int(man.get("unit_size") or 1))
    n_units = int(man.get("units") or (len(names) + us - 1) // us)
    # feed units are variable-size: the manifest carries the per-unit
    # name lists instead of a fixed unit_size stride
    unit_names_list = man.get("unit_names")
    committed = {str(u.get("unit")): u for u in unit_rows}
    lost_ids: Dict[str, Dict] = {}
    for r, fresh in kept:
        if fresh is None:
            continue
        for lu in (r.get("fleet") or {}).get("lost") or []:
            uid = str(lu.get("unit"))
            if uid not in committed:
                lost_ids.setdefault(uid, lu)
    analyzed = quarantined = lost = unaccounted = 0
    unacc_units: List[str] = []
    for k in range(n_units):
        uid = f"u{k:05d}"
        if unit_names_list is not None:
            unames = list(unit_names_list[k]) \
                if k < len(unit_names_list) else []
        else:
            unames = names[k * us:(k + 1) * us]
        if not unames:
            break
        if uid in committed:
            u = committed[uid]
            qn = {q.get("name") for q in (u.get("quarantined") or [])}
            nq = sum(1 for n in unames if n in qn)
            quarantined += nq
            analyzed += len(unames) - nq
        elif uid in lost_ids:
            lost += len(unames)
        else:
            unaccounted += len(unames)
            unacc_units.append(uid)
    cov: Dict = {
        "contracts": len(names),
        "analyzed": analyzed,
        "quarantined": quarantined,
        "lost": lost,
        "unaccounted": unaccounted,
        "units_total": n_units,
        "units_committed": len(committed),
        "lost_units": sorted(lost_ids),
        "unaccounted_units": unacc_units,
        "duplicate_units": sorted({d["unit"] for d in dup_units}),
        "full": lost == 0 and unaccounted == 0 and not mixed,
    }
    if mixed:
        cov["corpus_mismatch"] = True
    return cov
