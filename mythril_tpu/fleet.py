"""Elastic fleet campaigns: a filesystem-coordinated work ledger.

The static ``--num-hosts/--host-index`` strided split hands each host a
fixed 1/N of the corpus with no cross-host contract: if one host of
eight dies, its slice is silently never analyzed, and
``merge_campaigns`` happily sums whatever per-host JSONs it is given —
double-counting duplicates, never flagging the gap. This module is the
cross-host contract (docs/fleet.md):

- the corpus is cut into deterministic WORK UNITS (chunks of contracts,
  stamped with a corpus fingerprint + unit id) recorded once in a
  shared ``manifest.json``;
- workers CLAIM units via atomic lease files (``O_CREAT|O_EXCL`` — the
  filesystem is the lock; the ledger dir lives on the same shared
  NFS/GCS mount the per-host checkpoints already use, so no network
  daemon is needed);
- a claimed lease is HEARTBEAT-renewed (``os.utime``) by a background
  thread while the unit runs; a lease whose heartbeat exceeds the TTL
  is RECLAIMED by any live worker (atomic ``rename`` arbitration), so
  a killed or wedged host's units migrate to survivors instead of
  vanishing;
- reclaims are BOUNDED (``max_leases`` grants per unit) — a unit that
  keeps killing its workers is marked ``lost`` rather than retried
  forever, the fleet-level analog of the campaign's bisect-to-
  quarantine;
- a finished unit COMMITS one result file via hard-link-exclusive
  create: the first commit wins, a racing duplicate commit (split
  brain: a worker that was reclaimed-from but came back) is detected
  and dropped with an event — the foundation of ``merge_campaigns``'s
  exactly-once accounting and coverage manifest.

Every lease transition lands on the telemetry spine
(docs/observability.md): ``lease_claimed`` / ``lease_reclaimed`` /
``unit_committed`` / ``unit_lost`` / ``unit_duplicate`` events plus
``fleet_units_{claimed,reclaimed,lost}_total`` counters and a
``fleet_lease_age_seconds`` gauge (oldest live heartbeat observed — how
close the fleet runs to its TTL).

Import cost is deliberately light (stdlib + utils.checkpoint's durable
write helpers): ``campaign-merge`` over a ledger dir must run on a
backend-free host.
"""

from __future__ import annotations

import hashlib
import json
import os
import socket
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .obs import metrics as obs_metrics
from .obs import trace as obs_trace
from .utils.checkpoint import durable_write, exclusive_write, fsync_dir

#: on-disk manifest schema (bump on breaking layout changes; readers
#: reject newer-than-known versions)
LEDGER_SCHEMA = 1

_MANIFEST = "manifest.json"
_UNITS_DIR = "units"


def contract_record(item: Sequence) -> Tuple[str, bytes, Optional[bytes]]:
    """One contract of a corpus as ``(name, runtime code, creation code
    or None)``. A pair is a record without creation code: the form every
    corpus had before campaigns could deploy."""
    name, code, *rest = item
    return name, code, (rest[0] if rest and rest[0] else None)


def corpus_fingerprint(contracts: Sequence[tuple]) -> str:
    """Stable identity of an ordered corpus slice of ``(name, bytecode)``
    pairs or ``(name, bytecode, creation code)`` records: 16 hex chars
    of sha256 over names + per-contract code digests. Two corpora of
    equal length but different content fingerprint apart — the property
    the checkpoint shard stamp and the fleet manifest both need (a count
    alone cannot tell "same corpus" from "same size"). Creation code is
    content: two corpora that differ only in a constructor deploy to
    different storage. So is a record's place in a linked system (its
    fourth field, ``mythril/campaign.py`` ``load_corpus_dir``). A pair
    hashes as it always did."""
    h = hashlib.sha256()
    for item in contracts:
        name, code, creation = contract_record(item)
        h.update(str(name).encode())
        h.update(b"\0")
        h.update(hashlib.sha256(bytes(code)).digest())
        if creation is not None:
            h.update(b"\1")
            h.update(hashlib.sha256(bytes(creation)).digest())
        if len(item) > 3 and item[3]:
            # a member of a linked system: which, and where it lives
            h.update(b"\2")
            h.update(f"{item[3]['system']}@{item[3]['address']:x}".encode())
    return h.hexdigest()[:16]


# first-commit-wins / create-once primitive: now shared repo-wide from
# utils/checkpoint.py (the solver verdict store uses it too)
_exclusive_write = exclusive_write


@dataclass
class WorkUnit:
    """One claimed work unit: ``uid`` names it in the ledger, ``start``
    indexes its first contract in the manifest order, ``names`` are its
    contracts, ``attempt`` is which lease grant this is (1 = first
    claim; reclaims increment)."""

    uid: str
    index: int
    start: int
    names: List[str]
    attempt: int


class WorkLedger:
    """Filesystem work ledger in a shared directory.

    Layout (all writes atomic — claim via ``O_EXCL``, commit/lost via
    link-exclusive create, heartbeat via ``utime``)::

        <dir>/manifest.json          corpus fingerprint + unit layout
        <dir>/units/u00000.lease     held lease (mtime = heartbeat)
        <dir>/units/u00000.result.json  committed unit result (wins)
        <dir>/units/u00000.lost      re-lease cap exhausted

    ``on_event(kind, **attrs)`` receives lease-lifecycle events (the
    campaign routes them into ``backend_events`` + the trace bus);
    without one they go to the trace bus directly.
    """

    def __init__(self, path: str, ttl: float = 60.0, max_leases: int = 3,
                 worker: Optional[str] = None,
                 on_event: Optional[Callable] = None):
        self.path = path
        self.ttl = max(0.05, float(ttl))
        self.max_leases = max(1, int(max_leases))
        self.worker = worker or (
            f"{socket.gethostname()}-{os.getpid():x}"
            f"-{threading.get_ident():x}")
        self.on_event = on_event
        self.corpus: Optional[str] = None
        self.unit_size = 0
        self.names: List[str] = []
        self.n_units = 0
        # feed mode (docs/serving.md): the manifest GROWS — a serve
        # daemon appends variable-size units (each with its bytecode in
        # a descriptor file) and eventually closes the feed; workers
        # poll ``refresh()`` and claim through the same lease machinery
        self.mode = "static"
        self.unit_names_list: List[List[str]] = []
        self.closed = False
        # result files that already parsed once: claim sweeps re-check
        # only unverified units, so torn-result detection stays O(new)
        self._verified_results: set = set()

    # --- events / metrics ----------------------------------------------
    def _event(self, kind: str, **kw) -> None:
        if self.on_event is not None:
            self.on_event(kind, **kw)
        else:
            obs_trace.event(kind, worker=self.worker, **kw)

    # --- paths ----------------------------------------------------------
    @staticmethod
    def uid(index: int) -> str:
        return f"u{index:05d}"

    def _units_dir(self) -> str:
        return os.path.join(self.path, _UNITS_DIR)

    def _lease_path(self, uid: str) -> str:
        return os.path.join(self._units_dir(), uid + ".lease")

    def _result_path(self, uid: str) -> str:
        return os.path.join(self._units_dir(), uid + ".result.json")

    def _lost_path(self, uid: str) -> str:
        return os.path.join(self._units_dir(), uid + ".lost")

    def _unit_desc_path(self, uid: str) -> str:
        return os.path.join(self._units_dir(), uid + ".unit.json")

    # --- manifest --------------------------------------------------------
    def ensure(self, contracts: Sequence[tuple], unit_size: int) -> None:
        """Create the manifest (first worker) or verify the existing one
        matches this worker's corpus + unit layout. A mismatch raises
        ``ValueError`` — claiming units of a DIFFERENT corpus under the
        same ledger would attribute results to the wrong contracts."""
        names = [str(n) for n, _ in contracts]
        fp = corpus_fingerprint(contracts)
        unit_size = max(1, int(unit_size))
        os.makedirs(self._units_dir(), exist_ok=True)
        doc = {"schema": LEDGER_SCHEMA, "corpus": fp,
               "unit_size": unit_size, "names": names,
               "units": (len(names) + unit_size - 1) // unit_size}
        p = os.path.join(self.path, _MANIFEST)
        if not _exclusive_write(p, json.dumps(doc, sort_keys=True).encode()):
            have = self._read_manifest(p)
            if have.get("mode") == "feed":
                raise ValueError(
                    f"fleet ledger {self.path} is a FEED ledger (a "
                    "serve daemon appends its units); workers join it "
                    "with --fleet-follow, not with a local corpus")
            if (have.get("corpus") != fp
                    or int(have.get("unit_size", 0)) != unit_size
                    or have.get("names") != names):
                raise ValueError(
                    f"fleet ledger {self.path} was initialized for a "
                    f"different corpus/unit layout (manifest corpus "
                    f"{have.get('corpus')!r} x unit_size "
                    f"{have.get('unit_size')}, this worker has {fp!r} x "
                    f"{unit_size}); point every worker at the same "
                    "corpus or use a fresh ledger dir")
            doc = have
        self._apply_manifest(doc)

    def _apply_manifest(self, doc: Dict) -> None:
        self.mode = str(doc.get("mode", "static"))
        self.corpus = str(doc.get("corpus", ""))
        self.names = list(doc.get("names") or [])
        self.closed = bool(doc.get("closed", False))
        if self.mode == "feed":
            self.unit_size = 0
            self.unit_names_list = [list(u) for u
                                    in (doc.get("unit_names") or [])]
            self.n_units = int(doc.get("units")
                               or len(self.unit_names_list))
        else:
            self.unit_size = max(1, int(doc.get("unit_size", 1)))
            self.n_units = int(doc.get("units")
                               or (len(self.names) + self.unit_size - 1)
                               // self.unit_size)

    def load_manifest(self) -> None:
        """Attach to an existing ledger (merge/tools path — no corpus in
        hand to verify against)."""
        self._apply_manifest(
            self._read_manifest(os.path.join(self.path, _MANIFEST)))

    def _read_manifest(self, p: str) -> Dict:
        try:
            with open(p) as fh:
                doc = json.load(fh)
        except FileNotFoundError:
            raise ValueError(
                f"{self.path}: no fleet manifest (not a ledger dir?)"
            ) from None
        except ValueError as e:
            raise ValueError(f"{p}: unreadable fleet manifest ({e})") from e
        if not isinstance(doc, dict):
            raise ValueError(f"{p}: fleet manifest is not a JSON object")
        if int(doc.get("schema", 1)) > LEDGER_SCHEMA:
            raise ValueError(
                f"{p}: ledger schema v{doc.get('schema')} is newer than "
                f"this reader (supports <= v{LEDGER_SCHEMA})")
        return doc

    def manifest_summary(self) -> Dict:
        """The manifest as embedded in a worker's report ``fleet``
        section — what ``merge_campaigns`` needs for the coverage
        manifest (unit→contracts is rebuilt from names + unit_size for
        static ledgers, from the per-unit name lists for feeds)."""
        out = {"corpus": self.corpus, "unit_size": self.unit_size,
               "units": self.n_units, "names": list(self.names)}
        if self.mode == "feed":
            out["mode"] = "feed"
            out["unit_names"] = [list(u) for u in self.unit_names_list]
        return out

    def unit_names(self, index: int) -> List[str]:
        if self.mode == "feed":
            return (list(self.unit_names_list[index])
                    if index < len(self.unit_names_list) else [])
        s = index * self.unit_size
        return self.names[s:s + self.unit_size]

    def unit_start(self, index: int) -> int:
        """Offset of the unit's first contract in manifest order — the
        worker's GLOBAL batch-index base. Feed units are variable-size,
        so the offset is a prefix sum over the fed name lists."""
        if self.mode == "feed":
            return sum(len(u) for u in self.unit_names_list[:index])
        return index * self.unit_size

    # --- feed mode (docs/serving.md) -------------------------------------
    def ensure_feed(self) -> None:
        """Create (or re-attach to) a FEED ledger: the manifest starts
        empty and grows one unit at a time via :meth:`feed_unit`. The
        feeder (a serve daemon) is the SOLE manifest writer — workers
        only read it (``refresh``) and claim/commit through the usual
        lease files, so the single-writer manifest needs no lock."""
        os.makedirs(self._units_dir(), exist_ok=True)
        doc = {"schema": LEDGER_SCHEMA, "mode": "feed", "corpus": "feed",
               "unit_size": 0, "names": [], "unit_names": [],
               "units": 0, "closed": False}
        p = os.path.join(self.path, _MANIFEST)
        if not _exclusive_write(p, json.dumps(doc,
                                              sort_keys=True).encode()):
            have = self._read_manifest(p)
            if have.get("mode") != "feed":
                raise ValueError(
                    f"fleet ledger {self.path} holds a static corpus "
                    "manifest; a serve daemon needs a fresh (or feed) "
                    "ledger dir")
            doc = have
            # a restarted daemon re-opens its own feed: committed units
            # stay committed (restart serves them from the ledger), new
            # submissions append after them
            if doc.get("closed"):
                doc["closed"] = False
                self._write_manifest(doc)
        self._apply_manifest(doc)

    def attach_feed(self) -> None:
        """Worker-side join of a feed ledger (``--fleet-follow``)."""
        self.load_manifest()
        if self.mode != "feed":
            raise ValueError(
                f"{self.path}: not a feed ledger (manifest mode "
                f"{self.mode!r}); --fleet-follow joins a serve "
                "daemon's ledger — for a static corpus use --fleet "
                "with --corpus")

    def refresh(self) -> None:
        """Re-read a feed manifest (atomic rewrite on the feeder side
        means readers see the old or the new doc, never a torn one). A
        transiently unreadable manifest keeps the last good view."""
        try:
            self._apply_manifest(
                self._read_manifest(os.path.join(self.path, _MANIFEST)))
        except ValueError:
            pass

    def _write_manifest(self, doc: Dict) -> None:
        durable_write(os.path.join(self.path, _MANIFEST),
                      json.dumps(doc, sort_keys=True).encode())

    def _manifest_doc(self) -> Dict:
        return {"schema": LEDGER_SCHEMA, "mode": "feed", "corpus": "feed",
                "unit_size": 0, "names": list(self.names),
                "unit_names": [list(u) for u in self.unit_names_list],
                "units": self.n_units, "closed": self.closed}

    def feed_unit(self, contracts: Sequence[tuple],
                  config: Optional[Dict] = None) -> str:
        """Append one work unit of ``(name, bytecode)`` pairs or
        ``(name, bytecode, creation code)`` records. The unit
        DESCRIPTOR (names + bytecode hex + analysis config) lands
        durably BEFORE the manifest's unit count exposes it, so a
        worker can never claim a unit whose bytecode is not yet
        readable. Returns the unit id."""
        if self.mode != "feed":
            raise ValueError("feed_unit() on a static ledger")
        index = self.n_units
        uid = self.uid(index)
        recs = [contract_record(c) for c in contracts]
        names = [str(n) for n, _, _ in recs]
        desc = {"unit": uid, "names": names,
                "codes": [bytes(c).hex() for _, c, _ in recs],
                "config": dict(config or {}),
                "t": round(time.time(), 3)}
        if any(k is not None for _, _, k in recs):
            desc["creations"] = [None if k is None else bytes(k).hex()
                                 for _, _, k in recs]
        if not _exclusive_write(self._unit_desc_path(uid),
                                json.dumps(desc, sort_keys=True).encode()):
            raise ValueError(
                f"{self.path}: unit descriptor {uid} already exists — "
                "two feeders on one ledger?")
        self.unit_names_list.append(names)
        self.names.extend(names)
        self.n_units = index + 1
        self._write_manifest(self._manifest_doc())
        obs_metrics.REGISTRY.counter(
            "fleet_units_fed_total",
            help="work units appended to feed ledgers").inc()
        tids = ((config or {}).get("trace") or {}).get("ids") or []
        self._event("unit_fed", unit=uid, contracts=len(names),
                    trace_id=(tids[0] if tids else None))
        return uid

    def feed_close(self) -> None:
        """Mark the feed complete: workers drain what is claimable and
        exit instead of polling forever."""
        if self.mode != "feed" or self.closed:
            return
        self.closed = True
        self._write_manifest(self._manifest_doc())
        self._event("feed_closed", units=self.n_units)

    def feed_closed(self) -> bool:
        return self.closed

    def read_unit_items(self, uid: str) -> Tuple[List[tuple], Dict]:
        """A fed unit's contracts, as they were fed (pairs, or records
        where the feeder gave creation code), and its config."""
        with open(self._unit_desc_path(uid)) as fh:
            doc = json.load(fh)
        names = [str(n) for n in doc.get("names") or []]
        codes = [bytes.fromhex(c) for c in doc.get("codes") or []]
        creations = doc.get("creations")
        items = (list(zip(names, codes)) if not creations else
                 [(n, c, bytes.fromhex(k)) if k else (n, c)
                  for n, c, k in zip(names, codes, creations)])
        return items, dict(doc.get("config") or {})

    def read_unit(self, uid: str) -> Tuple[List[str], List[bytes], Dict]:
        """A fed unit's ``(names, bytecodes, config)`` from its
        descriptor file."""
        items, config = self.read_unit_items(uid)
        return [i[0] for i in items], [i[1] for i in items], config

    def result_record(self, uid: str) -> Optional[Dict]:
        """The committed result of one unit, or None while pending /
        unreadable (a torn read retries on the next poll)."""
        try:
            with open(self._result_path(uid)) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None

    def unit_lost(self, uid: str) -> bool:
        return (os.path.exists(self._lost_path(uid))
                and not os.path.exists(self._result_path(uid)))

    def _result_committed(self, uid: str) -> bool:
        """Whether the unit has a LOADABLE committed result. A torn or
        corrupt result file (external truncation, a misbehaving shared
        filesystem — ``exclusive_write`` itself is atomic) used to
        block the unit forever: no worker could re-claim it (the file
        existed) and no merge could read it (it didn't parse) — the
        chaos matrix's ``torn-ledger`` row. Now the corrupt file is
        set ASIDE (``.corrupt`` — evidence preserved, name freed) with
        a ``unit_result_corrupt`` event, and the unit becomes
        claimable again; the re-run's commit wins the freed name."""
        p = self._result_path(uid)
        if uid in self._verified_results:
            return True
        try:
            with open(p) as fh:
                json.load(fh)
        except FileNotFoundError:
            return False
        except (OSError, ValueError) as e:
            try:
                os.replace(p, p + ".corrupt")
            except OSError:
                return True  # can't free the name: leave it to merge
            obs_metrics.REGISTRY.counter(
                "fleet_result_corrupt_total",
                help="torn/corrupt unit result files set aside for "
                     "re-analysis").inc()
            self._event("unit_result_corrupt", unit=uid,
                        detail=f"{p}: {e}"[:300]
                               + "; set aside, unit re-claimable")
            return False
        self._verified_results.add(uid)
        return True

    # --- claim / reclaim -------------------------------------------------
    def _scan_order(self) -> range:
        return range(self.n_units)

    def _claim_offset(self) -> int:
        # start the scan at a worker-dependent offset so N workers
        # hitting a fresh ledger don't all fight over unit 0
        return (int(hashlib.sha256(self.worker.encode()).hexdigest()[:8],
                    16) % self.n_units) if self.n_units else 0

    def _try_claim(self, index: int, attempt: int) -> Optional[WorkUnit]:
        uid = self.uid(index)
        p = self._lease_path(uid)
        try:
            fd = os.open(p, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            return None
        try:
            os.write(fd, json.dumps(
                {"worker": self.worker, "attempt": attempt,
                 "claimed_t": round(time.time(), 3)}).encode())
            os.fsync(fd)
        finally:
            os.close(fd)
        obs_metrics.REGISTRY.counter(
            "fleet_units_claimed_total",
            help="work-unit leases granted to this process").inc()
        self._event("lease_claimed", unit=uid, attempt=attempt)
        return WorkUnit(uid=uid, index=index,
                        start=self.unit_start(index),
                        names=self.unit_names(index), attempt=attempt)

    def _try_reclaim(self, index: int, age: float) -> Optional[WorkUnit]:
        """Arbitrate a stale lease: the atomic rename-aside decides one
        winner among racing reclaimers; the winner re-leases the unit
        (attempt+1) or, past the cap, marks it lost."""
        uid = self.uid(index)
        lease = self._lease_path(uid)
        tomb = f"{lease}.{os.getpid()}-{threading.get_ident()}.reclaim"
        try:
            os.rename(lease, tomb)
        except OSError:
            return None  # another worker won the reclaim (or commit)
        try:
            with open(tomb) as fh:
                prev = json.load(fh)
        except (OSError, ValueError):
            prev = {}  # torn lease write: the holder died mid-claim
        try:
            os.unlink(tomb)
        except OSError:
            pass
        spent = max(1, int(prev.get("attempt", 1) or 1))
        holder = str(prev.get("worker", "?"))
        if spent >= self.max_leases:
            if _exclusive_write(self._lost_path(uid), json.dumps(
                    {"unit": uid, "attempts": spent, "last_worker": holder,
                     "t": round(time.time(), 3)}).encode()):
                obs_metrics.REGISTRY.counter(
                    "fleet_units_lost_total",
                    help="units abandoned after the re-lease cap").inc()
                self._event("unit_lost", unit=uid, attempts=spent,
                            detail=f"re-lease cap {self.max_leases} "
                                   f"exhausted (last holder {holder})")
            return None
        unit = self._try_claim(index, attempt=spent + 1)
        if unit is not None:
            obs_metrics.REGISTRY.counter(
                "fleet_units_reclaimed_total",
                help="stale leases taken over from a dead/wedged "
                     "worker").inc()
            self._event("lease_reclaimed", unit=uid, attempt=spent + 1,
                        prev_worker=holder, age=round(age, 3))
        return unit

    def claim_next(self) -> Optional[WorkUnit]:
        """Claim the next available unit: an unleased unit directly, or
        a stale lease (heartbeat older than the TTL) via reclaim.
        Returns ``None`` when nothing is claimable right now — the
        caller should check :meth:`pending` and poll (outstanding
        leases may yet expire)."""
        now = time.time()
        oldest_live = 0.0
        claimed: Optional[WorkUnit] = None
        off = self._claim_offset()
        for j in self._scan_order():
            k = (j + off) % self.n_units
            uid = self.uid(k)
            if (self._result_committed(uid)
                    or os.path.exists(self._lost_path(uid))):
                continue
            lease = self._lease_path(uid)
            if claimed is not None:
                # keep sweeping only for the lease-age gauge
                try:
                    oldest_live = max(
                        now - os.stat(lease).st_mtime, oldest_live)
                except OSError:
                    pass
                continue
            try:
                st = os.stat(lease)
            except FileNotFoundError:
                claimed = self._try_claim(k, attempt=1)
                continue
            age = now - st.st_mtime
            if age <= self.ttl:
                oldest_live = max(age, oldest_live)
                continue
            claimed = self._try_reclaim(k, age)
        obs_metrics.REGISTRY.gauge(
            "fleet_lease_age_seconds",
            help="oldest live lease heartbeat age observed this "
                 "sweep").set(oldest_live)
        return claimed

    def pending(self) -> bool:
        """Units neither committed nor lost remain (some may be leased
        by other workers — they become claimable when the TTL lapses)."""
        for k in self._scan_order():
            uid = self.uid(k)
            if not (self._result_committed(uid)
                    or os.path.exists(self._lost_path(uid))):
                return True
        return False

    # --- heartbeat -------------------------------------------------------
    def renew(self, unit: WorkUnit) -> None:
        """Stamp the lease heartbeat (mtime). A failed ``utime`` is NOT
        silent (it used to be — the unit would quietly drift toward
        reclaim while its worker believed it was heartbeating): every
        failure lands as a ``lease_renew_failed`` event +
        ``fleet_renew_failures_total`` tick, and the renewer RETRIES on
        its next tick — a transient NFS error must not end
        heartbeating for good. A missing lease file (we committed, or
        were presumed dead and reclaimed-from) is reported the same
        way; commit-time arbitration still decides who wins."""
        try:
            os.utime(self._lease_path(unit.uid))
        except OSError as e:
            obs_metrics.REGISTRY.counter(
                "fleet_renew_failures_total",
                help="lease heartbeat renewals that failed (missing "
                     "lease file or I/O error); retried next tick").inc()
            self._event(
                "lease_renew_failed", unit=unit.uid,
                detail=(f"{type(e).__name__}: {e}"[:200]
                        + "; retrying next tick"))
            return
        obs_trace.event("lease_renew", unit=unit.uid, worker=self.worker)

    class _Renewer:
        def __init__(self, ledger: "WorkLedger", unit: WorkUnit,
                     interval: float):
            self._ledger = ledger
            self._unit = unit
            self._interval = interval
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._beat, daemon=True,
                name=f"lease:{unit.uid}")

        def _beat(self) -> None:
            while not self._stop.wait(self._interval):
                self._ledger.renew(self._unit)

        def __enter__(self) -> "WorkLedger._Renewer":
            self._thread.start()
            return self

        def __exit__(self, *exc) -> bool:
            self._stop.set()
            self._thread.join(timeout=5.0)
            return False

    def renewer(self, unit: WorkUnit) -> "WorkLedger._Renewer":
        """Context manager: heartbeat the lease from a background
        thread every ``ttl/3`` while the unit runs. The heartbeat
        proves the PROCESS is alive; a wedged batch inside a live
        process is the batch watchdog's job (docs/fleet.md failure
        matrix). A real SIGKILL stops the thread with the process, so
        the lease goes stale exactly when the worker dies."""
        return WorkLedger._Renewer(self, unit,
                                   max(0.02, self.ttl / 3.0))

    # --- commit / release ------------------------------------------------
    def commit(self, unit: WorkUnit, record: Dict) -> bool:
        """Durably commit the unit's result. First commit wins; a
        duplicate (split-brain: this worker was reclaimed-from but came
        back and finished anyway) returns False with a
        ``unit_duplicate`` event — the caller must DROP its copy of the
        results so nothing is double-counted."""
        data = json.dumps(record, sort_keys=True).encode()
        if _exclusive_write(self._result_path(unit.uid), data):
            self.release(unit)
            self._event("unit_committed", unit=unit.uid,
                        attempt=unit.attempt)
            return True
        self._event("unit_duplicate", unit=unit.uid, attempt=unit.attempt,
                    detail="result already committed by another worker; "
                           "dropping this copy")
        return False

    def release(self, unit: WorkUnit) -> None:
        """Drop our lease if we still hold it (commit cleanup, or a
        deadline abort returning the unit to the pool without burning a
        re-lease grant)."""
        p = self._lease_path(unit.uid)
        try:
            with open(p) as fh:
                cur = json.load(fh)
        except (OSError, ValueError):
            return
        if (cur.get("worker") == self.worker
                and int(cur.get("attempt", -1) or -1) == unit.attempt):
            try:
                os.unlink(p)
            except OSError:
                pass

    # --- inspection ------------------------------------------------------
    def lost_units(self) -> List[Dict]:
        """Every ``lost`` marker, with the unit's contract names — the
        merge's input for the ``lost`` coverage bucket. A unit that was
        ALSO committed (marked lost, then a presumed-dead worker came
        back and won the commit race) is excluded: results win."""
        out = []
        for k in self._scan_order():
            uid = self.uid(k)
            p = self._lost_path(uid)
            if not os.path.exists(p) \
                    or os.path.exists(self._result_path(uid)):
                continue
            try:
                with open(p) as fh:
                    doc = json.load(fh)
            except (OSError, ValueError):
                doc = {}
            out.append({"unit": uid, "contracts": self.unit_names(k),
                        "attempts": int(doc.get("attempts", 0) or 0),
                        "last_worker": str(doc.get("last_worker", "?"))})
        return out

    def committed(self) -> List[Tuple[str, str]]:
        """``(uid, result_path)`` for every committed unit."""
        out = []
        for k in self._scan_order():
            uid = self.uid(k)
            p = self._result_path(uid)
            if os.path.exists(p):
                out.append((uid, p))
        return out


def ledger_results(path: str) -> List[Dict]:
    """Synthesize ``merge_campaigns`` input straight from a ledger dir:
    one pseudo-host result carrying every committed unit record, the
    lost list, and the manifest. This is how a killed worker's finished
    units (durably in the ledger, never in any per-worker report JSON)
    reach the merged report. An unreadable unit result counts as
    uncommitted — it surfaces in the coverage manifest as unaccounted,
    with a ``unit_result_corrupt`` event naming the file."""
    led = WorkLedger(path)
    led.load_manifest()
    units: List[Dict] = []
    events: List[Dict] = []
    for uid, p in led.committed():
        try:
            with open(p) as fh:
                units.append(json.load(fh))
        except (OSError, ValueError) as e:
            events.append({"kind": "unit_result_corrupt", "unit": uid,
                           "detail": f"{p}: {e}"[:300]})
    return [{
        "wall_sec": 0.0,
        "backend_events": events,
        "fleet": {"worker": f"ledger:{os.path.abspath(path)}",
                  "units": units, "lost": led.lost_units(),
                  "manifest": led.manifest_summary()},
    }]


__all__ = ["LEDGER_SCHEMA", "WorkLedger", "WorkUnit",
           "corpus_fingerprint", "ledger_results"]
