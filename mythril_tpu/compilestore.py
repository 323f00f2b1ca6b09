"""Durable fleet-wide compile-artifact store (the "kill cold start" item).

Every resilience mechanism in this repo ends in the same cold tax: the
warm-shape registry is process-local (serve/scheduler.py documented it
as such since PR 11), and ``_tier_sync`` invalidates warm markers and
respawns the worker by design (PR 13) — so every fresh replica, every
respawned worker, and every tier re-promotion pays full XLA compile
before its first verdict. This module promotes compilation to a durable
fleet artifact with two halves:

1. **Shape-bucket registry** under ``<data-dir>/compile_store/buckets/``:
   one JSON file per ``(tier, shape-class, semantic-config-hash)``
   bucket recording hit counts, last-seen timestamps, and the warm
   chunk step-counts observed for that shape. Writes use the repo's
   one shared durability discipline (``exclusive_write`` first-wins on
   create, ``durable_write`` with ``.1`` rotation on update), so N
   daemons on one data dir are correct; a torn newest file is
   quarantined ``.corrupt`` and the loader falls back to the rotated
   copy. A lost read-merge-update race costs at most one hit-count
   increment, never a bucket.

2. **The shared XLA cache** (``mythril_tpu/compile_cache.py``:
   ``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``):
   worker children, respawned workers, and sibling replicas all point
   at one persistent compilation cache, so a registry-driven prewarm
   (or even a lazy first compile) after restart is a cache *hit*, not
   a recompile. It is cache, not data, so it does not live under the
   data dir; this store only inspects and prunes it.

**Single-owner GC contract** (mirrors the segstore compactor): any
replica may read and record; only ONE process at a time may run
:meth:`CompileStore.gc` (operators run ``tools/store_admin.py
compile-gc``). GC never unlinks a bucket another writer could be
mid-updating destructively — bucket updates are atomic renames, so the
worst case is a concurrently re-created bucket, which the next
``record`` simply recreates.

The registry stores *shape skeletons only* (ints), never bytecode or
verdicts — prewarm compiles are driven from padded STOP-stub corpora
(the ``ShapeDtypeStruct`` idea from tools/scaling_report.py: content
never changes the jaxpr, only shape does).
"""

from __future__ import annotations

import json
import logging
import os
import hashlib
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import compile_cache
from .obs import metrics as obs_metrics
from .obs import trace as obs_trace
from .utils.checkpoint import (
    ROTATE_SUFFIX, durable_write, exclusive_write, fsync_dir)

log = logging.getLogger(__name__)

#: registry record schema
BUCKET_SCHEMA = 1
BUCKET_DIR = "buckets"
#: default recency cap: buckets beyond this are evicted oldest-first
DEFAULT_CAP = 256

#: test hook: SIGKILL-equivalent (``os._exit``) at a named point of the
#: registry write protocol, driven by the kill-mid-registry-write chaos
#: cell. Points: pre-write (before any byte lands — old record intact),
#: post-write (record durable, caller's bookkeeping not), torn-write
#: (simulates the non-atomic failure the protocol defends against:
#: rotate the good record to ``.1``, scribble half a payload over the
#: newest, die — the next reader must quarantine + fall back).
_KILL_ENV = "MYTHRIL_COMPILESTORE_KILL"


def _maybe_kill(point: str, path: str, payload: bytes) -> None:
    if os.environ.get(_KILL_ENV) != point:
        return
    if point == "torn-write":
        # emulate the torn-newest-file state: good copy rotated away,
        # garbage half-record in its place, then die mid-"write"
        if os.path.exists(path):
            os.replace(path, path + ROTATE_SUFFIX)
        with open(path, "wb") as fh:
            fh.write(payload[: max(1, len(payload) // 2)])
        fsync_dir(path)
    os._exit(9)


def semantic_config_hash(config: Dict) -> str:
    """16-hex digest of a semantic config dict (the caller already
    stripped operational keys — serve passes its ``config_hash``
    straight through instead). Sorted-JSON so dict order never forks
    the key space."""
    blob = json.dumps(config, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def bucket_name(tier: str, shape: Sequence[int], cfh: str) -> str:
    """``{tier}__{w}x{l}x{ms}x{tx}__{cfh}.json`` — the flat, greppable
    key schema (docs/serving.md has the table). ``shape`` is the
    campaign's ``_shape_key`` tuple: (width, lanes, max_steps, tx), and a
    fifth field 1 for the class of batches that deploy."""
    dims = "x".join(str(int(d)) for d in shape)
    return f"{tier}__{dims}__{cfh}.json"


def _parse_name(fname: str) -> Optional[Tuple[str, Tuple[int, ...], str]]:
    if not fname.endswith(".json"):
        return None
    parts = fname[:-5].split("__")
    if len(parts) != 3:
        return None
    tier, dims, cfh = parts
    try:
        shape = tuple(int(d) for d in dims.split("x"))
    except ValueError:
        return None
    return tier, shape, cfh


class CompileStore:
    """Crash-safe, replica-shared registry of hot compile buckets plus
    the fleet's persistent XLA cache dir. Thread-safe within a process
    (one lock), correct across processes by the write discipline."""

    def __init__(self, root: str, cap: int = DEFAULT_CAP):
        self.root = os.path.abspath(root)
        self.cap = int(cap)
        self._lock = threading.Lock()
        os.makedirs(os.path.join(self.root, BUCKET_DIR), exist_ok=True)

    # --- layout --------------------------------------------------------

    def xla_cache_dir(self) -> str:
        return compile_cache.cache_dir()

    def _bucket_dir(self) -> str:
        return os.path.join(self.root, BUCKET_DIR)

    def _path(self, tier: str, shape: Sequence[int], cfh: str) -> str:
        return os.path.join(self._bucket_dir(),
                            bucket_name(tier, shape, cfh))

    # --- events / metrics ---------------------------------------------

    def _event(self, kind: str, **kw) -> None:
        obs_trace.event(kind, **kw)
        obs_metrics.REGISTRY.counter(f"{kind}_total").inc()

    # --- read path -----------------------------------------------------

    def _load_one(self, path: str) -> Optional[Dict]:
        """One file, validated; ``None`` on missing, raises ValueError
        on corrupt (torn JSON or wrong schema shape)."""
        try:
            with open(path, "rb") as fh:
                rec = json.loads(fh.read().decode("utf-8"))
        except FileNotFoundError:
            return None
        except (ValueError, OSError) as e:
            raise ValueError(f"unreadable bucket {path}: {e}") from e
        if (not isinstance(rec, dict)
                or rec.get("schema") != BUCKET_SCHEMA
                or not isinstance(rec.get("shape"), list)
                or not isinstance(rec.get("hits"), int)):
            raise ValueError(f"bucket {path} fails schema validation")
        return rec

    def _load(self, path: str) -> Optional[Dict]:
        """Newest-then-rotated read with ``.corrupt`` quarantine: the
        same fallback ladder as ``load_json_checkpoint_resilient``, per
        bucket. A corrupt newest never shadows the last-known-good."""
        try:
            return self._load_one(path)
        except ValueError as e:
            try:
                os.replace(path, path + ".corrupt")
            except OSError:
                pass
            self._event("compile_store_corrupt",
                        file=os.path.basename(path), detail=str(e)[:200])
            log.warning("compile store bucket %s corrupt (%s); "
                        "falling back to rotated copy", path, e)
        try:
            return self._load_one(path + ROTATE_SUFFIX)
        except ValueError:
            try:
                os.replace(path + ROTATE_SUFFIX,
                           path + ROTATE_SUFFIX + ".corrupt")
            except OSError:
                pass
            return None

    # --- write path ----------------------------------------------------

    def record(self, tier: str, shape: Sequence[int], cfh: str,
               chunks: Iterable[int] = ()) -> Dict:
        """Record one warm observation for a bucket: create first-wins,
        else read-merge-update (hits+1, last_seen=now, chunk union).
        Returns the record as written. Concurrent updaters may each
        lose the other's single hit increment — by design; the bucket
        itself can never be lost or torn."""
        shape = [int(d) for d in shape]
        chunks = sorted({int(c) for c in chunks})
        path = self._path(tier, shape, cfh)
        now = round(time.time(), 3)
        with self._lock:
            rec = {"schema": BUCKET_SCHEMA, "tier": tier, "shape": shape,
                   "cfh": cfh, "hits": 1, "created": now,
                   "last_seen": now, "chunks": chunks}
            payload = json.dumps(rec, sort_keys=True).encode()
            _maybe_kill("pre-write", path, payload)
            if not os.path.exists(path):
                if exclusive_write(path, payload):
                    _maybe_kill("post-write", path, payload)
                    self._enforce_cap()
                    obs_metrics.REGISTRY.counter(
                        "compile_store_records_total",
                        help="bucket observations recorded").inc()
                    return rec
            prev = self._load(path)
            if prev is not None:
                rec["hits"] = prev.get("hits", 0) + 1
                rec["created"] = prev.get("created", now)
                rec["chunks"] = sorted(
                    set(chunks) | {int(c) for c in prev.get("chunks", [])})
            payload = json.dumps(rec, sort_keys=True).encode()
            _maybe_kill("torn-write", path, payload)
            durable_write(path, payload)
            _maybe_kill("post-write", path, payload)
            obs_metrics.REGISTRY.counter(
                "compile_store_records_total",
                help="bucket observations recorded").inc()
            return rec

    def _enforce_cap(self) -> int:
        """Recency cap: evict oldest-last-seen buckets beyond ``cap``.
        Called under the lock from ``record`` (create path only — the
        only path that grows the set)."""
        recs = self._scan()
        excess = len(recs) - self.cap
        if excess <= 0:
            return 0
        recs.sort(key=lambda r: r.get("last_seen", 0.0))
        for rec in recs[:excess]:
            self._unlink_bucket(rec["_path"])
        obs_metrics.REGISTRY.counter(
            "compile_store_evicted_total",
            help="buckets evicted by the recency cap").inc(excess)
        return excess

    @staticmethod
    def _unlink_bucket(path: str) -> None:
        for p in (path, path + ROTATE_SUFFIX):
            try:
                os.unlink(p)
            except OSError:
                pass

    # --- queries -------------------------------------------------------

    def _scan(self) -> List[Dict]:
        out = []
        try:
            names = sorted(os.listdir(self._bucket_dir()))
        except OSError:
            return out
        for fname in names:
            if _parse_name(fname) is None:
                continue
            rec = self._load(os.path.join(self._bucket_dir(), fname))
            if rec is not None:
                rec["_path"] = os.path.join(self._bucket_dir(), fname)
                out.append(rec)
        return out

    def buckets(self, tier: Optional[str] = None,
                cfh: Optional[str] = None) -> List[Dict]:
        """Registry records, hottest first (hits desc, then most
        recent) — the prewarm priority order. Filter by tier and/or
        semantic config hash."""
        recs = [r for r in self._scan()
                if (tier is None or r.get("tier") == tier)
                and (cfh is None or r.get("cfh") == cfh)]
        recs.sort(key=lambda r: (-r.get("hits", 0),
                                 -r.get("last_seen", 0.0)))
        for r in recs:
            r.pop("_path", None)
        return recs

    def warm_chunks(self, tier: str, shape: Sequence[int],
                    cfh: str) -> List[int]:
        """The chunk step-counts previously observed warm for one
        bucket — the seed for a recovered process's warm-shape sets."""
        rec = self._load(self._path(tier, [int(d) for d in shape], cfh))
        if rec is None:
            return []
        return sorted(int(c) for c in rec.get("chunks", []))

    def stats(self) -> Dict:
        """Offline-inspection doc (``store_admin.py compile-stats``)."""
        recs = self._scan()
        tiers: Dict[str, int] = {}
        for r in recs:
            tiers[r.get("tier", "?")] = tiers.get(r.get("tier", "?"), 0) + 1
        try:
            names = os.listdir(self._bucket_dir())
        except OSError:
            names = []
        corrupt = sum(1 for f in names if f.endswith(".corrupt"))
        cache_files = cache_bytes = 0
        for dirpath, _dirs, files in os.walk(self.xla_cache_dir()):
            for f in files:
                cache_files += 1
                try:
                    cache_bytes += os.path.getsize(
                        os.path.join(dirpath, f))
                except OSError:
                    pass
        obs_metrics.REGISTRY.gauge(
            "compile_store_buckets",
            help="registry buckets on disk").set(len(recs))
        return {"buckets": len(recs), "tiers": tiers,
                "hits_total": sum(r.get("hits", 0) for r in recs),
                "chunks_total": sum(len(r.get("chunks", []))
                                    for r in recs),
                "corrupt_quarantined": corrupt,
                "cap": self.cap,
                "xla_cache_files": cache_files,
                "xla_cache_bytes": cache_bytes}

    # --- GC (single-owner) --------------------------------------------

    def gc(self, max_buckets: Optional[int] = None,
           ttl: Optional[float] = None,
           cache_ttl: Optional[float] = None) -> Dict:
        """Offline GC (single-owner contract — see module docstring):
        drop buckets idle past ``ttl`` seconds, enforce ``max_buckets``
        oldest-first, sweep write-tmp leftovers and aged ``.corrupt``
        evidence, and prune XLA cache artifacts untouched for
        ``cache_ttl`` seconds (orphans from evicted buckets)."""
        now = time.time()
        recs = self._scan()
        expired = ([r for r in recs
                    if now - r.get("last_seen", now) > ttl]
                   if ttl is not None else [])
        for rec in expired:
            self._unlink_bucket(rec["_path"])
        live = [r for r in recs if r not in expired]
        over = 0
        cap = max_buckets if max_buckets is not None else self.cap
        if len(live) > cap:
            live.sort(key=lambda r: r.get("last_seen", 0.0))
            over = len(live) - cap
            for rec in live[:over]:
                self._unlink_bucket(rec["_path"])
        swept = 0
        try:
            names = os.listdir(self._bucket_dir())
        except OSError:
            names = []
        for fname in names:
            p = os.path.join(self._bucket_dir(), fname)
            stale_tmp = fname.endswith(".tmp")
            aged_corrupt = (fname.endswith(".corrupt")
                            and ttl is not None
                            and now - _mtime(p, now) > ttl)
            if stale_tmp or aged_corrupt:
                try:
                    os.unlink(p)
                    swept += 1
                except OSError:
                    pass
        pruned = 0
        if cache_ttl is not None:
            for dirpath, _dirs, files in os.walk(self.xla_cache_dir()):
                for f in files:
                    p = os.path.join(dirpath, f)
                    if now - _mtime(p, now) > cache_ttl:
                        try:
                            os.unlink(p)
                            pruned += 1
                        except OSError:
                            pass
        return {"expired": len(expired), "evicted": over,
                "swept": swept, "cache_pruned": pruned,
                "buckets": len(self._scan())}


def _mtime(path: str, default: float) -> float:
    try:
        return os.path.getmtime(path)
    except OSError:
        return default


__all__ = ["BUCKET_SCHEMA", "CompileStore", "DEFAULT_CAP", "bucket_name",
           "semantic_config_hash"]
