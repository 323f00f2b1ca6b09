#!/usr/bin/env python
"""Chaos fault-matrix runner: injection points x execution modes, with
issue-set parity and exactly-once accounting asserted per cell.

The acceptance harness for the process-isolation boundary
(docs/resilience.md "Process isolation & supervision"): every cell
runs the SAME small corpus through one execution mode with one fault
injected, then asserts

- **parity** — the final issue set is identical to an uninjected
  in-process baseline (same contracts flagged, same count: nothing
  lost to the fault, nothing double-counted through the recovery);
- **exactly-once** — mode-specific accounting closes: batch modes
  leave a checkpoint cursor at the last batch with every contract
  counted once, fleet mode closes a full coverage manifest (0 lost /
  0 unaccounted), serve mode resolves every contract exactly once;
- **the recovery actually happened** — worker deaths/restarts (or
  lease reclaims, or corrupt-result set-asides) are on the event
  record, not just absent-of-failure.

Injection points (columns):

  segv-mid-compile     SIGSEGV the engine worker before it touches the
                       engine for batch 1 (dying inside the XLA
                       compile, as libtpu does)
  segv-mid-superstep   SIGSEGV after the device phase ran, before the
                       host harvest (mid-batch state loss)
  kill-mid-reply       SIGKILL halfway through writing the IPC reply
                       (torn frame: the parent must treat a truncated
                       reply as death, not data)
  torn-ledger          truncate a COMMITTED fleet unit result file
                       mid-byte (a misbehaving shared filesystem); the
                       fleet must set it aside and re-analyze the unit
  frozen-heartbeat     a worker claims a lease and never heartbeats
                       (wedged before its first renew); a live worker
                       must reclaim after the TTL
  kill-replica-mid-batch  two REAL serve daemons share one --data-dir;
                       replica A is SIGKILLed while a batch is in
                       flight (an injected hang holds it); replica B
                       must answer the full corpus — A's committed
                       verdicts from the shared store, the rest fresh
                       — with exactly-once results and issue parity
  torn-store-verdict   truncate a committed verdict file in the shared
                       store mid-byte; the next replica must count it
                       a corrupt miss, re-analyze, and REWRITE it
  kill-mid-compaction  os._exit(9) the compactor at each of the three
                       protocol points (segment durable / manifest
                       durable / before loose unlink); after every
                       kill the store must verify clean and a re-run
                       must converge (docs/serving.md "Verdict
                       segments & edge replicas")
  torn-segment         truncate a committed SEGMENT file mid-byte; the
                       next replica must quarantine it ``.corrupt``,
                       re-analyze its keys, and a re-compaction must
                       heal the store to a clean new generation
  kill-mid-backfill-window  SIGKILL a ``serve --backfill`` daemon
                       mid-walk; the restarted walker must resume from
                       the durable two-ended cursor (re-ingesting
                       nothing already committed) and converge on one
                       stored verdict per historical contract
  kill-mid-registry-write  os._exit(9) a compile-store registry writer
                       at each protocol point (pre-write / post-write
                       / torn-write); after EVERY kill the bucket must
                       stay readable — a torn newest quarantined
                       ``.corrupt`` with the rotated copy served — and
                       the next observation must heal it
  corrupt-cache-quarantine  a poisoned persistent XLA cache flagged
                       ``.dirty`` by an unclean worker death; the
                       probe subprocess dies (SIGSEGV) in the worker's
                       place, the whole dir is set aside ``.corrupt``
                       (evidence preserved, never a silent wipe), and
                       the campaign completes cold on a fresh dir
  tier-flap-during-prewarm  a flapping device mid-campaign while the
                       registry prewarm pass brackets it: the pass
                       yields to live traffic (re-arming itself), the
                       flap's re-promotion re-arms it again, and the
                       settled tier replays its buckets — parity
                       intact, prewarm never aborts the campaign

Modes (rows): ``batch`` (serial campaign), ``pipelined`` (depth-1
pipeline), ``fleet`` (work-ledger campaign), ``serve`` (in-process
always-on daemon), ``replica`` (N real serve daemon SUBPROCESSES on
one shared data dir — docs/serving.md "Overload & multi-replica
serving"). Worker-signal points run with ``worker_isolation=on``;
ledger points exercise the fleet machinery directly. Not every point
applies to every mode — see ``MATRIX``.

CPU-only, TEST_LIMITS, deterministic (``once=`` cookie files make each
worker fault fire exactly once across restarts). Prints one JSON line
``{"ok": bool, "cells": {...}}`` and exits 0/1.

    JAX_PLATFORMS=cpu python tools/chaos_campaign.py
    JAX_PLATFORMS=cpu python tools/chaos_campaign.py \
        --cells batch:segv-mid-superstep,fleet:torn-ledger

The soak's ``chaos`` leg (tools/soak_campaign.py) runs the reduced
two-cell matrix above; the full matrix is the pre-release gate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_BATCH_TIMEOUT = float(os.environ.get("SOAK_BATCH_TIMEOUT", "300") or 300)

#: point -> MYTHRIL_WORKER_FAULT template (cookie path appended)
_WORKER_POINTS = {
    "segv-mid-compile": "segv:mid-compile:1",
    "segv-mid-superstep": "segv:mid-superstep:1",
    "kill-mid-reply": "kill:mid-reply:1",
}

MATRIX: Dict[str, Tuple[str, ...]] = {
    "batch": tuple(_WORKER_POINTS),
    "pipelined": tuple(_WORKER_POINTS),
    "fleet": tuple(_WORKER_POINTS) + ("torn-ledger", "frozen-heartbeat"),
    "serve": tuple(_WORKER_POINTS),
    "replica": ("kill-replica-mid-batch", "torn-store-verdict"),
    "tier": ("demote-mid-campaign", "repromote-mid-campaign",
             "tier-flap"),
    "store": ("kill-mid-compaction", "torn-segment",
              "kill-mid-backfill-window"),
    "compile": ("kill-mid-registry-write", "corrupt-cache-quarantine",
                "tier-flap-during-prewarm"),
}

N = 6  # distinct bytecodes (serve dedupe would collapse clones)


def _corpus():
    from mythril_tpu.disassembler.asm import assemble

    return [(f"c{i:03d}",
             assemble(i, "SELFDESTRUCT") if i % 2 == 0
             else assemble(1, i, "SSTORE", "STOP"))
            for i in range(N)]


def _campaign(contracts, ckpt: Optional[str], **kw):
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.mythril.campaign import CorpusCampaign

    kw.setdefault("batch_size", 2)
    return CorpusCampaign(
        contracts, lanes_per_contract=8, limits=TEST_LIMITS,
        max_steps=64, transaction_count=1,
        modules=["AccidentallyKillable"], checkpoint_dir=ckpt,
        batch_timeout=_BATCH_TIMEOUT, **kw)


def _issues(res) -> List[str]:
    return sorted(i["contract"] for i in res.issues)


def _worker_kinds(events) -> List[str]:
    return [e.get("kind") for e in events
            if str(e.get("kind", "")).startswith(("worker", "breaker"))]


class _fault_env:
    """MYTHRIL_WORKER_FAULT scoped to one cell, with a fresh once-
    cookie so the fault fires exactly once across worker restarts."""

    def __init__(self, point: str, d: str):
        self.spec = (f"{_WORKER_POINTS[point]}"
                     f":once={os.path.join(d, 'fault_cookie')}")

    def __enter__(self):
        os.environ["MYTHRIL_WORKER_FAULT"] = self.spec
        return self

    def __exit__(self, *exc):
        os.environ.pop("MYTHRIL_WORKER_FAULT", None)
        return False


def _cell_batch(mode: str, point: str, d: str, contracts,
                baseline: List[str]) -> Dict:
    from mythril_tpu.utils.checkpoint import load_json_checkpoint

    ckpt = os.path.join(d, "ck")
    with _fault_env(point, d):
        res = _campaign(contracts, ckpt, worker_isolation="on",
                        pipeline=(mode == "pipelined")).run()
    kinds = _worker_kinds(res.backend_events)
    final = load_json_checkpoint(os.path.join(ckpt, "campaign.json"))
    cell = {"issues": _issues(res), "retries": res.retries,
            "quarantined": [q["name"] for q in res.quarantined],
            "worker_events": kinds,
            "next_batch": final.get("next_batch")}
    cell["ok"] = (cell["issues"] == baseline
                  and len(res.issues) == len(baseline)
                  and not res.quarantined
                  and kinds.count("worker_death") >= 1
                  and kinds.count("worker_restart") >= 1
                  and final.get("next_batch") == (N + 1) // 2)
    return cell


def _tier_kinds(events) -> List[str]:
    return [e.get("kind") for e in events
            if str(e.get("kind", "")).startswith("tier")]


#: three stacked nth= specs = the worker dies on its first three
#: dispatches, which trips the supervisor's crash-loop breaker
_CRASH_LOOP = "worker-kill:nth=1;worker-kill:nth=2;worker-kill:nth=3"


def _tier_tm(probe_ok: bool, **kw):
    """Synthetic two-tier ladder for a CPU-only box: "tpu" is an
    accounting tier (``env_pin=False`` keeps execution on the host),
    so demote/re-promote mechanics run for real while every batch
    executes on the same backend as the uninjected baseline."""
    from mythril_tpu.backend import TierManager

    def probe(tier, timeout):
        return probe_ok, f"chaos probe ({'up' if probe_ok else 'down'})"

    kw.setdefault("sticky_window", 0.0)
    kw.setdefault("probe_every", 0.0)
    return TierManager(tiers=("tpu", "cpu"), probe_fn=probe,
                       env_pin=False, auto_prober=False, **kw)


def _cell_tier_crash(point: str, d: str, contracts,
                     baseline: List[str]) -> Dict:
    """demote-mid-campaign / repromote-mid-campaign: a worker crash
    loop opens the breaker mid-campaign; instead of a permanent CPU
    pin the campaign demotes one tier and keeps going. With a healthy
    probe the next batch boundary climbs back to the preferred tier."""
    from mythril_tpu.resilience import FaultInjector
    from mythril_tpu.utils.checkpoint import load_json_checkpoint

    repromote = (point == "repromote-mid-campaign")
    tm = _tier_tm(probe_ok=repromote)
    ckpt = os.path.join(d, "ck")
    res = _campaign(contracts, ckpt, worker_isolation="on",
                    fault_injector=FaultInjector.from_string(_CRASH_LOOP),
                    tier_manager=tm).run()
    wk = _worker_kinds(res.backend_events)
    tk = _tier_kinds(res.backend_events)
    final = load_json_checkpoint(os.path.join(ckpt, "campaign.json"))
    st = tm.status()
    cell = {"issues": _issues(res), "retries": res.retries,
            "quarantined": [q["name"] for q in res.quarantined],
            "worker_events": wk, "tier_events": tk, "tier": st,
            "next_batch": final.get("next_batch")}
    ok = (cell["issues"] == baseline
          and len(res.issues) == len(baseline)
          and not res.quarantined
          and wk.count("worker_death") >= 3
          and st["demotions"] == 1
          and tk.count("tier_demoted") == 1
          and final.get("next_batch") == (N + 1) // 2)
    if repromote:
        ok = (ok and st["current"] == st["preferred"]
              and st["repromotions"] == 1
              and tk.count("tier_repromoted") == 1)
    else:
        ok = (ok and st["current"] == "cpu" and st["demoted"]
              and st["repromotions"] == 0
              and st["probe_failures"] >= 1)
    cell["ok"] = ok
    return cell


def _cell_tier_flap(d: str, contracts, baseline: List[str]) -> Dict:
    """tier-flap: a flapping device (down on odd attempts, up on even)
    would bounce the campaign between tiers forever; the rolling flap
    window must cap transitions, hold the lower tier, and emit the
    damped marker exactly once — with issue parity and exactly-once
    batch accounting intact throughout."""
    from mythril_tpu.resilience import FaultInjector
    from mythril_tpu.utils.checkpoint import load_json_checkpoint

    tm = _tier_tm(probe_ok=True, flap_window=3600.0, flap_max=4)
    ckpt = os.path.join(d, "ck")
    res = _campaign(contracts, ckpt, worker_isolation="off",
                    fault_injector=FaultInjector.from_string("flap"),
                    tier_manager=tm).run()
    tk = _tier_kinds(res.backend_events)
    final = load_json_checkpoint(os.path.join(ckpt, "campaign.json"))
    st = tm.status()
    cell = {"issues": _issues(res), "retries": res.retries,
            "quarantined": [q["name"] for q in res.quarantined],
            "tier_events": tk, "tier": st,
            "next_batch": final.get("next_batch")}
    cell["ok"] = (cell["issues"] == baseline
                  and len(res.issues) == len(baseline)
                  and not res.quarantined
                  and res.retries == (N + 1) // 2
                  # one full round trip, then damping holds the floor
                  and st["demotions"] == 2
                  and st["repromotions"] == 1
                  and st["transitions_in_window"] <= tm.flap_max
                  and st["current"] == "cpu" and st["demoted"]
                  and tk.count("tier_flap_damped") == 1
                  and final.get("next_batch") == (N + 1) // 2)
    return cell


def _merge_fleet(res, fleet_dir: str) -> Dict:
    from mythril_tpu.fleet import ledger_results
    from mythril_tpu.mythril.campaign import merge_campaigns

    doc = res.as_dict()
    doc["issues_detail"] = res.issues
    return merge_campaigns([doc] + ledger_results(fleet_dir))


def _cell_fleet_worker(point: str, d: str, contracts,
                       baseline: List[str]) -> Dict:
    fl = os.path.join(d, "fleet")
    with _fault_env(point, d):
        res = _campaign(contracts, None, worker_isolation="on",
                        fleet_dir=fl, lease_ttl=5.0,
                        worker_id="w0").run()
    merged = _merge_fleet(res, fl)
    cov = merged.get("coverage") or {}
    kinds = _worker_kinds(res.backend_events)
    issues = sorted(i["contract"]
                    for i in merged.get("issues_detail", []))
    cell = {"issues": issues, "coverage": {
        k: cov.get(k) for k in ("analyzed", "quarantined", "lost",
                                "unaccounted", "full")},
        "worker_events": kinds}
    cell["ok"] = (issues == baseline
                  and merged.get("issues") == len(baseline)
                  and cov.get("full") is True
                  and kinds.count("worker_death") >= 1)
    return cell


def _cell_torn_ledger(d: str, contracts, baseline: List[str]) -> Dict:
    from mythril_tpu.resilience import FaultInjector, InjectedKill

    fl = os.path.join(d, "fleet")
    killed = False
    try:
        # w0 commits its first unit, then dies on its second attempt
        _campaign(contracts, None, fleet_dir=fl, lease_ttl=0.5,
                  worker_id="w0",
                  fault_injector=FaultInjector.from_string(
                      "kill:nth=2")).run()
    except InjectedKill:
        killed = True
    units_dir = os.path.join(fl, "units")
    committed = sorted(f for f in os.listdir(units_dir)
                       if f.endswith(".result.json"))
    torn = None
    if committed:
        torn = os.path.join(units_dir, committed[0])
        raw = open(torn, "rb").read()
        with open(torn, "wb") as fh:
            fh.write(raw[:len(raw) // 2])
    time.sleep(0.6)  # w0's remaining lease goes stale
    res = _campaign(contracts, None, fleet_dir=fl, lease_ttl=0.5,
                    worker_id="w1").run()
    merged = _merge_fleet(res, fl)
    cov = merged.get("coverage") or {}
    kinds = [e.get("kind") for e in res.backend_events]
    issues = sorted(i["contract"]
                    for i in merged.get("issues_detail", []))
    cell = {"killed": killed, "tore": bool(torn),
            "issues": issues,
            "corrupt_events": kinds.count("unit_result_corrupt"),
            "coverage": {k: cov.get(k) for k in
                         ("analyzed", "lost", "unaccounted", "full")}}
    cell["ok"] = (killed and torn is not None
                  and kinds.count("unit_result_corrupt") >= 1
                  and cov.get("full") is True
                  and issues == baseline
                  and merged.get("issues") == len(baseline))
    return cell


def _cell_frozen_heartbeat(d: str, contracts,
                           baseline: List[str]) -> Dict:
    from mythril_tpu.fleet import WorkLedger

    fl = os.path.join(d, "fleet")
    # a worker claims one unit and freezes before its first renew: the
    # lease exists, the heartbeat never moves
    frozen = WorkLedger(fl, ttl=0.5, worker="w-frozen")
    frozen.ensure(contracts, unit_size=2)
    unit = frozen.claim_next()
    time.sleep(0.6)  # the frozen heartbeat goes stale
    res = _campaign(contracts, None, fleet_dir=fl, lease_ttl=0.5,
                    worker_id="w1").run()
    merged = _merge_fleet(res, fl)
    cov = merged.get("coverage") or {}
    kinds = [e.get("kind") for e in res.backend_events]
    issues = sorted(i["contract"]
                    for i in merged.get("issues_detail", []))
    cell = {"frozen_unit": unit.uid if unit else None,
            "reclaims": kinds.count("lease_reclaimed"),
            "issues": issues,
            "coverage": {k: cov.get(k) for k in
                         ("analyzed", "lost", "unaccounted", "full")}}
    cell["ok"] = (unit is not None
                  and kinds.count("lease_reclaimed") >= 1
                  and cov.get("full") is True
                  and issues == baseline)
    return cell


def _cell_serve(point: str, d: str, contracts,
                baseline: List[str]) -> Dict:
    from mythril_tpu.obs import metrics as obs_metrics
    from mythril_tpu.serve import AnalysisDaemon, ServeOptions

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_client

    def counter(name: str) -> float:
        return obs_metrics.REGISTRY.counter(name).value

    opts = ServeOptions(batch_size=2, lanes_per_contract=8,
                        max_steps=64, transaction_count=1,
                        modules=["AccidentallyKillable"],
                        limits_profile="test",
                        batch_timeout=_BATCH_TIMEOUT,
                        worker_isolation="on")
    restarts0 = counter("engine_worker_restarts_total")
    with _fault_env(point, d):
        dm = AnalysisDaemon(opts, data_dir=os.path.join(d, "sd"),
                            port=0)
        dm.start()
        url = f"http://127.0.0.1:{dm.port}"
        try:
            snap = serve_client.submit(url, contracts, tenant="chaos")
            final = serve_client.get_result(url, snap["id"], wait=600.0)
            health = serve_client.healthz(url)
        finally:
            dm.shutdown("chaos-cell")
    results = final["results"]
    by_name: Dict[str, int] = {}
    for r in results:
        by_name[r["name"]] = by_name.get(r["name"], 0) + 1
    issues = sorted(i["contract"] for r in results
                    for i in (r.get("issues") or []))
    restarts = counter("engine_worker_restarts_total") - restarts0
    cell = {"issues": issues, "completed": final["completed"],
            "state": final["state"],
            "worker_restarts": restarts,
            "health_state": health.get("state"),
            "statuses": sorted({r["status"] for r in results})}
    cell["ok"] = (final["state"] == "done"
                  and final["completed"] == N
                  and all(n == 1 for n in by_name.values())
                  and issues == baseline
                  and restarts >= 1
                  and all(r["status"] == "ok" for r in results))
    return cell


def _start_replica(d: str, tag: str, data_dir: str,
                   fault: Optional[str] = None,
                   extra: Optional[List[str]] = None):
    """One REAL serve daemon subprocess on the shared data dir;
    returns ``(proc, base_url)`` once it is listening."""
    import subprocess

    pf = os.path.join(d, f"port_{tag}")
    cmd = [sys.executable, "-m", "mythril_tpu", "serve",
           "--port", "0", "--port-file", pf, "--data-dir", data_dir,
           "--batch-size", "2", "--lanes-per-contract", "8",
           "--max-steps", "64", "-t", "1",
           "-m", "AccidentallyKillable", "--limits-profile", "test",
           "--drain-timeout", "2"]
    if fault:
        cmd += ["--fault-inject", fault]
    if extra:
        cmd += extra
    proc = subprocess.Popen(cmd, cwd=ROOT,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"),
                            stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + 120
    while not os.path.exists(pf):
        if proc.poll() is not None or time.monotonic() > deadline:
            raise RuntimeError(f"replica {tag} failed to start")
        time.sleep(0.1)
    with open(pf) as fh:
        return proc, f"http://127.0.0.1:{fh.read().strip()}"


def _cell_replica_kill(d: str, contracts, baseline: List[str]) -> Dict:
    """Two live replicas, one data dir: SIGKILL replica A mid-batch
    (harder than the soak's SIGTERM — no drain, no persist-on-exit),
    the surviving replica must answer everything exactly once, serving
    A's committed verdicts from the shared first-wins store."""
    import re
    import signal

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_client

    dd = os.path.join(d, "sd")
    pa, url_a = _start_replica(d, "a", dd, fault="hang:batch=1")
    pb, url_b = _start_replica(d, "b", dd)
    try:
        sid = serve_client.submit(url_a, contracts,
                                  tenant="chaos")["id"]
        committed = 0
        deadline = time.monotonic() + 300
        while committed < 2 and time.monotonic() < deadline:
            committed = serve_client.get_result(
                url_a, sid, wait=2.0)["completed"]
        pa.send_signal(signal.SIGKILL)
        pa.wait(timeout=60)
        final = serve_client.get_result(
            url_b, serve_client.submit(url_b, contracts,
                                       tenant="chaos")["id"],
            wait=600.0)
        met = serve_client.metrics(url_b)
    finally:
        for p in (pa, pb):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
                p.wait(timeout=60)
    results = final["results"]
    by_name: Dict[str, int] = {}
    for r in results:
        by_name[r["name"]] = by_name.get(r["name"], 0) + 1
    issues = sorted(i["contract"] for r in results
                    for i in (r.get("issues") or []))
    from_store = sorted(r["name"] for r in results
                        if r.get("served_from") == "dedupe-store")
    m = re.search(r"^mythril_serve_dedupe_hits_total (\d+)", met,
                  re.MULTILINE)
    cell = {"pre_kill_committed": committed,
            "completed": final["completed"], "state": final["state"],
            "from_store": from_store, "issues": issues,
            "b_dedupe_hits": int(m.group(1)) if m else -1}
    cell["ok"] = (committed >= 2
                  and final["state"] == "done"
                  and final["completed"] == N
                  and all(n == 1 for n in by_name.values())
                  and len(from_store) >= 2        # A's commits served by B
                  and issues == baseline)
    return cell


def _cell_replica_torn_store(d: str, contracts,
                             baseline: List[str]) -> Dict:
    """A committed verdict file torn mid-byte in the shared store: the
    next replica must count a corrupt miss, unlink, re-analyze the one
    contract, and leave a clean rewritten verdict behind."""
    import re
    import signal

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_client

    dd = os.path.join(d, "sd")
    pa, url_a = _start_replica(d, "a", dd)
    try:
        first = serve_client.get_result(
            url_a, serve_client.submit(url_a, contracts,
                                       tenant="chaos")["id"],
            wait=600.0)
    finally:
        pa.send_signal(signal.SIGTERM)
        pa.wait(timeout=60)
    store_dir = os.path.join(dd, "store")
    victims = sorted(f for f in os.listdir(store_dir)
                     if f.endswith(".json"))
    torn = os.path.join(store_dir, victims[0]) if victims else None
    if torn:
        raw = open(torn, "rb").read()
        with open(torn, "wb") as fh:
            fh.write(raw[:len(raw) // 2])
    pb, url_b = _start_replica(d, "b", dd)
    try:
        final = serve_client.get_result(
            url_b, serve_client.submit(url_b, contracts,
                                       tenant="chaos")["id"],
            wait=600.0)
        met = serve_client.metrics(url_b)
    finally:
        pb.send_signal(signal.SIGTERM)
        pb.wait(timeout=60)
    m = re.search(r"^mythril_serve_store_corrupt_total (\d+)", met,
                  re.MULTILINE)
    corrupt = int(m.group(1)) if m else 0
    rewritten = False
    if torn and os.path.exists(torn):
        try:
            json.load(open(torn))
            rewritten = True
        except ValueError:
            pass
    issues = sorted(i["contract"] for r in final["results"]
                    for i in (r.get("issues") or []))
    from_store = sum(1 for r in final["results"]
                     if r.get("served_from") == "dedupe-store")
    cell = {"tore": bool(torn), "corrupt_misses": corrupt,
            "rewritten": rewritten, "from_store": from_store,
            "completed": final["completed"], "issues": issues}
    cell["ok"] = (torn is not None and corrupt >= 1 and rewritten
                  and final["state"] == "done"
                  and final["completed"] == N
                  and from_store == N - 1   # only the torn one re-ran
                  and issues == baseline)
    return cell


def _store_admin(cmd: str, store_dir: str,
                 kill: Optional[str] = None) -> Tuple[int, Optional[Dict]]:
    """Run ``tools/store_admin.py CMD --store DIR`` as a subprocess,
    optionally with a MYTHRIL_SEGSTORE_KILL point armed; returns
    ``(returncode, parsed_json_or_None)``."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MYTHRIL_SEGSTORE_KILL", None)
    if kill:
        env["MYTHRIL_SEGSTORE_KILL"] = kill
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "store_admin.py"),
         cmd, "--store", store_dir],
        capture_output=True, text=True, env=env, cwd=ROOT)
    try:
        doc = json.loads(r.stdout)
    except ValueError:
        doc = None
    return r.returncode, doc


def _submit_all(url: str, contracts):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_client

    return serve_client.get_result(
        url, serve_client.submit(url, contracts, tenant="chaos")["id"],
        wait=600.0)


def _backfill_status(url: str) -> Dict:
    """Poll-friendly ``/healthz backfill`` read: a daemon mid-compile
    holds the GIL hard enough on a loaded CPU box to starve its HTTP
    threads past the client's socket timeout — that is slowness, not
    death, so the poll loop swallows it and asks again."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_client

    try:
        return serve_client.healthz(url).get("backfill") or {}
    except OSError:
        return {}


def _final_shape(final) -> Tuple[int, List[str]]:
    """(verdicts served from the dedupe store, sorted issue names)."""
    results = final["results"]
    from_store = sum(1 for r in results
                     if r.get("served_from") == "dedupe-store")
    issues = sorted(i["contract"] for r in results
                    for i in (r.get("issues") or []))
    return from_store, issues


def _cell_store_kill_compaction(d: str, contracts,
                                baseline: List[str]) -> Dict:
    """Die (os._exit, SIGKILL-equivalent) at each of the compaction
    protocol's three points in sequence — segment durable but manifest
    not, manifest durable but loose files not yet unlinked, and the
    store-level fold just before the unlink sweep. After EVERY kill
    the store must verify clean (all verdicts readable from one tier
    or the other), and the final clean pass must converge: every key
    in the manifest, zero loose files, and a fresh replica answering
    the whole corpus from segments alone."""
    import signal

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_client

    dd = os.path.join(d, "sd")
    pa, url_a = _start_replica(d, "a", dd)
    try:
        first = _submit_all(url_a, contracts)
    finally:
        pa.send_signal(signal.SIGTERM)
        pa.wait(timeout=60)
    store_dir = os.path.join(dd, "store")
    kills: List[int] = []
    verifies: List[bool] = []
    for point in ("after-segment", "after-manifest", "before-unlink"):
        rc, _ = _store_admin("compact", store_dir, kill=point)
        kills.append(rc)
        rc, rep = _store_admin("verify", store_dir)
        verifies.append(rc == 0 and bool(rep and rep.get("ok")))
    rc_final, _ = _store_admin("compact", store_dir)
    _, stats = _store_admin("stats", store_dir)
    pb, url_b = _start_replica(d, "b", dd)
    try:
        final = _submit_all(url_b, contracts)
    finally:
        pb.send_signal(signal.SIGTERM)
        pb.wait(timeout=60)
    from_store, issues = _final_shape(final)
    cell = {"kills": kills, "verifies": verifies,
            "final_compact_rc": rc_final, "stats": stats,
            "from_store": from_store,
            "completed": final["completed"], "issues": issues}
    cell["ok"] = (first["state"] == "done"
                  and kills == [9, 9, 9]          # every point fired
                  and all(verifies)               # readable after each
                  and rc_final == 0
                  and stats is not None
                  and stats.get("loose_keys") == 0
                  and stats.get("segment_keys") == N
                  and stats.get("generation", 0) >= 1
                  and final["state"] == "done"
                  and final["completed"] == N
                  and from_store == N             # all from segments
                  and issues == baseline)
    return cell


def _cell_store_torn_segment(d: str, contracts,
                             baseline: List[str]) -> Dict:
    """A committed segment file torn mid-byte: the next replica must
    quarantine it ``.corrupt`` on first read (checksum, not a parse
    error 500), re-analyze its keys with issue parity intact, and a
    re-compaction afterwards must heal the store to a clean new
    generation."""
    import re
    import signal

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_client

    dd = os.path.join(d, "sd")
    pa, url_a = _start_replica(d, "a", dd)
    try:
        _submit_all(url_a, contracts)
    finally:
        pa.send_signal(signal.SIGTERM)
        pa.wait(timeout=60)
    store_dir = os.path.join(dd, "store")
    rc_compact, _ = _store_admin("compact", store_dir)
    seg_dir = os.path.join(store_dir, "segments")
    segs = sorted(f for f in os.listdir(seg_dir)
                  if f.startswith("seg-") and f.endswith(".json"))
    torn = os.path.join(seg_dir, segs[0]) if segs else None
    if torn:
        raw = open(torn, "rb").read()
        with open(torn, "wb") as fh:
            fh.write(raw[:len(raw) // 2])
    pb, url_b = _start_replica(d, "b", dd)
    try:
        final = _submit_all(url_b, contracts)
        met = serve_client.metrics(url_b)
    finally:
        pb.send_signal(signal.SIGTERM)
        pb.wait(timeout=60)
    m = re.search(r"^mythril_serve_store_segment_corrupt_total (\d+)",
                  met, re.MULTILINE)
    corrupt = int(m.group(1)) if m else 0
    quarantined = any(f.endswith(".corrupt")
                      for f in os.listdir(seg_dir))
    # the re-analyzed verdicts land loose; a re-compaction heals the
    # store to a clean generation that verifies end to end
    rc_heal, _ = _store_admin("compact", store_dir)
    rc_verify, rep = _store_admin("verify", store_dir)
    from_store, issues = _final_shape(final)
    cell = {"tore": bool(torn), "segment_corrupt": corrupt,
            "quarantined": quarantined, "from_store": from_store,
            "completed": final["completed"], "issues": issues,
            "healed": rc_heal == 0 and rc_verify == 0}
    cell["ok"] = (rc_compact == 0 and torn is not None
                  and corrupt >= 1 and quarantined
                  and final["state"] == "done"
                  and final["completed"] == N
                  and from_store == 0             # every key re-ran
                  and issues == baseline
                  and rc_heal == 0 and rc_verify == 0
                  and bool(rep and rep.get("ok")))
    return cell


def _chain_node(contracts):
    """Canned loopback JSON-RPC chain for the backfill cell: contract
    ``i`` is deployed in block ``i+1``, head == len(contracts).
    Returns ``(server, url, head)``."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    head = len(contracts)
    blocks: Dict[int, List[Dict]] = {}
    receipts: Dict[str, Dict] = {}
    codes: Dict[str, str] = {}
    for i, (_name, code) in enumerate(contracts):
        n = i + 1
        addr = "0x" + f"{n:02x}" * 20
        txh = f"0xtx{n:04d}"
        blocks[n] = [{"hash": txh, "to": None}]
        receipts[txh] = {"contractAddress": addr}
        codes[addr] = "0x" + code.hex()

    class _Node(BaseHTTPRequestHandler):
        def do_POST(self):  # noqa: N802 — BaseHTTPRequestHandler API
            body = json.loads(
                self.rfile.read(int(self.headers["Content-Length"])))
            method, params = body["method"], body["params"]
            if method == "eth_blockNumber":
                result = hex(head)
            elif method == "eth_getBlockByNumber":
                n = int(params[0], 16)
                result = ({"number": params[0],
                           "transactions": blocks.get(n, [])}
                          if n <= head else None)
            elif method == "eth_getTransactionReceipt":
                result = receipts.get(params[0])
            elif method == "eth_getCode":
                result = codes.get(params[0].lower(), "0x")
            else:
                result = None
            data = json.dumps({"jsonrpc": "2.0", "id": body["id"],
                               "result": result}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Node)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}", head


def _cell_backfill_kill(d: str, contracts, baseline: List[str]) -> Dict:
    """SIGKILL a ``serve --backfill`` daemon mid-walk (no drain, no
    persist-on-exit). The restarted walker must resume from the
    durable two-ended cursor — ``hi`` still anchored at the original
    head, ``lo`` exactly where the last committed window left it — and
    ingest ONLY the blocks below it (exactly-once: nothing already
    committed is walked again), converging on one stored verdict per
    historical contract with issue parity."""
    import signal

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_client

    srv, rpc, head = _chain_node(contracts)
    dd = os.path.join(d, "sd")
    extra = ["--backfill", rpc, "--backfill-window", "1"]
    cursor = os.path.join(dd, "backfill_cursor.json")
    pre_lo = None
    pa, url_a = _start_replica(d, "a", dd, extra=extra)
    try:
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            bf = _backfill_status(url_a)
            lo = bf.get("lo")
            if lo is not None and 1 <= lo <= head:
                pre_lo = lo       # mid-walk: >=1 window committed,
                break             # blocks below lo still unwalked
            time.sleep(0.1)
    finally:
        pa.send_signal(signal.SIGKILL)
        pa.wait(timeout=60)
    lo_kill = json.load(open(cursor))["lo"]
    b_status: Dict = {}
    pb, url_b = _start_replica(d, "b", dd, extra=extra)
    try:
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            b_status = _backfill_status(url_b) or b_status
            if b_status.get("done"):
                break
            time.sleep(0.2)
        final = _submit_all(url_b, contracts)
    finally:
        pb.send_signal(signal.SIGTERM)
        pb.wait(timeout=60)
        srv.shutdown()
        srv.server_close()
    cur = json.load(open(cursor))
    from_store, issues = _final_shape(final)
    cell = {"pre_kill_lo": pre_lo, "lo_after_kill": lo_kill,
            "resumed": b_status, "cursor": cur,
            "from_store": from_store,
            "completed": final["completed"], "issues": issues}
    cell["ok"] = (pre_lo is not None
                  and 0 <= lo_kill <= head
                  and b_status.get("done") is True
                  and cur["lo"] == 0 and cur["hi"] == head
                  # exactly-once: the resumed walker ingested ONLY the
                  # blocks below the durable cursor (one deploy each)
                  and b_status.get("ingested") == max(0, lo_kill - 1)
                  and final["state"] == "done"
                  and final["completed"] == N
                  and from_store == N             # all precomputed
                  and issues == baseline)
    return cell


#: one compile-store registry observation, run in a subprocess so the
#: armed kill point takes out a separate writer, not the matrix
_COMPILE_RECORD_SRC = """\
import sys
from mythril_tpu.compilestore import CompileStore
CompileStore(sys.argv[1]).record(
    "cpu", (2, 8, 64, 1), "deadbeefcafe0000", chunks=(16, 32))
print("RECORDED")
"""


def _compile_record(root: str, kill: Optional[str] = None) -> int:
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MYTHRIL_COMPILESTORE_KILL", None)
    if kill:
        env["MYTHRIL_COMPILESTORE_KILL"] = kill
    r = subprocess.run(
        [sys.executable, "-c", _COMPILE_RECORD_SRC, root],
        capture_output=True, text=True, env=env, cwd=ROOT)
    return r.returncode


def _cell_compile_kill_registry(d: str, contracts,
                                baseline: List[str]) -> Dict:
    """Die (os._exit, SIGKILL-equivalent) at each point of the compile
    registry's write protocol. After EVERY kill the bucket must read
    back whole — the torn-write point leaves a half-written newest
    that the reader must quarantine ``.corrupt`` and answer from the
    rotated copy — and one more observation must heal the bucket to a
    clean durable record (docs/serving.md "Compile artifacts &
    prewarm")."""
    from mythril_tpu.compilestore import CompileStore

    root = os.path.join(d, "cstore")
    seed_rc = _compile_record(root)    # create path (first-wins link)
    merge_rc = _compile_record(root)   # merge path (rotates a .1 copy)
    kills: Dict[str, int] = {}
    readable: Dict[str, bool] = {}
    for point in ("pre-write", "post-write", "torn-write"):
        kills[point] = _compile_record(root, kill=point)
        bks = CompileStore(root).buckets()
        readable[point] = (len(bks) == 1
                           and bks[0]["tier"] == "cpu"
                           and bks[0]["hits"] >= 1
                           and bks[0]["chunks"] == [16, 32])
    heal_rc = _compile_record(root)
    stats = CompileStore(root).stats()
    cell = {"kills": kills, "readable": readable,
            "heal_rc": heal_rc, "stats": stats}
    cell["ok"] = (seed_rc == 0 and merge_rc == 0
                  and all(rc == 9 for rc in kills.values())
                  and all(readable.values())
                  # the torn newest was set aside, not silently eaten
                  and stats.get("corrupt_quarantined", 0) >= 1
                  and heal_rc == 0
                  and stats.get("buckets") == 1)
    return cell


def _cell_compile_cache_quarantine(d: str, contracts,
                                   baseline: List[str]) -> Dict:
    """A poisoned persistent XLA cache, flagged ``.dirty`` by a prior
    unclean worker death: the probe compile (forced to SIGSEGV by the
    chaos hook, as a torn cache entry would) must die in a THROWAWAY
    subprocess, the whole dir must be set aside ``.corrupt`` with its
    contents preserved, and the campaign must complete cold on a
    fresh dir — never a worker segfault, never a silent wipe."""
    cache = os.path.join(d, "xla_cache")
    os.makedirs(cache)
    with open(os.path.join(cache, "entry-0"), "wb") as fh:
        fh.write(b"\x00poisoned-xla-entry")
    with open(os.path.join(cache, ".dirty"), "w") as fh:
        fh.write("pid=0 t=0\n")
    saved = {k: os.environ.get(k) for k in
             ("JAX_COMPILATION_CACHE_DIR", "MYTHRIL_CACHE_PROBE_FAULT")}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["MYTHRIL_CACHE_PROBE_FAULT"] = "segv"
    try:
        res = _campaign(contracts, os.path.join(d, "ck"),
                        worker_isolation="on").run()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    quarantined = sorted(f for f in os.listdir(d)
                         if f.startswith("xla_cache.corrupt"))
    evidence = any(
        os.path.exists(os.path.join(d, q, "entry-0"))
        for q in quarantined)
    kinds = _worker_kinds(res.backend_events)
    cell = {"issues": _issues(res), "retries": res.retries,
            "quarantined_dirs": quarantined, "evidence": evidence,
            "worker_events": kinds,
            "contracts_quarantined": [q["name"]
                                      for q in res.quarantined]}
    cell["ok"] = (cell["issues"] == baseline
                  and len(res.issues) == len(baseline)
                  and not res.quarantined
                  and bool(quarantined) and evidence
                  # the fresh dir took the poisoned one's place
                  and os.path.isdir(cache)
                  and not os.path.exists(
                      os.path.join(cache, ".dirty"))
                  # the worker never died: the probe took the hit
                  and kinds.count("worker_death") == 0)
    return cell


def _cell_compile_flap_prewarm(d: str, contracts,
                               baseline: List[str]) -> Dict:
    """The registry prewarm pass bracketing a flapping device. Before
    the campaign: a pass preempted by live traffic must YIELD and
    re-arm itself, and an uncontended pass must replay the active
    tier's buckets. During: the flap's re-promotion must re-arm the
    pass (the recovered tier comes back warm, ISSUE 20's trigger).
    After: the settled tier's pass must converge — with issue parity
    and exactly-once accounting untouched by any of it."""
    from mythril_tpu.compilestore import CompileStore
    from mythril_tpu.resilience import FaultInjector

    store = CompileStore(os.path.join(d, "cstore"))
    tm = _tier_tm(probe_ok=True, flap_window=3600.0, flap_max=4)
    camp = _campaign(contracts, os.path.join(d, "ck"),
                     worker_isolation="off",
                     fault_injector=FaultInjector.from_string("flap"),
                     tier_manager=tm)
    camp.attach_compile_store(store)
    # seed both rungs of the ladder, as a prior daemon generation
    # would have (batch shape: 2 contracts x 8 lanes x 64 x 1)
    for tier in ("tpu", "cpu"):
        store.record(tier, (2, 8, 64, 1), camp.semantic_hash(),
                     chunks=(16,))
    yielded = camp.prewarm_from_store(should_stop=lambda: True)
    rearmed_after_yield = camp._prewarm_pending
    first = camp.prewarm_from_store()
    res = camp.run()
    rearmed_by_flap = camp._prewarm_pending
    second = camp.prewarm_from_store()
    st = tm.status()
    cell = {"issues": _issues(res), "retries": res.retries,
            "yielded": yielded, "first_pass": first,
            "second_pass": second, "tier": st,
            "rearmed_after_yield": rearmed_after_yield,
            "rearmed_by_flap": rearmed_by_flap}
    cell["ok"] = (cell["issues"] == baseline
                  and len(res.issues) == len(baseline)
                  and not res.quarantined
                  and yielded.get("state") == "yielded"
                  and rearmed_after_yield
                  and first.get("state") == "done"
                  and first.get("done", 0) >= 1
                  and st["repromotions"] >= 1
                  and rearmed_by_flap
                  and second.get("state") == "done"
                  and second.get("done", 0) >= 1)
    return cell


def run_cell(mode: str, point: str, contracts,
             baseline: List[str]) -> Dict:
    with tempfile.TemporaryDirectory() as d:
        if point in _WORKER_POINTS:
            if mode in ("batch", "pipelined"):
                return _cell_batch(mode, point, d, contracts, baseline)
            if mode == "fleet":
                return _cell_fleet_worker(point, d, contracts, baseline)
            if mode == "serve":
                return _cell_serve(point, d, contracts, baseline)
        if mode == "fleet" and point == "torn-ledger":
            return _cell_torn_ledger(d, contracts, baseline)
        if mode == "fleet" and point == "frozen-heartbeat":
            return _cell_frozen_heartbeat(d, contracts, baseline)
        if mode == "tier" and point in ("demote-mid-campaign",
                                        "repromote-mid-campaign"):
            return _cell_tier_crash(point, d, contracts, baseline)
        if mode == "tier" and point == "tier-flap":
            return _cell_tier_flap(d, contracts, baseline)
        if mode == "replica" and point == "kill-replica-mid-batch":
            return _cell_replica_kill(d, contracts, baseline)
        if mode == "replica" and point == "torn-store-verdict":
            return _cell_replica_torn_store(d, contracts, baseline)
        if mode == "store" and point == "kill-mid-compaction":
            return _cell_store_kill_compaction(d, contracts, baseline)
        if mode == "store" and point == "torn-segment":
            return _cell_store_torn_segment(d, contracts, baseline)
        if mode == "store" and point == "kill-mid-backfill-window":
            return _cell_backfill_kill(d, contracts, baseline)
        if mode == "compile" and point == "kill-mid-registry-write":
            return _cell_compile_kill_registry(d, contracts, baseline)
        if mode == "compile" and point == "corrupt-cache-quarantine":
            return _cell_compile_cache_quarantine(d, contracts,
                                                  baseline)
        if mode == "compile" and point == "tier-flap-during-prewarm":
            return _cell_compile_flap_prewarm(d, contracts, baseline)
        raise ValueError(f"cell {mode}:{point} is not in the matrix")


def run_matrix(cells: List[Tuple[str, str]]) -> Dict:
    """Run the given (mode, point) cells against one shared baseline.
    Importable — the soak's ``chaos`` leg calls this with the reduced
    matrix."""
    contracts = _corpus()
    base = _campaign(contracts, None, worker_isolation="off").run()
    baseline = _issues(base)
    out: Dict = {"baseline": baseline, "cells": {}, "ok": True}
    if not baseline:
        out["ok"] = False  # a no-issue baseline asserts nothing
        return out
    for mode, point in cells:
        key = f"{mode}:{point}"
        try:
            cell = run_cell(mode, point, contracts, baseline)
        except Exception as e:  # noqa: BLE001 — a cell must not kill the matrix
            cell = {"ok": False,
                    "error": f"{type(e).__name__}: {str(e)[:300]}"}
        out["cells"][key] = cell
        out["ok"] &= bool(cell.get("ok"))
        print(f"chaos {key}: {'ok' if cell.get('ok') else 'FAIL'}",
              file=sys.stderr, flush=True)
    return out


def parse_cells(text: Optional[str]) -> List[Tuple[str, str]]:
    if not text:
        return [(m, p) for m, pts in MATRIX.items() for p in pts]
    cells = []
    for item in text.split(","):
        item = item.strip()
        if not item:
            continue
        mode, _, point = item.partition(":")
        if mode not in MATRIX or point not in MATRIX[mode]:
            raise ValueError(
                f"unknown cell {item!r}; modes {tuple(MATRIX)} with "
                f"points per mode {MATRIX}")
        cells.append((mode, point))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", metavar="MODE:POINT,...", default=None,
                    help="subset of the matrix, e.g. "
                         "'batch:segv-mid-superstep,fleet:torn-ledger' "
                         "(default: every applicable cell)")
    args = ap.parse_args()
    try:
        cells = parse_cells(args.cells)
    except ValueError as e:
        ap.error(str(e))
    out = run_matrix(cells)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
