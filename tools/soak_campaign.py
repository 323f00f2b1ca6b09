#!/usr/bin/env python
"""Resilience soak smoke: a small corpus with injected faults, end to
end on the CPU backend.

Five legs, one process (see docs/resilience.md + docs/checkpointing.md):

  1. transient — a raise fault at batch 0 with ``times=1``; the
     retry-once policy must cure it with nothing quarantined;
  2. poison — a persistent raise fault on one contract; the campaign
     must bisect, quarantine exactly that contract, and finish every
     other batch;
  3. kill+resume — a simulated SIGKILL (InjectedKill) mid-campaign on
     top of the poison; the resumed session must converge to the same
     final issue set and quarantine list as leg 2;
  4. oom — an injected RESOURCE_EXHAUSTED at batch 0; the degradation
     ladder must shrink the batch (visible as ``degrade`` backend
     events) and the campaign must still find every issue with nothing
     quarantined (``--fault-inject`` overrides the injected spec);
  5. torn-checkpoint — kill mid-campaign, then truncate the newest
     checkpoint mid-file (a kill -9 DURING the checkpoint write); the
     resume must fall back to the rotated last-known-good copy and
     converge to leg 2's final state with nothing double-counted;
  6. telemetry — the same fault-injected campaign run with the trace +
     metrics + heartbeat spine on (docs/observability.md): the emitted
     JSONL must parse line-by-line with the required schema keys
     (``kind``, ``t``, ``schema``) on EVERY event, the Chrome trace
     must be valid JSON with superstep/batch/checkpoint spans and
     degrade events, and the metrics snapshot must carry the campaign
     counters;
  7. pipeline — the depth-1 pipelined campaign (docs/performance.md)
     killed mid-pipeline while the BACKGROUND checkpoint writer owns
     durability, the newest checkpoint then torn mid-file (a kill -9
     landing during the background write); the pipelined resume must
     detect the tear, replay only undurable batches, converge to the
     same issue set with no contract counted twice, and leave a newest
     checkpoint that loads cleanly;
  8. fleet — a 2-worker in-process fleet on one work ledger
     (docs/fleet.md): worker 0 is killed mid-batch (InjectedKill blows
     through uncheckpointed, its lease goes stale), worker 1 must
     RECLAIM the orphaned unit and finish the corpus; the merged
     report (surviving worker + the ledger's committed units) must
     show 100% analyzed+quarantined coverage, zero lost, no
     double-counted issues, and the lease_reclaimed event on record;
  9. serve — the always-on daemon (docs/serving.md) as a real
     subprocess: submit the corpus, let batch 0 commit its verdicts to
     the store, then SIGTERM the daemon while batch 1 is IN FLIGHT
     (an injected hang holds it); the bounded drain must exit anyway,
     and a restarted daemon given the same data dir must serve the
     completed contracts from the dedupe store (serve_dedupe_hits_total
     == 2, served_from == dedupe-store) and analyze only the rest —
     every contract exactly once, the same issue set as a batch run;
 10. solver-store — the staged solver portfolio's durable verdict
     store (docs/solver.md): kill a campaign mid-corpus with
     --solver-store attached, restart on the same checkpoint + store
     dirs to completion, then run a FULL second campaign over the warm
     store with the in-process LRU cleared (a fresh process's view):
     warm-store hits must be >= the verdicts committed before the
     kill, and the final issue set must be byte-identical to a
     store-disabled baseline — no verdict divergence, exactly-once
     durability for solver work like for everything else.
 11. chaos — a reduced tools/chaos_campaign.py fault matrix on CPU
     (docs/resilience.md "Process isolation & supervision"): a real
     SIGSEGV into the engine-worker subprocess mid-superstep (batch
     mode) and a torn fleet-ledger result file, each asserting issue
     parity with an uninjected baseline, exactly-once accounting, and
     the recovery events on record. The full matrix is the
     pre-release gate; this leg keeps the boundary honest per-change.
 12. replicas — multi-replica shared state under a hard kill
     (docs/serving.md "Overload & multi-replica serving"): TWO serve
     daemons as real subprocesses on ONE --data-dir; the corpus is
     submitted to replica A, which commits batch 0's verdicts to the
     shared first-wins store and then hangs on batch 1 (injected);
     A is SIGKILLed mid-batch — no drain, no persist-on-exit — and
     the SAME corpus goes to replica B, which must serve A's two
     committed verdicts from the shared store and analyze only the
     rest: every contract exactly once, issue parity with a batch
     run, and a final full resubmission to B answered 100% from
     dedupe (the merged exactly-once check).
 14. segments — the historical-index pipeline killed at every stage
     (docs/serving.md "Verdict segments & edge replicas"): a
     ``--backfill`` walker SIGKILLed mid-window must resume from the
     durable two-ended cursor and ingest ONLY the blocks below it
     (exactly-once across the kill); the compactor killed right after
     the manifest commit must re-run to convergence (zero loose files,
     every key in the manifest, no double-fold); a ``--store-only``
     edge replica on the same data dir must then answer the whole
     corpus from segments alone with issue parity and type the one
     unknown bytecode as ``unknown-contract`` instead of 500ing.
 15. coldstart — the fleet compile-artifact store across a HARD kill
     (docs/serving.md "Compile artifacts & prewarm"): daemon A warms
     a corpus and is SIGKILLed with no drain; daemon B on the same
     data dir must AOT-prewarm from the durable shape-bucket registry
     and answer a FRESH same-shape submission with
     ``engine_compiles_total`` flat and
     ``serve_warm_compile_hits_total`` rising — the recovered replica
     comes back warm, the cold-start cliff is gone.

Prints ONE JSON line {"ok": bool, "legs": {...}} and exits 0/1 —
suitable as a CI smoke or a manual post-change sanity run:

    JAX_PLATFORMS=cpu python tools/soak_campaign.py
    JAX_PLATFORMS=cpu python tools/soak_campaign.py --legs oom,torn
    JAX_PLATFORMS=cpu python tools/soak_campaign.py \
        --fault-inject oom:batch=0:times=2

Env gates (all opt-in):

  SOAK_INIT_TIMEOUT=<sec>   probe backend init in a subprocess first,
                            falling back to CPU on failure
  SOAK_BATCH_TIMEOUT=<sec>  per-batch watchdog budget (default 300)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the soak is a CPU functional check; never let it touch (and possibly
# wedge on) a configured accelerator backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_INIT_TIMEOUT = float(os.environ.get("SOAK_INIT_TIMEOUT", "0") or 0)
_BATCH_TIMEOUT = float(os.environ.get("SOAK_BATCH_TIMEOUT", "300") or 300)

if _INIT_TIMEOUT > 0:
    # gate BEFORE the engine import, like the campaign CLI does
    from mythril_tpu.resilience import BackendManager

    _ok, _diag = BackendManager(init_timeout=_INIT_TIMEOUT).ensure_or_fallback()
    if not _ok:
        print(f"soak: backend unavailable ({_diag}); continuing on CPU",
              file=sys.stderr)

import mythril_tpu  # noqa: E402,F401  (enables x64)
from mythril_tpu.config import TEST_LIMITS  # noqa: E402
from mythril_tpu.disassembler.asm import assemble  # noqa: E402
from mythril_tpu.mythril.campaign import (  # noqa: E402
    CorpusCampaign, load_corpus_dir)
from mythril_tpu.resilience import (  # noqa: E402
    FaultInjector, InjectedKill)

KILLABLE = assemble(0, "SELFDESTRUCT")
SAFE = assemble(1, 0, "SSTORE", "STOP")
N = 6  # even indices killable -> expected issues c000/c002/c004

LEGS = ("transient", "poison", "kill_resume", "oom", "torn", "telemetry",
        "pipeline", "fleet", "serve", "solver_store", "chaos",
        "replicas", "tiers", "segments", "coldstart")


def write_corpus(d: str) -> str:
    corpus = os.path.join(d, "corpus")
    os.makedirs(corpus, exist_ok=True)
    for i in range(N):
        code = KILLABLE if i % 2 == 0 else SAFE
        with open(os.path.join(corpus, f"c{i:03d}.hex"), "w") as fh:
            fh.write(code.hex())
    return corpus


def campaign(corpus: str, ckpt: str, fault: str | None, **kw):
    kw.setdefault("batch_size", 4)
    return CorpusCampaign(
        load_corpus_dir(corpus),
        lanes_per_contract=8, limits=TEST_LIMITS,
        max_steps=64, transaction_count=1,
        modules=["AccidentallyKillable"], checkpoint_dir=ckpt,
        batch_timeout=_BATCH_TIMEOUT,  # guards the soak, not the test
        fault_injector=FaultInjector.from_string(fault),
        **kw)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help=f"comma-separated subset of {LEGS}")
    ap.add_argument("--fault-inject", default="oom:batch=0:times=1",
                    metavar="SPEC",
                    help="fault spec for the oom leg (e.g. "
                         "'oom:batch=0:times=2' to walk two rungs)")
    args = ap.parse_args()
    want = {leg.strip() for leg in args.legs.split(",") if leg.strip()}
    bad = want - set(LEGS)
    if bad:
        ap.error(f"unknown legs {sorted(bad)}; choose from {LEGS}")

    legs: dict = {}
    ok = True
    with tempfile.TemporaryDirectory() as d:
        corpus = write_corpus(d)

        if "transient" in want:
            # leg 1: transient fault cured by the retry-once policy
            r = campaign(corpus, os.path.join(d, "ck1"),
                         "raise:batch=0:times=1").run()
            legs["transient"] = {"retries": r.retries,
                                 "quarantined": len(r.quarantined),
                                 "issues": len(r.issues)}
            ok &= (r.retries == 1 and not r.quarantined
                   and len(r.issues) == 3)

        expected_issues = ["c000", "c004"]  # c002 lost to the poison
        if "poison" in want or "torn" in want:
            # leg 2: persistent poison -> bisect -> quarantine, run
            # survives (also the reference state for the torn leg)
            r2 = campaign(corpus, os.path.join(d, "ck2"),
                          "raise:contract=c002").run()
            legs["poison"] = {
                "quarantined": [q["name"] for q in r2.quarantined],
                "batch_status": r2.batch_status,
                "issues": sorted(i["contract"] for i in r2.issues)}
            ok &= ([q["name"] for q in r2.quarantined] == ["c002"]
                   and legs["poison"]["issues"] == expected_issues)

        if "kill_resume" in want:
            # leg 3: kill mid-campaign, then resume to the same final state
            ck3 = os.path.join(d, "ck3")
            killed = False
            try:
                campaign(corpus, ck3,
                         "raise:contract=c002;kill:batch=1").run()
            except InjectedKill:
                killed = True
            r3 = campaign(corpus, ck3, "raise:contract=c002").run()
            legs["kill_resume"] = {
                "killed": killed,
                "batches": r3.batches,
                "quarantined": [q["name"] for q in r3.quarantined],
                "issues": sorted(i["contract"] for i in r3.issues)}
            ok &= (killed and r3.batches == 2
                   and legs["kill_resume"]["quarantined"] == ["c002"]
                   and legs["kill_resume"]["issues"] == expected_issues)

        if "oom" in want:
            # leg 4: RESOURCE_EXHAUSTED absorbed by the degradation
            # ladder — batch completes smaller instead of failing
            r4 = campaign(corpus, os.path.join(d, "ck4"),
                          args.fault_inject).run()
            steps = [e.get("step") for e in r4.backend_events
                     if e.get("kind") == "degrade"]
            legs["oom"] = {
                "degrade_steps": steps,
                "batch_status": r4.batch_status,
                "quarantined": len(r4.quarantined),
                "issues": sorted(i["contract"] for i in r4.issues)}
            ok &= (bool(steps) and not r4.quarantined
                   and legs["oom"]["issues"] == ["c000", "c002", "c004"]
                   and any(s.startswith("ok-degraded:")
                           for s in r4.batch_status))

        if "torn" in want:
            # leg 5: kill -9 DURING a checkpoint write — run the poison
            # campaign to completion, then truncate its NEWEST
            # checkpoint mid-file (exactly what a kill mid-write leaves
            # behind); the resume must detect the tear via checksum,
            # fall back to the rotated last-known-good copy, replay only
            # the batch the torn file described, and converge to leg 2's
            # final state with nothing double-counted
            ck5 = os.path.join(d, "ck5")
            campaign(corpus, ck5, "raise:contract=c002").run()
            p = os.path.join(ck5, "campaign.json")
            raw = open(p, "rb").read()
            with open(p, "wb") as fh:
                fh.write(raw[:len(raw) // 2])   # torn mid-write
            r5 = campaign(corpus, ck5, "raise:contract=c002").run()
            kinds = [e.get("kind") for e in r5.backend_events]
            legs["torn"] = {
                "recovered": "checkpoint_recovered" in kinds,
                "batches": r5.batches,
                "quarantined": [q["name"] for q in r5.quarantined],
                "issues": sorted(i["contract"] for i in r5.issues)}
            ok &= (legs["torn"]["recovered"]
                   and r5.batches == 2
                   and legs["torn"]["quarantined"] == ["c002"]
                   and legs["torn"]["issues"] == legs["poison"]["issues"])

        if "telemetry" in want:
            # leg 6: the --trace/--metrics/--heartbeat spine on a real
            # fault-injected campaign — every emitted JSONL event must
            # parse and carry the schema'd required keys
            from mythril_tpu.obs import metrics as obs_metrics
            from mythril_tpu.obs import trace as obs_trace

            tpath = os.path.join(d, "t.json")
            jpath = obs_trace.jsonl_path_for(tpath)
            mpath = os.path.join(d, "m.json")
            obs_trace.configure(tpath)
            # legs 1-5 already incremented the process-global registry
            # (counters tick even while disabled); start this leg clean
            # so the batches_total assertion sees only its own campaign
            obs_metrics.REGISTRY.reset()
            obs_metrics.REGISTRY.enabled = True
            try:
                r6 = campaign(corpus, os.path.join(d, "ck6"),
                              "oom:batch=0:times=1",
                              heartbeat_every=0.0).run()
            finally:
                obs_trace.close()
                obs_metrics.REGISTRY.write(mpath)
            events = []
            parse_ok = True
            with open(jpath) as fh:
                for line in fh:
                    if not line.strip():
                        continue
                    try:
                        events.append(json.loads(line))
                    except ValueError:
                        parse_ok = False
            keys_ok = bool(events) and all(
                "kind" in e and "t" in e and "schema" in e
                for e in events)
            with open(tpath) as fh:
                chrome = json.load(fh)
            names = {e.get("name") for e in chrome.get("traceEvents", [])}
            snap = json.load(open(mpath))
            legs["telemetry"] = {
                "events": len(events), "parse_ok": parse_ok,
                "keys_ok": keys_ok,
                "span_names": sorted(n for n in names if n),
                "heartbeats": sum(1 for e in events
                                  if e.get("kind") == "heartbeat"),
                "batches_total": snap.get("counters", {}).get(
                    "batches_total"),
            }
            ok &= (parse_ok and keys_ok
                   and {"superstep", "batch",
                        "checkpoint_save", "degrade"} <= names
                   and legs["telemetry"]["heartbeats"] >= 1
                   and snap.get("counters", {}).get("batches_total") == 2
                   and not r6.quarantined
                   and sorted(i["contract"] for i in r6.issues)
                   == ["c000", "c002", "c004"])

        if "pipeline" in want:
            # leg 7: pipelined campaign + background checkpoint writer
            # under kill + torn-write. batch_size=2 -> 3 batches; the
            # kill fires in batch 2's DEVICE phase, i.e. while batch 1's
            # host phase waits for its start (batch 2's first sym_run
            # call: the kill gives it up) and the background write of
            # batch 0's durable state is in flight — exactly the window
            # the pipeline opened. The newest checkpoint is then
            # truncated mid-file
            # (a kill -9 landing during the background write itself);
            # the resume must see the tear, start from the last durable
            # point (here: nothing — first-ever write torn), replay,
            # and count every contract exactly once.
            from mythril_tpu.utils.checkpoint import load_json_checkpoint

            ck7 = os.path.join(d, "ck7")
            killed = False
            try:
                campaign(corpus, ck7, "kill:batch=2",
                         batch_size=2, pipeline=True).run()
            except InjectedKill:
                killed = True
            p = os.path.join(ck7, "campaign.json")
            # whether batch 0's background write beat the kill is a
            # genuine race (that is the point of the leg); both sides
            # must converge — tear the file when it exists, else the
            # kill itself already denied durability
            had_ckpt = os.path.exists(p)
            if had_ckpt:  # tear the background writer's newest file
                raw = open(p, "rb").read()
                with open(p, "wb") as fh:
                    fh.write(raw[:len(raw) // 2])
            r7 = campaign(corpus, ck7, None,
                          batch_size=2, pipeline=True).run()
            issues = sorted(i["contract"] for i in r7.issues)
            final = load_json_checkpoint(p)  # newest durable file loads
            legs["pipeline"] = {
                "killed": killed, "had_ckpt": had_ckpt,
                "batches": r7.batches, "issues": issues,
                "final_next_batch": final.get("next_batch"),
                "batch_status": r7.batch_status}
            ok &= (killed and r7.batches == 3
                   and issues == ["c000", "c002", "c004"]
                   and len(r7.issues) == 3        # nothing counted twice
                   and not r7.quarantined
                   and final.get("next_batch") == 3)

        if "fleet" in want:
            # leg 8: elastic fleet — worker 0 dies holding a lease,
            # worker 1 reclaims after the TTL and closes coverage.
            # batch_size=2 -> 3 one-batch units; the kill fires on
            # whichever unit carries global batch 1, so w0 always dies
            # holding exactly that unit's lease.
            import time as _time

            from mythril_tpu.fleet import ledger_results
            from mythril_tpu.mythril.campaign import merge_campaigns

            fl = os.path.join(d, "fleet")
            killed = False
            try:
                campaign(corpus, None, "kill:batch=1", batch_size=2,
                         fleet_dir=fl, lease_ttl=0.5,
                         worker_id="w0").run()
            except InjectedKill:
                killed = True
            _time.sleep(0.6)                  # w0's heartbeat goes stale
            r8 = campaign(corpus, None, None, batch_size=2,
                          fleet_dir=fl, lease_ttl=0.5,
                          worker_id="w1").run()
            d8 = r8.as_dict()
            d8["issues_detail"] = r8.issues
            # surviving worker first; the ledger contributes exactly the
            # units no report spoke for (w0's pre-kill commits)
            merged = merge_campaigns([d8] + ledger_results(fl))
            cov = merged.get("coverage") or {}
            issues = sorted(i["contract"]
                            for i in merged.get("issues_detail", []))
            kinds = [e.get("kind") for e in r8.backend_events]
            legs["fleet"] = {
                "killed": killed,
                "reclaimed": kinds.count("lease_reclaimed"),
                "coverage": {k: cov.get(k) for k in
                             ("analyzed", "quarantined", "lost",
                              "unaccounted", "full")},
                "issues": issues,
                "w1_units": [u["unit"] for u in r8.fleet["units"]]}
            ok &= (killed
                   and kinds.count("lease_reclaimed") >= 1
                   and cov.get("full") is True
                   and cov.get("analyzed") == N and not cov.get("lost")
                   and merged.get("issues") == 3   # nothing twice
                   and issues == ["c000", "c002", "c004"])

        if "serve" in want:
            # leg 9: kill the resident daemon mid-batch, restart, and
            # prove exactly-once via the dedupe store. The daemon runs
            # as a REAL subprocess (signals, drain, process death are
            # the contract under test); batch 1 is held by an injected
            # hang so SIGTERM provably lands during an in-flight batch
            # and the bounded drain (--drain-timeout) must abandon it.
            import re
            import signal
            import subprocess
            import time as _time

            sys.path.insert(0, os.path.join(ROOT, "tools"))
            import serve_client

            # six DISTINCT bytecodes (the shared soak corpus has only
            # two: odd/even contracts are byte-clones, which the
            # admission dedupe collapses into one batch — correct for
            # serving, useless for a kill-mid-batch scenario). Varying
            # the pushed operand keeps even contracts killable while
            # making every bytecode hash unique, so the daemon really
            # runs 3 batches of 2.
            contracts = [
                (f"c{i:03d}",
                 assemble(i, "SELFDESTRUCT") if i % 2 == 0
                 else assemble(1, i, "SSTORE", "STOP"))
                for i in range(N)]
            dd = os.path.join(d, "serve_data")
            env = dict(os.environ, JAX_PLATFORMS="cpu")

            def start_daemon(tag, fault=None):
                pf = os.path.join(d, f"port_{tag}")
                cmd = [sys.executable, "-m", "mythril_tpu", "serve",
                       "--port", "0", "--port-file", pf,
                       "--data-dir", dd, "--batch-size", "2",
                       "--lanes-per-contract", "8",
                       "--max-steps", "64", "-t", "1",
                       "-m", "AccidentallyKillable",
                       "--limits-profile", "test",
                       "--drain-timeout", "2"]
                if fault:
                    cmd += ["--fault-inject", fault]
                proc = subprocess.Popen(cmd, env=env, cwd=ROOT,
                                        stderr=subprocess.DEVNULL)
                deadline = _time.monotonic() + 120
                while not os.path.exists(pf):
                    if (proc.poll() is not None
                            or _time.monotonic() > deadline):
                        raise RuntimeError("serve daemon failed to start")
                    _time.sleep(0.1)
                with open(pf) as fh:
                    return proc, f"http://127.0.0.1:{fh.read().strip()}"

            p1, url1 = start_daemon("a", fault="hang:batch=1")
            sid1 = serve_client.submit(url1, contracts,
                                       tenant="soak")["id"]
            # wait for batch 0's two verdicts to commit durably; batch
            # 1 then hangs — the in-flight window we SIGTERM into
            committed = 0
            deadline = _time.monotonic() + 300
            while committed < 2 and _time.monotonic() < deadline:
                committed = serve_client.get_result(
                    url1, sid1, wait=2.0)["completed"]
            p1.send_signal(signal.SIGTERM)
            rc1 = p1.wait(timeout=120)

            p2, url2 = start_daemon("b")
            try:
                snap = serve_client.submit(url2, contracts,
                                           tenant="soak")
                final = serve_client.get_result(url2, snap["id"],
                                                wait=300.0)
                met = serve_client.metrics(url2)
            finally:
                p2.send_signal(signal.SIGTERM)
                p2.wait(timeout=120)
            mdedupe = re.search(
                r"^mythril_serve_dedupe_hits_total (\d+)", met,
                re.MULTILINE)
            dedupe_hits = int(mdedupe.group(1)) if mdedupe else -1
            results = final["results"]
            by_name = {}
            for r in results:
                by_name.setdefault(r["name"], []).append(r)
            issues = sorted(i["contract"] for r in results
                            for i in (r.get("issues") or []))
            from_store = sorted(
                r["name"] for r in results
                if r.get("served_from") == "dedupe-store")
            legs["serve"] = {
                "pre_kill_committed": committed,
                "daemon1_rc": rc1,
                "completed": final["completed"],
                "state": final["state"],
                "dedupe_hits": dedupe_hits,
                "from_store": from_store,
                "issues": issues,
            }
            ok &= (committed == 2 and rc1 == 0
                   and final["state"] == "done"
                   and final["completed"] == N
                   and all(len(v) == 1 for v in by_name.values())
                   and dedupe_hits == 2
                   and from_store == ["c000", "c001"]
                   and issues == ["c000", "c002", "c004"])

        if "solver_store" in want:
            # leg 10: the solver-portfolio verdict store under a kill.
            # The shared soak corpus is branchless (a bare SELFDESTRUCT
            # resolves at the probe stage — nothing ever reaches the
            # search, so nothing would be stored); this leg uses a
            # clone-heavy GUARDED corpus whose selfdestruct hides
            # behind a require-style bound, forcing a real witness
            # search whose verdict the store must carry across the
            # kill.
            from mythril_tpu.smt.solver import _SOLVE_CACHE

            guarded = assemble(
                4, "CALLDATALOAD", ("push2", 1000), "LT",  # 1000 < arg
                ("ref", "ok"), "JUMPI", "STOP",
                ("label", "ok"), 0, "SELFDESTRUCT")
            corpus10 = os.path.join(d, "corpus10")
            os.makedirs(corpus10, exist_ok=True)
            for i in range(N):
                code = guarded if i % 2 == 0 else SAFE
                with open(os.path.join(corpus10, f"g{i:03d}.hex"),
                          "w") as fh:
                    fh.write(code.hex())
            store_dir = os.path.join(d, "solver_store")
            ck10 = os.path.join(d, "ck10")
            # store-disabled baseline: the no-divergence reference
            _SOLVE_CACHE.clear()
            base_r = campaign(corpus10, os.path.join(d, "ck10b"), None,
                              solver_store=None).run()
            base_issues = sorted(i["contract"] for i in base_r.issues)
            _SOLVE_CACHE.clear()
            killed = False
            try:
                campaign(corpus10, ck10, "kill:batch=1",
                         solver_store=store_dir).run()
            except InjectedKill:
                killed = True
            pre_kill = len([f for f in os.listdir(store_dir)
                            if f.endswith(".json")]) \
                if os.path.isdir(store_dir) else 0
            # resume on the same dirs to completion (exactly-once)
            r10a = campaign(corpus10, ck10, None,
                            solver_store=store_dir).run()
            # a "fresh process": only the durable store survives — the
            # LRU (which would mask store hits) is cleared
            _SOLVE_CACHE.clear()
            r10 = campaign(corpus10, os.path.join(d, "ck10w"), None,
                           solver_store=store_dir).run()
            stages = (r10.solver_portfolio or {}).get("stages") or {}
            store_hits = (stages.get("store") or {}).get("hits", 0)
            issues = sorted(i["contract"] for i in r10.issues)
            legs["solver_store"] = {
                "killed": killed,
                "pre_kill_verdicts": pre_kill,
                "resumed_batches": r10a.batches,
                "warm_store_hits": store_hits,
                "z3_avoided_pct": (r10.solver_portfolio or {}).get(
                    "z3_avoided_pct"),
                "issues": issues,
            }
            ok &= (killed and r10a.batches == 2
                   and pre_kill >= 1
                   and store_hits >= pre_kill
                   and issues == base_issues
                   and sorted(i["contract"] for i in r10a.issues)
                   == base_issues)

        if "replicas" in want:
            # leg 12: kill one replica mid-batch, the other answers —
            # the multi-replica shared-store contract end to end with
            # real processes and a real SIGKILL (no drain)
            import signal
            import subprocess
            import time as _time

            sys.path.insert(0, os.path.join(ROOT, "tools"))
            import serve_client

            contracts = [
                (f"c{i:03d}",
                 assemble(i, "SELFDESTRUCT") if i % 2 == 0
                 else assemble(1, i, "SSTORE", "STOP"))
                for i in range(N)]
            dd = os.path.join(d, "replica_data")
            env = dict(os.environ, JAX_PLATFORMS="cpu")

            def start_replica(tag, fault=None):
                pf = os.path.join(d, f"rport_{tag}")
                cmd = [sys.executable, "-m", "mythril_tpu", "serve",
                       "--port", "0", "--port-file", pf,
                       "--data-dir", dd, "--batch-size", "2",
                       "--lanes-per-contract", "8",
                       "--max-steps", "64", "-t", "1",
                       "-m", "AccidentallyKillable",
                       "--limits-profile", "test",
                       "--drain-timeout", "2"]
                if fault:
                    cmd += ["--fault-inject", fault]
                proc = subprocess.Popen(cmd, env=env, cwd=ROOT,
                                        stderr=subprocess.DEVNULL)
                deadline = _time.monotonic() + 120
                while not os.path.exists(pf):
                    if (proc.poll() is not None
                            or _time.monotonic() > deadline):
                        raise RuntimeError(
                            f"replica {tag} failed to start")
                    _time.sleep(0.1)
                with open(pf) as fh:
                    return proc, f"http://127.0.0.1:{fh.read().strip()}"

            pa, url_a = start_replica("a", fault="hang:batch=1")
            pb, url_b = start_replica("b")
            try:
                sid = serve_client.submit(url_a, contracts,
                                          tenant="soak")["id"]
                committed = 0
                deadline = _time.monotonic() + 300
                while committed < 2 and _time.monotonic() < deadline:
                    committed = serve_client.get_result(
                        url_a, sid, wait=2.0)["completed"]
                pa.send_signal(signal.SIGKILL)
                pa.wait(timeout=60)
                final = serve_client.get_result(
                    url_b, serve_client.submit(url_b, contracts,
                                               tenant="soak")["id"],
                    wait=300.0)
                # merged exactly-once: a full resubmission answers
                # 100% from the now-complete shared store
                again = serve_client.get_result(
                    url_b, serve_client.submit(url_b, contracts,
                                               tenant="soak")["id"],
                    wait=60.0)
            finally:
                for p in (pa, pb):
                    if p.poll() is None:
                        p.send_signal(signal.SIGTERM)
                        p.wait(timeout=60)
            results = final["results"]
            by_name = {}
            for r in results:
                by_name.setdefault(r["name"], []).append(r)
            issues = sorted(i["contract"] for r in results
                            for i in (r.get("issues") or []))
            from_store = sorted(
                r["name"] for r in results
                if r.get("served_from") == "dedupe-store")
            legs["replicas"] = {
                "pre_kill_committed": committed,
                "completed": final["completed"],
                "state": final["state"],
                "from_store": from_store,
                "issues": issues,
                "resubmit_all_dedupe": all(
                    r.get("served_from") == "dedupe-store"
                    for r in again["results"]),
            }
            ok &= (committed == 2
                   and final["state"] == "done"
                   and final["completed"] == N
                   and all(len(v) == 1 for v in by_name.values())
                   and from_store == ["c000", "c001"]
                   and issues == ["c000", "c002", "c004"]
                   and again["state"] == "done"
                   and legs["replicas"]["resubmit_all_dedupe"])

        if "tiers" in want:
            # leg 13: wedge the preferred tier mid-campaign — the
            # campaign finishes on the demoted tier exactly-once; un-
            # wedging lets the BACKGROUND prober re-promote with no
            # operator intervention, and the next campaign runs on the
            # recovered tier
            import time as _time

            from mythril_tpu.backend import TierManager
            from mythril_tpu.utils.checkpoint import load_json_checkpoint

            wedge = os.path.join(d, "tier_wedge")
            with open(wedge, "w") as fh:
                fh.write("wedged")

            def tier_probe(tier, timeout):
                up = not os.path.exists(wedge)
                return up, "clear" if up else "wedged"

            tm = TierManager(tiers=("tpu", "cpu"), probe_fn=tier_probe,
                             sticky_window=0.0, flap_window=60.0,
                             flap_max=6, probe_every=0.05,
                             env_pin=False)
            r1 = campaign(corpus, os.path.join(d, "ck13"),
                          "device-lost:batch=1:times=1",
                          tier_manager=tm).run()
            st1 = tm.status()
            fin1 = load_json_checkpoint(
                os.path.join(d, "ck13", "campaign.json"))
            os.unlink(wedge)  # the "tpu" tier recovers
            deadline = _time.monotonic() + 30
            while tm.demoted() and _time.monotonic() < deadline:
                _time.sleep(0.05)
            st_up = tm.status()
            r2 = campaign(corpus, os.path.join(d, "ck13b"), None,
                          tier_manager=tm).run()
            st2 = tm.status()
            tm.stop_prober()
            legs["tiers"] = {
                "after_wedged_campaign": st1,
                "checkpoint": fin1.get("next_batch"),
                "after_unwedge": st_up,
                "after_recovered_campaign": st2,
                "issues1": sorted(i["contract"] for i in r1.issues),
                "issues2": sorted(i["contract"] for i in r2.issues),
                "retries": r1.retries}
            ok &= (r1.retries == 1 and not r1.quarantined
                   and legs["tiers"]["issues1"] == ["c000", "c002",
                                                    "c004"]
                   and st1["demoted"] and st1["current"] == "cpu"
                   and st1["demotions"] == 1
                   and fin1.get("next_batch") == 2  # exactly-once
                   and not st_up["demoted"]  # prober climbed back
                   and st_up["repromotions"] == 1
                   and st2["current"] == st2["preferred"]
                   and st2["demotions"] == 1  # campaign 2 clean
                   and not r2.quarantined
                   and legs["tiers"]["issues2"] == ["c000", "c002",
                                                    "c004"])

        if "segments" in want:
            # leg 14: kill->resume exactly-once across the whole
            # historical-index pipeline — backfill walker, compactor,
            # and the store-only edge replica that serves the result
            import signal
            import time as _time

            sys.path.insert(0, os.path.join(ROOT, "tools"))
            import chaos_campaign
            import serve_client

            contracts = [
                (f"c{i:03d}",
                 assemble(i, "SELFDESTRUCT") if i % 2 == 0
                 else assemble(1, i, "SSTORE", "STOP"))
                for i in range(N)]
            srv, rpc, head = chaos_campaign._chain_node(contracts)
            dd = os.path.join(d, "segments_data")
            bf_extra = ["--backfill", rpc, "--backfill-window", "1"]
            cursor = os.path.join(dd, "backfill_cursor.json")
            # phase 1: SIGKILL the backfill walker mid-walk; the
            # restart resumes from the durable cursor and ingests only
            # the blocks below it
            pre_lo = None
            pa, url_a = chaos_campaign._start_replica(
                d, "seg_a", dd, extra=bf_extra)
            try:
                deadline = _time.monotonic() + 300
                while _time.monotonic() < deadline:
                    bf = chaos_campaign._backfill_status(url_a)
                    lo = bf.get("lo")
                    if lo is not None and 1 <= lo <= head:
                        pre_lo = lo
                        break
                    _time.sleep(0.1)
            finally:
                pa.send_signal(signal.SIGKILL)
                pa.wait(timeout=60)
            lo_kill = json.load(open(cursor))["lo"]
            b_bf: dict = {}
            pb, url_b = chaos_campaign._start_replica(
                d, "seg_b", dd, extra=bf_extra)
            try:
                deadline = _time.monotonic() + 600
                while _time.monotonic() < deadline:
                    b_bf = chaos_campaign._backfill_status(
                        url_b) or b_bf
                    if b_bf.get("done"):
                        break
                    _time.sleep(0.2)
            finally:
                pb.send_signal(signal.SIGTERM)
                pb.wait(timeout=60)
                srv.shutdown()
                srv.server_close()
            cur = json.load(open(cursor))
            # phase 2: kill the compactor right AFTER the manifest
            # commit (fold durable, loose unlink never ran); the store
            # must verify clean and the re-run must converge instead
            # of double-folding
            store_dir = os.path.join(dd, "store")
            rc_kill, _ = chaos_campaign._store_admin(
                "compact", store_dir, kill="after-manifest")
            rc_verify, rep = chaos_campaign._store_admin(
                "verify", store_dir)
            rc_compact, _ = chaos_campaign._store_admin(
                "compact", store_dir)
            _, stats = chaos_campaign._store_admin("stats", store_dir)
            # phase 3: an engine-free --store-only replica answers the
            # backfilled corpus from segments alone and TYPES the one
            # unknown bytecode
            unknown = assemble(7, 7, "SSTORE", "STOP")
            ps, url_s = chaos_campaign._start_replica(
                d, "seg_s", dd, extra=["--store-only"])
            try:
                snap = serve_client.submit(
                    url_s, contracts + [("mystery", unknown)],
                    tenant="soak")
                health = serve_client.healthz(url_s)
            finally:
                ps.send_signal(signal.SIGTERM)
                ps.wait(timeout=60)
            by_name = {r["name"]: r for r in snap["results"]}
            issues = sorted(i["contract"] for r in snap["results"]
                            for i in (r.get("issues") or []))
            from_store = sorted(
                n for n, r in by_name.items()
                if r.get("served_from") == "dedupe-store")
            legs["segments"] = {
                "pre_kill_lo": pre_lo, "lo_after_kill": lo_kill,
                "resumed": b_bf, "cursor": cur,
                "compactor_kill_rc": rc_kill, "stats": stats,
                "from_store": from_store, "issues": issues,
                "mystery": by_name.get("mystery", {}).get("status"),
                "store_only_health": {
                    k: health.get(k)
                    for k in ("store_only", "store_generation", "ok")}}
            ok &= (pre_lo is not None and 0 <= lo_kill <= head
                   and b_bf.get("done") is True
                   and cur["lo"] == 0 and cur["hi"] == head
                   # exactly-once: only the blocks below the durable
                   # cursor were walked again (one deploy per block)
                   and b_bf.get("ingested") == max(0, lo_kill - 1)
                   and rc_kill == 9 and rc_verify == 0
                   and bool(rep and rep.get("ok"))
                   and rc_compact == 0 and stats is not None
                   and stats.get("loose_keys") == 0
                   and stats.get("segment_keys") == N
                   and snap["state"] == "done"
                   and from_store == [f"c{i:03d}" for i in range(N)]
                   and issues == ["c000", "c002", "c004"]
                   and by_name["mystery"]["status"]
                   == "unknown-contract"
                   and by_name["mystery"].get("retry_after", 0) > 0
                   and health.get("store_only") is True
                   and health.get("store_generation") == 1
                   and health.get("ok") is True)

        if "chaos" in want:
            # leg 11: the reduced chaos matrix (one engine-worker
            # SIGSEGV cell, one torn-ledger cell) — the subprocess
            # isolation boundary and the ledger's torn-result recovery
            # exercised end to end with parity + exactly-once asserted
            # inside the tool itself
            sys.path.insert(0, os.path.join(ROOT, "tools"))
            import chaos_campaign

            out = chaos_campaign.run_matrix(
                [("batch", "segv-mid-superstep"),
                 ("fleet", "torn-ledger")])
            legs["chaos"] = out
            ok &= bool(out.get("ok"))

        if "coldstart" in want:
            # leg 15: the compile-artifact store across a HARD kill
            # (docs/serving.md "Compile artifacts & prewarm"). Daemon A
            # warms the corpus and is SIGKILLed — no drain, no
            # persist-on-exit; only the durable registry + shared XLA
            # cache survive. Daemon B on the same data dir must prewarm
            # from the registry and reach its first verdict with
            # engine_compiles_total FLAT and serve_warm_compile_hits
            # rising: the recovered replica came back warm.
            import re as _re
            import signal
            import time as _time

            sys.path.insert(0, os.path.join(ROOT, "tools"))
            import chaos_campaign
            import serve_client

            contracts = [
                (f"w{i:03d}",
                 assemble(i, "SELFDESTRUCT") if i % 2 == 0
                 else assemble(1, i, "SSTORE", "STOP"))
                for i in range(N)]
            dd = os.path.join(d, "coldstart_data")
            pa, url_a = chaos_campaign._start_replica(d, "cs_a", dd)
            try:
                warmup = serve_client.get_result(
                    url_a, serve_client.submit(url_a, contracts,
                                               tenant="soak")["id"],
                    wait=600.0)
            finally:
                pa.send_signal(signal.SIGKILL)
                rc_a = pa.wait(timeout=120)
            bdir = os.path.join(dd, "compile_store", "buckets")
            buckets_on_disk = (
                len([f for f in os.listdir(bdir)
                     if f.endswith(".json")])
                if os.path.isdir(bdir) else 0)

            pb, url_b = chaos_campaign._start_replica(d, "cs_b", dd)
            prewarm: dict = {}
            try:
                deadline = _time.monotonic() + 300
                while _time.monotonic() < deadline:
                    try:
                        prewarm = (serve_client.healthz(url_b)
                                   .get("prewarm") or prewarm)
                    except OSError:
                        pass
                    if prewarm.get("state") in ("done", "failed",
                                                "disabled"):
                        break
                    _time.sleep(0.25)
                met0 = serve_client.metrics(url_b)
                # fresh bytecodes, same shape class: dedupe can't
                # answer them — only a warm engine can skip compiles
                fresh = [("f000", assemble(100, "SELFDESTRUCT")),
                         ("f001", assemble(1, 100, "SSTORE", "STOP"))]
                first = serve_client.get_result(
                    url_b, serve_client.submit(url_b, fresh,
                                               tenant="soak")["id"],
                    wait=300.0)
                met1 = serve_client.metrics(url_b)
            finally:
                pb.send_signal(signal.SIGTERM)
                pb.wait(timeout=120)

            def _met(text, name):
                m = _re.search(r"^mythril_%s (\d+)" % name, text,
                               _re.MULTILINE)
                return int(m.group(1)) if m else 0

            compiles = [_met(met0, "engine_compiles_total"),
                        _met(met1, "engine_compiles_total")]
            warm_hits = [_met(met0, "serve_warm_compile_hits_total"),
                         _met(met1, "serve_warm_compile_hits_total")]
            issues = sorted(i["contract"] for r in first["results"]
                            for i in (r.get("issues") or []))
            legs["coldstart"] = {
                "warmup_state": warmup["state"], "kill_rc": rc_a,
                "buckets_on_disk": buckets_on_disk,
                "prewarm": prewarm, "engine_compiles": compiles,
                "warm_hits": warm_hits, "issues": issues,
            }
            ok &= (warmup["state"] == "done"
                   and warmup["completed"] == N
                   and rc_a == -signal.SIGKILL
                   and buckets_on_disk >= 1
                   and prewarm.get("state") == "done"
                   and prewarm.get("done", 0) >= 1
                   and first["state"] == "done"
                   and first["completed"] == 2
                   # the restarted daemon's first verdict compiled
                   # NOTHING: prewarm + the shared persistent cache
                   # carried every artifact across the kill
                   and compiles[1] == compiles[0]
                   and warm_hits[1] > warm_hits[0]
                   and issues == ["f000"])

    print(json.dumps({"ok": bool(ok), "legs": legs}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
