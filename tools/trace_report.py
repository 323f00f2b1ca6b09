#!/usr/bin/env python
"""Summarize a telemetry trace (docs/observability.md).

Reads either output of the span tracer — the Chrome-trace JSON
(``--trace t.json``) or the JSONL event log (``t.jsonl``) — and prints:

  1. top spans by total wall time (count / total / mean / max per name),
     then a row a transaction (tx, tx_kind), a row a fate of the seam's
     admission step (tx, fate) and the contracts' paths and lost forks,
  2. a batch stall table (slowest campaign batches with their status),
  3. the degrade timeline (every ladder step, in order),
  4. a checkpoint summary (saves/loads, total and worst latency),
  5. a pipeline overlap summary (device/host phase totals, each host
     phase's split into CPU and device reads with its count of
     ``kernel:`` reads — reads that dispatched a program, marked when
     not 0 — stall time by direction, how much host-phase time passed
     while a ``sym_run`` call was in flight (the overlap of the
     ``host_phase`` spans with the feeder thread's ``superstep`` spans:
     what the ``pipeline_occupancy`` gauge counts), what released the
     host phases' starts (``after``: the next batch's first call, the
     end of its device phase, no next phase), and one row a
     device phase for its lead-in: whether its batch was built ahead,
     the ``batch_build`` stages' split (a bundle built ahead: the
     worker's stages under the phase's own, marked ``^``), the first
     call's ``enqueue_s`` and the host phase that ran beside it —
     docs/performance.md),
  6. a fleet summary (unit leases claimed/committed/reclaimed/lost and
     the reclaim/lost timeline — docs/fleet.md),
  7. solver totals (attempts / sat / unsat / unknown and the unknown
     rate — the silent-false-negative channel, docs/solver.md),
  8. a solver portfolio ladder (per-stage attempts / hits / hit rate /
     time across lru -> refute -> probe -> store -> search, plus the
     Z3-avoided headline, and one row a refute rule: what the stage
     proved under each, from the ``solver_stage`` events —
     docs/solver.md),
  9. a serve admission summary (docs/serving.md "Overload &
     multi-replica serving"): the shed/quota timeline (every
     shed_enter / shed_exit / quota_rejected, in order) and a
     per-tenant table of resolutions, shed answers and deadline
     hits/misses from the per-entry serve_resolved events.

Usage:
    python tools/trace_report.py t.json [--top N]
    python tools/trace_report.py t.jsonl

Stdlib-only (no jax, no engine import): runs anywhere, including on a
laptop against a trace scp'd off a pod host.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple


def load_trace(path: str) -> Tuple[List[Dict], List[Dict]]:
    """``(spans, instants)`` from either trace format.

    Spans normalize to ``{"name", "dur" (sec), "args" {...}}``;
    instants to ``{"kind", "t" (sec, wall or trace-relative), "args"}``.
    """
    with open(path, encoding="utf-8") as fh:
        head = fh.read(1)
        fh.seek(0)
        if head == "{" and not path.endswith(".jsonl"):
            doc = json.load(fh)
            if isinstance(doc, dict) and "traceEvents" in doc:
                return _from_chrome(doc["traceEvents"])
            # a single JSON object that isn't a chrome trace: treat the
            # one object as one event line
            lines: List[Dict] = [doc] if isinstance(doc, dict) else []
        else:
            lines = []
            for i, raw in enumerate(fh):
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    lines.append(json.loads(raw))
                except ValueError as e:
                    raise SystemExit(
                        f"error: {path}:{i + 1}: unparseable JSONL ({e})")
    return _from_jsonl(lines)


def _from_chrome(events: List[Dict]) -> Tuple[List[Dict], List[Dict]]:
    spans, instants = [], []
    for e in events:
        ph = e.get("ph")
        if ph == "X":
            spans.append({"name": e.get("name", "?"),
                          "dur": float(e.get("dur", 0.0)) / 1e6,
                          "mono": float(e.get("ts", 0.0)) / 1e6,
                          "tid": e.get("tid"),
                          "args": e.get("args", {}) or {}})
        elif ph == "i":
            instants.append({"kind": e.get("name", "?"),
                             "t": float(e.get("ts", 0.0)) / 1e6,
                             "mono": float(e.get("ts", 0.0)) / 1e6,
                             "args": e.get("args", {}) or {}})
    return spans, instants


def _from_jsonl(lines: List[Dict]) -> Tuple[List[Dict], List[Dict]]:
    spans, instants = [], []
    meta = {"schema", "kind", "name", "t", "mono", "dur", "tid", "session"}
    for e in lines:
        args = {k: v for k, v in e.items() if k not in meta}
        mono = e.get("mono", 0.0)
        mono = float(mono) if isinstance(mono, (int, float)) else 0.0
        if e.get("kind") == "span":
            spans.append({"name": e.get("name", "?"),
                          "dur": float(e.get("dur", 0.0)),
                          "mono": mono, "tid": e.get("tid"),
                          "args": args})
        else:
            t = e.get("t", 0.0)
            instants.append({"kind": e.get("kind", "?"),
                             "t": float(t) if isinstance(t, (int, float))
                             else 0.0,
                             "mono": mono,
                             "args": args})
    return spans, instants


def _overlap(lo: float, hi: float, intervals) -> float:
    """Seconds of ``[lo, hi]`` that the union of ``intervals`` covers."""
    total, at = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, at), min(b, hi)
        if b > a:
            total += b - a
            at = b
    return total


def _lead_in_rows(dev: List[Dict], host: List[Dict],
                  spans: List[Dict]) -> List[str]:
    """One row a device phase: what became of the look-ahead's build of
    its batch (``prebuilt``: ``inline`` / ``taken`` / ``waited`` /
    ``failed``), its lead-in (start to first ``sym_run`` call), how
    much of it the ``batch_build`` stages on its thread hold and their
    split, the first call's enqueue, and the host phase that ran beside
    the lead-in; under it a line a stage, and for a bundle built ahead
    the worker's stages, marked ``^``."""
    builds = [s for s in spans if s["name"] == "batch_build"]
    calls = sorted((s for s in spans if s["name"] == "superstep"),
                   key=lambda s: s["mono"])
    rows: List[str] = []
    for d in sorted(dev, key=lambda s: s["mono"]):
        lo, hi = d["mono"], d["mono"] + d["dur"]

        def mine(group):
            return [s for s in group if s.get("tid") == d.get("tid")
                    and lo <= s["mono"] < hi]

        first = next(iter(mine(calls)), None)
        if first is None:
            continue
        lead_in = first["mono"] - lo
        stages = sorted(mine(builds), key=lambda s: s["mono"])
        held = sum(s["dur"] for s in stages)
        beside = _overlap(lo, first["mono"], [
            (h["mono"], h["mono"] + h["dur"]) for h in host])
        cpu = sum(s["args"].get("cpu_s", 0.0) for s in stages)
        wait = sum(s["args"].get("device_wait_s", 0.0) for s in stages)
        proc = sum(s["args"].get("proc_cpu_s", 0.0) for s in stages)
        enq = first["args"].get("enqueue_s")
        rows.append(
            f"{d['args'].get('bi', '?')!s:>6}"
            f"{d['args'].get('prebuilt', '-'):>8}{_fmt_s(lead_in):>10}"
            f"{_fmt_s(held):>10}{_fmt_s(cpu):>10}{_fmt_s(wait):>10}"
            f"{_fmt_s(held - cpu - wait):>10}{_fmt_s(proc):>10}"
            f"{(proc / held if held else 0.0):>7.2f}"
            f"{(_fmt_s(enq) if enq is not None else '-'):>10}"
            f"{_fmt_s(beside):>10}")
        # a phase that took a bundle built ahead says where the build
        # ran: the worker's stages, marked, under the phase's own
        ahead = []
        for s in stages:
            a = s["args"]
            if a.get("stage") == "prebuilt" and "built_tid" in a:
                t0 = a["built_mono"] - 1e-5
                t1 = a["built_mono"] + a["built_dur"] + 1e-5
                ahead += sorted(
                    (b for b in builds if b.get("tid") == a["built_tid"]
                     and t0 <= b["mono"] and b["mono"] + b["dur"] <= t1),
                    key=lambda b: b["mono"])
        for s, mark in [(s, "") for s in stages] + [(s, "^") for s in ahead]:
            a = s["args"]
            c, w = a.get("cpu_s", 0.0), a.get("device_wait_s", 0.0)
            rows.append(
                f"{'':>6}{mark + a.get('stage', '?'):>18}"
                f"{_fmt_s(s['dur']):>10}"
                f"{_fmt_s(c):>10}{_fmt_s(w):>10}"
                f"{_fmt_s(s['dur'] - c - w):>10}"
                f"{_fmt_s(a.get('proc_cpu_s', 0.0)):>10}")
    return rows


def _fmt_s(v: float) -> str:
    if v >= 100:
        return f"{v:8.1f}s"
    if v >= 0.1:
        return f"{v:8.3f}s"
    return f"{v * 1e3:7.2f}ms"


def report(spans: List[Dict], instants: List[Dict], top: int = 10) -> str:
    out: List[str] = []

    # 1. top spans by total wall time
    agg: Dict[str, List[float]] = {}
    for s in spans:
        agg.setdefault(s["name"], []).append(s["dur"])
    out.append("== top spans by total wall time ==")
    if agg:
        out.append(f"{'span':<18}{'count':>7}{'total':>10}{'mean':>10}"
                   f"{'max':>10}")
        rows = sorted(agg.items(), key=lambda kv: -sum(kv[1]))[:top]
        for name, durs in rows:
            out.append(f"{name:<18}{len(durs):>7}{_fmt_s(sum(durs)):>10}"
                       f"{_fmt_s(sum(durs) / len(durs)):>10}"
                       f"{_fmt_s(max(durs)):>10}")
            if name == "superstep":
                # spans that end when the device does carry what ran
                ran = [s for s in spans if s["name"] == "superstep"
                       and "steps_run" in s["args"]]
                warm = [s for s in ran if not s["args"].get("cold")]
                n_warm = sum(s["args"]["steps_run"] for s in warm)
                if ran:
                    out.append(
                        f"  steps_run {sum(s['args']['steps_run'] for s in ran)}"
                        f" of {sum(s['args'].get('steps', 0) for s in ran)}"
                        " allowed" + (
                            f", warm {_fmt_s(sum(s['dur'] for s in warm) / n_warm).strip()}"
                            " a superstep" if n_warm else ""))
    else:
        out.append("(no spans)")

    # 1b. the transactions of a batch: which kind each was, what its
    # sym_run calls cost, the paths that ended it and the forks it lost;
    # the calls that left their loop at the pool's fixpoint (``early``,
    # with the supersteps of their budget they did not run: ``unrun``),
    # the calls the fixpoint saved whole (``skipped``), those that still
    # started from a stuck seam and could only hand their frontier back
    # (``spun``: none since the loop itself sees the fixpoint), the
    # supersteps in which some lane ran a copy opcode's handler
    # (``copies``: the ``copy_steps`` of its calls), and what ended it.
    # Then what the seam's admission step made of the end
    # states that passed the pruners, one row a (tx, fate), the
    # contracts' shares of the paths and the lost forks, and what
    # decoding dynamic arguments did to a transaction's paths, one row a
    # transaction (``harvest``: ``mem_floored_paths`` ..)
    by_tx: Dict[tuple, Dict] = {}
    dynamic: Dict[object, Dict[str, int]] = {}
    fates: Dict[tuple, int] = {}
    rounds: Dict[object, int] = {}
    by_contract: Dict[tuple, List[int]] = {}
    before = None       # the superstep span before, if of this transaction
    for s in sorted(spans, key=lambda s: s["mono"]):
        a = s["args"]
        if s["name"] not in ("superstep", "harvest", "tx_seam") \
                or "tx_kind" not in a:
            continue
        row = by_tx.setdefault((a.get("tx"), a["tx_kind"]), {
            "calls": 0, "sec": 0.0, "paths": 0, "dropped": 0,
            "carried": 0, "seam": 0.0, "skipped": 0, "spun": 0,
            "spun_sec": 0.0, "early": 0, "unrun": 0, "copies": 0,
            "ended": {}})
        if s["name"] == "superstep":
            rounds[a.get("tx")] = max(rounds.get(a.get("tx"), 0),
                                      int(a.get("round", 0)))
            row["calls"] += 1
            row["sec"] += s["dur"]
            row["skipped"] += int(a.get("skipped", 0))
            row["copies"] += int(a.get("copy_steps", 0))
            if a.get("ended_in") == "fixpoint":
                row["early"] += 1
                row["unrun"] += int(a.get("steps", 0)) - int(
                    a.get("steps_run", 0))
            if before is not None and before.get("stuck"):
                row["spun"] += 1
                row["spun_sec"] += s["dur"]
            if "ended" in a:
                row["ended"][a["ended"]] = row["ended"].get(
                    a["ended"], 0) + 1
            before = None if "ended" in a else a
        elif s["name"] == "harvest":
            row["paths"] += int(a.get("paths", 0))
            row["dropped"] += int(a.get("dropped", 0))
            if "mem_floored_paths" in a:
                got = dynamic.setdefault(a.get("tx"), {})
                for key in ("paths", "mem_floored_paths", "mem_havoc_paths",
                            "cd_selects", "loop_trapped"):
                    got[key] = got.get(key, 0) + int(a.get(key, 0))
            for what in ("paths", "dropped"):
                got = a.get(what + "_by_contract")
                if not got:
                    continue
                tot = by_contract.setdefault((a.get("tx"), what),
                                             [0] * len(got))
                for i, n in enumerate(got[:len(tot)]):
                    tot[i] += int(n)
        else:
            row["carried"] += int(a.get("carried", 0))
            row["seam"] += s["dur"]
            for fate in ("passed", "admitted", "merged", "deferred",
                         "dropped"):
                if fate in a:
                    fates[a.get("tx"), fate] = fates.get(
                        (a.get("tx"), fate), 0) + int(a[fate])
    if by_tx:
        out.append("")
        out.append("== transactions (tx, tx_kind) ==")
        out.append(f"{'tx':>3} {'kind':<9}{'calls':>6}{'early':>6}"
                   f"{'unrun':>7}{'skipped':>8}"
                   f"{'spun':>5}{'spun_s':>10}{'sym_run':>10}{'copies':>8}"
                   f"{'paths':>8}{'dropped':>9}{'admitted':>10}"
                   f"{'carried':>9}{'seam':>10}  ended")
        for (tx, kind), r in sorted(by_tx.items(), key=lambda kv: str(kv[0])):
            tot = r["paths"] + r["dropped"]
            ended = ", ".join(f"{k} x{n}" for k, n in sorted(
                r["ended"].items(), key=lambda kv: -kv[1]))
            out.append(
                f"{tx!s:>3} {kind:<9}{r['calls']:>6}{r['early']:>6}"
                f"{r['unrun']:>7}{r['skipped']:>8}"
                f"{r['spun']:>5}{_fmt_s(r['spun_sec']):>10}"
                f"{_fmt_s(r['sec']):>10}{r['copies']:>8}"
                f"{r['paths']:>8}{r['dropped']:>9}"
                f"{(100.0 * r['paths'] / tot if tot else 100.0):>9.1f}%"
                f"{r['carried']:>9}{_fmt_s(r['seam']):>10}  {ended}")
    if fates:
        out.append("")
        out.append("== seam admission (tx, fate): the end states that "
                   "passed the pruners ==")
        out.append(f"{'tx':>3} {'fate':<10}{'states':>8}{'share':>8}")
        for (tx, fate), n in sorted(fates.items(), key=lambda kv: (
                str(kv[0][0]), kv[0][1] != "passed", kv[0][1])):
            passed = fates.get((tx, "passed"), 0)
            out.append(f"{tx!s:>3} {fate:<10}{n:>8}"
                       f"{(100.0 * n / passed if passed else 0.0):>7.1f}%"
                       + (f"  (waiting lanes started in "
                          f"{rounds[tx + 1]} later round(s) of tx {tx + 1})"
                          if fate == "deferred" and isinstance(tx, int)
                          and rounds.get(tx + 1) else ""))
    if dynamic:
        out.append("")
        out.append("== dynamic arguments (tx): memory invalidated from a "
                   "floor up, calldata selects, the loop bound ==")
        out.append(f"{'tx':>3}{'paths':>8}{'floored':>9}{'havoc':>7}"
                   f"{'selects':>9}{'loop_trapped':>14}")
        for tx, r in sorted(dynamic.items(), key=lambda kv: str(kv[0])):
            out.append(f"{tx!s:>3}{r['paths']:>8}{r['mem_floored_paths']:>9}"
                       f"{r['mem_havoc_paths']:>7}{r['cd_selects']:>9}"
                       f"{r['loop_trapped']:>14}")
    if by_contract:
        out.append("")
        out.append("== paths and lost forks per contract of a batch "
                   "(tx, what) ==")
        for (tx, what), tot in sorted(by_contract.items(),
                                      key=lambda kv: str(kv[0])):
            out.append(f"{tx!s:>3} {what:<8}" + "".join(
                f"{n:>7}" for n in tot))

    # 2. batch stall table: slowest batches, with their outcome
    status_by_bi: Dict[int, str] = {}
    for e in instants:
        if e["kind"] == "batch_status" and "bi" in e["args"]:
            status_by_bi[int(e["args"]["bi"])] = str(
                e["args"].get("status", "?"))
    batches = [s for s in spans if s["name"] == "batch"]
    out.append("")
    out.append("== batch stall table (slowest first) ==")
    if batches:
        mean = sum(b["dur"] for b in batches) / len(batches)
        out.append(f"{'batch':>6}{'wall':>10}{'x mean':>8}  status")
        for b in sorted(batches, key=lambda b: -b["dur"])[:top]:
            bi = b["args"].get("bi", "?")
            status = status_by_bi.get(
                int(bi) if isinstance(bi, (int, float)) else -1, "")
            ratio = b["dur"] / mean if mean else 0.0
            out.append(f"{bi!s:>6}{_fmt_s(b['dur']):>10}{ratio:>7.1f}x"
                       f"  {status}")
    else:
        out.append("(no batch spans — not a campaign trace?)")

    # 3. degrade timeline
    degr = sorted((e for e in instants
                   if e["kind"] in ("degrade", "degrade_ok")),
                  key=lambda e: e["t"])
    out.append("")
    out.append("== degrade timeline ==")
    if degr:
        t0 = degr[0]["t"]
        for e in degr:
            a = e["args"]
            if e["kind"] == "degrade":
                out.append(
                    f"+{e['t'] - t0:8.2f}s batch {a.get('batch', '?')}: "
                    f"{a.get('step', '?')} -> lanes={a.get('lanes', '?')} "
                    f"width={a.get('width', '?')} "
                    f"({str(a.get('detail', ''))[:60]})")
            else:
                out.append(f"+{e['t'] - t0:8.2f}s batch "
                           f"{a.get('batch', '?')}: recovered at rung "
                           f"{a.get('step', '?')}")
    else:
        out.append("(no degrade events — the run never hit "
                   "RESOURCE_EXHAUSTED)")

    # 4. checkpoint summary
    saves = [s for s in spans if s["name"] == "checkpoint_save"]
    loads = [s for s in spans if s["name"] == "checkpoint_load"]
    out.append("")
    out.append("== checkpoints ==")
    if saves or loads:
        if saves:
            out.append(f"saves: {len(saves)}  total "
                       f"{_fmt_s(sum(s['dur'] for s in saves)).strip()}  "
                       f"worst {_fmt_s(max(s['dur'] for s in saves)).strip()}")
        if loads:
            out.append(f"loads: {len(loads)}  total "
                       f"{_fmt_s(sum(s['dur'] for s in loads)).strip()}  "
                       f"worst {_fmt_s(max(s['dur'] for s in loads)).strip()}")
    else:
        out.append("(no checkpoint spans)")

    # 5. pipeline overlap: how much host-phase (modules + solver) time
    # the pipelined campaign hid behind device execution: the seconds
    # of the host phases that passed while a ``sym_run`` call of the
    # thread that feeds the device was in flight (a host phase starts
    # when the next batch's first call is enqueued; one that started
    # later, or outlived the calls, hides less: the device waits)
    dev = [s for s in spans if s["name"] == "device_phase"]
    host = [s for s in spans if s["name"] == "host_phase"]
    stalls = [s for s in spans if s["name"] == "pipeline_stall"]
    out.append("")
    out.append("== pipeline overlap ==")
    if dev or host or stalls:
        dev_tot = sum(s["dur"] for s in dev)
        host_tot = sum(s["dur"] for s in host)
        by_dir: Dict[str, float] = {}
        for s in stalls:
            k = str(s["args"].get("wait", "?"))
            by_dir[k] = by_dir.get(k, 0.0) + s["dur"]
        dwh = by_dir.get("device-waits-host", 0.0)
        hwd = by_dir.get("host-waits-device", 0.0)
        feeder = {s.get("tid") for s in dev}
        calls = [(s["mono"], s["mono"] + s["dur"]) for s in spans
                 if s["name"] == "superstep" and s.get("tid") in feeder]
        hidden = sum(_overlap(s["mono"], s["mono"] + s["dur"], calls)
                     for s in host)
        out.append(f"device phases: {len(dev):>4}  total "
                   f"{_fmt_s(dev_tot).strip()}")
        out.append(f"host phases:   {len(host):>4}  total "
                   f"{_fmt_s(host_tot).strip()}")
        split = [s for s in host if "cpu_s" in s["args"]]
        if split:
            cpu = sum(s["args"]["cpu_s"] for s in split)
            wait = sum(s["args"]["device_wait_s"] for s in split)
            rest = sum(s["dur"] for s in split) - cpu - wait
            out.append(
                f"  of which cpu {_fmt_s(cpu).strip()}, device reads "
                f"{_fmt_s(wait).strip()} "
                f"({sum(s['args']['device_fetches'] for s in split)} "
                f"fetches), rest (lock, pool) {_fmt_s(rest).strip()}")
            # a read whose ``what`` starts with "kernel:" dispatched a
            # program first, which queues behind the device phase that
            # runs beside the host phase: a host phase should have none
            kernels = [s for s in spans if s["name"] == "device_fetch"
                       and str(s["args"].get("what", "")
                               ).startswith("kernel:")]
            for s in split:
                n = sum(1 for k in kernels
                        if k.get("tid") == s.get("tid")
                        and s["mono"] <= k["mono"] <= s["mono"] + s["dur"])
                a = s["args"]
                out.append(
                    f"  bi {a.get('bi', '?')}: {_fmt_s(s['dur']).strip()}, "
                    f"cpu {_fmt_s(a['cpu_s']).strip()}, device reads "
                    f"{_fmt_s(a['device_wait_s']).strip()} "
                    f"({a['device_fetches']} fetches, {n} kernel: reads)"
                    + ("  <-- dispatches on the device" if n else ""))
        out.append(f"stall device-waits-host: {_fmt_s(dwh).strip()}   "
                   f"host-waits-device: {_fmt_s(hwd).strip()}")
        if host_tot > 0:
            out.append(f"host time hidden behind device execution: "
                       f"{_fmt_s(hidden).strip()} "
                       f"({100.0 * hidden / host_tot:.0f}% of host work)")
            # what released each host phase's start: the next batch's
            # first sym_run call (the one that can hide it), the end of
            # its device phase (it made no call), or no next phase
            started = [s["args"].get("after") for s in host]
            out.append("host phases started after: " + ", ".join(
                f"{k} {started.count(k)}"
                for k in ("first_call", "phase_end", "no_next_phase")))
        drained = sum(1 for s in spans if s["name"] == "batch"
                      and s["args"].get("drained"))
        if drained:
            out.append(f"batches drained to the serial path: {drained}")
        rows = _lead_in_rows(dev, host, spans)
        if rows:
            out.append("lead-in of each device phase (start to first "
                       "sym_run call), then its batch_build stages:")
            out.append("(^: built ahead on the pipeline's worker, beside "
                       "the previous phase's sym_run calls: in nobody's "
                       "lead-in)")
            out.append(f"{'bi':>6}{'built':>8}{'lead-in':>10}"
                       f"{'in stages':>10}"
                       f"{'cpu':>10}{'dev reads':>10}{'rest':>10}"
                       f"{'proc cpu':>10}{'/dur':>7}{'enqueue':>10}"
                       f"{'host by':>10}")
            out.extend(rows)
    else:
        out.append("(no pipeline spans — serial run or --no-pipeline)")

    # 6. fleet: lease lifecycle — how elastic the run actually was
    # (every reclaim is a dead/wedged worker's units migrating; every
    # lost unit is coverage the merge will flag)
    by_kind: Dict[str, List[Dict]] = {}
    for e in instants:
        if e["kind"] in ("lease_claimed", "lease_reclaimed",
                         "unit_committed", "unit_lost", "unit_duplicate"):
            by_kind.setdefault(e["kind"], []).append(e)
    out.append("")
    out.append("== fleet ==")
    if by_kind:
        out.append(
            f"leases claimed: {len(by_kind.get('lease_claimed', [])):>4}  "
            f"committed: {len(by_kind.get('unit_committed', []))}  "
            f"reclaimed: {len(by_kind.get('lease_reclaimed', []))}  "
            f"lost: {len(by_kind.get('unit_lost', []))}  "
            f"duplicate commits: {len(by_kind.get('unit_duplicate', []))}")
        drama = sorted((e for k in ("lease_reclaimed", "unit_lost",
                                    "unit_duplicate")
                        for e in by_kind.get(k, [])),
                       key=lambda e: e["t"])
        if drama:
            t0 = drama[0]["t"]
            for e in drama:
                a = e["args"]
                if e["kind"] == "lease_reclaimed":
                    out.append(
                        f"+{e['t'] - t0:8.2f}s reclaim "
                        f"{a.get('unit', '?')} attempt "
                        f"{a.get('attempt', '?')} (from "
                        f"{a.get('prev_worker', '?')}, lease age "
                        f"{a.get('age', '?')}s)")
                elif e["kind"] == "unit_lost":
                    out.append(
                        f"+{e['t'] - t0:8.2f}s LOST "
                        f"{a.get('unit', '?')} after "
                        f"{a.get('attempts', '?')} lease(s)")
                else:
                    out.append(
                        f"+{e['t'] - t0:8.2f}s duplicate commit of "
                        f"{a.get('unit', '?')} dropped")
    else:
        out.append("(no fleet events — static single/multi-host run?)")

    # 7 + 8. solver totals and the portfolio ladder: the campaign emits
    # one CUMULATIVE `solver_portfolio` event per batch commit, so the
    # LAST one is the run's final state — no summing needed here
    pf = [e for e in instants if e["kind"] == "solver_portfolio"]
    last = pf[-1]["args"] if pf else {}
    out.append("")
    out.append("== solver totals ==")
    attempts = int(last.get("attempts", 0) or 0)
    if attempts:
        unk = int(last.get("unknown", 0) or 0)
        out.append(f"attempts: {attempts}  sat: {last.get('sat', 0)}  "
                   f"unsat: {last.get('unsat', 0)}  unknown: {unk}")
        out.append(f"unknown rate: {100.0 * unk / attempts:.1f}% "
                   "(queries that silently dropped a candidate finding)")
    else:
        out.append("(no solver_portfolio events — pre-portfolio trace "
                   "or no solver queries)")

    out.append("")
    out.append("== solver portfolio ==")
    stages = last.get("stages") or {}
    if stages:
        q = int(last.get("queries", 0) or 0)
        out.append(f"queries: {q}  Z3-avoided: "
                   f"{float(last.get('z3_avoided_pct', 0.0)):.1f}% "
                   "(resolved before the witness search)")
        out.append(f"{'stage':<10}{'attempts':>10}{'hits':>8}"
                   f"{'hit%':>7}{'sat':>7}{'unsat':>7}{'time':>10}")
        for s in ("lru", "refute", "probe", "store", "search"):
            st = stages.get(s) or {}
            a = int(st.get("attempts", 0) or 0)
            h = int(st.get("hits", 0) or 0)
            rate = f"{100.0 * h / a:.0f}%" if a else "-"
            out.append(
                f"{s:<10}{a:>10}{h:>8}{rate:>7}"
                f"{int(st.get('sat', 0) or 0):>7}"
                f"{int(st.get('unsat', 0) or 0):>7}"
                f"{_fmt_s(float(st.get('time_sec', 0.0) or 0.0)):>10}")
        rules: Dict[str, int] = {}
        for e in instants:
            a = e["args"]
            if e["kind"] == "solver_stage" and a.get("stage") == "refute":
                rule = str(a.get("rule", "?"))
                rules[rule] = rules.get(rule, 0) + 1
        for rule in sorted(rules, key=lambda r: (-rules[r], r)):
            out.append(f"  refute by {rule:<12}{rules[rule]:>8}")
        mm = int(last.get("witness_mismatch", 0) or 0)
        if mm:
            out.append(f"witness re-verification misses: {mm} "
                       "(served entries that fell through)")
    else:
        out.append("(no per-stage data — pre-portfolio trace?)")

    # 9. serve admission: the overload story — when the daemon shed or
    # rejected on quota, and how each tenant's SLO actually landed
    drama = sorted((e for e in instants
                    if e["kind"] in ("shed_enter", "shed_exit",
                                     "quota_rejected")),
                   key=lambda e: e["t"])
    resolved = [e for e in instants if e["kind"] == "serve_resolved"]
    out.append("")
    out.append("== serve admission ==")
    if drama or resolved:
        if drama:
            t0 = drama[0]["t"]
            for e in drama:
                a = e["args"]
                if e["kind"] == "shed_enter":
                    out.append(
                        f"+{e['t'] - t0:8.2f}s SHED enter "
                        f"({a.get('reason', '?')}: depth="
                        f"{a.get('depth', '?')} age={a.get('age', '?')})")
                elif e["kind"] == "shed_exit":
                    out.append(
                        f"+{e['t'] - t0:8.2f}s shed exit (depth="
                        f"{a.get('depth', '?')} age={a.get('age', '?')})")
                else:
                    out.append(
                        f"+{e['t'] - t0:8.2f}s quota 429 tenant="
                        f"{a.get('tenant', '?')} "
                        f"({a.get('reason', '?')}"
                        + (f", retry in {a['retry_after']}s"
                           if a.get("retry_after") is not None else "")
                        + ")")
        else:
            out.append("(no shed/quota events — never overloaded)")
        if resolved:
            per: Dict[str, Dict[str, float]] = {}
            for e in resolved:
                a = e["args"]
                row = per.setdefault(str(a.get("tenant", "?")), {
                    "n": 0, "ok": 0, "shed": 0, "evicted": 0,
                    "error": 0, "dl_hit": 0, "dl_miss": 0,
                    "wait": 0.0})
                row["n"] += 1
                status = str(a.get("status", "ok"))
                if status in ("shed", "evicted", "error"):
                    row[status] += 1
                else:
                    row["ok"] += 1
                if a.get("deadline_hit") is True:
                    row["dl_hit"] += 1
                elif a.get("deadline_hit") is False:
                    row["dl_miss"] += 1
                w = a.get("wait")
                if isinstance(w, (int, float)):
                    row["wait"] += float(w)
            out.append(f"{'tenant':<14}{'entries':>8}{'ok':>6}"
                       f"{'shed':>6}{'evict':>6}{'err':>5}"
                       f"{'dl-hit':>8}{'dl-miss':>8}{'mean wait':>11}")
            for tenant in sorted(per):
                r = per[tenant]
                mean = r["wait"] / r["n"] if r["n"] else 0.0
                out.append(
                    f"{tenant:<14}{int(r['n']):>8}{int(r['ok']):>6}"
                    f"{int(r['shed']):>6}{int(r['evicted']):>6}"
                    f"{int(r['error']):>5}{int(r['dl_hit']):>8}"
                    f"{int(r['dl_miss']):>8}{_fmt_s(mean):>11}")
    else:
        out.append("(no serve admission events — not a serve trace?)")

    # 10. cross-process timeline / request critical path
    # (docs/observability.md "Distributed tracing"): every record that
    # carries a trace_id, regrouped per request and rendered in
    # monotonic order — including worker-subprocess spans the
    # supervisor backhauled and clock-corrected, marked [worker].
    by_trace: Dict[str, List[Dict]] = {}
    for s in spans:
        tid = s["args"].get("trace_id")
        if tid:
            by_trace.setdefault(str(tid), []).append(
                {"mono": s.get("mono", 0.0), "what": s["name"],
                 "dur": s["dur"], "args": s["args"], "span": True})
    for e in instants:
        tid = e["args"].get("trace_id")
        if tid:
            by_trace.setdefault(str(tid), []).append(
                {"mono": e.get("mono", 0.0), "what": e["kind"],
                 "dur": None, "args": e["args"], "span": False})
    out.append("")
    out.append("== cross-process timeline / request critical path ==")
    if by_trace:
        # per-stage totals across every traced request: where request
        # wall time went, fleet-wide
        stage_tot: Dict[str, List[float]] = {}
        for recs in by_trace.values():
            for r in recs:
                if r["span"]:
                    stage_tot.setdefault(r["what"], []).append(r["dur"])
        out.append(f"traces: {len(by_trace)}   stage totals:")
        out.append(f"{'stage':<18}{'count':>7}{'total':>10}{'mean':>10}")
        for name, durs in sorted(stage_tot.items(),
                                 key=lambda kv: -sum(kv[1])):
            out.append(f"{name:<18}{len(durs):>7}"
                       f"{_fmt_s(sum(durs)):>10}"
                       f"{_fmt_s(sum(durs) / len(durs)):>10}")
        # the most recent few requests, each as one stitched timeline
        recent = sorted(by_trace.items(),
                        key=lambda kv: max(r["mono"] for r in kv[1]))
        shown = recent[-min(8, max(1, top)):]
        if len(recent) > len(shown):
            out.append(f"(showing the {len(shown)} most recent of "
                       f"{len(recent)} traces)")
        for tid, recs in shown:
            recs.sort(key=lambda r: r["mono"])
            nproc = len({(r["args"].get("proc"),
                          r["args"].get("src_session"))
                         for r in recs})
            wk = sum(1 for r in recs
                     if r["args"].get("proc") == "worker")
            out.append("")
            out.append(f"-- trace {tid} ({len(recs)} records, "
                       f"{nproc} process(es), {wk} worker-side) --")
            t0 = recs[0]["mono"]
            for r in recs:
                proc = ("worker" if r["args"].get("proc") == "worker"
                        else "  -   ")
                d = f"  {_fmt_s(r['dur']).strip()}" if r["span"] else ""
                out.append(f"+{r['mono'] - t0:8.3f}s [{proc}] "
                           f"{r['what']}{d}")
    else:
        out.append("(no trace_id-stamped records — pre-tracing run, or "
                   "no requests traversed this process)")
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome-trace JSON (--trace output) or "
                                  "its JSONL event log")
    ap.add_argument("--top", type=int, default=10,
                    help="rows per table (default 10)")
    args = ap.parse_args(argv)
    try:
        spans, instants = load_trace(args.trace)
    except FileNotFoundError:
        print(f"error: no such trace file: {args.trace}", file=sys.stderr)
        return 2
    print(report(spans, instants, top=args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
