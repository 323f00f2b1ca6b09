#!/usr/bin/env python
"""Standing proof that the analyzer still starts on the chip.

A launcher: this process never imports JAX, so it never holds the
accelerator. Each phase is a child started through the entry points a
user would call, one after the other, at the width the CLI gives
without ``--limits-profile test`` (DEFAULT_LIMITS, 32 contracts x 32
lanes, 256 steps, the full detection suite) over a corpus it generates
(60 contracts of tools/gen_corpus.py + the four realworld images: two
batches of one shape):

- ``device``              what JAX finds, asked in a child that exits
- ``serve``               the daemon with its default worker isolation,
                          driven twice by ``tools/serve_client.py``
                          (analysed, then all from the store), healthz,
                          SIGTERM
- ``campaign``            ``analyze --corpus`` in-process, behind the
                          start-up probe child (``--init-timeout``)
- ``campaign-supervised`` the same with ``--worker-isolation on``

Every engine phase must have run its engine on a TPU, with host
callbacks, the native tape evaluator built in this run, no fallback of
any kind, a second batch that compiled nothing, and the verdicts the
corpus has by construction; the phases must agree on every contract.
Each prints one JSON line; the last line of output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and anything else ends in a non-zero exit code and no such line.

With no arguments it runs ``device`` and ``serve``: every engine
process compiles ``sym_run`` for itself (450 s on the v5e's host; JAX
does not persist an executable that holds host callbacks), so one
engine phase is what fits the 1200 s a smoke may take. ``--all`` runs
all four (about 1700 s). ``--rehearse`` runs the phases at the test
limits, expecting the cpu, for a dry run off the chip; it never prints
the last line and always exits non-zero.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
BUDGET_SEC = 1150.0          # of the 1200 a smoke may take (--all: 3300)
N_GENERATED = 60             # + the four realworld images = 2 x 32
ENGINE_FLAGS = ["--batch-size", "32", "--lanes-per-contract", "32",
                "--max-steps", "256"]
#: backend_events kinds that mean the run did not stay on the chip
BAD_EVENTS = {"cpu_fallback", "tier_fallback", "degrade", "breaker_open",
              "worker_death", "worker_breaker_pinned"}
#: filename suffix -> (SWC id, must it be reported?) — what the
#: generators in tools/gen_corpus.py build in and their safe siblings
#: leave out. (``*_timestamp_gate`` is not here: its timestamp gates a
#: storage write, and SWC-116 is reported for gated calls only.)
VERDICTS = [("_guarded_killable", "106", False), ("_killable", "106", True),
            ("_add_overflow", "101", True), ("_checked_add", "101", False),
            ("_origin_auth", "115", True)]

#: the platform every engine must report (``--rehearse`` expects cpu)
WANT_PLATFORM = "tpu"

_T0 = time.monotonic()
_CHILDREN: list = []


class SmokeFailure(Exception):
    pass


def remaining() -> float:
    return BUDGET_SEC - (time.monotonic() - _T0)


def spawn(argv, **kw):
    """Start a child in its own process group, so that stop_all() takes
    its workers and probe children with it."""
    p = subprocess.Popen(argv, cwd=ROOT, start_new_session=True, **kw)
    _CHILDREN.append(p)
    return p


def stop_all() -> None:
    for p in _CHILDREN:
        for sig in (signal.SIGTERM, signal.SIGKILL):
            try:
                os.killpg(p.pid, sig)
            except (ProcessLookupError, PermissionError):
                break
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                continue


def run(argv, log_path, env=None) -> tuple:
    """Run a child to its end inside what is left of the budget.
    Returns (rc, stdout); stderr goes to ``log_path``."""
    with open(log_path, "w") as err:
        p = spawn(argv, stdout=subprocess.PIPE, stderr=err, text=True,
                  env=env)
        try:
            out, _ = p.communicate(timeout=max(1.0, remaining()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{argv[1:4]} outlasted the {BUDGET_SEC:.0f}s budget "
                f"(log: {log_path})") from None
    return p.returncode, out


def tail(path: str, n: int = 1500) -> str:
    try:
        with open(path) as fh:
            return fh.read()[-n:]
    except OSError:
        return ""


# --- corpus ---------------------------------------------------------------

def make_corpus(d: str) -> list:
    """60 generated contracts + the four realworld runtime images: two
    full batches of one shape. Returns the contract names."""
    rc, out = run([sys.executable, os.path.join(ROOT, "tools",
                                                "gen_corpus.py"),
                   d, str(N_GENERATED)], d + ".gen.log",
                  env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if rc != 0:
        raise SmokeFailure(f"gen_corpus.py rc={rc}: {tail(d + '.gen.log')}")
    real = sorted(glob.glob(os.path.join(
        ROOT, "tests", "fixtures", "realworld", "*.bin-runtime")))
    if len(real) != 4:
        raise SmokeFailure(f"expected 4 realworld images, found {real}")
    for p in real:
        shutil.copy(p, d)
    names = sorted(f.rsplit(".", 1)[0] for f in os.listdir(d))
    if len(names) != N_GENERATED + 4:
        raise SmokeFailure(f"corpus holds {len(names)} files, not 64")
    return names


def rebuild_native() -> None:
    """The tape evaluator is built from tape_eval.c on this machine,
    by the phase's own engine process: remove what the disk carried."""
    for p in glob.glob(os.path.join(ROOT, "mythril_tpu", "native",
                                    "_tape_eval.so*")):
        os.unlink(p)


# --- checks ----------------------------------------------------------------

def check_engine(eng: dict, fails: list) -> None:
    dev = eng.get("device") or {}
    if dev.get("platform") != WANT_PLATFORM:
        fails.append(f"engine ran on {dev!r}, not on a {WANT_PLATFORM}")
    if eng.get("host_callbacks") is not True:
        fails.append(f"host callbacks: {eng.get('host_callbacks')!r}")
    nat = eng.get("native_tape_eval") or {}
    if not nat.get("loaded") or not nat.get("built"):
        fails.append(f"native tape evaluator not built+loaded: {nat!r}")


def check_flat_compiles(batches: list, fails: list) -> None:
    """``engine_compiles`` after each batch's device phase: everything
    compiles in the first, nothing after it."""
    counts = [b.get("engine_compiles") for b in batches]
    if len(counts) < 2 or not counts[0] or len(set(counts)) != 1:
        fails.append(f"engine_compiles per batch not flat after the "
                     f"first: {counts}")


def check_verdicts(swc_by_name: dict, names: list, fails: list) -> None:
    missing = [n for n in names if n not in swc_by_name]
    if missing:
        fails.append(f"no result for {missing[:4]} (+{len(missing)})")
    for name, swcs in sorted(swc_by_name.items()):
        for suffix, swc, want in VERDICTS:
            if name.endswith(suffix):
                if (swc in swcs) != want:
                    fails.append(
                        f"{name}: SWC-{swc} "
                        f"{'missing' if want else 'reported'}: "
                        f"{sorted(swcs)}")
                break


def swc_sets(names: list, issues: list) -> dict:
    out = {n: set() for n in names}
    for i in issues:
        out.setdefault(i["contract"], set()).add(i["swc-id"])
    return out


def emit(phase: str, fails: list, **info) -> dict:
    rec = {"phase": phase, "pass": not fails, **info}
    if fails:
        rec["failures"] = [f[:800] for f in fails[:12]]
    print(json.dumps(rec), flush=True)
    return rec


# --- phases ----------------------------------------------------------------

def phase_device(work: str) -> dict:
    """Ask JAX, in a child that exits again, what it finds: without an
    accelerator the smoke ends here, before any width is compiled."""
    log = os.path.join(work, "device.log")
    rc, out = run([sys.executable, "-c",
                   "import json, jax; d = jax.devices(); print(json.dumps("
                   "{'platform': d[0].platform, 'kind': d[0].device_kind,"
                   " 'count': len(d)}))"], log)
    fails: list = []
    dev = {}
    if rc != 0:
        fails.append(f"exit code {rc}: {tail(log)}")
    else:
        dev = json.loads(out.strip().splitlines()[-1])
        if dev["platform"] != WANT_PLATFORM:
            fails.append(f"JAX finds {dev!r}, not a {WANT_PLATFORM}")
    return emit("device", fails, device=dev)


def phase_campaign(phase: str, isolation: str, corpus: str, names: list,
                   work: str, profile: list) -> dict:
    rebuild_native()
    log = os.path.join(work, phase + ".log")
    t0 = time.monotonic()
    rc, out = run([sys.executable, "-m", "mythril_tpu", "analyze",
                   "--corpus", corpus, *ENGINE_FLAGS, *profile,
                   "-o", "json", "--init-timeout", "120",
                   "--worker-isolation", isolation], log)
    wall = time.monotonic() - t0
    fails: list = []
    if rc != 0:
        fails.append(f"exit code {rc}: {tail(log)}")
        return emit(phase, fails, wall_sec=round(wall, 1))
    doc = json.loads(out)
    eng = doc.get("engine") or {}
    check_engine(eng, fails)
    kinds = [e["kind"] for e in doc["backend_events"]]
    bad = sorted(BAD_EVENTS.intersection(kinds))
    if bad:
        fails.append(f"backend_events hold {bad}")
    if "probe_ok" not in kinds:
        fails.append("the start-up probe child left no probe_ok event")
    if isolation == "on":
        spawns = [e for e in doc["backend_events"]
                  if e["kind"] == "worker_spawn"]
        if len(spawns) != 1 or (spawns[0].get("device") or {}).get(
                "platform") != WANT_PLATFORM:
            fails.append(f"worker_spawn events: {spawns!r}")
    if doc["quarantined"]:
        fails.append(f"quarantined: {doc['quarantined']}")
    if doc["batch_status"] != ["ok", "ok"]:
        fails.append(f"batch_status {doc['batch_status']}")
    check_flat_compiles([e for e in doc["backend_events"]
                         if e["kind"] == "engine_batch"], fails)
    swcs = swc_sets(names, doc["issues_detail"])
    check_verdicts(swcs, names, fails)
    dev = eng.get("device") or {}
    # "batch 1/2: 30.2s, ..." on stderr: the cold and the warm batch
    batch_sec = [float(x) for x in re.findall(
        r"^batch \d+/\d+: ([0-9.]+)s", tail(log, 10 ** 6), re.M)]
    rec = emit(phase, fails, device=dev,
               wall_sec=round(wall, 1), batch_sec=batch_sec,
               xla_compiles=eng.get("xla_compiles"),
               xla_compile_sec=eng.get("xla_compile_sec"),
               cache_hits=eng.get("cache_hits"),
               peak_bytes_in_use=eng.get("peak_bytes_in_use"),
               contracts_per_sec_steady=doc.get("contracts_per_sec_steady"),
               issues=doc["issues"])
    rec["swcs"] = swcs
    return rec


def http_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


def phase_serve(corpus: str, names: list, work: str, profile: list) -> dict:
    phase = "serve"
    rebuild_native()
    log = os.path.join(work, "serve.log")
    port_file = os.path.join(work, "serve.port")
    fails: list = []
    t0 = time.monotonic()
    with open(log, "w") as err:
        daemon = spawn([sys.executable, "-m", "mythril_tpu", "serve",
                        "--port", "0", "--port-file", port_file,
                        "--data-dir", os.path.join(work, "serve_data"),
                        *ENGINE_FLAGS, *profile],
                       stdout=err, stderr=subprocess.STDOUT)
    while not os.path.exists(port_file):
        if daemon.poll() is not None or remaining() <= 0:
            raise SmokeFailure(f"serve never bound a port: {tail(log)}")
        time.sleep(0.2)
    with open(port_file) as fh:
        url = f"http://127.0.0.1:{int(fh.read().strip())}"
    passes = []
    for n in (1, 2):
        t1 = time.monotonic()
        rc, out = run([sys.executable,
                       os.path.join(ROOT, "tools", "serve_client.py"),
                       "--url", url, "--corpus", corpus, "--stream",
                       "--wait", str(int(max(1.0, remaining())))],
                      os.path.join(work, f"client{n}.log"))
        if rc != 0:
            fails.append(f"client pass {n} exit code {rc}: "
                         f"{tail(os.path.join(work, f'client{n}.log'))}")
            break
        doc = json.loads(out)
        doc["wall_sec"] = round(time.monotonic() - t1, 1)
        passes.append(doc)
    swcs: dict = {}
    health: dict = {}
    if len(passes) == 2:
        p1, p2 = passes
        not_ok = [(r.get("name"), r.get("status")) for p in passes
                  for r in p["results"] if r.get("status") != "ok"]
        if not_ok:
            fails.append(f"results not ok: {not_ok[:4]}")
        if p1["served_from"] != {"analysis": len(names)}:
            fails.append(f"pass 1 served_from {p1['served_from']}")
        if p2["dedupe_served"] != len(names):
            fails.append(f"pass 2 served {p2['dedupe_served']} of "
                         f"{len(names)} from the store")
        swcs = swc_sets(names, [i for r in p1["results"]
                                for i in r.get("issues") or []])
        again = swc_sets(names, [i for r in p2["results"]
                                 for i in r.get("issues") or []])
        if swcs != again:
            fails.append("the store answered pass 2 differently")
        check_verdicts(swcs, names, fails)
        health = http_json(url + "/healthz")
        engines = health.get("engines") or [{}]
        eng = engines[0]
        check_engine(eng, fails)
        bad = sorted(BAD_EVENTS.intersection(eng.get("event_kinds") or {}))
        if bad:
            fails.append(f"serve campaign events hold {bad}")
        if len(engines) != 1 or health.get("state") != "serving" \
                or health.get("degraded_configs") \
                or health.get("engine_worker_restarts"):
            fails.append(f"healthz: {json.dumps(health)[:600]}")
        check_flat_compiles(eng.get("recent_batches") or [], fails)
    daemon.send_signal(signal.SIGTERM)
    try:
        rc = daemon.wait(timeout=max(5.0, min(120.0, remaining())))
    except subprocess.TimeoutExpired:
        rc = "no exit after SIGTERM"
    if rc != 0:
        fails.append(f"serve exit code after SIGTERM: {rc}: {tail(log)}")
    eng = (health.get("engines") or [{}])[0]
    rec = emit(phase, fails, device=eng.get("device"),
               wall_sec=round(time.monotonic() - t0, 1),
               pass_wall_sec=[p["wall_sec"] for p in passes],
               pass1_latency=passes[0]["latency"] if passes else None,
               pass2_latency=passes[1]["latency"] if len(passes) > 1
               else None,
               xla_compiles=eng.get("xla_compiles"),
               xla_compile_sec=eng.get("xla_compile_sec"),
               cache_hits=eng.get("cache_hits"),
               peak_bytes_in_use=eng.get("peak_bytes_in_use"))
    rec["swcs"] = swcs
    return rec


# --- main -------------------------------------------------------------------

def one_chip(corpus: str, names: list, work: str, profile: list,
             everything: bool) -> list:
    phases = [lambda: phase_device(work),
              lambda: phase_serve(corpus, names, work, profile)]
    if everything:
        phases += [
            lambda: phase_campaign("campaign", "off", corpus, names,
                                   work, profile),
            lambda: phase_campaign("campaign-supervised", "on", corpus,
                                   names, work, profile)]
    recs: list = []
    for phase in phases:
        recs.append(phase())
        if not recs[-1]["pass"]:
            return recs      # a failed phase fails the smoke: stop here
    ref = recs[1]["swcs"]
    for r in recs[2:]:
        diff = sorted(n for n in names if r["swcs"].get(n) != ref.get(n))
        if diff:
            r["pass"] = False
            print(json.dumps({"phase": r["phase"], "pass": False,
                              "failures": [
                                  f"issue sets differ from the campaign "
                                  f"phase on {diff[:6]} (+{len(diff)})"]}),
                  flush=True)
    return recs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--all", action="store_true",
                    help="also run the campaign and campaign-supervised "
                         "phases (about 1700 s on a v5e)")
    ap.add_argument("--rehearse", action="store_true",
                    help="test limits on the cpu, for a dry run off "
                         "the chip: never prints the last line, never "
                         "exits 0")
    ap.add_argument("--work", metavar="DIR",
                    help="keep corpus, logs and serve data in DIR "
                         "(default: a temporary directory, removed)")
    args = ap.parse_args()
    global WANT_PLATFORM, BUDGET_SEC
    if args.all:
        BUDGET_SEC = 3300.0
    profile = []
    if args.rehearse:
        WANT_PLATFORM = "cpu"
        profile = ["--limits-profile", "test"]
    if args.work:
        os.makedirs(args.work, exist_ok=True)
    work = tempfile.mkdtemp(prefix="chip_smoke_", dir=args.work)
    try:
        corpus = os.path.join(work, "corpus")
        os.makedirs(corpus)
        names = make_corpus(corpus)
        recs = one_chip(corpus, names, work, profile, args.all)
    except SmokeFailure as e:
        print(json.dumps({"pass": False, "failures": [str(e)]}),
              flush=True)
        return 1
    finally:
        stop_all()
        if not args.work:
            shutil.rmtree(work, ignore_errors=True)
    # every engine must have got the very device the first child saw
    dev = recs[0]["device"]
    same = all({k: (r.get("device") or {}).get(k) for k in dev} == dev
               for r in recs)
    if not all(r["pass"] for r in recs) or not same:
        return 1
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "pass": True,
                          "note": "not a chip check"}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
