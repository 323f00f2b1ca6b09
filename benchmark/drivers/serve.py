"""Driver ``serve``: the ``serve`` daemon with its default worker
isolation and a fresh ``--data-dir``, driven over HTTP by the open-loop
generator (``benchmark/openloop.py``) from this process, which never
imports JAX: the chip belongs to the daemon's engine worker.

Set-up is a first submission of fresh contracts (the first batch
compiles or loads every program, the next is warm) and ends with its
last verdict. The window sends one contract a request at the times of
the schedule; ``repeat_share`` of the requests repeat an earlier
bytecode, the rest are new from the corpus. When the schedule ends the
outstanding requests are awaited, then every distinct bytecode that got
a verdict is submitted once more: the store has to answer it, and
equal to the first answer.

A traced run also asks the engine worker, through
``benchmark/site/sitecustomize.py``, for a ``jax.profiler`` trace of
the middle of the window.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

def _http(url: str, doc=None, timeout: float = 60.0) -> dict:
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


def submit(base: str, contracts, tenant: str = "bench") -> dict:
    return _http(base + "/v1/submit", {
        "contracts": [{"name": c["name"], "code": c["code"].hex()}
                      for c in contracts], "tenant": tenant})


def await_result(base: str, sid: str, until: float) -> dict:
    """Long-poll the submission until it is done or ``until``
    (monotonic) passes; returns the last snapshot."""
    snap = {"state": "pending", "results": []}
    while snap["state"] != "done" and time.monotonic() < until:
        wait = max(1.0, min(60.0, until - time.monotonic()))
        snap = _http(f"{base}/v1/result/{sid}?wait={wait:g}",
                     timeout=wait + 30.0)
    return snap


def ask_device(root: str) -> dict:
    """What JAX finds, asked in a child that exits again (this process
    must not hold the chip)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, jax; d = jax.devices(); print(json.dumps("
         "{'platform': d[0].platform, 'kind': d[0].device_kind,"
         " 'count': len(d)}))"],
        cwd=root, capture_output=True, text=True, timeout=300)
    if out.returncode != 0:
        print(out.stderr[-2000:], file=sys.stderr)
        raise SystemExit(3)
    return json.loads(out.stdout.strip().splitlines()[-1])


class Daemon:
    """``python -m mythril_tpu serve`` in its own process group."""

    def __init__(self, ctx, profile_dir=None):
        cfg = ctx.config
        self.log_path = os.path.join(ctx.work, "serve.log")
        data = os.path.join(ctx.work, "serve_data")
        port_file = os.path.join(ctx.work, "serve.port")
        shutil.rmtree(data, ignore_errors=True)
        if os.path.exists(port_file):
            os.unlink(port_file)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(ctx.here, "site"), ctx.root]
            + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        if profile_dir:
            env["BENCH_PROFILE_DIR"] = profile_dir
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "mythril_tpu", "serve", "--port", "0",
             "--port-file", port_file, "--data-dir", data,
             *cfg["serve_args"]],
            cwd=ctx.root, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)
        deadline = time.monotonic() + 300
        while not os.path.exists(port_file):
            if self.proc.poll() is not None or time.monotonic() > deadline:
                self.stop()
                raise RuntimeError("serve never bound a port: "
                                   + self.tail())
            time.sleep(0.1)
        with open(port_file) as fh:
            self.url = f"http://127.0.0.1:{int(fh.read().strip())}"

    def tail(self, n: int = 2000) -> str:
        try:
            with open(self.log_path) as fh:
                return fh.read()[-n:]
        except OSError:
            return ""

    def health(self) -> dict:
        for _ in range(5):
            try:
                return _http(self.url + "/healthz", timeout=60.0)
            except (urllib.error.URLError, TimeoutError, OSError):
                time.sleep(1.0)
        return {}

    def stop(self):
        """SIGTERM (graceful drain), then the whole group; waits until
        the daemon has ended. Returns its exit code."""
        rc = None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                rc = "no exit after SIGTERM"
        else:
            rc = self.proc.returncode
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self._log.close()
        return rc


def swcs(result: dict) -> set:
    return {str(i["swc-id"]) for i in result.get("issues") or []}


def issue_key(result: dict) -> list:
    return sorted((str(i.get("swc-id")), i.get("address"), i.get("title"))
                  for i in result.get("issues") or [])


class Stream:
    """Fresh contracts of the corpus, in order."""

    def __init__(self, ctx, max_code: int):
        self.ctx, self.max_code = ctx, max_code
        self.bi, self.buf = 0, []

    def take(self, n: int) -> list:
        while len(self.buf) < n:
            self.buf += self.ctx.corpus.batch(self.ctx.seed, self.bi,
                                              max_code=self.max_code)
            self.bi += 1
        out, self.buf = self.buf[:n], self.buf[n:]
        return out


def serve_args(cfg: dict) -> dict:
    a = cfg["serve_args"]
    return {a[i]: a[i + 1] for i in range(len(a) - 1)
            if a[i].startswith("-")}


def warm_up(ctx, daemon, stream, batch: int) -> None:
    warm = stream.take(int(ctx.traffic["warmup_batches"]) * batch)
    snap = submit(daemon.url, warm)
    snap = await_result(daemon.url, snap["id"], time.monotonic() + float(
        ctx.traffic["setup_timeout_s"]))
    if snap["state"] != "done":
        raise RuntimeError("warm-up never finished: " + daemon.tail())


def window(daemon, stream, traffic: dict, seconds: float):
    """One open-loop window; returns (records, fresh contracts, start,
    end)."""
    import openloop

    plan = openloop.schedule(traffic, seconds, ctx.seed)
    fresh = stream.take(sum(1 for p in plan if p["fresh"] is not None))
    give_up = float(traffic["give_up_after_s"])

    def send(rec):
        k = rec["fresh"] if rec["fresh"] is not None else rec["repeat_of"]
        s = submit(daemon.url, [fresh[k]])
        if s["state"] != "done":
            s = await_result(daemon.url, s["id"],
                             time.monotonic() + give_up + 60)
        if s["state"] != "done" or not s["results"]:
            return {"status": "unanswered"}
        return s["results"][0]

    t_start = time.monotonic()
    recs = openloop.drive(plan, send, give_up)
    return recs, fresh, t_start, time.monotonic()


def run(ctx) -> dict:
    cfg, traffic, log = ctx.config, ctx.traffic, ctx.log
    sys.path.insert(0, ctx.here)
    import openloop
    import verdicts

    if ctx.require_tpu:
        device = ask_device(ctx.root)
        if device["platform"] != "tpu" or device["count"] < ctx.chips:
            print(f"benchmark: needs {ctx.chips} TPU chip(s), JAX finds "
                  f"{device}: not measuring", file=sys.stderr)
            raise SystemExit(3)
    args = serve_args(cfg)
    batch = int(args.get("--batch-size", 8))
    max_code = 512 if args.get("--limits-profile") == "test" else 24576
    unit = ctx.corpus.BATCH

    stream = Stream(ctx, max_code)
    prof_dir = (os.path.join(ctx.work, "profile") if ctx.trace else None)
    if prof_dir:
        shutil.rmtree(prof_dir, ignore_errors=True)
        for suffix in (".start", ".stop", ".done"):
            if os.path.exists(prof_dir + suffix):
                os.unlink(prof_dir + suffix)
    daemon = Daemon(ctx, prof_dir)
    try:
        # --- set-up ------------------------------------------------------------
        warm_up(ctx, daemon, stream, batch)
        setup_s = time.monotonic() - ctx.t0
        h0 = daemon.health()
        eng0 = (h0.get("engines") or [{}])[0]
        device = eng0.get("device") or {}
        log(f"device: {device}")
        log(f"set-up: {setup_s:.1f}s, xla_compile_sec "
            f"{eng0.get('xla_compile_sec')} in {eng0.get('xla_compiles')} "
            f"requests, {eng0.get('cache_hits')} from the cache")

        # --- the window --------------------------------------------------------
        if prof_dir:
            import threading

            def mark(suffix, after):
                time.sleep(after)
                open(prof_dir + suffix, "w").close()

            lo = float(traffic["trace_from_s"])
            for suffix, after in ((".start", lo),
                                  (".stop", lo + float(
                                      traffic["trace_for_s"]))):
                threading.Thread(target=mark, args=(suffix, after),
                                 daemon=True).start()
        recs, fresh, t_start, t_end = window(daemon, stream, traffic,
                                             ctx.seconds)
        n_fresh = len(fresh)
        h1 = daemon.health()
        eng1 = (h1.get("engines") or [{}])[0]

        # --- read back -----------------------------------------------------------
        first: dict = {}
        for r in recs:
            k = r["fresh"] if r["fresh"] is not None else r["repeat_of"]
            ans = r["answer"] or {}
            if ans.get("status") == "ok" and k not in first:
                first[k] = ans
        again = {}
        keys = sorted(first)
        for i in range(0, len(keys), 64):
            part = keys[i:i + 64]
            s = submit(daemon.url, [fresh[k] for k in part])
            s = await_result(daemon.url, s["id"], time.monotonic() + 120)
            by_name = {r["name"]: r for r in s.get("results") or []}
            for k in part:
                again[k] = by_name.get(fresh[k]["name"]) or {}
        if prof_dir:
            open(prof_dir + ".stop", "w").close()
            until = time.monotonic() + 120
            while (os.path.exists(prof_dir + ".start")
                   and not os.path.exists(prof_dir + ".done")
                   and time.monotonic() < until):
                time.sleep(0.2)
    finally:
        rc = daemon.stop()

    # --- metrics -----------------------------------------------------------------
    lat = [r["latency"] for r in recs]
    late = [r["lateness"] for r in recs]
    p50 = openloop.percentile(lat, 0.50)
    p95 = openloop.percentile(lat, 0.95)
    log(f"window: {len(recs)} requests ({n_fresh} fresh) in "
        f"{t_end - t_start:.2f}s; generator lateness p50 "
        f"{openloop.percentile(late, 0.5) * 1e3:.2f}ms max "
        f"{max(late) * 1e3:.2f}ms")
    served: dict = {}
    for r in recs:
        key = (r["answer"] or {}).get("served_from") or (
            "analysis" if (r["answer"] or {}).get("status") == "ok"
            else str((r["answer"] or {}).get("status")))
        served[key] = served.get(key, 0) + 1
    log(f"served_from: {served}")

    # --- correct -------------------------------------------------------------------
    not_ok = [r for r in recs if (r["answer"] or {}).get("status") != "ok"]
    reported = {fresh[k]["name"]: swcs(a) for k, a in first.items()}
    rows = verdicts.compare([fresh[k] for k in keys], reported,
                            cfg.get("swc_in_scope"))
    bad = [r for r in rows if verdicts.wrong(r)]
    for r in bad[:12]:
        log(f"wrong verdict: {r['name']} missing={r['missing']} "
            f"extra={r['extra']} reported="
            f"{sorted(reported.get(r['name'], ()))}")
    # a repeat has to be answered like the first of its bytecode
    differ = sum(1 for r in recs
                 if (r["answer"] or {}).get("status") == "ok"
                 and issue_key(r["answer"]) != issue_key(
                     first[r["fresh"] if r["fresh"] is not None
                           else r["repeat_of"]]))
    stale = [k for k in keys
             if issue_key(again[k]) != issue_key(first[k])
             or not str(again[k].get("served_from") or "")
             .startswith("dedupe")]
    dev1 = eng1.get("device") or {}
    nat = eng1.get("native_tape_eval") or {}
    kinds = sorted(set(eng1.get("event_kinds") or {})
                   & verdicts.BAD_EVENTS)
    # the engine's program may not compile in the window; the solver's
    # small kernels can (see drivers/campaign.py): shown, not held
    compiles = (eng1.get("engine_compiles") or 0) - (
        eng0.get("engine_compiles") or 0)
    log(f"compile requests of any program inside the window: "
        f"{(eng1.get('xla_compiles') or 0) - (eng0.get('xla_compiles') or 0)}"
        f" in {(eng1.get('xla_compile_sec') or 0) - (eng0.get('xla_compile_sec') or 0):.3f}s")
    checks_rows = [
        ("requests not answered ok", len(not_ok), 0),
        ("repeats answered unlike the first", differ, 0),
        ("verdicts not read back equal from the store", len(stale), 0),
        ("engine platform", dev1.get("platform"),
         "tpu" if ctx.require_tpu else dev1.get("platform")),
        ("host_callbacks", eng1.get("host_callbacks"),
         bool(cfg["guarantees"]["host_callbacks"])),
        ("native tape evaluator loaded", bool(nat.get("loaded")), True),
        ("fallback/degrade events", kinds, []),
        ("engine worker restarts", h1.get("engine_worker_restarts") or 0,
         0),
        ("degraded configs", len(h1.get("degraded_configs") or []), 0),
        ("engine programs compiled inside the window", compiles, 0),
        ("daemon exit code after SIGTERM", rc, 0),
    ]
    checks = verdicts.summary_lines(rows) + [
        f"check {name}: {got!r} (limit {want!r})"
        for name, got, want in checks_rows]
    wrong_names = {r["name"] for r in bad}
    failed = sum(1 for r in recs
                 if (r["answer"] or {}).get("status") != "ok"
                 or fresh[r["fresh"] if r["fresh"] is not None
                          else r["repeat_of"]]["name"] in wrong_names)
    correct = (not bad and all(g == w for _, g, w in checks_rows))

    requests = [dict(r["answer"] or {}, due=r["due"],
                     latency=r["latency"]) for r in recs]
    obs = {"kind": "serve", "requests": requests,
           "window": (t_start, t_end), "window_s": t_end - t_start,
           "engine_setup": eng0, "profile": None}
    out = {"correct": correct, "attempted": len(recs), "failed": failed,
           "checks": checks, "obs": obs,
           "e2e": {"verdict_p50_s": p50, "verdict_p95_s": p95,
                   "setup_s": setup_s},
           "device": {"platform": dev1.get("platform"),
                      "kind": dev1.get("kind"),
                      "count": dev1.get("count"),
                      "memory_peak_bytes": int(
                          eng1.get("peak_bytes_in_use") or 0)}}
    if prof_dir and os.path.exists(prof_dir + ".done"):
        import trace_reduce

        path = trace_reduce.find_xplane(prof_dir)
        if path:
            log(f"profile: {path} ({os.path.getsize(path)} bytes)")
            red = trace_reduce.reduce(trace_reduce.load(path))
            obs["profile"] = red
            out["device"]["busy_s"] = red["busy_s"]
            out["device"]["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
            log(f"profile modules: {red['modules']}")
    return out


def sweep(ctx, rates, seconds: float) -> None:
    """One daemon, one compile, one window at each rate: how the knee in
    the traffic file was found. Prints a row per rate; a rate is
    sustained while the completions keep up with the arrivals and the
    tail does not grow from the window's first half to its second."""
    sys.path.insert(0, ctx.here)
    import openloop

    args = serve_args(ctx.config)
    stream = Stream(ctx, 512 if args.get("--limits-profile") == "test"
                    else 24576)
    daemon = Daemon(ctx)
    try:
        warm_up(ctx, daemon, stream, int(args.get("--batch-size", 8)))
        ctx.log(f"set-up: {time.monotonic() - ctx.t0:.1f}s")
        for rate in rates:
            traffic = dict(ctx.traffic, rate_per_s=rate)
            recs, fresh, t0, t1 = window(daemon, stream, traffic,
                                         seconds)
            lat = [r["latency"] for r in recs]
            half = [r["latency"] for r in recs if r["due"] > seconds / 2]
            ok = sum((r["answer"] or {}).get("status") == "ok"
                     for r in recs)
            ctx.log(json.dumps({
                "rate_per_s": rate, "requests": len(recs),
                "fresh": len(fresh), "ok": ok,
                "drained_s": round(t1 - t0 - seconds, 2),
                "p50_s": round(openloop.percentile(lat, 0.5), 3),
                "p95_s": round(openloop.percentile(lat, 0.95), 3),
                "p95_second_half_s": round(
                    openloop.percentile(half, 0.95), 3),
                "max_s": round(max(lat), 3)}))
    finally:
        daemon.stop()


if __name__ == "__main__":
    # python3 benchmark/drivers/serve.py <workload> <seed> <seconds> <rate>...
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import run as bench

    _loaded = bench.load_cell(bench.ROOT, sys.argv[1])
    _work = os.path.join(bench.WORK, "sweep")
    os.makedirs(_work, exist_ok=True)
    from types import SimpleNamespace

    _ctx = SimpleNamespace(
        root=bench.ROOT, here=bench.HERE, work=_work, t0=bench.T0,
        config=_loaded.config, traffic=_loaded.traffic,
        corpus=bench.load_module(os.path.join(
            bench.HERE, "corpora", _loaded.config["corpus"] + ".py"),
            "corpus_sweep"),
        seed=int(sys.argv[2]), chips=1, require_tpu=True,
        log=lambda line: print(line, flush=True))
    sweep(_ctx, [float(r) for r in sys.argv[4:]], float(sys.argv[3]))
