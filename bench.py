#!/usr/bin/env python
"""Driver benchmark: concrete + symbolic engine throughput on the real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

Headline metric (round-over-round comparable): vectorized CONCRETE
interpreter opcode-steps/sec on the ERC-20-like transfer workload, vs the
same workload on the in-repo pure-Python reference EVM on one CPU core —
the honest stand-in for the reference's per-state Python interpreter loop
(SURVEY.md §6: the reference publishes no numbers).

``extra`` carries the BASELINE.md product metrics:
  - sym_lane_steps_per_sec: the SYMBOLIC engine (sym_run: overlay + tape
    + forking + propagation sweeps) on the same contract with symbolic
    calldata — the metric the analysis pipeline actually rides on;
  - analyze_contracts_per_sec: SymExecWrapper + fire_lasers end-to-end
    on a batch of contracts (BASELINE config-2 shape, single chip);
  - paths_per_sec: live paths explored per second in that run;
  - solver: host witness-search statistics (attempts/sat/unknown/time).

Modes (each keeps the one-record-per-line contract):
  - ``BENCH_SWEEP=1``: per-P lane-scaling records for the symbolic
    engine (``BENCH_SWEEP_P`` overrides the P list);
  - ``BENCH_E2E=1``: full CorpusCampaign over a synthetic corpus
    (tools/gen_corpus MIX, ``BENCH_E2E_N`` contracts) — headline
    ``analyze_contracts_per_min`` + device/host/other stage wall
    breakdown. Standalone it rides in ``extra``; combined with
    ``BENCH_SWEEP`` it adds per-P e2e records (deepest-P legs are
    skipped first under budget pressure, as recorded skips);
  - ``BENCH_SCALING=1``: compiled-cost attribution (tools/
    scaling_report.py) — fitted per-phase growth exponents from jaxpr
    traces, no execution, hardware-independent;
  - ``BENCH_COLDSTART=1``: serve-daemon time-to-first-verdict, cold
    (fresh data dir, empty compile store) vs restarted on the same
    data dir with the registry prewarm replayed (docs/serving.md
    "Compile artifacts & prewarm").
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))

# NO jax-touching imports at module level: importing mythril_tpu.core
# builds jnp tables, which INITIALIZES the backend — on a wedged TPU
# runtime that hangs before the probe can run (this is exactly how the
# round-3 driver bench died). Everything heavy loads in _lazy_imports()
# AFTER _probe_backend() has proven the backend comes up.
#
# obs.trace is the one exception: stdlib-only (no jnp tables — the same
# backend-free guarantee resilience.py gives the pre-probe phase, which
# already imports the mythril_tpu package). All phase timing below rides
# its timer spans instead of ad-hoc perf_counter/monotonic pairs; set
# BENCH_TRACE=FILE to get a Perfetto-loadable trace of a bench run.
from mythril_tpu.obs import trace as obs_trace

if os.environ.get("BENCH_TRACE"):
    obs_trace.configure(os.environ["BENCH_TRACE"])


def _lazy_imports():
    global mythril_tpu, jax, jnp, np, DEFAULT_LIMITS, run
    global abi_call, erc20_like, CALLER, TRANSFER_SELECTOR
    global erc20_transfer_workload, RefEVM, RefEnv
    import mythril_tpu  # noqa: F401  (enables x64)
    import jax
    # persistent compiled-executable cache: the P=4096 engine compiles
    # for minutes cold. Same helper as every engine-owning process;
    # delete the dir if it corrupts.
    if os.environ.get("MYTHRIL_NO_JAX_CACHE") != "1":
        from mythril_tpu import compile_cache
        compile_cache.enable()
    import jax.numpy as jnp
    import numpy as np
    from mythril_tpu.config import DEFAULT_LIMITS
    from mythril_tpu.core import run
    from mythril_tpu.disassembler.asm import abi_call, erc20_like
    from mythril_tpu.workloads import (
        BENCH_CALLER as CALLER,
        TRANSFER_SELECTOR,
        erc20_transfer_workload,
    )
    from pyevm_ref import RefEVM, RefEnv

P = 4096  # lanes (concrete bench)
MAX_STEPS = 256
SYM_P = 4096        # lanes (symbolic bench)
SYM_MAX_STEPS = 256
ANALYZE_CONTRACTS = 32
ANALYZE_LANES_PER = 32


def count_ref_steps(code: bytes) -> int:
    """Steps the reference interpreter takes for one transfer() call."""
    vm = RefEVM(code, calldata=abi_call(TRANSFER_SELECTOR, 0x1000, 0), env=RefEnv(caller=CALLER))
    res = vm.run(max_steps=MAX_STEPS)
    assert res.halted and not res.error and not res.reverted, "bench contract must succeed"
    return res.steps


def bench_cpu_baseline(code: bytes, min_seconds: float = 1.0) -> float:
    """Pure-Python interpreter lane-steps/sec (one core)."""
    with obs_trace.timer("bench.cpu_baseline") as sp:
        n, steps = 0, 0
        while sp.elapsed < min_seconds:
            vm = RefEVM(code, calldata=abi_call(TRANSFER_SELECTOR, 0x1000 + n, 0), env=RefEnv(caller=CALLER))
            steps += vm.run(max_steps=MAX_STEPS).steps
            n += 1
        return steps / sp.elapsed


def bench_concrete():
    code, f, env, corpus = erc20_transfer_workload(P, DEFAULT_LIMITS)
    ref_steps = count_ref_steps(code)

    runner = lambda fr: run(fr, env, corpus, max_steps=MAX_STEPS)  # jitted
    out = runner(f)  # compile + warm up
    jax.block_until_ready(out.pc)
    if not bool(jnp.all(out.halted & ~out.error & ~out.reverted)):
        return None, None, "concrete lanes failed"

    reps = 5
    with obs_trace.timer("bench.concrete", reps=reps, P=P) as sp:
        for _ in range(reps):
            out = runner(f)
        jax.block_until_ready(out.pc)
    dt = sp.elapsed / reps

    device_steps_per_sec = P * ref_steps / dt
    cpu_steps_per_sec = bench_cpu_baseline(code)
    return device_steps_per_sec, device_steps_per_sec / cpu_steps_per_sec, None


def bench_symbolic() -> dict:
    """sym_run throughput: SYM_P seed lanes, symbolic calldata, forking on."""
    from mythril_tpu.core import Corpus, make_env
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

    L = DEFAULT_LIMITS
    code = erc20_like()
    img = ContractImage.from_bytecode(code, L.max_code)
    corpus = Corpus.from_images([img])
    # half the lanes seeded, half head-room for forks (the analysis-shaped
    # layout); every seed explores the full dispatcher symbolically
    active = np.zeros(SYM_P, dtype=bool)
    active[::2] = True
    sf = make_sym_frontier(SYM_P, L, active=active)
    env = make_env(SYM_P)
    spec = SymSpec()

    runner = lambda s: sym_run(s, env, corpus, spec, L, max_steps=SYM_MAX_STEPS)
    out = runner(sf)  # compile + warm
    jax.block_until_ready(out.base.pc)
    steps_total = int(np.asarray(out.base.n_steps).sum())

    reps = 3
    with obs_trace.timer("bench.symbolic", reps=reps, P=SYM_P) as sp:
        for _ in range(reps):
            out = runner(sf)
        jax.block_until_ready(out.base.pc)
    dt = sp.elapsed / reps
    return {
        "sym_lane_steps_per_sec": round(steps_total / dt, 1),
        "sym_paths": int((np.asarray(out.base.active)
                          & ~np.asarray(out.base.error)).sum()),
        "sym_wall_sec": round(dt, 3),
    }


def bench_analyze() -> dict:
    """End-to-end: SymExecWrapper + fire_lasers on a contract batch.
    One warm-up pass first — the first invocation is dominated by XLA
    compilation, which a long-running analysis service pays once."""
    from mythril_tpu.analysis import SymExecWrapper, fire_lasers
    from mythril_tpu.smt.solver import SOLVER_STATS

    code = erc20_like()

    def once():
        sym = SymExecWrapper(
            [code] * ANALYZE_CONTRACTS,
            lanes_per_contract=ANALYZE_LANES_PER,
            max_steps=SYM_MAX_STEPS,
            transaction_count=1,
        )
        return sym, fire_lasers(sym)

    once()  # compile warm-up
    SOLVER_STATS.reset()
    with obs_trace.timer("bench.analyze",
                         contracts=ANALYZE_CONTRACTS) as sp:
        sym, report = once()
    dt = sp.elapsed
    cov = sym.coverage
    steps_total = int(np.asarray(sym.sf.base.n_steps).sum())
    return {
        "analyze_contracts_per_sec": round(ANALYZE_CONTRACTS / dt, 3),
        "analyze_wall_sec": round(dt, 3),
        "paths_per_sec": round(cov["surviving_paths"] / dt, 1),
        "analyze_lane_steps_per_sec": round(steps_total / dt, 1),
        "issues": len(report.issues),
        "solver": SOLVER_STATS.as_dict(),
    }


def bench_e2e(p_total: int = 1024) -> dict:
    """``BENCH_E2E=1`` end-to-end campaign benchmark: a full
    :class:`CorpusCampaign` (checkpointless) over an N-contract synthetic
    corpus built from tools/gen_corpus's generator MIX — the whole
    ingestion→explore→solve→verdict pipeline, not just the engine — and
    the headline is the ROADMAP's operator metric: contracts/min. The
    same number the campaign heartbeat prints and serve /metrics exports
    (``campaign_contracts_per_min`` / ``serve_contracts_per_min``), so
    bench records, live telemetry and dashboards are one comparable
    series. ``BENCH_E2E_N`` overrides the corpus size; ``p_total`` sets
    the device lane budget (batch_size × lanes_per_contract), which is
    how the sweep drives the e2e legs across the P-curve."""
    from mythril_tpu.mythril.campaign import CorpusCampaign

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import gen_corpus

    small = bool(os.environ.get("MYTHRIL_BENCH_SMALL"))
    n = int(os.environ.get("BENCH_E2E_N", "8" if small else "24"))
    mix = gen_corpus.MIX
    contracts = [("e2e%04d_%s" % (i, mix[i % len(mix)].__name__),
                  mix[i % len(mix)](i)) for i in range(n)]
    bs = min(8, n)
    lanes = max(4, p_total // bs)
    camp = CorpusCampaign(contracts, batch_size=bs,
                          lanes_per_contract=lanes,
                          max_steps=SYM_MAX_STEPS, transaction_count=1)
    # stage attribution: _exec_batch accumulates device/host phase wall
    # into this dict when present (the serve path does the same)
    camp._phase_acc = {"device": 0.0, "host": 0.0}
    with obs_trace.timer("bench.e2e", contracts=n, P=bs * lanes):
        res = camp.run()
    d = res.as_dict()
    wall = d["wall_sec"]
    phases = {k: round(v, 3) for k, v in camp._phase_acc.items()}
    phases["other"] = round(
        max(0.0, wall - sum(camp._phase_acc.values())), 3)
    return {
        "analyze_contracts_per_min": d["contracts_per_min"],
        "e2e": {
            "contracts": d["contracts"],
            "batches": d["batches"],
            "issues": d["issues"],
            "P": bs * lanes,
            "wall_sec": wall,
            # first batch is compile-dominated; the steady rate is the
            # long-campaign projection
            "contracts_per_min_steady": round(
                d["contracts_per_sec_steady"] * 60.0, 2),
            "phases": phases,
        },
    }


def bench_sweep(remaining) -> None:
    """``BENCH_SWEEP=1`` lane-scaling sweep: the SYMBOLIC engine at
    P ∈ {1024, 4096, 16384} (override: ``BENCH_SWEEP_P=comma,list``),
    ONE JSON record per P on stdout. Exists so the 4096→16384
    throughput cliff measured on the last TPU round (1.08M → 771k
    lane-steps/s) is tracked per-PR instead of anecdotally — a scaling
    regression shows up as a changed P-curve, not a vibe. ``remaining``
    is the budget callable; a P whose run would not fit is emitted as a
    skipped record rather than silently dropped."""
    global SYM_P
    ps = [int(x) for x in
          os.environ.get("BENCH_SWEEP_P", "1024,4096,16384").split(",")
          if x.strip()]
    for p in ps:
        if remaining() < 120:
            print(json.dumps({"metric": "sym_lane_steps_per_sec", "P": p,
                              "skipped": "budget: %.0fs left" % remaining()}),
                  flush=True)
            continue
        SYM_P = p
        try:
            with obs_trace.timer("bench.sweep", P=p):
                rec = bench_symbolic()
        except Exception as e:  # one failing shape must not end the sweep
            print(json.dumps({"metric": "sym_lane_steps_per_sec", "P": p,
                              "error": repr(e)[:300]}), flush=True)
            continue
        from mythril_tpu.backend import tier_of_platform
        plat = jax.default_backend()
        print(json.dumps({"metric": "sym_lane_steps_per_sec", "P": p,
                          "value": rec["sym_lane_steps_per_sec"],
                          "unit": "lane-steps/s",
                          "platform": plat,
                          "tier": tier_of_platform(plat),
                          "extra": rec}), flush=True)
    if os.environ.get("BENCH_E2E"):
        # e2e legs ride AFTER the engine sweep and climb P ascending, so
        # when the budget tightens the deepest-P e2e legs are the first
        # sacrificed — and each sacrifice is a recorded skip, never a
        # silent hole in the P-curve
        from mythril_tpu.backend import tier_of_platform
        plat = jax.default_backend()
        for p in ps:
            if remaining() < 180:
                print(json.dumps({"metric": "analyze_contracts_per_min",
                                  "P": p,
                                  "skipped": "budget: %.0fs left"
                                             % remaining()}), flush=True)
                continue
            try:
                with obs_trace.timer("bench.sweep_e2e", P=p):
                    rec = bench_e2e(p_total=p)
            except Exception as e:
                print(json.dumps({"metric": "analyze_contracts_per_min",
                                  "P": p, "error": repr(e)[:300]}),
                      flush=True)
                continue
            print(json.dumps({"metric": "analyze_contracts_per_min",
                              "P": p,
                              "value": rec["analyze_contracts_per_min"],
                              "unit": "contracts/min",
                              "platform": plat,
                              "tier": tier_of_platform(plat),
                              "extra": rec["e2e"]}), flush=True)


def _run_sweep_per_tier(tiers, remaining) -> None:
    """Run the lane-scaling sweep once per healthy tier, each in a
    subprocess pinned to that tier's platform (the parent must stay
    backend-free: initializing tier A's runtime here would leak into
    tier B's child via forked state). Child records pass through
    verbatim — they already carry platform/tier labels."""
    import subprocess

    from mythril_tpu.backend import profile

    for tier in tiers:
        if remaining() < 120:
            print(json.dumps({"metric": "sym_lane_steps_per_sec",
                              "tier": tier,
                              "skipped": "budget: %.0fs left"
                                         % remaining()}), flush=True)
            continue
        env = dict(os.environ)
        env.update(JAX_PLATFORMS=profile(tier).jax_platform,
                   MYTHRIL_BENCH_TIER=tier,     # recursion guard
                   MYTHRIL_BENCH_NO_PROBE="1")  # the tier just probed
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                capture_output=True, text=True,
                timeout=max(60.0, remaining() - 10.0), env=env)
            out = r.stdout.strip()
            if out:
                print(out, flush=True)
            else:
                print(json.dumps({"metric": "sym_lane_steps_per_sec",
                                  "tier": tier,
                                  "error": "no output (rc=%s): %s"
                                           % (r.returncode,
                                              r.stderr[-200:])}),
                      flush=True)
        except Exception as e:  # one failing tier must not end the sweep
            print(json.dumps({"metric": "sym_lane_steps_per_sec",
                              "tier": tier, "error": repr(e)[:300]}),
                  flush=True)


# --- cold-start benchmark (docs/serving.md "Compile artifacts & ---------
# --- prewarm") ----------------------------------------------------------

def _coldstart_phase(mode: str) -> None:
    """One ``BENCH_COLDSTART`` daemon generation, run in its own
    process so XLA's in-process jit cache can't leak between the cold
    and the prewarmed measurement. Starts an AnalysisDaemon on the
    shared ``BENCH_COLDSTART_DIR`` (compile store on by default),
    waits for the background prewarm pass to settle, submits ONE
    fresh contract and times the first verdict. Prints a
    ``COLDSTART {json}`` marker line for the orchestrator — not a
    bench record."""
    import time

    data_dir = os.environ["BENCH_COLDSTART_DIR"]
    t_boot = time.monotonic()
    from mythril_tpu.disassembler.asm import assemble
    from mythril_tpu.obs import metrics as obs_metrics
    from mythril_tpu.serve import AnalysisDaemon, ServeOptions

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import serve_client

    opts = ServeOptions(batch_size=2, lanes_per_contract=8,
                        max_steps=64, transaction_count=1,
                        modules=["AccidentallyKillable"],
                        limits_profile="test")
    dm = AnalysisDaemon(opts, data_dir=data_dir, port=0)
    dm.start()
    url = f"http://127.0.0.1:{dm.port}"
    doc = {"phase": mode, "ok": False}
    try:
        # let the prewarm pass settle before measuring (the cold
        # generation has no buckets and settles immediately)
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            pd = dm.health().get("prewarm") or {}
            if pd.get("state") in ("done", "failed", "disabled"):
                break
            time.sleep(0.25)
        doc["prewarm"] = dm.health().get("prewarm")
        compiles0 = obs_metrics.REGISTRY.counter(
            "engine_compiles_total").value
        # distinct bytecode per generation — the dedupe store must not
        # short-circuit the prewarmed generation's measurement
        code = assemble({"cold": 0, "warm": 2}.get(mode, 4),
                        "SELFDESTRUCT")
        t0 = time.monotonic()
        out = serve_client.get_result(
            url, serve_client.submit(url, [("c", code)])["id"],
            wait=300.0)
        doc.update(
            ok=(out.get("state") == "done"),
            first_verdict_sec=round(time.monotonic() - t0, 3),
            startup_sec=round(t0 - t_boot, 3),
            engine_compiles=obs_metrics.REGISTRY.counter(
                "engine_compiles_total").value - compiles0,
            warm_hits=obs_metrics.REGISTRY.counter(
                "serve_warm_compile_hits_total").value)
    finally:
        dm.shutdown("bench-coldstart")
    print("COLDSTART " + json.dumps(doc), flush=True)


def bench_coldstart(remaining) -> None:
    """``BENCH_COLDSTART=1``: time-to-first-verdict for a COLD serve
    daemon vs a RESTARTED one on the same data dir whose registry
    prewarm replayed the hot shape buckets. Each generation is a
    subprocess (XLA's in-process jit cache would otherwise make the
    'restart' trivially warm); emits one record with both walls and
    the speedup."""
    import shutil
    import subprocess
    import tempfile

    work = tempfile.mkdtemp(prefix="bench_coldstart_")
    phases = {}
    try:
        for mode in ("cold", "warm"):
            if remaining() < 60:
                phases[mode] = {"error": "budget: %.0fs left"
                                         % remaining()}
                break
            env = dict(os.environ)
            env.pop("BENCH_COLDSTART", None)
            env.update(BENCH_COLDSTART_PHASE=mode,
                       BENCH_COLDSTART_DIR=os.path.join(work, "sd"),
                       MYTHRIL_BENCH_NO_PROBE="1")
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__)],
                    capture_output=True, text=True,
                    timeout=max(60.0, remaining() - 10.0), env=env)
                line = next((ln for ln in r.stdout.splitlines()
                             if ln.startswith("COLDSTART ")), None)
                if line:
                    phases[mode] = json.loads(line[len("COLDSTART "):])
                else:
                    phases[mode] = {
                        "error": "no marker (rc=%s): %s"
                                 % (r.returncode,
                                    (r.stderr or r.stdout)[-300:])}
            except Exception as e:  # one failed generation: still emit
                phases[mode] = {"error": repr(e)[:300]}
        cold, warm = phases.get("cold") or {}, phases.get("warm") or {}
        rec = {"metric": "coldstart_first_verdict_sec",
               "value": warm.get("first_verdict_sec", 0.0),
               "unit": "s (registry-prewarmed restart)",
               "extra": {"cold": cold, "warm": warm}}
        if cold.get("first_verdict_sec") and warm.get("first_verdict_sec"):
            rec["extra"]["speedup_vs_cold"] = round(
                cold["first_verdict_sec"]
                / max(1e-9, warm["first_verdict_sec"]), 2)
        print(json.dumps(rec), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_profile(timeout_s: float = 600.0) -> dict:
    """Superstep time breakdown: per-variant dispatch
    cost + bandwidth floor, via tools/profile_superstep.py in a subprocess
    (its extra XLA programs must not crowd this process's compile budget)."""
    import subprocess

    env = dict(os.environ)
    env.setdefault("PROF_P", str(P))
    env.setdefault("PROF_STEPS", str(MAX_STEPS))
    env.setdefault("PROF_REPS", "5")
    # ONE variant: the profiler's own default sweeps 4 dispatch variants
    # = 4 large XLA programs, which a cold cache
    # cannot compile inside the driver's budget
    env.setdefault("PROF_VARIANTS", "all_cond")
    r = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                      "tools", "profile_superstep.py")],
        capture_output=True, text=True, timeout=max(30.0, timeout_s), env=env,
    )
    line = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else "{}"
    prof = json.loads(line)
    prof.pop("backend", None)
    return {"profile": prof}


import threading as _threading

_EMIT_LOCK = _threading.Lock()
_EMITTED = False


def _safe_copy(d):
    """Copy a dict the other thread may be mutating; never raise."""
    for _ in range(3):
        try:
            return dict(d)
        except RuntimeError:
            continue
    return {"partial": "extra dict was mutating during watchdog emit"}
# headline result stashed as soon as it is measured, so a watchdog fire
# during a LATER section (sym/analyze/profile overrunning the budget)
# still reports the primary metric instead of value=0
_HEADLINE = None  # (value, vs, unit_note, extra)


def _emit(value, vs, unit_note, extra, error=None):
    """Print the ONE JSON line, exactly once, atomically w.r.t. the
    watchdog thread (check-then-print under a lock: without it the timer
    could os._exit mid-print, truncating the line, or both threads could
    pass the flag check and print two lines)."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        # every record carries its platform + tier at top level (not
        # buried in extra), so the perf trajectory can tell a CPU-
        # fallback round from a hardware round without heuristics
        from mythril_tpu.backend import tier_of_platform
        plat = (extra or {}).get("platform")
        rec = {
            "metric": "lane_steps_per_sec",
            "value": round(float(value), 1),
            "unit": "opcode-steps/s (%s)" % unit_note,
            "vs_baseline": round(float(vs), 2),
            "platform": plat,
            "tier": tier_of_platform(plat),
            # snapshot: the main thread may still be inserting keys when
            # the watchdog serializes ("dict changed size during
            # iteration" would otherwise lose the line entirely)
            "extra": _safe_copy(extra),
        }
        if error:
            rec["error"] = str(error)[:400]
        line = json.dumps(rec)
        _EMITTED = True  # only after a successful serialize
        print(line, flush=True)


def _arm_watchdog(budget: float):
    """A single cold-cache XLA compile can exceed the whole driver budget,
    and the outer timeout then kills the process before ANY JSON was
    printed. A daemon
    timer emits the error-shaped line just before the budget expires and
    hard-exits; on a normal finish `_emit` has already printed and the
    timer's emit is a no-op. The exit happens under the emit lock so it
    can never kill the process while the main thread is mid-print."""

    def fire():
        err = ("watchdog: budget %.0fs expired mid-section "
               "(likely a cold-cache XLA compile)" % budget)
        if _HEADLINE is not None:  # headline measured before the overrun
            value, vs, note, extra = _HEADLINE
            _emit(value, vs, note, extra, error=err)
        else:
            _emit(0.0, 0.0, "no result", {}, error=err)
        with _EMIT_LOCK:  # serialize with any in-flight main-thread emit
            os._exit(0)

    t = _threading.Timer(max(5.0, budget - 15.0), fire)
    t.daemon = True
    t.start()
    return t


def _probe_backend(timeout_s: float = 75.0, retries: int = 2):
    """Initialize the JAX backend in a SUBPROCESS with a timeout, so a hung
    TPU runtime (round 3: driver bench + judge re-run both hung >590 s in
    backend init) cannot take this process down with it. Now delegated to
    the shared BackendManager (mythril_tpu/resilience.py) — the same
    probe/abandon machinery the campaign and the profiler use. The import
    is lazy and backend-free (resilience touches no jnp tables). Returns
    (ok, diagnosis)."""
    from mythril_tpu.resilience import BackendManager

    bm = BackendManager(init_timeout=timeout_s, max_attempts=retries,
                        backoff=0.0)
    return bm.probe()


def _tier_fallback(diag: str) -> None:
    """Configured backend unreachable: walk the ranked tier ladder
    (mythril_tpu/backend.py) to the first lower tier that probes
    healthy and re-run this benchmark there with small shapes, so the
    driver still records a parsed JSON line. The numbers are labeled
    with the fallback tier — NOT comparable to preferred-tier rounds."""
    import subprocess

    from mythril_tpu.backend import (probe_tier, profile, terminal_tier,
                                     tiers_below)
    from mythril_tpu.resilience import BackendManager

    configured = BackendManager._configured_tier()
    tier = terminal_tier()
    for cand in tiers_below(configured):
        if cand == terminal_tier():
            break  # the floor is trusted, not probed
        ok, _ = probe_tier(cand, timeout_s=30.0)
        if ok:
            tier = cand
            break
    env = dict(os.environ)
    # concrete only: sym_run/fire_lasers XLA compiles take minutes on a
    # fallback backend and would blow the driver's remaining time budget
    env.update(JAX_PLATFORMS=profile(tier).jax_platform,
               MYTHRIL_BENCH_SMALL="1",
               MYTHRIL_BENCH_NO_PROBE="1", MYTHRIL_BENCH_NO_PROFILE="1",
               MYTHRIL_BENCH_NO_ANALYZE="1", MYTHRIL_BENCH_NO_SYM="1")
    try:
        r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           capture_output=True, text=True, timeout=360, env=env)
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        extra = rec.get("extra", {})
        extra["platform"] = "%s-fallback" % tier
        extra["tpu_error"] = diag[:300]
        _emit(rec.get("value", 0.0), rec.get("vs_baseline", 0.0),
              "%s-FALLBACK %s" % (tier.upper(), rec.get("unit", "")),
              extra, error="configured backend unavailable: " + diag)
    except Exception as e:
        _emit(0.0, 0.0, "no backend", {"tpu_error": diag[:300]},
              error="backend unavailable (%s); %s fallback also failed: "
                    "%r" % (diag[:200], tier, e))


def main():
    global P, MAX_STEPS, SYM_P, SYM_MAX_STEPS, ANALYZE_CONTRACTS
    global _EMITTED
    if os.environ.get("MYTHRIL_BENCH_SMALL"):
        P, MAX_STEPS, SYM_P, SYM_MAX_STEPS = 1024, 192, 1024, 128
        ANALYZE_CONTRACTS = 8

    # total wall-clock budget (round-3 lesson: the driver kills the whole
    # process at ~590 s — a partial JSON line beats a SIGKILL'd full one).
    # Each extra section only starts if its cost estimate still fits.
    # The budget clock is a stopwatch span: its live `elapsed` gates the
    # sections, and a BENCH_TRACE run records the driver as one span.
    budget = float(os.environ.get("MYTHRIL_BENCH_BUDGET", "520"))
    _arm_watchdog(budget)
    sw = obs_trace.timer("bench.main", budget=budget).start()

    def remaining() -> float:
        return budget - sw.elapsed

    if os.environ.get("BENCH_COLDSTART_PHASE"):
        # one subprocess generation of the BENCH_COLDSTART mode below —
        # prints a COLDSTART marker line, never a bench record
        try:
            _coldstart_phase(os.environ["BENCH_COLDSTART_PHASE"])
        except Exception as e:
            print("COLDSTART " + json.dumps(
                {"phase": os.environ["BENCH_COLDSTART_PHASE"],
                 "ok": False, "error": repr(e)[:300]}), flush=True)
        sw.stop()
        with _EMIT_LOCK:
            _EMITTED = True
        return
    if os.environ.get("BENCH_COLDSTART"):
        bench_coldstart(remaining)
        sw.stop()
        with _EMIT_LOCK:
            _EMITTED = True
        return

    if not os.environ.get("MYTHRIL_BENCH_NO_PROBE"):
        ok, diag = _probe_backend()
        if not ok:
            _tier_fallback(diag)
            return

    if (os.environ.get("BENCH_SWEEP")
            and not os.environ.get("MYTHRIL_BENCH_TIER")):
        # per-tier sweep (docs/resilience.md "Backend tiers"): when
        # more than one tier probes healthy, re-run the sweep once per
        # tier in a pinned subprocess so the perf trajectory gets a
        # labeled P-curve per platform. One healthy tier (the common
        # CPU-only box) falls straight through to the in-process sweep.
        from mythril_tpu.backend import available_tiers

        tiers = available_tiers()
        if len(tiers) > 1:
            _run_sweep_per_tier(tiers, remaining)
            sw.stop()
            with _EMIT_LOCK:
                _EMITTED = True
            return

    _lazy_imports()
    if os.environ.get("BENCH_SCALING"):
        # compiled-cost attribution mode (tools/scaling_report.py): trace
        # the engine's jaxprs at the sweep's P values and emit the fitted
        # growth exponent per phase bucket — pure tracing, no execution,
        # so the record is hardware-independent (the perf trajectory can
        # watch for superlinear terms even on a CPU-only round)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import scaling_report
        ps = tuple(int(x) for x in
                   os.environ.get("BENCH_SWEEP_P", "1024,4096,16384")
                   .split(",") if x.strip())
        for impl in ("legacy", "packed"):
            if remaining() < 60:
                print(json.dumps({"metric": "scaling_attribution",
                                  "fork_impl": impl,
                                  "skipped": "budget: %.0fs left"
                                             % remaining()}), flush=True)
                continue
            try:
                rep = scaling_report.attribution(ps, fork_impl=impl)
            except Exception as e:
                print(json.dumps({"metric": "scaling_attribution",
                                  "fork_impl": impl,
                                  "error": repr(e)[:300]}), flush=True)
                continue
            print(json.dumps({
                "metric": "scaling_attribution", "fork_impl": impl,
                "value": rep["superstep_body_exponent"], "unit": "exponent",
                "dominant_superlinear": rep["dominant_superlinear"],
                "extra": {n: {"exponent": b["exponent"],
                              "elems_max_p": b["elems"][ps[-1]]}
                          for n, b in rep["buckets"].items()}}), flush=True)
        sw.stop()
        with _EMIT_LOCK:
            _EMITTED = True
        return
    if os.environ.get("BENCH_SWEEP"):
        # lane-scaling sweep mode: per-P records instead of the single
        # headline line; suppress the watchdog's error-shaped emit —
        # the sweep's own records are the output
        bench_sweep(remaining)
        sw.stop()
        with _EMIT_LOCK:
            _EMITTED = True
        return
    try:
        value, vs, err = bench_concrete()
    except Exception as e:
        _emit(0.0, 0.0, "P=%d lanes, ERC20 transfer" % P, {}, error=repr(e)[:300])
        return
    if err:
        _emit(0.0, 0.0, "P=%d lanes, ERC20 transfer" % P, {}, error=err)
        return
    global _HEADLINE
    extra = {"platform": jax.default_backend()}
    note = "P=%d lanes, ERC20 transfer" % P
    _HEADLINE = (value, vs, note, extra)  # extra mutates in place below,
    # so later sections' partial results ride along on a watchdog emit
    if not os.environ.get("MYTHRIL_BENCH_NO_SYM"):
        if remaining() > 150:
            try:
                extra.update(bench_symbolic())
            except Exception as e:  # never lose the headline number
                extra["sym_error"] = repr(e)[:200]
        else:
            extra["sym_skipped"] = "budget: %.0fs left" % remaining()
    if not os.environ.get("MYTHRIL_BENCH_NO_ANALYZE"):
        if remaining() > 150:
            try:
                extra.update(bench_analyze())
            except Exception as e:
                extra["analyze_error"] = repr(e)[:200]
        else:
            extra["analyze_skipped"] = "budget: %.0fs left" % remaining()
    if os.environ.get("BENCH_E2E"):
        # full-pipeline campaign leg: the ROADMAP's contracts/min
        # headline rides in extra next to the engine-only numbers
        if remaining() > 180:
            try:
                extra.update(bench_e2e())
            except Exception as e:
                extra["e2e_error"] = repr(e)[:200]
        else:
            extra["e2e_skipped"] = "budget: %.0fs left" % remaining()
    if not os.environ.get("MYTHRIL_BENCH_NO_PROFILE"):
        if remaining() > 120:
            try:
                extra.update(bench_profile(timeout_s=remaining() - 20))
            except Exception as e:
                extra["profile_error"] = repr(e)[:200]
        else:
            extra["profile_skipped"] = "budget: %.0fs left" % remaining()
    sw.stop()
    _emit(value, vs, note, extra)


if __name__ == "__main__":
    try:
        main()
    except BaseException as e:  # the one-JSON-line contract is absolute
        _emit(0.0, 0.0, "unhandled", {}, error="unhandled: %r" % (e,))
        raise SystemExit(0)
    finally:
        obs_trace.close()  # writes the BENCH_TRACE Chrome file, if any
