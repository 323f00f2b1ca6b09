"""Driver ``campaign``: a closed loop through ``CorpusCampaign.run()``
in this process, as ``analyze --corpus`` drives it.

Set-up is a short campaign over the first contracts of the corpus (the
first batch compiles or loads every program, the next gives the warm
batch time). The window is a second campaign over fresh contracts with
``--execution-timeout`` set to the window's length: no batch starts
after it, and the batches in flight commit. The rate counts whole
committed batches over the time from the window's start to the last
commit, so a batch in flight does not quantise it.

A traced run records the program's spans (``obs.trace`` in buffer mode),
the registry's solver-stage seconds, and a ``jax.profiler`` trace of a
slice of the window (``trace_seconds`` from ``trace_start_batches`` warm
batch times after its start). The slice is short and early: writing it
out takes the interpreter lock for tens of seconds, and has to be over
before the first host phase, whose spans the other readers time.
"""

from __future__ import annotations

import gc
import logging
import os
import shutil
import sys
import threading
import time

def parse_analyze_args(config: dict):
    """The configuration's ``analyze_args`` through the CLI's own
    parser, so that every default is the product's."""
    from mythril_tpu.interfaces.cli import create_parser

    return create_parser().parse_args(
        ["analyze", "--corpus", "-", *config["analyze_args"]])


def build_campaign(args, contracts, execution_timeout=None):
    """``CorpusCampaign`` as ``analyze --corpus`` (cli ``_exec_campaign``)
    builds it without ``--fleet``, ``--init-timeout`` or a checkpoint
    directory."""
    from mythril_tpu.interfaces.cli import _limits_for
    from mythril_tpu.mythril.campaign import CorpusCampaign
    from mythril_tpu.resilience import FaultInjector, parse_ladder
    from mythril_tpu.symbolic import SymSpec

    return CorpusCampaign(
        contracts,
        batch_size=args.batch_size,
        lanes_per_contract=args.lanes_per_contract,
        limits=_limits_for(args),
        spec=SymSpec(storage=not args.concrete_storage),
        max_steps=(args.max_depth if args.max_depth is not None
                   else args.max_steps),
        solver_timeout=(args.solver_timeout / 1000.0
                        if args.solver_timeout is not None else None),
        solver_iters=args.solver_iters,
        parallel_solving=args.parallel_solving,
        transaction_count=args.transaction_count,
        modules=args.modules.split(",") if args.modules else None,
        execution_timeout=execution_timeout,
        enable_iprof=args.enable_iprof,
        batch_timeout=args.batch_timeout,
        max_batch_retries=args.max_batch_retries,
        fault_injector=FaultInjector.from_string(args.fault_inject),
        oom_ladder=parse_ladder(args.oom_ladder),
        checkpoint_every=args.checkpoint_every,
        pipeline=args.pipeline,
        solver_workers=args.solver_workers,
        solver_store=None,
        worker_isolation="off",
    )


def swc_sets(issues) -> dict:
    out: dict = {}
    for i in issues:
        out.setdefault(i["contract"], set()).add(str(i["swc-id"]))
    return out


def run_campaign(camp):
    """Run to the end; returns (result, commit times, batch walls)."""
    commits, walls = [], []

    def progress(done, total, dt, n_issues):
        commits.append(time.monotonic())
        walls.append(dt)

    res = camp.run(progress=progress)
    return res, commits, walls


def engine_checks(ctx, engine: dict, res, want_callbacks: bool):
    """(lines, ok): the engine stayed where the configuration puts it."""
    from verdicts import BAD_EVENTS

    dev = engine.get("device") or {}
    nat = engine.get("native_tape_eval") or {}
    kinds = sorted({e["kind"] for e in res.backend_events} & BAD_EVENTS)
    not_ok = [s for s in res.batch_status if s != "ok"]
    rows = [
        ("engine platform", dev.get("platform"),
         "tpu" if ctx.require_tpu else dev.get("platform")),
        ("host_callbacks", engine.get("host_callbacks"), want_callbacks),
        ("native tape evaluator loaded", bool(nat.get("loaded")), True),
        ("fallback/degrade events", kinds, []),
        ("quarantined contracts", len(res.quarantined), 0),
        ("batches not ok", len(not_ok), 0),
        ("batch retries", res.retries, 0),
    ]
    lines = [f"check {name}: {got!r} (limit {want!r})"
             for name, got, want in rows]
    return lines, all(got == want for _, got, want in rows)


class CompileLog(logging.Handler):
    """Names of the programs JAX compiles (or loads) while this is open:
    ``jax_log_compiles`` makes pxla log one line per request."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        import jax

        super().__init__(level=logging.DEBUG)
        self.names: list = []
        self._was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._logger = logging.getLogger(self.LOGGER)
        self._propagate = self._logger.propagate
        self._logger.propagate = False
        self._logger.addHandler(self)

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split()[1])

    def close(self):
        import jax

        self._logger.removeHandler(self)
        self._logger.propagate = self._propagate
        jax.config.update("jax_log_compiles", self._was)
        super().close()


def run(ctx) -> dict:
    cfg, traffic, log = ctx.config, ctx.traffic, ctx.log
    unit = ctx.corpus.BATCH
    device = ctx.require_devices(ctx.chips, ctx.require_tpu)
    log(f"device: {device}")
    sys.path.insert(0, ctx.here)
    import hostcb_cache

    hostcb_cache.install()

    from mythril_tpu import compile_cache
    from mythril_tpu.obs import metrics as obs_metrics
    from mythril_tpu.obs import trace as obs_trace

    args = parse_analyze_args(cfg)
    max_code = (512 if getattr(args, "limits_profile", None) == "test"
                else 24576)

    def contracts_of(units):
        return [c for u in units
                for c in ctx.corpus.batch(ctx.seed, u, max_code=max_code)]

    # --- set-up: compile (or load) and one warm batch ---------------------
    n_warm = int(traffic["warmup_batches"])
    warm_units = range(-(-n_warm * args.batch_size // unit))
    warm = contracts_of(warm_units)[:n_warm * args.batch_size]
    camp = build_campaign(args, [(c["name"], c["code"]) for c in warm])
    res0, _, walls0 = run_campaign(camp)
    engine0 = dict(res0.engine)
    warm_batch_s = walls0[-1]
    # what tracing and compiling left behind is collected now, not in
    # the window's first batch
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - ctx.t0
    log(f"set-up: {setup_s:.1f}s, batch walls "
        f"{[round(w, 2) for w in walls0]}, xla_compile_sec "
        f"{engine0.get('xla_compile_sec')} in "
        f"{engine0.get('xla_compiles')} requests, "
        f"{engine0.get('cache_hits')} from the cache")

    # --- the window ----------------------------------------------------------
    n_batches = int(1.5 * ctx.seconds / max(warm_batch_s, 0.05)) + 2
    first = len(warm_units)
    units = range(first, first - (-n_batches * args.batch_size // unit))
    due = contracts_of(units)[:n_batches * args.batch_size]
    camp = build_campaign(args, [(c["name"], c["code"]) for c in due],
                          execution_timeout=ctx.seconds)

    prof_dir = os.path.join(ctx.work, "profile")
    prof = {"t0": None, "t1": None}
    stop_early = threading.Event()

    def record_slice(t_start: float) -> None:
        import jax

        if stop_early.wait(max(0.0, t_start + float(
                traffic["trace_start_batches"]) * warm_batch_s
                - time.monotonic())):
            return
        shutil.rmtree(prof_dir, ignore_errors=True)
        # no Python call tracing: it slows the host phase severalfold
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        prof["t0"] = time.monotonic()
        with jax.profiler.TraceAnnotation(
                "bench_clock_sync", mono_ns=int(time.monotonic() * 1e9)):
            pass
        stop_early.wait(float(traffic["trace_seconds"]))
        prof["t1"] = time.monotonic()
        jax.profiler.stop_trace()

    tracer = None
    if ctx.trace:
        tracer = obs_trace.configure(buffer=True)
    compiled = CompileLog()
    reg0 = obs_metrics.REGISTRY.snapshot()
    xla0 = compile_cache.stats()
    t_start = time.monotonic()
    slicer = threading.Thread(target=record_slice, args=(t_start,),
                              name="bench-profile")
    if ctx.trace:
        slicer.start()
    try:
        res, commits, walls = run_campaign(camp)
    finally:
        t_end = time.monotonic()
        stop_early.set()
        if ctx.trace:
            slicer.join()
    compiled.close()
    xla1 = compile_cache.stats()
    reg1 = obs_metrics.REGISTRY.snapshot()
    spans = tracer.drain_buffer() if tracer is not None else []
    if tracer is not None:
        obs_trace.close()

    # --- the rate ---------------------------------------------------------------
    committed = due[:len(commits) * args.batch_size]
    elapsed = (commits[-1] - t_start) if commits else float("nan")
    rate = 60.0 * len(committed) / elapsed if commits else 0.0
    for k, (t, w) in enumerate(zip(commits, walls)):
        log(f"batch {k}: committed at {t - t_start:.3f}s, wall {w:.3f}s")
    log(f"window: {len(commits)} batches, {len(committed)} contracts, "
        f"last commit at {elapsed:.3f}s of {ctx.seconds:g}s, campaign "
        f"returned at {t_end - t_start:.3f}s; dropped_forks "
        f"{res.dropped_forks}, paths {res.paths_total}")

    for sp in spans:
        if sp.get("kind") == "span" and sp["name"] in (
                "device_phase", "host_phase", "pipeline_stall", "drain",
                "rebalance", "superstep"):
            log(f"span {sp['name']} bi={sp.get('bi')} tx={sp.get('tx')} "
                f"at {sp['mono'] - t_start:.3f}s dur {sp['dur']:.3f}s "
                f"steps={sp.get('steps')} wait={sp.get('wait')}")

    # --- correct -----------------------------------------------------------------
    import verdicts

    reported = swc_sets(res.issues)
    for c in committed:
        reported.setdefault(c["name"], set())
    rows = verdicts.compare(committed, reported, cfg.get("swc_in_scope"))
    bad = [r for r in rows if verdicts.wrong(r)]
    for r in bad[:12]:
        log(f"wrong verdict: {r['name']} missing={r['missing']} "
            f"extra={r['extra']} reported="
            f"{sorted(reported.get(r['name'], ()))}")
    checks = verdicts.summary_lines(rows)
    want_cb = bool(cfg["guarantees"]["host_callbacks"])
    lines, engine_ok = engine_checks(ctx, dict(res.engine), res, want_cb)
    checks += lines
    # nothing may compile in the window, the solver's small kernels
    # included: the warm-up meets every shape the window uses
    compiles = max(xla1["xla_compiles"] - xla0["xla_compiles"],
                   len(compiled.names))
    checks.append(f"check programs compiled inside the window: "
                  f"{compiles} (limit 0) in "
                  f"{xla1['xla_compile_sec'] - xla0['xla_compile_sec']:.3f}s "
                  f"{compiled.names[:8]}")
    checks.append(f"check committed batches: {len(commits)} (limit >= 1)")
    correct = (not bad and engine_ok and compiles == 0
               and len(commits) >= 1)

    obs = {"kind": "campaign", "spans": spans,
           "window": (t_start, t_end), "window_s": t_end - t_start,
           "batches": len(commits), "engine_setup": engine0,
           "registry_before": reg0, "registry_after": reg1,
           "profile": None}
    out = {"correct": correct, "attempted": len(committed),
           "failed": len(bad), "checks": checks, "obs": obs,
           "e2e": {"contracts_per_min": rate, "setup_s": setup_s},
           "device": {**device,
                      "memory_peak_bytes": ctx.memory_peak_bytes()}}
    if ctx.trace and prof["t1"] is not None:
        import trace_reduce

        path = trace_reduce.find_xplane(prof_dir)
        if path:
            log(f"profile: {path} ({os.path.getsize(path)} bytes), "
                f"{prof['t1'] - prof['t0']:.2f}s from "
                f"{prof['t0'] - t_start:.2f}s into the window")
            tr = trace_reduce.load(path)
            # a gap is named after what the thread that feeds the device
            # was doing: the host phase runs beside it, on another thread
            feeder = {s["tid"] for s in spans if s.get("kind") == "span"
                      and s["name"] == "device_phase"}
            red = trace_reduce.reduce(tr, spans=[
                s for s in spans if s.get("kind") == "span"
                and s["name"] in traffic["gap_spans"]
                and s["tid"] in feeder])
            obs["profile"] = red
            out["device"]["busy_s"] = red["busy_s"]
            out["device"]["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
            log(f"profile modules: {red['modules']}")
    return out
