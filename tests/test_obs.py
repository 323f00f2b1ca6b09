"""Telemetry spine (mythril_tpu/obs, docs/observability.md).

All tests here are engine-free: the tracer/metrics layer is stdlib-only,
and the campaign-side checks use the stub batch runner — the tier-1
budget pays no XLA compile for observability coverage.
"""

import importlib.util
import json
import os
import re
import time

import pytest

from mythril_tpu.obs import metrics as obs_metrics
from mythril_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts and ends with no global tracer and a fresh
    metrics registry — telemetry state must never leak between tests."""
    obs_trace.close()
    obs_metrics.REGISTRY.reset()
    yield
    obs_trace.close()
    obs_metrics.REGISTRY.reset()


def read_jsonl(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


# --- tracer -----------------------------------------------------------


def test_span_nesting_and_schema_roundtrip(tmp_path):
    t = str(tmp_path / "t.json")
    obs_trace.configure(t)
    with obs_trace.span("outer", bi=3, status="ok"):
        time.sleep(0.01)
        with obs_trace.span("inner", step="halve-lanes"):
            time.sleep(0.002)
    obs_trace.event("degrade", batch=3, step="cpu")
    obs_trace.close()

    events = read_jsonl(str(tmp_path / "t.jsonl"))
    assert len(events) == 3
    # required keys on EVERY event, span or instant
    for e in events:
        assert e["schema"] == obs_trace.SCHEMA
        assert "kind" in e and "t" in e
    # spans close inner-first; attributes round-trip verbatim
    inner, outer, degrade = events
    assert (inner["kind"], inner["name"]) == ("span", "inner")
    assert inner["step"] == "halve-lanes"
    assert (outer["name"], outer["bi"], outer["status"]) == ("outer", 3, "ok")
    assert outer["dur"] >= inner["dur"] > 0
    assert outer["mono"] <= inner["mono"]          # outer started first
    assert degrade["kind"] == "degrade" and degrade["batch"] == 3
    # both clocks on every event
    assert all("mono" in e and "session" in e for e in events)


def test_chrome_trace_json_validity(tmp_path):
    t = str(tmp_path / "t.json")
    obs_trace.configure(t)
    with obs_trace.span("batch", bi=0):
        pass
    obs_trace.event("heartbeat", batch=1)
    obs_trace.close()

    doc = json.load(open(t))                       # valid JSON or raises
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"batch", "heartbeat"}
    for e in evs:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    x = next(e for e in evs if e["ph"] == "X")
    assert x["dur"] >= 0 and x["args"] == {"bi": 0}


def test_disabled_tracer_is_noop_and_touches_no_files(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert obs_trace.get_tracer() is None and not obs_trace.active()
    # zero-allocation: every disabled span is the SAME shared singleton
    s1, s2 = obs_trace.span("a", x=1), obs_trace.span("b")
    assert s1 is s2
    with s1:
        pass
    assert s1.elapsed == 0.0
    assert obs_trace.event("degrade", batch=1) is None
    # timer still measures with tracing off (bench/profilers rely on it)
    with obs_trace.timer("measured") as sp:
        time.sleep(0.005)
    assert sp.elapsed >= 0.004
    assert os.listdir(tmp_path) == []              # no file anywhere


def test_timer_stopwatch_start_stop():
    sw = obs_trace.timer("budget").start()
    time.sleep(0.003)
    live = sw.elapsed
    assert live >= 0.002
    dur = sw.stop()
    assert dur >= live and sw.elapsed == dur       # frozen after stop


@pytest.mark.parametrize("beside", ["nobody", "a_busy_thread"])
def test_phase_span_says_what_the_process_did_meanwhile(beside):
    """``proc_cpu_s`` is the CPU clock of every thread: a phase that
    sleeps beside a busy thread reads it well above its own
    ``cpu_s``."""
    import threading

    from mythril_tpu.obs import device as obs_device

    def burn(stop):
        while not stop.is_set():
            sum(range(2000))

    stop = threading.Event()
    helper = threading.Thread(target=burn, args=(stop,))
    tr = obs_trace.configure(buffer=True)
    if beside == "a_busy_thread":
        helper.start()
    try:
        with obs_device.phase_timer("batch_build", stage="start") as sp:
            time.sleep(0.3)
            mono_in = time.monotonic()
    finally:
        stop.set()
        if helper.ident is not None:
            helper.join()
    assert sp.t_mono <= mono_in <= sp.t_mono + sp.dur
    (rec,) = [r for r in tr.drain_buffer() if r["name"] == "batch_build"]
    assert rec["stage"] == "start" and rec["mono"] == round(sp.t_mono, 6)
    assert rec["cpu_s"] + rec["device_wait_s"] <= rec["dur"]
    assert rec["proc_cpu_s"] >= 0.0 and rec["cpu_s"] <= 0.05
    # the process's clock holds this thread's (to the clocks' tick)
    assert rec["proc_cpu_s"] >= rec["cpu_s"] - 0.02
    if beside == "a_busy_thread":
        # (a loaded machine gives the helper a share of a CPU, not one)
        assert rec["proc_cpu_s"] - rec["cpu_s"] >= 0.03


def test_jsonl_path_derivation():
    assert obs_trace.jsonl_path_for("t.json") == "t.jsonl"
    assert obs_trace.jsonl_path_for("out/trace") == "out/trace.jsonl"


# --- distributed tracing ----------------------------------------------


def test_trace_context_stamps_and_indexes(tmp_path):
    """Inside a trace_context scope every span/event is stamped with
    trace_id + span/parent linkage, lands in the bounded trace index
    under EVERY linked id, and reads back in monotonic order
    (docs/observability.md "Distributed tracing")."""
    obs_trace.configure(str(tmp_path / "t.json"))
    tid, other = "a" * 16, "b" * 16
    assert obs_trace.trace_records(tid) is None
    with obs_trace.trace_context(tid, link_ids=[other]):
        assert obs_trace.current_trace_id() == tid
        with obs_trace.span("schedule", bi=0):
            obs_trace.event("verdict_commit", eid="e0")
    assert obs_trace.current_trace_id() is None    # scope exited
    recs = obs_trace.trace_records(tid)
    assert recs is not None
    sp = next(r for r in recs if r["kind"] == "span")
    ev = next(r for r in recs if r["kind"] == "verdict_commit")
    assert sp["trace_id"] == tid and ev["trace_id"] == tid
    # the event nested under the span links to it as parent
    assert ev["parent"] == sp["span"]
    # the linked (batched-together) request indexes the same records
    assert obs_trace.trace_records(other)
    monos = [r["mono"] for r in recs]
    assert monos == sorted(monos)


def test_context_snapshot_roundtrip(tmp_path):
    """The snapshot/apply pair that crosses thread and IPC boundaries
    reproduces the scope verbatim; apply(None) is a no-op guard."""
    with obs_trace.trace_context("c" * 16, link_ids=["d" * 16]):
        snap = obs_trace.context_snapshot()
    assert snap["ids"] == ["c" * 16, "d" * 16]
    assert obs_trace.context_snapshot() is None
    with obs_trace.apply_context(snap):
        assert obs_trace.current_trace_id() == "c" * 16
    with obs_trace.apply_context(None):
        assert obs_trace.current_trace_id() is None


def test_worker_clock_stitch_monotone(tmp_path):
    """Backhauled worker records carry the CHILD's monotonic clock;
    re-emission with the spawn-handshake offset must land them on the
    parent timeline — after the parent span that contains them, in
    child order — even under an arbitrarily skewed child clock."""
    obs_trace.configure(str(tmp_path / "t.json"))
    tid = "e" * 16
    with obs_trace.trace_context(tid):
        with obs_trace.span("schedule", bi=0):
            # fake child: its monotonic clock reads ~5.0 while the
            # parent's reads time.monotonic() — wildly skewed
            child = [
                {"schema": 1, "kind": "span", "name": "device_phase",
                 "t": 123.0, "mono": 5.0, "dur": 0.25, "tid": 1,
                 "session": "fakewkr", "bi": 0, "trace_id": tid},
                {"schema": 1, "kind": "solver_stage", "t": 123.3,
                 "mono": 5.3, "session": "fakewkr", "stage": "lru",
                 "verdict": "unsat", "trace_id": tid},
            ]
            offset = time.monotonic() - 5.0   # the supervisor handshake
            n = obs_trace.reemit_records(child, mono_offset=offset,
                                         proc="worker", wpid=1234)
    obs_trace.close()
    assert n == 2
    recs = obs_trace.trace_records(tid)
    worker = [r for r in recs if r.get("proc") == "worker"]
    assert len(worker) == 2
    # transport meta was re-stamped; the child session survives as
    # provenance, not as the ordering key
    assert all(r["src_session"] == "fakewkr" for r in worker)
    assert all(r["session"] != "fakewkr" for r in worker)
    # ONE monotone timeline on the parent clock: the worker device
    # span starts after the parent schedule span that dispatched it
    monos = [r["mono"] for r in recs]
    assert monos == sorted(monos)
    sched = next(r for r in recs if r.get("name") == "schedule")
    dev = next(r for r in recs if r.get("name") == "device_phase")
    stage = next(r for r in recs if r["kind"] == "solver_stage")
    assert sched["mono"] <= dev["mono"] <= stage["mono"]


def test_jsonl_rotation_set_aside_and_byte_gauge(tmp_path):
    """Crossing the size cap rotates the live log to ``.1`` (one
    set-aside generation), opens the fresh log with a
    ``trace_log_rotated`` seam record, ticks the rotation counter and
    keeps the obs_event_log_bytes gauge on the live file."""
    jl = str(tmp_path / "t.jsonl")
    obs_trace.configure(str(tmp_path / "t.json"), max_jsonl_bytes=600)
    for i in range(30):
        obs_trace.event("heartbeat", batch=i, pad="x" * 40)
    snap = obs_metrics.REGISTRY.snapshot()
    assert snap["counters"]["obs_event_log_rotations_total"] >= 1
    assert os.path.exists(jl + ".1")
    assert read_jsonl(jl + ".1")                   # parseable prefix
    live = read_jsonl(jl)
    assert live[0]["kind"] == "trace_log_rotated"
    assert live[0]["rotated_bytes"] >= 600
    assert live[0]["set_aside"] == jl + ".1"
    assert snap["gauges"]["obs_event_log_bytes"] == os.path.getsize(jl)
    obs_trace.close()


def test_worker_buffer_drain_and_drop_counter():
    """Buffer-mode (engine-worker) tracer: records accumulate for the
    batch-reply drain and touch no files; a record arriving after
    close is DECLARED via obs_events_dropped_total, never silent."""
    tr = obs_trace.configure(buffer=True)
    with obs_trace.trace_context("f" * 16):
        obs_trace.event("solver_stage", stage="lru", verdict="unsat")
    recs = tr.drain_buffer()
    assert len(recs) == 1 and recs[0]["trace_id"] == "f" * 16
    assert tr.drain_buffer() == []                 # drained
    tr.close()
    obs_trace.event("heartbeat", batch=1)
    snap = obs_metrics.REGISTRY.snapshot()
    assert snap["counters"]["obs_events_dropped_total"] == 1.0


# --- schema lint: source scan vs naming rules and the docs table ------

_METRIC_CALL = re.compile(
    r'(?:counter|gauge|histogram)\(\s*[\'"]([A-Za-z0-9_]+)[\'"]')
_EVENT_CALL = re.compile(r'\b_?event\(\s*[\'"]([A-Za-z0-9_]+)[\'"]')
_PROM_NAME = re.compile(r"^[a-z][a-z0-9_]*$")


def _scan_sources():
    """Every metric-name and event-kind literal in the package (the
    regexes span the multi-line call style used everywhere)."""
    metrics, events = set(), set()
    for dirpath, _dirs, files in os.walk(os.path.join(ROOT,
                                                      "mythril_tpu")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn), encoding="utf-8") as fh:
                src = fh.read()
            metrics.update(_METRIC_CALL.findall(src))
            events.update(_EVENT_CALL.findall(src))
    return metrics, events


def test_metric_names_follow_prometheus_conventions():
    metrics, _ = _scan_sources()
    assert len(metrics) > 40                       # the scan works
    bad = sorted(m for m in metrics
                 if not _PROM_NAME.match(m) or "__" in m
                 or m.endswith("_"))
    assert not bad, f"metric names violating prometheus naming: {bad}"


def test_every_event_kind_is_documented():
    """Every emitted event ``kind`` must appear (backticked) in
    docs/observability.md's schema table — adding an event without
    documenting it fails here."""
    _, events = _scan_sources()
    # dynamic prefix concatenations (event("tier_" + kind)) scan as
    # the prefix; their concrete kinds also appear as literals
    events = {e for e in events if not e.endswith("_")}
    events.add("trace_log_rotated")    # written inline at the seam
    with open(os.path.join(ROOT, "docs", "observability.md"),
              encoding="utf-8") as fh:
        doc = fh.read()
    missing = sorted(k for k in events if f"`{k}`" not in doc)
    assert not missing, ("event kinds missing from "
                         f"docs/observability.md: {missing}")


# --- metrics ----------------------------------------------------------


def test_metrics_snapshot_shape_and_prometheus():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("batches_total").inc()
    reg.counter("batches_total").inc(2)
    reg.gauge("frontier_occupancy").set(0.75)
    h = reg.histogram("batch_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(30.0)

    snap = reg.snapshot()
    assert snap["schema"] == obs_metrics.SCHEMA and "t" in snap
    assert snap["counters"]["batches_total"] == 3.0
    assert snap["gauges"]["frontier_occupancy"] == 0.75
    hs = snap["histograms"]["batch_seconds"]
    assert (hs["count"], hs["min"], hs["max"]) == (3, 0.05, 30.0)
    assert hs["sum"] == pytest.approx(30.55)
    # cumulative le semantics, +Inf covers everything
    assert hs["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}

    prom = reg.to_prometheus()
    assert "# TYPE mythril_batches_total counter" in prom
    assert "mythril_batches_total 3" in prom
    assert "# TYPE mythril_batch_seconds histogram" in prom
    assert 'mythril_batch_seconds_bucket{le="+Inf"} 3' in prom
    assert "mythril_batch_seconds_count 3" in prom
    # same-name re-registration under a different type is a bug
    with pytest.raises(TypeError):
        reg.gauge("batches_total")


def test_metrics_labeled_series_share_one_family():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("shed_total", help="sheds",
                labels={"reason": "depth"}).inc()
    reg.counter("shed_total", labels={"reason": "age"}).inc(2)
    reg.gauge("inflight", labels={"tenant": "a"}).set(3)
    # label order is canonicalized: same labels -> same series
    assert (obs_metrics.label_key("x", {"b": 1, "a": 2})
            == obs_metrics.label_key("x", {"a": 2, "b": 1}))
    prom = reg.to_prometheus()
    # ONE header block for the family, one sample line per series
    assert prom.count("# TYPE mythril_shed_total counter") == 1
    assert 'mythril_shed_total{reason="depth"} 1' in prom
    assert 'mythril_shed_total{reason="age"} 2' in prom
    assert 'mythril_inflight{tenant="a"} 3' in prom
    # snapshot keys carry the label block (JSON-side disambiguation)
    snap = reg.snapshot()
    assert snap["counters"]['shed_total{reason="age"}'] == 2.0
    # label values are escaped, never able to break the line format
    reg.counter("esc_total", labels={"v": 'a"b\nc'}).inc()
    assert 'mythril_esc_total{v="a\\"b c"} 1' in reg.to_prometheus()


def test_metrics_write_json_and_prom(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c").inc()
    j = str(tmp_path / "m.json")
    p = str(tmp_path / "m.prom")
    reg.write(j)
    reg.write(p)
    assert json.load(open(j))["counters"]["c"] == 1.0
    assert "mythril_c 1" in open(p).read()


def test_histogram_quantile():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 0.5, 1.0))
    assert h.quantile(0.5) is None                 # empty
    for v in (0.05, 0.2, 0.3, 0.8):
        h.observe(v)
    # bucket-walk estimate, clamped to the observed max
    assert h.quantile(0.5) == 0.5
    assert h.quantile(0.95) == 0.8


def test_metrics_delta_roundtrip():
    """snapshot_delta/apply_delta — the worker-telemetry metrics
    backhaul: only what changed crosses the IPC boundary, and folding
    it into the parent registry reproduces the increments."""
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c_total").inc(2)
    h = reg.histogram("h_seconds", buckets=(1.0,))
    h.observe(0.5)
    before = reg.snapshot()
    reg.counter("c_total").inc(3)
    h.observe(2.0)
    reg.gauge("g").set(7)
    delta = obs_metrics.snapshot_delta(reg.snapshot(), before)
    assert delta["counters"] == {"c_total": 3.0}
    dst = obs_metrics.MetricsRegistry()
    dst.histogram("h_seconds", buckets=(1.0,))     # same shape
    obs_metrics.apply_delta(delta, dst)
    snap = dst.snapshot()
    assert snap["counters"]["c_total"] == 3.0
    assert snap["gauges"]["g"] == 7.0
    hs = snap["histograms"]["h_seconds"]
    assert hs["count"] == 1 and hs["sum"] == 2.0
    assert hs["buckets"] == {"1.0": 0, "+Inf": 1}
    # an unchanged registry produces an EMPTY delta
    again = reg.snapshot()
    d2 = obs_metrics.snapshot_delta(again, again)
    assert not d2["counters"] and not d2["histograms"]


# --- campaign integration (stub runner — no engine) -------------------

N = 6
STUB_CONTRACTS = [(f"c{i:03d}", b"\x00") for i in range(N)]


def _stub_runner(bi, names, codes, lanes=None, width=None):
    return {"issues": [], "paths": len(names), "dropped": 0, "iprof": {}}


def _campaign(ckpt, fault=None, **kw):
    from mythril_tpu.mythril.campaign import CorpusCampaign
    from mythril_tpu.resilience import FaultInjector

    return CorpusCampaign(
        STUB_CONTRACTS, batch_size=2, checkpoint_dir=ckpt, spec=object(),
        batch_timeout=5.0, batch_runner=_stub_runner,
        fault_injector=FaultInjector.from_string(fault), **kw)


def test_campaign_events_carry_wall_mono_and_session(tmp_path):
    res = _campaign(str(tmp_path / "ck"), "oom:batch=1:times=1").run()
    degr = [e for e in res.backend_events if e["kind"] == "degrade"]
    assert degr
    for e in degr:
        assert e["t"] > 1e9                        # wall clock (epoch)
        assert isinstance(e["mono"], float)        # monotonic clock
        assert isinstance(e["session"], str) and e["session"]
    # one campaign instance = one session token on all its events
    assert len({e["session"] for e in degr}) == 1


def test_campaign_trace_bus_and_heartbeat_cadence(tmp_path, capsys):
    t = str(tmp_path / "t.json")
    obs_trace.configure(t)
    # heartbeat_every=0: a beat after EVERY batch
    res = _campaign(str(tmp_path / "ck"), heartbeat_every=0.0).run()
    obs_trace.close()
    assert res.batches == 3
    beats = [line for line in capsys.readouterr().err.splitlines()
             if line.startswith("heartbeat: ")]
    assert len(beats) == 3
    # the pulse carries the promised fields
    assert "contracts 6/6" in beats[-1]
    assert "paths/s" in beats[-1] and "ckpt-age" in beats[-1]
    events = read_jsonl(str(tmp_path / "t.jsonl"))
    kinds = [e["kind"] for e in events]
    assert kinds.count("heartbeat") == 3
    assert kinds.count("batch_status") == 3
    assert sum(1 for e in events
               if e["kind"] == "span" and e["name"] == "batch") == 3
    # every bus event satisfies the soak's schema contract
    assert all("kind" in e and "t" in e and "schema" in e for e in events)


def test_campaign_heartbeat_rate_limited(tmp_path, capsys):
    # a huge interval -> exactly one beat (the immediate first one)
    _campaign(str(tmp_path / "ck"), heartbeat_every=3600.0).run()
    beats = [line for line in capsys.readouterr().err.splitlines()
             if line.startswith("heartbeat: ")]
    assert len(beats) == 1


def test_campaign_batch_metrics(tmp_path):
    _campaign(str(tmp_path / "ck"), "raise:contract=c002").run()
    snap = obs_metrics.REGISTRY.snapshot()
    assert snap["counters"]["batches_total"] == 3.0
    assert snap["counters"]["contracts_quarantined_total"] == 1.0
    assert snap["counters"]["batch_retries_total"] == 1.0
    assert snap["histograms"]["batch_seconds"]["count"] == 3
    assert snap["histograms"]["checkpoint_write_seconds"]["count"] >= 3


def test_merge_campaigns_orders_events_by_session_then_time():
    from mythril_tpu.mythril.campaign import merge_campaigns

    # host A resumed once: session a1 (t 10..11) then a2 (t 20..21);
    # host B's single session overlaps both in wall time. Concatenation
    # order deliberately interleaves; the merge must group per session
    # and order within each by timestamp, stably.
    ra = {"backend_events": [
        {"kind": "x1", "t": 20.0, "session": "a2"},
        {"kind": "x2", "t": 21.0, "session": "a2"},
        {"kind": "x3", "t": 10.0, "session": "a1"},
        {"kind": "tie1", "t": 11.0, "session": "a1"},
        {"kind": "tie2", "t": 11.0, "session": "a1"},
    ]}
    rb = {"backend_events": [{"kind": "y1", "t": 15.0, "session": "b1"}]}
    got = merge_campaigns([ra, rb])["backend_events"]
    assert [e["kind"] for e in got] == ["x3", "tie1", "tie2", "x1", "x2",
                                       "y1"]
    # legacy events without session/t keep their relative order, first
    legacy = {"backend_events": [{"kind": "old1"}, {"kind": "old2"}]}
    got = merge_campaigns([legacy, rb])["backend_events"]
    assert [e["kind"] for e in got] == ["old1", "old2", "y1"]


def test_checkpoint_save_emits_span_and_latency(tmp_path):
    from mythril_tpu.utils.checkpoint import (load_json_checkpoint,
                                              save_json_checkpoint)

    t = str(tmp_path / "t.json")
    obs_trace.configure(t)
    p = str(tmp_path / "state.json")
    save_json_checkpoint(p, {"next_batch": 2})
    assert load_json_checkpoint(p)["next_batch"] == 2
    obs_trace.close()
    names = [e.get("name") for e in read_jsonl(str(tmp_path / "t.jsonl"))]
    assert "checkpoint_save" in names and "checkpoint_load" in names
    h = obs_metrics.REGISTRY.snapshot()["histograms"]
    assert h["checkpoint_write_seconds"]["count"] == 1


# --- trace_report tool ------------------------------------------------


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(ROOT, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_summarizes_both_formats(tmp_path, capsys):
    t = str(tmp_path / "t.json")
    obs_trace.configure(t)
    _campaign(str(tmp_path / "ck"), "oom:batch=1:times=1").run()
    obs_trace.close()

    tr = _load_trace_report()
    for path in (t, str(tmp_path / "t.jsonl")):
        assert tr.main([path]) == 0
        out = capsys.readouterr().out
        assert "top spans by total wall time" in out
        assert "batch stall table" in out
        assert "halve-lanes" in out                # degrade timeline row
        assert "checkpoint_save" in out or "saves:" in out
    assert tr.main([str(tmp_path / "nope.json")]) == 2


def test_trace_report_cross_process_timeline(tmp_path, capsys):
    """Section 10 regroups trace_id-stamped records per request and
    renders worker-side records (backhauled spans) as [worker] rows in
    one monotone timeline."""
    obs_trace.configure(str(tmp_path / "t.json"))
    tid = "9" * 16
    with obs_trace.trace_context(tid):
        with obs_trace.span("schedule", bi=0):
            obs_trace.reemit_records(
                [{"schema": 1, "kind": "span", "name": "device_phase",
                  "t": 1.0, "mono": 0.5, "dur": 0.2,
                  "session": "fakewkr", "trace_id": tid}],
                mono_offset=time.monotonic() - 0.5, proc="worker")
        obs_trace.event("verdict_commit", eid="e0")
    obs_trace.close()
    tr = _load_trace_report()
    assert tr.main([str(tmp_path / "t.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "cross-process timeline" in out
    assert f"trace {tid}" in out
    assert "[worker]" in out and "device_phase" in out
    assert "verdict_commit" in out
    # the per-stage totals table names the parent-side span too
    assert "schedule" in out
