"""Pipelined campaign (docs/performance.md): batch i's host phase
overlaps batch i+1's device phase from that phase's first ``sym_run``
call on, checkpoints move to a background writer — and NONE of it may
change results. The contract under test:

- pipelined == serial, byte-for-byte, on issues / paths / iprof /
  quarantine / batch_status (the acceptance bar for the overlap layer);
- any fault drains the pipeline back to the serial retry/bisect
  machinery with identical outcomes;
- kill+resume still never double-counts a contract, even though the
  durability point moved onto the writer thread.
"""

import os
import threading

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.mythril.campaign import CorpusCampaign, load_corpus_dir
from mythril_tpu.resilience import FaultInjector, InjectedKill
from mythril_tpu.utils.checkpoint import (BackgroundCheckpointWriter,
                                          ROTATE_SUFFIX,
                                          load_json_checkpoint)

KILLABLE = assemble(0, "SELFDESTRUCT")
SAFE = assemble(1, 0, "SSTORE", "STOP")


def write_corpus(tmp_path, n=6):
    d = tmp_path / "corpus"
    d.mkdir()
    for i in range(n):
        code = KILLABLE if i % 2 == 0 else SAFE
        (d / f"c{i:03d}.hex").write_text(code.hex())
    return str(d)


def make_campaign(corpus_dir, ckpt=None, fault=None, **kw):
    return CorpusCampaign(
        load_corpus_dir(corpus_dir),
        batch_size=4, lanes_per_contract=8, limits=TEST_LIMITS,
        max_steps=64, transaction_count=1,
        modules=["AccidentallyKillable"], checkpoint_dir=ckpt,
        fault_injector=FaultInjector.from_string(fault), **kw)


def _sig(res):
    """Everything the acceptance criteria require to be identical
    between a pipelined and a serial run (timings excluded — those
    are the point of the pipeline)."""
    return {
        "issues": sorted((i["contract"], i["swc-id"], i["batch"])
                         for i in res.issues),
        "paths_total": res.paths_total,
        "dropped_forks": res.dropped_forks,
        "iprof": res.iprof,
        "quarantined": [q["name"] for q in res.quarantined],
        "batch_status": res.batch_status,
        "retries": res.retries,
    }


def test_pipelined_matches_serial(tmp_path):
    corpus = write_corpus(tmp_path)
    serial = make_campaign(corpus, pipeline=False).run()
    piped = make_campaign(corpus, pipeline=True).run()
    assert _sig(piped) == _sig(serial)
    assert piped.batches == serial.batches == 2
    # sanity on the shared fixture: the three killable contracts
    assert _sig(piped)["issues"] and _sig(piped)["quarantined"] == []


def test_pipelined_drains_to_serial_on_fault(tmp_path):
    """A poison contract inside a pipelined batch must produce the
    EXACT serial outcome: drain, retry once, bisect, quarantine the
    poison — statuses, retries and the quarantine set all equal."""
    corpus = write_corpus(tmp_path)
    serial = make_campaign(corpus, fault="raise:contract=c002",
                           pipeline=False).run()
    piped = make_campaign(corpus, fault="raise:contract=c002",
                          pipeline=True).run()
    assert _sig(piped) == _sig(serial)
    assert [q["name"] for q in piped.quarantined] == ["c002"]
    assert piped.batch_status[0].startswith("quarantined:")


def test_pipelined_transient_fault_retries_once(tmp_path):
    """times=1 transient fault: the pipelined first attempt counts as
    THE first attempt (injector fires once in the device phase), so
    the retry-once policy cures it with retries == 1, like serial."""
    corpus = write_corpus(tmp_path)
    piped = make_campaign(corpus, fault="raise:batch=0:times=1",
                          pipeline=True).run()
    assert piped.retries == 1
    assert piped.batch_status == ["ok-retry", "ok"]
    assert not piped.quarantined
    assert sorted({i["contract"] for i in piped.issues}) == \
        ["c000", "c002", "c004"]


def test_pipelined_kill_resume_no_double_count(tmp_path):
    """InjectedKill mid-pipeline blows through uncommitted (the
    background writer must NOT flush on the way down); the resumed
    pipelined run replays only undurable batches and counts every
    contract exactly once."""
    corpus = write_corpus(tmp_path)
    ck = str(tmp_path / "ck")
    with pytest.raises(InjectedKill):
        make_campaign(corpus, ckpt=ck, fault="kill:batch=1",
                      pipeline=True).run()
    resumed = make_campaign(corpus, ckpt=ck, pipeline=True).run()
    assert resumed.batches == 2
    assert sorted(i["contract"] for i in resumed.issues) == \
        ["c000", "c002", "c004"]
    assert len(resumed.issues) == 3  # nothing double-counted
    state = load_json_checkpoint(os.path.join(ck, "campaign.json"))
    assert state["next_batch"] == 2


def test_pipeline_emits_overlap_telemetry(tmp_path):
    """The obs spine must carry the pipeline story: device/host phase
    spans, pipeline_stall spans, a pipeline_occupancy gauge, and the
    trace-report overlap summary must render it."""
    import importlib.util
    import json

    from mythril_tpu.obs import metrics as obs_metrics
    from mythril_tpu.obs import trace as obs_trace

    corpus = write_corpus(tmp_path)
    tpath = str(tmp_path / "t.json")
    obs_trace.configure(tpath)
    try:
        make_campaign(corpus, pipeline=True).run()
    finally:
        obs_trace.close()
    names = set()
    with open(str(tmp_path / "t.jsonl")) as fh:
        for line in fh:
            e = json.loads(line)
            if e.get("kind") == "span":
                names.add(e["name"])
    assert {"device_phase", "host_phase", "pipeline_stall",
            "batch"} <= names
    gauges = obs_metrics.REGISTRY.snapshot()["gauges"]
    assert "pipeline_occupancy" in gauges
    assert 0.0 <= gauges["pipeline_occupancy"] <= 1.0

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(root, "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    spans, instants = tr.load_trace(str(tmp_path / "t.jsonl"))
    text = tr.report(spans, instants)
    assert "pipeline overlap" in text
    assert "host time hidden behind device execution" in text


# --- the lead-in's spans, and what "hidden" means ----------------------

PHASES = ("device_phase", "host_phase", "batch_build")
STAGES = ["images", "corpus", "frontier", "start"]


def _named(recs, name):
    return sorted((r for r in recs if r.get("kind") == "span"
                   and r["name"] == name), key=lambda r: r["mono"])


def _hidden_by_the_spans(recs):
    """Seconds of the ``host_phase`` spans that passed while a
    ``superstep`` span of the feeder thread (the ``device_phase``
    spans' ``tid``) was open, and the sum of their durations."""
    feeder = {d["tid"] for d in _named(recs, "device_phase")}
    calls = [(c["mono"], c["mono"] + c["dur"])
             for c in _named(recs, "superstep") if c["tid"] in feeder]
    hosts = _named(recs, "host_phase")
    hidden = sum(max(0.0, min(h["mono"] + h["dur"], c1) - max(h["mono"], c0))
                 for h in hosts for c0, c1 in calls)
    return hidden, sum(h["dur"] for h in hosts)


AFTER = ("first_call", "phase_end", "no_next_phase")


def _delta(before, after, key):
    return (after["counters"].get(key, 0.0)
            - before["counters"].get(key, 0.0))


def _starts(before, after):
    """``pipeline_host_phase_starts_total{after}`` over a run, without
    the labels that stayed at 0."""
    got = {k: _delta(before, after,
                     f'pipeline_host_phase_starts_total{{after="{k}"}}')
           for k in AFTER}
    return {k: int(v) for k, v in got.items() if v}


def _first_call(recs, dev):
    """The first ``superstep`` span of ``dev``'s thread inside it."""
    return next(c for c in _named(recs, "superstep")
                if c["tid"] == dev["tid"]
                and dev["mono"] <= c["mono"] <= dev["mono"] + dev["dur"])


def _traced_run(camp, traced=True):
    """``camp.run()`` under a buffering tracer (``traced=False``: under
    none, and the buffer holds whatever a span emitted late): its result
    (or the kill it raised), its records and the registry before and
    after."""
    from mythril_tpu.obs import metrics as obs_metrics
    from mythril_tpu.obs import trace as obs_trace

    obs_trace.close()
    tracer = obs_trace.configure(buffer=True) if traced else None
    before = obs_metrics.REGISTRY.snapshot()
    try:
        res = camp.run()
    except InjectedKill as e:
        res = e
    finally:
        after = obs_metrics.REGISTRY.snapshot()
        if tracer is None:
            tracer = obs_trace.configure(buffer=True)
        recs = tracer.drain_buffer()
        obs_trace.close()
    return res, recs, before, after


@pytest.fixture(scope="module")
def lead_in_runs(tmp_path_factory):
    """Four runs of one two-batch pipelined campaign: untraced, traced,
    traced with a host phase slowed until it outlives the next batch's
    device phase, and traced with every ``sym_run`` call's read slowed
    (the lock released, as while the chip runs the call: on the CPU a
    call of this size may run inside its enqueue). For each its signature,
    its records and the registry's pipeline series over it."""
    import time

    from mythril_tpu.analysis import symbolic

    fetch = symbolic.fetch

    def slow_fetch(x, what):
        if what.startswith("visited"):
            time.sleep(0.3)
        return fetch(x, what)

    corpus = write_corpus(tmp_path_factory.mktemp("lead_in"))
    make_campaign(corpus, pipeline=True).run()      # compiles, if cold
    runs = {}
    for case in ("untraced", "traced", "slow_host", "slow_call"):
        camp = make_campaign(corpus, pipeline=True)
        if case == "slow_host":
            harvest = camp._harvest_batch

            def slow(bi, sym, harvest=harvest):
                time.sleep(0.4)
                return harvest(bi, sym)

            camp._harvest_batch = slow
        try:
            if case == "slow_call":
                symbolic.fetch = slow_fetch
            res, recs, before, after = _traced_run(
                camp, traced=case != "untraced")
        finally:
            symbolic.fetch = fetch
        runs[case] = {
            "sig": _sig(res), "recs": recs,
            "occupancy": after["gauges"]["pipeline_occupancy"],
            "hidden": _delta(before, after,
                             "pipeline_host_hidden_seconds_total"),
            "starts": _starts(before, after)}
    return runs


def test_batch_build_stages_once_a_batch_on_the_feeder_in_order(
        lead_in_runs):
    recs = lead_in_runs["traced"]["recs"]
    devs = _named(recs, "device_phase")
    builds = _named(recs, "batch_build")
    assert len(devs) == 2 and len(builds) == 2 * len(STAGES)
    for d in devs:
        end = d["mono"] + d["dur"]
        mine = [b for b in builds if b["tid"] == d["tid"]
                and d["mono"] <= b["mono"]
                and b["mono"] + b["dur"] <= end + 1e-5]
        assert [b["stage"] for b in mine] == STAGES
        images, _, frontier, _ = mine
        assert images["images"] == 4 and images["code_bytes"] > 0
        assert frontier["frontier_bytes"] > 0


def test_batch_build_stages_cover_the_lead_in_without_overlap(
        lead_in_runs):
    recs = lead_in_runs["traced"]["recs"]
    builds = _named(recs, "batch_build")
    for d in _named(recs, "device_phase"):
        end = d["mono"] + d["dur"]
        mine = [b for b in builds if d["mono"] <= b["mono"] <= end]
        first = next(c for c in _named(recs, "superstep")
                     if c["tid"] == d["tid"]
                     and d["mono"] <= c["mono"] <= end)
        # one after the other, the last ends where the call starts
        for a, b in zip(mine, mine[1:]):
            assert a["mono"] + a["dur"] <= b["mono"] + 2e-6
        last = mine[-1]
        assert last["stage"] == "start"
        assert last["mono"] + last["dur"] <= first["mono"] + 2e-6
        # what no stage holds is the hand-overs between them (a loaded
        # machine may take the thread off the CPU in one: 0.1 s of room)
        lead_in = first["mono"] - d["mono"]
        assert lead_in - sum(b["dur"] for b in mine) <= 0.05 * lead_in + 0.1


@pytest.mark.parametrize("name", PHASES)
def test_phase_spans_split_their_wall_clock(lead_in_runs, name):
    got = _named(lead_in_runs["traced"]["recs"], name)
    assert got
    for sp in got:
        assert sp["cpu_s"] >= 0.0 and sp["device_wait_s"] >= 0.0
        assert sp["cpu_s"] + sp["device_wait_s"] <= sp["dur"]
        assert sp["proc_cpu_s"] >= 0.0


def test_tracing_off_leaves_no_record_and_the_same_results(lead_in_runs):
    assert lead_in_runs["untraced"]["recs"] == []
    assert lead_in_runs["untraced"]["sig"] == lead_in_runs["traced"]["sig"]
    assert lead_in_runs["untraced"]["sig"]["issues"]
    # what is counted does not wait for a tracer
    assert 0.0 <= lead_in_runs["untraced"]["occupancy"] <= 1.0


@pytest.mark.parametrize("case", ["untraced", "traced", "slow_host",
                                  "slow_call"])
def test_host_phase_starts_at_the_next_batchs_first_call(lead_in_runs,
                                                         case):
    """Batch k's host phase starts once batch k+1's device phase has
    enqueued its first ``sym_run`` call, not beside its lead-in; the
    window's last starts at once. The span and the counter say what
    released each start, and the worker's idle time runs up to it."""
    run = lead_in_runs[case]
    assert run["starts"] == {"first_call": 1, "no_next_phase": 1}
    assert run["sig"] == lead_in_runs["traced"]["sig"]
    if case == "untraced":
        return
    recs = run["recs"]
    devs, hosts = _named(recs, "device_phase"), _named(recs, "host_phase")
    assert [h["bi"] for h in hosts] == [d["bi"] for d in devs] == [0, 1]
    assert [h["after"] for h in hosts] == ["first_call", "no_next_phase"]
    first = _first_call(recs, devs[1])
    # after the enqueue (the span's ``enqueue_s`` is rounded), so beside
    # no stage of the lead-in
    assert hosts[0]["mono"] >= first["mono"] + first["enqueue_s"] - 1e-5
    assert all(b["mono"] + b["dur"] <= hosts[0]["mono"]
               for b in _named(recs, "batch_build"))
    assert hosts[1]["mono"] >= devs[1]["mono"] + devs[1]["dur"]
    # the worker idle between the two host phases, up to the release
    (idle,) = [s for s in _named(recs, "pipeline_stall")
               if s["wait"] == "host-waits-device"]
    assert idle["bi"] == 1 and idle["tid"] == hosts[1]["tid"]
    assert abs(idle["mono"] - (hosts[0]["mono"] + hosts[0]["dur"])) <= 0.02
    assert idle["mono"] + idle["dur"] <= hosts[1]["mono"] + 1e-5


@pytest.mark.parametrize("case", ["traced", "slow_host", "slow_call"])
def test_occupancy_is_the_overlap_with_sym_run_calls(lead_in_runs, case):
    """``hidden`` means "while a ``sym_run`` call was in flight": the
    gauge, the counter and the ``batch`` spans' ``hidden`` agree with
    the overlap of the run's own ``host_phase`` and ``superstep``
    spans. A host phase starts when the next batch's first call is
    enqueued, so it hides what that call still has to run: nothing of
    its enqueue, and on the CPU a call of this size may be all enqueue."""
    run = lead_in_runs[case]
    hidden, host = _hidden_by_the_spans(run["recs"])
    assert host > 0.0
    assert 0.0 <= run["occupancy"] <= 1.0
    assert abs(run["occupancy"] - hidden / host) <= 0.02
    assert abs(run["hidden"] - hidden) <= 0.02 * host
    batches = [b for b in _named(run["recs"], "batch")
               if b.get("pipelined") and not b.get("drained")]
    assert len(batches) == 2
    assert abs(sum(b["hidden"] for b in batches) - run["hidden"]) <= 1e-4
    assert all(0.0 <= b["hidden"] <= b["host_dur"] + 1e-6
               for b in batches)
    # the window's last host phase has no device phase beside it
    assert batches[-1]["hidden"] == 0.0
    first = _named(run["recs"], "superstep")[-1]
    assert hidden <= first["dur"] - first["enqueue_s"] + 1e-4
    if case == "slow_call":
        # the call's read waits with the lock released, as on the chip:
        # the whole host phase fits under it
        assert batches[0]["hidden"] >= 0.9 * batches[0]["host_dur"] > 0.0
        assert batches[0]["stall"] <= 0.05
    if case == "slow_host":
        # it started inside the call and outlived the device phase: the
        # loop waited for it
        assert batches[0]["stall"] >= 0.3
    assert run["sig"] == lead_in_runs["traced"]["sig"]


@pytest.mark.parametrize("case", ["traced", "slow_host", "slow_call"])
def test_trace_report_reads_hidden_and_the_lead_in_off_the_spans(
        lead_in_runs, case, tmp_path):
    """The operator's reading: the report's hidden seconds are the
    gauge's (not host work less the stalls), beside them what released
    the host phases' starts, and every device phase has a row for its
    lead-in with a line a ``batch_build`` stage."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(root, "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    run = lead_in_runs[case]
    path = tmp_path / "t.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in run["recs"]))
    text = tr.report(*tr.load_trace(str(path)))
    hidden, host = _hidden_by_the_spans(run["recs"])
    (line,) = [ln for ln in text.splitlines() if ln.startswith(
        "host time hidden behind device execution:")]
    assert line == ("host time hidden behind device execution: "
                    f"{tr._fmt_s(hidden).strip()} "
                    f"({100.0 * hidden / host:.0f}% of host work)")
    assert text.splitlines()[text.splitlines().index(line) + 1] == (
        "host phases started after: first_call 1, phase_end 0, "
        "no_next_phase 1")
    if case == "slow_call":
        assert hidden > 0.25 * host
    at = text.splitlines().index(
        "lead-in of each device phase (start to first sym_run call), "
        "then its batch_build stages:")
    rows = text.splitlines()[at + 2:at + 2 + 2 * (1 + len(STAGES))]
    assert [r.split()[0] for r in rows] == ["0", *STAGES, "1", *STAGES]
    # no lead-in has a host phase beside it, batch 1's either (batch
    # 0's host phase waits for batch 1's first call): the last column
    assert rows[0].split()[-1] == "0.00ms"
    assert rows[1 + len(STAGES)].split()[-1] == "0.00ms"


def test_device_phase_that_fails_before_its_first_call_releases_at_its_end(
        tmp_path):
    """Batch 1's device phase raises before any ``sym_run`` call (the
    injector fires first): batch 0's host phase starts when that phase
    has ended, commits before batch 1 drains, and the run is the serial
    loop's."""
    corpus = write_corpus(tmp_path)
    fault = "raise:batch=1:times=1"
    serial = make_campaign(corpus, fault=fault, pipeline=False).run()
    piped, recs, before, after = _traced_run(
        make_campaign(corpus, fault=fault, pipeline=True))
    assert _sig(piped) == _sig(serial)
    assert piped.batch_status == ["ok", "ok-retry"]
    assert _starts(before, after) == {"phase_end": 1}
    # batch 1 drained to the serial loop, whose spans have no ``after``
    (host,) = [h for h in _named(recs, "host_phase") if "after" in h]
    failed = _named(recs, "device_phase")[1]
    assert host["bi"] == 0 and host["after"] == "phase_end"
    assert host["mono"] >= failed["mono"] + failed["dur"]
    assert not [c for c in _named(recs, "superstep")
                if failed["mono"] <= c["mono"] <= failed["mono"]
                + failed["dur"]]


def test_stub_runner_handles_release_at_the_phase_end(tmp_path):
    """An ``"out"`` handle made no ``sym_run`` call: the host phase
    before it (a pass-through) starts when the runner has returned, the
    last at once, and the run is the serial loop's."""
    def runner(bi, names, codes, lanes=None, width=None):
        return {"issues": [], "paths": len(names), "dropped": 0,
                "iprof": {}}

    def camp(pipeline):
        return CorpusCampaign(
            [(f"c{i:03d}", b"\x00") for i in range(8)], batch_size=2,
            batch_runner=runner, pipeline=pipeline, fault_injector=None)

    serial = camp(False).run()
    piped, recs, before, after = _traced_run(camp(True))
    assert _sig(piped) == _sig(serial)
    assert _starts(before, after) == {"phase_end": 3, "no_next_phase": 1}
    hosts, devs = _named(recs, "host_phase"), _named(recs, "device_phase")
    assert [h["after"] for h in hosts] == ["phase_end"] * 3 + [
        "no_next_phase"]
    for h, nxt in zip(hosts, devs[1:]):
        assert h["mono"] >= nxt["mono"] + nxt["dur"]


def test_kill_leaves_no_host_phase_thread_waiting(tmp_path):
    """An ``InjectedKill`` in batch 1's device phase blows through while
    batch 0's host phase still waits for its start: the start is given
    up, the worker does no work and ends (the interpreter joins pool
    threads at exit: one blocked on an event would hang it)."""
    corpus = write_corpus(tmp_path)
    err, recs, before, after = _traced_run(
        make_campaign(corpus, fault="kill:batch=1", pipeline=True))
    assert isinstance(err, InjectedKill)
    workers = [t for t in threading.enumerate()
               if t.name.startswith("host-phase")]
    for t in workers:
        t.join(timeout=20.0)
    assert not [t.name for t in workers if t.is_alive()]
    assert _starts(before, after) == {}
    assert not _named(recs, "host_phase")
    assert [d["bi"] for d in _named(recs, "device_phase")] == [0]


def test_a_start_is_released_once_whoever_races_for_it():
    """The first ``release`` wins and every waiter reads that one, with
    more racing threads than cores and the interpreter switching between
    them as often as it can."""
    import sys

    from mythril_tpu.mythril.campaign import _HostPhaseStart

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(50):
            start = _HostPhaseStart()
            seen, won = [], []

            def race(after, start=start, won=won):
                start.release(after)
                won.append(start.wait())

            threads = [threading.Thread(
                target=lambda: seen.append(start.wait()))
                for _ in range(4)] + [
                threading.Thread(target=race, args=(after,))
                for after in AFTER * 4]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=20.0)
            assert not any(t.is_alive() for t in threads)
            assert len(set(seen + won)) == 1 and seen[0] in AFTER
            assert len(seen) == 4 and len(won) == 12
            start.release(None)             # too late to give it up
            assert start.wait() == seen[0]
    finally:
        sys.setswitchinterval(was)


def test_pipeline_with_stub_runner_falls_through(tmp_path):
    """A custom batch_runner has no device/host seam: the handle
    carries its finished result and the pipeline degenerates to the
    serial order (runner called once per batch, in order). It has no
    host phase to hide either: 0 hidden seconds."""
    from mythril_tpu.obs import metrics as obs_metrics

    def hidden_total():
        return obs_metrics.REGISTRY.snapshot()["counters"].get(
            "pipeline_host_hidden_seconds_total", 0.0)

    hidden0 = hidden_total()
    calls = []

    def runner(bi, names, codes, lanes=None, width=None):
        calls.append(bi)
        return {"issues": [], "paths": len(names), "dropped": 0,
                "iprof": {}}

    c = CorpusCampaign([(f"c{i:03d}", b"\x00") for i in range(8)],
                       batch_size=2, batch_runner=runner, pipeline=True,
                       fault_injector=None)
    r = c.run()
    assert calls == [0, 1, 2, 3]
    assert r.batches == 4 and r.paths_total == 8
    assert r.batch_status == ["ok"] * 4
    assert hidden_total() == hidden0


# --- the background checkpoint writer ---------------------------------

def test_background_writer_durable_and_rotating(tmp_path):
    p = str(tmp_path / "campaign.json")
    w = BackgroundCheckpointWriter(p)
    w.submit({"next_batch": 1})
    w.flush()
    assert load_json_checkpoint(p)["next_batch"] == 1
    w.submit({"next_batch": 2})
    w.close()  # close flushes the queued write
    assert load_json_checkpoint(p)["next_batch"] == 2
    # the v2 rotation contract survived the move off-thread
    assert os.path.exists(p + ROTATE_SUFFIX)
    assert load_json_checkpoint(p + ROTATE_SUFFIX)["next_batch"] == 1
    with pytest.raises(RuntimeError):
        w.submit({"next_batch": 3})  # closed writer refuses work


def test_background_writer_coalesces_to_latest(tmp_path):
    p = str(tmp_path / "c.json")
    w = BackgroundCheckpointWriter(p)
    for i in range(50):  # submissions outpace fsync: latest must win
        w.submit({"next_batch": i})
    w.flush()
    w.close()
    assert load_json_checkpoint(p)["next_batch"] == 49


def test_background_writer_discard_pending(tmp_path):
    """close(discard_pending=True) is the simulated-kill path: a queued
    snapshot must NOT gain durability a real SIGKILL would deny it."""
    p = str(tmp_path / "c.json")
    w = BackgroundCheckpointWriter(p)
    w.submit({"next_batch": 1})
    w.flush()
    w.submit({"next_batch": 2})
    w.close(discard_pending=True)
    # the queued write may or may not have STARTED before close; either
    # way the on-disk state is one of the two consistent snapshots
    assert load_json_checkpoint(p)["next_batch"] in (1, 2)

    w2 = BackgroundCheckpointWriter(p + "x")
    w2.close(discard_pending=True)  # close with nothing queued is clean
    assert not os.path.exists(p + "x")
