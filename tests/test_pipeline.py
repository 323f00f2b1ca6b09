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


USED = ("taken", "waited", "failed", "inline")


def _prebuilt(before, after):
    """``pipeline_prebuilt_total{used}`` over a run, without the labels
    that stayed at 0."""
    got = {k: _delta(before, after,
                     f'pipeline_prebuilt_total{{used="{k}"}}')
           for k in USED}
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
            "starts": _starts(before, after),
            "prebuilt": _prebuilt(before, after)}
    return runs


BUILT = STAGES[:3]      # what ``build_batch`` emits, on whichever thread


def _feeder_builds(recs, dev):
    """The ``batch_build`` spans on ``dev``'s thread inside it."""
    end = dev["mono"] + dev["dur"]
    return [b for b in _named(recs, "batch_build")
            if b["tid"] == dev["tid"] and dev["mono"] <= b["mono"]
            and b["mono"] + b["dur"] <= end + 1e-5]


def _built_ahead(recs, taken):
    """The spans of the build a ``stage="prebuilt"`` span took: on the
    thread and inside the interval it names."""
    lo = taken["built_mono"] - 1e-5
    hi = taken["built_mono"] + taken["built_dur"] + 1e-5
    return [b for b in _named(recs, "batch_build")
            if b["tid"] == taken["built_tid"]
            and lo <= b["mono"] and b["mono"] + b["dur"] <= hi]


@pytest.mark.parametrize("which", ["first", "later"])
def test_batch_build_stages_once_a_batch_on_the_feeder_in_order(
        lead_in_runs, which):
    """The window's first batch is built by its own phase: four stages
    on the feeder. Every later one is built ahead, three stages on the
    pipeline's worker inside the phase before, after that phase's first
    call; its own phase takes the bundle (``prebuilt``) and starts. (The
    run whose calls wait with the lock released, as on the chip: where a
    call runs inside its enqueue the build may still be under way when
    it is asked for, ``waited``.)"""
    run = lead_in_runs["slow_call"]
    recs = run["recs"]
    assert lead_in_runs["traced"]["prebuilt"] in (
        {"inline": 1, "taken": 1}, {"inline": 1, "waited": 1})
    devs = _named(recs, "device_phase")
    builds = _named(recs, "batch_build")
    assert len(devs) == 2
    assert len(builds) == len(STAGES) + len(BUILT) + 2
    assert [d["prebuilt"] for d in devs] == ["inline", "taken"]
    if which == "first":
        mine = _feeder_builds(recs, devs[0])
        assert [b["stage"] for b in mine] == STAGES
        built = mine[:3]
    else:
        mine = _feeder_builds(recs, devs[1])
        assert [b["stage"] for b in mine] == ["prebuilt", "start"]
        taken = mine[0]
        assert taken["used"] == "taken"
        assert taken["built_tid"] != devs[1]["tid"]
        built = _built_ahead(recs, taken)
        assert [b["stage"] for b in built] == BUILT
        # beside the first phase's calls: nobody's lead-in
        first = _first_call(recs, devs[0])
        assert built[0]["mono"] >= first["mono"] + first["enqueue_s"] - 1e-5
        assert (built[-1]["mono"] + built[-1]["dur"]
                <= devs[0]["mono"] + devs[0]["dur"])
        assert run["prebuilt"] == {"inline": 1, "taken": 1}
    images, _, frontier = built
    assert images["images"] == 4 and images["code_bytes"] > 0
    assert frontier["frontier_bytes"] > 0


@pytest.mark.parametrize("which", ["first", "later"])
def test_batch_build_stages_cover_the_lead_in_without_overlap(
        lead_in_runs, which):
    recs = lead_in_runs["traced"]["recs"]
    d = _named(recs, "device_phase")[which == "later"]
    end = d["mono"] + d["dur"]
    mine = _feeder_builds(recs, d)
    assert len(mine) == (2 if which == "later" else len(STAGES))
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
    assert text.splitlines()[at + 1].startswith("(^: built ahead")
    rows = text.splitlines()[at + 3:at + 3 + 2 * (1 + len(STAGES)) + 1]
    # batch 1 was built ahead: its phase took the bundle and started,
    # and the worker's three stages are listed under it, marked
    assert [r.split()[0] for r in rows] == [
        "0", *STAGES, "1", "prebuilt", "start", *("^" + b for b in BUILT)]
    assert rows[1 + len(STAGES)].split()[1] in ("taken", "waited")
    assert rows[0].split()[1] == "inline"
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


# --- the look-ahead: batch k+1 is built while batch k's calls run --------

def _wallets():
    """The deploying shape of tests/test_campaign_creation.py: a wallet
    anyone may initialise, and one its constructor handed to the
    creator."""
    from test_campaign_creation import (CTOR_OPEN, CTOR_OWNER, GUARDED,
                                        WALLET)

    return [(WALLET, CTOR_OPEN), (GUARDED, CTOR_OWNER), (WALLET, CTOR_OWNER),
            (GUARDED, CTOR_OPEN)]


def _corpus_campaign(kind, tmp_path, **kw):
    """A campaign of three or four batches over a corpus of pairs, one
    whose contracts deploy, or one of linked systems of two."""
    import dataclasses

    from mythril_tpu.symbolic import SymSpec

    if kind == "pairs":
        corpus = str(tmp_path / "corpus")
        if not os.path.isdir(corpus):
            write_corpus(tmp_path, n=10)
        return make_campaign(corpus, **kw)
    common = dict(spec=SymSpec(storage=False), transaction_count=2,
                  modules=["AccidentallyKillable", "EtherThief"],
                  lanes_per_contract=16, **kw)
    if kind == "deploying":
        recs = [(f"d{i}", code, ctor)
                for i, (code, ctor) in enumerate(_wallets())]
        return CorpusCampaign(recs, batch_size=1, limits=TEST_LIMITS,
                              max_steps=128, **common)
    recs = [(f"s{k}_{j}", code, ctor,
             {"system": f"s{k}", "address": 0x1000 * (k + 1) + j})
            for k in range(12)
            for j, (code, ctor) in enumerate(_wallets()[k % 2::2])]
    return CorpusCampaign(
        recs, batch_size=8, max_steps=256,
        limits=dataclasses.replace(TEST_LIMITS, max_accounts=6), **common)


def _spy_builds(camp):
    """Record the batches ``camp``'s look-ahead builds: the contract
    names of every ``_build_batch`` call and the thread it ran on."""
    calls = []
    real = camp._build_batch

    def spy(items, tctx=None):
        calls.append(([i[0] for i in items],
                      threading.current_thread().name))
        return real(items, tctx)

    camp._build_batch = spy
    return calls


def _no_pool_thread_left():
    workers = [t for t in threading.enumerate()
               if t.name.startswith("host-phase")]
    for t in workers:
        t.join(timeout=20.0)
    return not [t.name for t in workers if t.is_alive()]


@pytest.mark.parametrize("kind", ["pairs", "deploying", "linked"])
def test_look_ahead_equals_the_serial_loop(tmp_path, kind):
    """Every batch but the window's first starts from a bundle built on
    the worker while the batch before explored, and nothing a run
    reports can tell: issues with their witnesses' steps, paths, dropped
    forks, statuses, and no program compiled by either loop once the
    shapes are warm."""
    _corpus_campaign(kind, tmp_path).run()          # compiles, if cold
    serial = _corpus_campaign(kind, tmp_path, pipeline=False).run()
    camp = _corpus_campaign(kind, tmp_path, pipeline=True)
    built = _spy_builds(camp)
    piped, recs, before, after = _traced_run(camp)
    assert _sig(piped) == _sig(serial)
    assert _sig(piped)["issues"]
    assert ([(i["contract"], i["swc-id"], len(i.get("tx_sequence") or ()))
             for i in piped.issues]
            == [(i["contract"], i["swc-id"], len(i.get("tx_sequence") or ()))
                for i in serial.issues])
    # (``engine_compiles`` counts a campaign's first use of a shape)
    assert piped.engine["xla_compiles"] == serial.engine["xla_compiles"]
    n = piped.batches
    assert n == serial.batches >= 3
    # one build a batch but the first, on the worker, in batch order
    assert [names for names, _ in built] == [
        [i[0] for i in camp._batch_items(bi)] for bi in range(1, n)]
    assert all(t.startswith("host-phase") for _, t in built)
    got = _prebuilt(before, after)
    assert got.pop("inline") == 1
    assert sum(got.values()) == n - 1 and set(got) <= {"taken", "waited"}
    devs = _named(recs, "device_phase")
    assert devs[0]["prebuilt"] == "inline"
    assert all(d["prebuilt"] in ("taken", "waited") for d in devs[1:])
    assert _no_pool_thread_left()


@pytest.mark.parametrize("kind", ["pairs", "deploying", "linked"])
def test_wrapper_handed_a_bundle_is_the_one_that_built_inline(kind,
                                                              tmp_path):
    """``build_batch`` on another thread gives the leaves it gives
    inline, and a wrapper that starts from the bundle ends where one
    that built for itself does."""
    import jax
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor

    from mythril_tpu.mythril.campaign import _split_records

    camp = _corpus_campaign(kind, tmp_path)
    items = camp._batch_items(0)
    with ThreadPoolExecutor(max_workers=1) as pool:
        ahead = pool.submit(camp._build_batch, items).result()
    here = camp._build_batch(items)
    assert ahead.tid != here.tid == threading.get_ident()
    assert ahead.names == here.names
    assert ahead.n_creation == here.n_creation
    assert ahead.known_addrs == here.known_addrs
    assert ahead.systems == here.systems

    def same(a, b):
        la, ta = jax.tree.flatten(a)
        lb, tb = jax.tree.flatten(b)
        assert ta == tb and len(la) == len(lb)
        for x, y in zip(la, lb):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(np.asarray(x), np.asarray(y))

    for leaf in ("sf", "corpus", "env"):
        same(getattr(ahead, leaf), getattr(here, leaf))
    names, codes, creations, links = _split_records(items)
    handed = camp._explore_batch(0, names, codes, creations=creations,
                                 links=links, build=ahead)
    inline = camp._explore_batch(0, names, codes, creations=creations,
                                 links=links)
    same(handed.sf, inline.sf)
    same(handed.corpus, inline.corpus)
    assert handed.images is ahead.images
    out = [camp._harvest_batch(0, sym) for sym in (handed, inline)]
    assert out[0]["issues"] == out[1]["issues"]
    assert (out[0]["paths"], out[0]["dropped"]) == (
        out[1]["paths"], out[1]["dropped"])


def test_wrapper_without_a_bundle_builds_on_its_own_thread_in_order():
    """``analyze``, ``serve``, a fleet unit and the serial loop pass no
    bundle: four ``batch_build`` spans on the calling thread, in order,
    as ever."""
    from mythril_tpu.analysis import SymExecWrapper
    from mythril_tpu.obs import trace as obs_trace

    obs_trace.close()
    tracer = obs_trace.configure(buffer=True)
    try:
        SymExecWrapper([KILLABLE, SAFE, KILLABLE, SAFE], limits=TEST_LIMITS,
                       lanes_per_contract=8, max_steps=64)
    finally:
        recs = tracer.drain_buffer()
        obs_trace.close()
    builds = _named(recs, "batch_build")
    assert [b["stage"] for b in builds] == STAGES
    assert {b["tid"] for b in builds} == {threading.get_ident()}


def test_a_build_that_raises_is_built_inline_and_fails_where_it_did(
        tmp_path, monkeypatch):
    """A batch whose build raises: the look-ahead's error is dropped,
    the phase builds for itself and raises there, and the batch drains
    through retry and bisection exactly as in the serial loop, the
    injector fired once an attempt; the batch after the drain is built
    inline."""
    from mythril_tpu.analysis import symbolic

    real = symbolic.build_batch

    def poisoned(bytecodes, contract_names=None, **kw):
        if "c005" in (contract_names or ()):
            raise ValueError("cannot pack c005")
        return real(bytecodes, contract_names=contract_names, **kw)

    monkeypatch.setattr(symbolic, "build_batch", poisoned)
    write_corpus(tmp_path, n=10)
    fired = {}

    def run(pipeline):
        camp = make_campaign(str(tmp_path / "corpus"),
                             fault="raise:contract=nobody",
                             pipeline=pipeline)
        fire = camp.fault_injector.fire
        fired[pipeline] = []

        def counting(**kw):
            fired[pipeline].append(kw["batch"])
            return fire(**kw)

        camp.fault_injector.fire = counting
        return camp

    serial = run(False).run()
    piped, recs, before, after = _traced_run(run(True))
    assert _sig(piped) == _sig(serial)
    assert [q["name"] for q in piped.quarantined] == ["c005"]
    assert piped.batch_status == ["ok", "quarantined:1", "ok"]
    assert piped.retries == serial.retries == 1
    assert fired[True] == fired[False]
    # batch 0 and, after the drain, batch 2 built for themselves; batch
    # 1's build raised on the worker and again in its own phase
    assert _prebuilt(before, after) == {"inline": 2, "failed": 1}
    failed = _named(recs, "device_phase")[1]
    assert failed["prebuilt"] == "failed"
    assert [b["stage"] for b in _feeder_builds(recs, failed)] == ["prebuilt"]
    assert _no_pool_thread_left()


@pytest.mark.parametrize("how", ["last_batch", "deadline", "kill"])
def test_no_build_beyond_the_run(tmp_path, how):
    """Nothing is built for a batch past ``n_batches``; a build whose
    batch the deadline or an ``InjectedKill`` cut off is dropped, not
    committed, and no pool thread is left."""
    import time

    write_corpus(tmp_path, n=10)
    camp = make_campaign(str(tmp_path / "corpus"), pipeline=True,
                         fault="kill:batch=1" if how == "kill" else None)
    built = _spy_builds(camp)
    if how == "deadline":
        # the window's deadline passes while batch 0 explores, with
        # batch 1's build at hand or under way
        loop, phase, at = camp._run_pipelined, camp._device_phase, []

        def with_deadline(start_batch, n_batches, deadline, commit):
            at.append(time.monotonic() + 2.0)
            return loop(start_batch, n_batches, at[0], commit)

        def outlasting(bi, items, **kw):
            handle = phase(bi, items, **kw)
            time.sleep(max(0.0, at[0] - time.monotonic()) + 0.01)
            return handle

        camp._run_pipelined, camp._device_phase = with_deadline, outlasting
    res, recs, before, after = _traced_run(camp)
    names = [n for n, _ in built]
    if how == "last_batch":
        assert res.batches == 3
        assert names == [[f"c{i:03d}" for i in range(4, 8)],
                         ["c008", "c009"]]
        got = _prebuilt(before, after)
        assert got.pop("inline") == 1
        assert sum(got.values()) == 2 and set(got) <= {"taken", "waited"}
    elif how == "kill":
        assert isinstance(res, InjectedKill)
        # batch 1 was built beside batch 0's calls; the kill fires
        # before its phase asks for the bundle
        assert names == [[f"c{i:03d}" for i in range(4, 8)]]
        assert _prebuilt(before, after) == {"inline": 1}
        assert [d["bi"] for d in _named(recs, "device_phase")] == [0]
    else:
        assert res.batches == 1 and res.batch_status == ["ok"]
        assert names == [[f"c{i:03d}" for i in range(4, 8)]]
        assert _prebuilt(before, after) == {"inline": 1}
        assert [d["bi"] for d in _named(recs, "device_phase")] == [0]
    assert _no_pool_thread_left()


def test_no_look_ahead_without_an_exploration_on_this_side(tmp_path):
    """A stub ``batch_runner`` and a worker-isolated batch explore
    nothing in this process: no build is submitted."""
    from test_campaign_creation import Spy

    def runner(bi, names, codes, lanes=None, width=None):
        return {"issues": [], "paths": len(names), "dropped": 0,
                "iprof": {}}

    from mythril_tpu.obs import metrics as obs_metrics

    before = obs_metrics.REGISTRY.snapshot()
    stub = CorpusCampaign([(f"c{i:03d}", b"\x00") for i in range(8)],
                          batch_size=2, batch_runner=runner, pipeline=True,
                          fault_injector=None)
    built = _spy_builds(stub)
    assert stub.run().batches == 4 and built == []
    isolated = CorpusCampaign(
        [(f"c{i:03d}", KILLABLE) for i in range(6)], batch_size=2,
        lanes_per_contract=8, limits=TEST_LIMITS, max_steps=64,
        pipeline=True, worker_isolation="on", worker_supervisor=Spy())
    built = _spy_builds(isolated)
    assert isolated.run().batches == 3 and built == []
    assert _prebuilt(before, obs_metrics.REGISTRY.snapshot()) == {}
    assert _no_pool_thread_left()


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
