"""Fault-isolated campaign runner (resilience layer).

The failure modes this repo has actually hit — a wedged backend that
hangs ``jax.devices()`` forever, a hung XLA
compile, a pathological contract crashing a batch — must cost a 10k
campaign at most the poison contracts, never the run. All fault paths
are exercised deterministically on CPU via the injection hook; the
tier-1 budget is respected by testing the supervisor machinery against
a stub batch runner (no engine) and reserving the real engine for one
raise-variant quarantine + kill/resume scenario that reuses the
test_campaign compiled shape.
"""

import json
import os

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.mythril.campaign import (CorpusCampaign, load_corpus_dir,
                                          merge_campaigns)
from mythril_tpu.resilience import (BackendManager, BatchTimeout,
                                    DeviceLostError, FaultInjector,
                                    FaultSpec, InjectedKill,
                                    ResilienceError, ResourceExhausted,
                                    classify_backend_error, parse_ladder,
                                    run_with_watchdog)
from mythril_tpu.utils.checkpoint import (CheckpointCorrupt,
                                          load_json_checkpoint)

# --- watchdog ---------------------------------------------------------


def test_watchdog_passthrough_and_timeout():
    import time

    assert run_with_watchdog(lambda: 42, None) == 42      # inline path
    assert run_with_watchdog(lambda: "ok", 5.0) == "ok"   # thread path
    with pytest.raises(BatchTimeout, match="wall-clock budget"):
        run_with_watchdog(lambda: time.sleep(30), 0.2, label="hung work")


def test_watchdog_relays_exceptions_including_base():
    def boom():
        raise ValueError("from the worker")

    with pytest.raises(ValueError, match="from the worker"):
        run_with_watchdog(boom, 5.0)

    def kill():
        raise InjectedKill("simulated SIGKILL")

    # BaseException must blow through too — a simulated kill cannot be
    # downgraded to a retryable batch failure by the watchdog seam
    with pytest.raises(InjectedKill):
        run_with_watchdog(kill, 5.0)


# --- fault specs ------------------------------------------------------


def test_fault_spec_parse_and_matching():
    s = FaultSpec.parse("raise:contract=c002:times=1")
    assert (s.mode, s.contract, s.times) == ("raise", "c002", 1)
    assert s.matches(0, ["c002", "c003"])
    assert not s.matches(0, ["c000"])
    s.fired = 1
    assert not s.matches(0, ["c002"])      # times budget spent

    b = FaultSpec.parse("hang:batch=2")
    assert b.matches(2, []) and not b.matches(1, [])

    for bad in ("explode:batch=1", "raise", "raise:frob=1", "raise:batch"):
        with pytest.raises(ValueError):
            FaultSpec.parse(bad)


def test_fault_injector_from_env(monkeypatch):
    monkeypatch.setenv("MYTHRIL_FAULT_INJECT",
                       "raise:batch=0:times=1;kill:batch=3")
    inj = FaultInjector.from_env()
    assert [s.mode for s in inj.specs] == ["raise", "kill"]
    with pytest.raises(ResilienceError):
        inj.fire(batch=0, contracts=["x"])
    inj.fire(batch=0, contracts=["x"])     # times=1: second pass clean
    with pytest.raises(InjectedKill):
        inj.fire(batch=3, contracts=[])
    assert len(inj.log) == 2
    monkeypatch.delenv("MYTHRIL_FAULT_INJECT")
    assert FaultInjector.from_env() is None


# --- backend-error classification + ladder parsing --------------------


def test_classify_backend_error():
    assert classify_backend_error(ResourceExhausted("boom")) == "oom"
    assert classify_backend_error(MemoryError()) == "oom"
    assert classify_backend_error(DeviceLostError("gone")) == "device-lost"

    class XlaRuntimeError(RuntimeError):
        """jaxlib look-alike: no stable subclasses per status code, so
        the classifier must go by the status string in the message."""

    assert classify_backend_error(XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 2147483648 bytes"
    )) == "oom"
    assert classify_backend_error(XlaRuntimeError(
        "Execution failed: DEVICE_LOST: device poll timeout")) == "device-lost"
    assert classify_backend_error(XlaRuntimeError(
        "XLA compilation failure: invalid HLO")) == "compile"
    assert classify_backend_error(ValueError("ordinary bug")) is None
    assert classify_backend_error(RuntimeError("failed to allocate "
                                               "device buffer")) == "oom"


def test_parse_ladder():
    assert parse_ladder(None) == ("halve-lanes", "halve-batch", "cpu")
    assert parse_ladder("halve-batch,cpu") == ("halve-batch", "cpu")
    assert parse_ladder("none") == ()
    assert parse_ladder("") == ()
    with pytest.raises(ValueError, match="rung"):
        parse_ladder("halve-lanes,frobnicate")


def test_oom_fault_mode_fires_resource_exhausted():
    inj = FaultInjector.from_string("oom:batch=1:times=1")
    with pytest.raises(ResourceExhausted, match="RESOURCE_EXHAUSTED"):
        inj.fire(batch=1, contracts=[])
    inj.fire(batch=1, contracts=[])        # times budget spent
    assert [e["mode"] for e in inj.log] == ["oom"]


# --- backend manager --------------------------------------------------


def test_backend_manager_bounded_retries_and_events():
    calls = []

    def probe(timeout_s):
        calls.append(timeout_s)
        return False, "injected probe failure"

    bm = BackendManager(init_timeout=0.5, max_attempts=3, backoff=0.0,
                        probe_fn=probe)
    ok, diag = bm.probe()
    assert not ok and "injected" in diag
    assert calls == [0.5, 0.5, 0.5]        # bounded re-init attempts
    assert [e["kind"] for e in bm.events] == ["probe_fail"] * 3
    assert [e["attempt"] for e in bm.events] == [1, 2, 3]


def test_backend_manager_cpu_fallback_event(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # restore after test
    bm = BackendManager(init_timeout=0.1, max_attempts=1, backoff=0.0,
                        probe_fn=lambda t: (False, "wedged"))
    ok, diag = bm.ensure_or_fallback()
    assert not ok
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert bm.events[-1]["kind"] == "cpu_fallback"

    good = BackendManager(probe_fn=lambda t: (True, "OK cpu 1"))
    ok, diag = good.ensure_or_fallback()
    assert ok and diag == "OK cpu 1"
    assert [e["kind"] for e in good.events] == ["probe_ok"]


def test_backend_manager_recover_records_device_loss():
    bm = BackendManager(probe_fn=lambda t: (True, "OK"), backoff=0.0)
    assert bm.recover(reason="injected device loss")
    kinds = [e["kind"] for e in bm.events]
    assert kinds == ["device_lost", "probe_ok"]


def test_backend_manager_real_subprocess_probe_on_cpu():
    """The genuine probe path: a child process inits the CPU backend
    inside the deadline (the wedge case can't be reproduced on CPU; the
    timeout path is covered by probe_fn injection above)."""
    bm = BackendManager(init_timeout=120.0, max_attempts=1)
    ok, diag = bm.probe()
    assert ok, diag
    assert diag.startswith("OK")


# --- campaign supervisor against a stub runner ------------------------

N = 6
STUB_CONTRACTS = [(f"c{i:03d}", b"\x00") for i in range(N)]


def _stub_runner(bi, names, codes):
    return {"issues": [{"contract": n, "batch": bi}
                       for n in names if not n.startswith("_pad_")],
            "paths": len(names), "dropped": 0, "iprof": {}}


def stub_campaign(ckpt, fault, batch_timeout=2.0, retries=1):
    return CorpusCampaign(
        STUB_CONTRACTS, batch_size=2, checkpoint_dir=ckpt,
        spec=object(),               # stub runner never touches the spec
        batch_timeout=batch_timeout,
        max_batch_retries=retries,
        fault_injector=FaultInjector.from_string(fault),
        batch_runner=_stub_runner,
    )


def test_stub_raise_fault_quarantines_only_poison(tmp_path):
    res = stub_campaign(str(tmp_path / "a"), "raise:contract=c002").run()
    assert res.batches == 3                      # run completed
    assert res.batch_status == ["ok", "quarantined:1", "ok"]
    assert [(q["name"], q["batch"]) for q in res.quarantined] == [("c002", 1)]
    assert "ResilienceError" in res.quarantined[0]["reason"]
    # the poison's batchmate and every other batch still analyzed
    assert ({i["contract"] for i in res.issues}
            == {"c000", "c001", "c003", "c004", "c005"})
    assert res.retries == 1                      # the retry-once attempt


def test_stub_hang_fault_times_out_and_quarantines(tmp_path):
    res = stub_campaign(str(tmp_path / "h"), "hang:contract=c003",
                        batch_timeout=0.3).run()
    assert res.batches == 3
    assert [(q["name"], q["batch"]) for q in res.quarantined] == [("c003", 1)]
    assert res.quarantined[0]["reason"].startswith("timeout:")
    assert ({i["contract"] for i in res.issues}
            == {"c000", "c001", "c002", "c004", "c005"})


def test_stub_transient_fault_cured_by_retry(tmp_path):
    res = stub_campaign(str(tmp_path / "t"), "raise:batch=0:times=1").run()
    assert res.retries == 1 and not res.quarantined
    assert res.batch_status == ["ok-retry", "ok", "ok"]
    assert len(res.issues) == N                  # nothing lost


def test_stub_device_lost_triggers_backend_recovery(tmp_path):
    bm = BackendManager(probe_fn=lambda t: (True, "OK"), backoff=0.0)
    c = stub_campaign(str(tmp_path / "d"), "device-lost:batch=1:times=1")
    c.backend = bm
    res = c.run()
    assert res.batch_status[1] == "ok-retry" and res.retries == 1
    kinds = [e["kind"] for e in res.backend_events]
    assert "device_lost" in kinds and "probe_ok" in kinds


def test_stub_kill_resume_no_double_count(tmp_path):
    """Acceptance: kill mid-campaign via injected fault, resume, and the
    final issue set / contract counts / quarantine list match a straight
    faulted run — nothing double-counted, nothing silently skipped."""
    ck = str(tmp_path / "k")
    with pytest.raises(InjectedKill):
        stub_campaign(ck, "raise:contract=c002;kill:batch=2").run()
    # the kill struck AFTER batch 1 checkpointed, BEFORE batch 2 did
    state = load_json_checkpoint(os.path.join(ck, "campaign.json"))
    assert state["next_batch"] == 2
    assert [q["name"] for q in state["quarantined"]] == ["c002"]

    resumed = stub_campaign(ck, "raise:contract=c002").run()
    straight = stub_campaign(str(tmp_path / "s"),
                             "raise:contract=c002").run()
    for a, b in ((resumed, straight),):
        assert a.batches == b.batches == 3
        assert a.contracts == b.contracts == N
        assert (sorted(i["contract"] for i in a.issues)
                == sorted(i["contract"] for i in b.issues))
        assert a.quarantined == b.quarantined
    # quarantine persisted across the kill: exactly one entry, not two
    assert [q["name"] for q in resumed.quarantined] == ["c002"]


def test_stub_old_checkpoint_schema_resumes(tmp_path):
    """A pre-resilience checkpoint (no quarantined/retries/batch_status/
    backend_events keys) must resume cleanly with defaulted fields."""
    ck = str(tmp_path / "old")
    with pytest.raises(InjectedKill):
        stub_campaign(ck, "kill:batch=1").run()
    p = os.path.join(ck, "campaign.json")
    state = load_json_checkpoint(p)
    for k in ("quarantined", "retries", "batch_status", "backend_events"):
        del state[k]
    # written back as a BARE state dict — the pre-versioning (v1) JSON
    # format, so this also covers the old-format load path
    json.dump(state, open(p, "w"))
    if os.path.exists(p + ".1"):
        os.unlink(p + ".1")  # v1 runs never rotated
    res = stub_campaign(ck, None).run()
    assert res.batches == 3 and res.retries == 0
    # pre-kill batches carry no status marker in the rewound schema —
    # only the post-resume batches are re-attributed
    assert res.batch_status == ["ok", "ok"]


# --- degradation ladder (stub runner) ---------------------------------


def _degradable_stub(calls):
    """Stub runner that understands degraded capacity: records every
    (batch, n_items, lanes, width) attempt for assertions."""

    def runner(bi, names, codes, lanes=None, width=None):
        calls.append((bi, len(names), lanes, width))
        return {"issues": [{"contract": n, "batch": bi}
                           for n in names if not n.startswith("_pad_")],
                "paths": len(names), "dropped": 0, "iprof": {}}

    return runner


def degradable_campaign(ckpt, fault, calls, **kw):
    return CorpusCampaign(
        STUB_CONTRACTS, batch_size=2, checkpoint_dir=ckpt,
        spec=object(), batch_timeout=5.0,
        fault_injector=FaultInjector.from_string(fault),
        batch_runner=_degradable_stub(calls), **kw)


def test_oom_degrades_one_rung_and_completes(tmp_path):
    """Acceptance: a batch that OOMs completes after an automatic lane
    shrink — visible as backend_events ladder steps — instead of
    failing/quarantining anything."""
    calls = []
    res = degradable_campaign(str(tmp_path / "o1"),
                              "oom:batch=1:times=1", calls).run()
    assert res.batches == 3
    assert res.batch_status == ["ok", "ok-degraded:halve-lanes", "ok"]
    assert not res.quarantined and res.retries == 0
    steps = [e["step"] for e in res.backend_events
             if e["kind"] == "degrade"]
    assert steps == ["halve-lanes"]
    assert any(e["kind"] == "degrade_ok" for e in res.backend_events)
    # every contract analyzed exactly once
    assert (sorted(i["contract"] for i in res.issues)
            == [f"c{i:03d}" for i in range(N)])
    # the degraded attempt really ran with halved frontier lanes
    degraded = [c for c in calls if c[2] is not None]
    assert degraded == [(1, 2, 16, 2)]     # default 32 lanes -> 16


def test_oom_walks_ladder_cumulatively_to_halve_batch(tmp_path):
    """Two consecutive OOMs walk to the second rung: lanes stay halved
    AND the batch replays as two half-width sub-batches."""
    calls = []
    res = degradable_campaign(str(tmp_path / "o2"),
                              "oom:batch=0:times=2", calls).run()
    assert res.batch_status[0] == "ok-degraded:halve-batch"
    steps = [e["step"] for e in res.backend_events
             if e["kind"] == "degrade"]
    assert steps == ["halve-lanes", "halve-batch"]
    # the successful rung: two sub-batches of width 1, lanes still 16
    sub = [c for c in calls if c[3] == 1]
    assert sub == [(0, 1, 16, 1), (0, 1, 16, 1)]
    assert (sorted(i["contract"] for i in res.issues)
            == [f"c{i:03d}" for i in range(N)])


def test_oom_cpu_rung_and_event_trail(tmp_path):
    """Three consecutive OOMs reach the CPU rung (full ladder)."""
    calls = []
    res = degradable_campaign(str(tmp_path / "o3"),
                              "oom:batch=0:times=3", calls).run()
    # times=3: full attempt, halve-lanes, and halve-batch's FIRST
    # sub-attempt each fire (a failed rung discards partial results);
    # the cpu rung's sub-attempts run clean
    assert res.batch_status[0] == "ok-degraded:cpu"
    steps = [e["step"] for e in res.backend_events
             if e["kind"] == "degrade"]
    assert steps == ["halve-lanes", "halve-batch", "cpu"]
    assert (sorted(i["contract"] for i in res.issues)
            == [f"c{i:03d}" for i in range(N)])


def test_oom_ladder_exhausted_falls_to_quarantine(tmp_path):
    """A persistent per-contract OOM (poison, not pressure) exhausts the
    ladder and lands in the retry→bisect machinery: the run survives,
    the poison is quarantined with the RESOURCE_EXHAUSTED reason."""
    res = stub_campaign(str(tmp_path / "oq"), "oom:contract=c002").run()
    assert res.batches == 3
    assert [(q["name"], q["batch"]) for q in res.quarantined] == [("c002", 1)]
    assert "RESOURCE_EXHAUSTED" in res.quarantined[0]["reason"]
    assert res.batch_status == ["ok", "quarantined:1", "ok"]
    steps = [e["step"] for e in res.backend_events
             if e["kind"] == "degrade"]
    assert steps == ["halve-lanes", "halve-batch", "cpu"]
    assert ({i["contract"] for i in res.issues}
            == {"c000", "c001", "c003", "c004", "c005"})


def test_oom_ladder_disabled_goes_straight_to_retry(tmp_path):
    calls = []
    res = degradable_campaign(str(tmp_path / "o0"),
                              "oom:batch=1:times=1", calls,
                              oom_ladder=()).run()
    # no ladder: the transient OOM is cured by the ordinary retry
    assert res.batch_status == ["ok", "ok-retry", "ok"]
    assert res.retries == 1
    assert not [e for e in res.backend_events if e["kind"] == "degrade"]


# --- checkpoint cadence + torn-checkpoint resume ----------------------


def test_checkpoint_every_bounds_loss_no_double_count(tmp_path):
    ck = str(tmp_path / "ce")

    def mk(fault):
        return CorpusCampaign(
            STUB_CONTRACTS, batch_size=1, checkpoint_dir=ck,
            spec=object(), batch_runner=_stub_runner,
            checkpoint_every=2,
            fault_injector=FaultInjector.from_string(fault))

    with pytest.raises(InjectedKill):
        mk("kill:batch=3").run()
    # batches 0..2 ran; with N=2 cadence only batches 0-1 are durable —
    # the kill loses at most checkpoint_every batches
    state = load_json_checkpoint(os.path.join(ck, "campaign.json"))
    assert state["next_batch"] == 2
    assert len(state["issues"]) == 2
    res = mk(None).run()
    assert res.batches == N
    # batch 2's unpersisted first-attempt results died with the kill, so
    # its replay cannot double-count
    assert (sorted(i["contract"] for i in res.issues)
            == [f"c{i:03d}" for i in range(N)])


def test_torn_campaign_checkpoint_falls_back_to_rotation(tmp_path):
    """Acceptance: SIGKILL mid-checkpoint-write (simulated by truncating
    the newest checkpoint at several offsets) resumes from the rotated
    last-known-good copy, losing at most one batch, analyzing nothing
    twice."""
    ck = str(tmp_path / "torn")
    with pytest.raises(InjectedKill):
        stub_campaign(ck, "kill:batch=2").run()   # batches 0,1 durable
    p = os.path.join(ck, "campaign.json")
    raw = open(p, "rb").read()
    for cut in (0, 7, len(raw) // 2, len(raw) - 2):
        with open(p, "wb") as fh:
            fh.write(raw[:cut])
        res = stub_campaign(ck, None).run()
        assert "checkpoint_recovered" in [e["kind"]
                                          for e in res.backend_events]
        # rotated copy says next_batch=1: batch 1 replays (its results
        # were only in the discarded torn file), batch 2 runs — every
        # contract exactly once
        assert res.batches == 3
        assert (sorted(i["contract"] for i in res.issues)
                == [f"c{i:03d}" for i in range(N)])
        # restore the torn newest for the next tear shape
        with open(p, "wb") as fh:
            fh.write(raw)


def test_first_checkpoint_torn_starts_fresh(tmp_path):
    ck = str(tmp_path / "fresh")
    with pytest.raises(InjectedKill):
        stub_campaign(ck, "kill:batch=1").run()   # only batch 0 durable
    p = os.path.join(ck, "campaign.json")
    if os.path.exists(p + ".1"):
        os.unlink(p + ".1")
    with open(p, "w") as fh:
        fh.write('{"__schema__": 2, "sha256": "tor')
    res = stub_campaign(ck, None).run()
    assert res.batches == 3
    assert (sorted(i["contract"] for i in res.issues)
            == [f"c{i:03d}" for i in range(N)])
    assert "checkpoint_reset" in [e["kind"] for e in res.backend_events]


def test_merge_campaigns_carries_resilience_fields():
    r0 = {"contracts": 3, "batches": 1, "issues": 1, "wall_sec": 1.0,
          "quarantined": [{"name": "c002", "reason": "x", "batch": 0}],
          "retries": 2, "batch_status": ["quarantined:1"],
          "backend_events": [{"kind": "probe_ok"}]}
    r1 = {"contracts": 3, "batches": 1, "issues": 2, "wall_sec": 2.0,
          "quarantined": [], "retries": 0, "batch_status": ["ok"]}
    m = merge_campaigns([r0, r1])
    assert [q["name"] for q in m["quarantined"]] == ["c002"]
    assert m["retries"] == 2
    assert m["batch_status"] == ["quarantined:1", "ok"]
    assert [e["kind"] for e in m["backend_events"]] == ["probe_ok"]


# --- real engine: raise-variant quarantine + kill/resume --------------

KILLABLE = assemble(0, "SELFDESTRUCT")
SAFE = assemble(1, 0, "SSTORE", "STOP")


def write_corpus(tmp_path, n=6):
    d = tmp_path / "corpus"
    d.mkdir(exist_ok=True)
    for i in range(n):
        code = KILLABLE if i % 2 == 0 else SAFE
        (d / f"c{i:03d}.hex").write_text(code.hex())
    return str(d)


def engine_campaign(corpus_dir, ckpt=None, fault=None):
    # same shapes as tests/test_campaign.py: one compiled engine serves
    # both files' batches via the persistent compilation cache
    return CorpusCampaign(
        load_corpus_dir(corpus_dir),
        batch_size=4, lanes_per_contract=8, limits=TEST_LIMITS,
        max_steps=64, transaction_count=1,
        modules=["AccidentallyKillable"], checkpoint_dir=ckpt,
        fault_injector=FaultInjector.from_string(fault),
    )


def test_engine_fault_quarantine_kill_and_resume(tmp_path):
    """Real-engine acceptance path: poison contract c002 (itself
    killable) in batch 0 of 2, killed before batch 1 checkpoints, then
    resumed — all non-poison contracts are analyzed exactly once and
    the poison is quarantined with a reason, across the kill."""
    corpus = write_corpus(tmp_path)
    ck = str(tmp_path / "ck")
    with pytest.raises(InjectedKill):
        engine_campaign(corpus, ckpt=ck,
                        fault="raise:contract=c002;kill:batch=1").run()
    state = load_json_checkpoint(os.path.join(ck, "campaign.json"))
    assert state["next_batch"] == 1
    assert [q["name"] for q in state["quarantined"]] == ["c002"]

    resumed = engine_campaign(corpus, ckpt=ck,
                              fault="raise:contract=c002").run()
    assert resumed.batches == 2 and resumed.contracts == 6
    assert [(q["name"], q["batch"])
            for q in resumed.quarantined] == [("c002", 0)]
    assert resumed.batch_status == ["quarantined:1", "ok"]
    # killable contracts are c000/c002/c004; the quarantined poison is
    # the ONLY missing finding, and nothing is double-counted
    found = sorted(i["contract"] for i in resumed.issues)
    assert found == ["c000", "c004"], found
    assert all(i["swc-id"] == "106" for i in resumed.issues)

    # straight faulted run (no kill) reproduces the same final state
    straight = engine_campaign(corpus, ckpt=str(tmp_path / "ck2"),
                               fault="raise:contract=c002").run()
    assert straight.contracts == resumed.contracts
    assert (sorted(i["contract"] for i in straight.issues) == found)
    assert ([(q["name"], q["batch"]) for q in straight.quarantined]
            == [(q["name"], q["batch"]) for q in resumed.quarantined])


def test_cli_campaign_oom_degrade_end_to_end(tmp_path, capsys):
    """Acceptance via the CLI with the REAL engine: a batch that OOMs
    (injected) completes after the automatic lane shrink — the ladder
    step is visible in backend_events, nothing is quarantined, and the
    issue set matches an unfaulted run."""
    from mythril_tpu.interfaces.cli import main

    corpus = write_corpus(tmp_path)
    rc = main(["analyze", "--corpus", corpus, "--batch-size", "4",
               "--lanes-per-contract", "8", "--max-steps", "64",
               "--limits-profile", "test", "-t", "1",
               "-m", "AccidentallyKillable", "-o", "json",
               "--fault-inject", "oom:batch=0:times=1",
               "--oom-ladder", "halve-lanes",
               "--checkpoint-every", "2",
               "--checkpoint-dir", str(tmp_path / "ck")])
    out = capsys.readouterr().out
    assert rc == 0
    payload = json.loads(out)
    assert payload["batch_status"][0] == "ok-degraded:halve-lanes"
    steps = [e.get("step") for e in payload["backend_events"]
             if e["kind"] == "degrade"]
    assert steps == ["halve-lanes"]
    assert not payload["quarantined"]
    # the degraded (4-lane) replay still finds every killable contract
    assert ({i["contract"] for i in payload["issues_detail"]}
            == {"c000", "c002", "c004"})


def test_cli_campaign_fault_flags(tmp_path, capsys):
    """--fault-inject / --batch-timeout / --max-batch-retries thread
    through the CLI into the campaign; the JSON report carries the
    quarantine."""
    from mythril_tpu.interfaces.cli import main

    corpus = write_corpus(tmp_path)
    rc = main(["analyze", "--corpus", corpus, "--batch-size", "4",
               "--lanes-per-contract", "8", "--max-steps", "64",
               "--limits-profile", "test", "-t", "1",
               "-m", "AccidentallyKillable", "-o", "json",
               "--fault-inject", "raise:contract=c002",
               "--max-batch-retries", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    payload = json.loads(out)
    assert [q["name"] for q in payload["quarantined"]] == ["c002"]
    assert payload["retries"] >= 1
    assert payload["batch_status"][0] == "quarantined:1"
    assert ({i["contract"] for i in payload["issues_detail"]}
            == {"c000", "c004"})
