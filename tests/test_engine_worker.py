"""Supervised engine worker: subprocess isolation, restart backoff,
crash-loop breaker, and campaign/serve wiring (docs/resilience.md
"Process isolation & supervision").

Most tests drive the STUB worker — a real subprocess speaking the real
length-prefixed pickle protocol over real pipes, killed by real
signals, but skipping the engine import — so the supervision machinery
(deadlines, deaths, breaker transitions, exactly-once accounting under
kill+resume) is exercised in milliseconds. One slow test runs the
headline acceptance scenario against the real engine: a SIGSEGV
injected mid-superstep is survived with a byte-identical issue set.
"""

import os
import signal
import time

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.mythril.campaign import CorpusCampaign
from mythril_tpu.resilience import (BatchTimeout, FaultInjector,
                                    FaultSpec, InjectedKill,
                                    WorkerCrashLoop, WorkerDied,
                                    WorkerSupervisor)


def stub_supervisor(**kw):
    kw.setdefault("stub", True)
    kw.setdefault("batch_timeout", 30.0)
    kw.setdefault("backoff_base", 0.01)
    kw.setdefault("spawn_timeout", 60.0)
    return WorkerSupervisor(**kw)


def kinds(events):
    return [e["kind"] for e in events]


# --- supervisor mechanics -------------------------------------------------

def test_stub_worker_roundtrip_and_rss():
    sup = stub_supervisor()
    try:
        out = sup.run_batch(0, ["a", "b"], [b"\x00", b"\x01"])
        # the reply carries the child's stage attribution (host-phase
        # seconds; device = parent wall - host, computed campaign-side)
        ph = out.pop("phases")
        assert set(ph) == {"device", "host"} and ph["host"] >= 0.0
        assert out == {"issues": [], "paths": 2, "dropped": 0,
                       "iprof": {}}
        st = sup.status()
        assert st["alive"] and st["breaker"] == "closed"
        assert st["rss_bytes"] > 0          # /proc-read gauge source
        assert "worker_spawn" in kinds(sup.events)
    finally:
        sup.close()
    assert not sup.alive()


def test_parent_deadline_kills_hung_worker():
    sup = stub_supervisor(batch_timeout=0.5)
    try:
        with pytest.raises(BatchTimeout):
            sup.run_batch(0, ["__hang__"], [b"\x00"])
        assert not sup.alive()              # the wedged worker is dead
        assert kinds(sup.events).count("worker_death") == 1
        # the next batch respawns and succeeds
        out = sup.run_batch(1, ["a"], [b"\x00"])
        assert out["paths"] == 1
        assert sup.restarts == 1
        assert "worker_restart" in kinds(sup.events)
    finally:
        sup.close()


@pytest.mark.parametrize("mode,signo", [("worker-kill", signal.SIGKILL),
                                        ("worker-segv", signal.SIGSEGV)])
def test_worker_signal_death_and_restart(mode, signo):
    """A real signal into the worker process surfaces as WorkerDied
    with the signal in the exit code, never as parent death."""
    inj = FaultInjector([FaultSpec.parse(f"{mode}:nth=1")])
    sup = stub_supervisor(fault_injector=inj)
    try:
        with pytest.raises(WorkerDied) as ei:
            sup.run_batch(0, ["a"], [b"\x00"])
        assert f"rc={-signo}" in str(ei.value)
        assert inj.log and inj.log[0]["mode"] == mode
        # restart cures it (the spec fired once)
        assert sup.run_batch(0, ["a"], [b"\x00"])["paths"] == 1
    finally:
        sup.close()


def test_breaker_opens_pins_and_closes_after_clean_window():
    """worker-kill:nth=1..3 -> three rapid deaths -> breaker opens
    (WorkerCrashLoop); after the cooldown one half-open probe closes
    it."""
    inj = FaultInjector([FaultSpec.parse("worker-kill:nth=1"),
                         FaultSpec.parse("worker-kill:nth=2"),
                         FaultSpec.parse("worker-kill:nth=3")])
    sup = stub_supervisor(fault_injector=inj, breaker_threshold=3,
                          breaker_window=30.0, breaker_cooldown=0.4)
    try:
        for bi in range(3):
            with pytest.raises(WorkerDied):
                sup.run_batch(bi, ["a"], [b"\x00"])
        assert sup.breaker_state() == "open"
        assert "breaker_open" in kinds(sup.events)
        with pytest.raises(WorkerCrashLoop):
            sup.run_batch(3, ["a"], [b"\x00"])
        time.sleep(0.5)
        assert sup.breaker_state() == "half-open"
        out = sup.run_batch(4, ["a"], [b"\x00"])  # the probe succeeds
        assert out["paths"] == 1
        assert sup.breaker_state() == "closed"
        assert "breaker_close" in kinds(sup.events)
    finally:
        sup.close()


def test_breaker_reopens_when_half_open_probe_dies():
    inj = FaultInjector([FaultSpec.parse(f"worker-kill:nth={k}")
                         for k in (1, 2, 3)])
    sup = stub_supervisor(fault_injector=inj, breaker_threshold=2,
                          breaker_window=30.0, breaker_cooldown=0.2)
    try:
        for bi in range(2):
            with pytest.raises(WorkerDied):
                sup.run_batch(bi, ["a"], [b"\x00"])
        assert sup.breaker_state() == "open"
        time.sleep(0.3)
        with pytest.raises(WorkerDied):   # half-open probe dies (nth=3)
            sup.run_batch(2, ["a"], [b"\x00"])
        assert sup.breaker_state() == "open"   # re-opened, fresh cooldown
        assert kinds(sup.events).count("breaker_open") == 2
    finally:
        sup.close()


# --- campaign wiring ------------------------------------------------------

def make_campaign(contracts, sup, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("lanes_per_contract", 4)
    kw.setdefault("max_steps", 16)
    return CorpusCampaign(contracts, limits=TEST_LIMITS,
                          worker_isolation="on", worker_supervisor=sup,
                          **kw)


STUB_CORPUS = [(f"c{i:03d}", bytes([i])) for i in range(6)]


def test_campaign_worker_path_accounting_and_close():
    sup = stub_supervisor()
    camp = make_campaign(STUB_CORPUS, sup)
    res = camp.run()
    assert res.batches == 3 and res.paths_total == 6
    assert res.batch_status == ["ok", "ok", "ok"]
    assert "worker_spawn" in [e.get("kind") for e in res.backend_events]
    # run() closed the worker: no orphan subprocess outlives the run
    assert camp._supervisor is None and not sup.alive()


def test_campaign_worker_death_replays_through_retry():
    inj = FaultInjector([FaultSpec.parse("worker-kill:nth=2")])
    sup = stub_supervisor(fault_injector=inj)
    camp = make_campaign(STUB_CORPUS, sup, fault_injector=inj)
    res = camp.run()
    # batch 1's dispatch died; the retry replayed it on a fresh worker
    assert res.retries == 1 and not res.quarantined
    assert res.paths_total == 6             # every contract once
    assert res.batch_status == ["ok", "ok-retry", "ok"]
    ks = [e.get("kind") for e in res.backend_events]
    assert ks.count("worker_death") == 1
    assert ks.count("worker_restart") == 1


def test_campaign_breaker_pins_cpu_and_finishes(tmp_path):
    """A crash-looping worker opens the breaker mid-campaign; the
    remaining batches run in-process pinned to CPU — with a stub
    batch_runner standing in for the engine there, injected through
    the supervisor-bypass seam."""
    inj = FaultInjector([FaultSpec.parse(f"worker-kill:nth={k}")
                         for k in (1, 2)])
    sup = stub_supervisor(fault_injector=inj, breaker_threshold=2,
                          breaker_window=30.0, breaker_cooldown=60.0)
    camp = make_campaign(STUB_CORPUS, sup, fault_injector=inj,
                         max_batch_retries=1)
    # the in-process fallback must not need the real engine for this
    # machinery test: swap _exec_batch for a stub AFTER construction
    # (keeping _batch_runner=None so the worker path stays enabled)
    camp._exec_batch = (lambda bi, names, codes, lanes=None, width=None:
                        {"issues": [], "paths": len(names),
                         "dropped": 0, "iprof": {}})
    res = camp.run()
    ks = [e.get("kind") for e in res.backend_events]
    assert ks.count("worker_death") == 2
    assert "breaker_open" in ks
    assert "worker_breaker_pinned" in ks
    assert res.paths_total == 6             # parity: nothing lost/doubled
    assert not res.quarantined
    st = [e for e in res.backend_events
          if e.get("kind") == "worker_breaker_pinned"]
    assert st                               # CPU pin is on the record


def test_campaign_kill_resume_exactly_once_with_worker(tmp_path):
    """InjectedKill (parent-side) mid-campaign with worker isolation:
    the resumed session replays only undurable batches — paths count
    every contract exactly once across both sessions."""
    ck = str(tmp_path / "ck")
    sup = stub_supervisor()
    camp = make_campaign(
        STUB_CORPUS, sup, checkpoint_dir=ck,
        fault_injector=FaultInjector([FaultSpec.parse("kill:batch=1")]))
    with pytest.raises(InjectedKill):
        camp.run()
    assert not sup.alive()  # run()'s finally closed the worker
    sup2 = stub_supervisor()
    res = make_campaign(STUB_CORPUS, sup2, checkpoint_dir=ck).run()
    assert res.batches == 3
    assert res.paths_total == 6             # nothing double-counted


def test_worker_warm_marker_set_and_dropped_on_death():
    inj = FaultInjector([FaultSpec.parse("worker-kill:nth=2")])
    sup = stub_supervisor(fault_injector=inj)
    camp = make_campaign(STUB_CORPUS, sup, fault_injector=inj)
    assert not camp.shape_is_warm()
    res = camp.run()
    assert res.paths_total == 6
    # after batch 0 the shape was worker-warm; the death cleared it;
    # the post-restart batches re-marked it
    assert camp.shape_is_warm()
    deaths = [e for e in res.backend_events
              if e.get("kind") == "worker_death"]
    assert deaths


def test_stub_batch_runner_bypasses_worker():
    """A custom batch_runner has nothing to isolate: no subprocess is
    spawned even with isolation on — fault-machinery tests keep their
    in-process semantics."""
    calls = []

    def runner(bi, names, codes):
        calls.append(bi)
        return {"issues": [], "paths": len(names), "dropped": 0,
                "iprof": {}}

    camp = CorpusCampaign(STUB_CORPUS, batch_size=2,
                          lanes_per_contract=4, limits=TEST_LIMITS,
                          worker_isolation="on", batch_runner=runner)
    res = camp.run()
    assert calls == [0, 1, 2] and res.paths_total == 6
    assert camp._supervisor is None         # never created


def test_worker_isolation_auto_resolution(tmp_path):
    base = dict(batch_size=2, lanes_per_contract=4,
                limits=TEST_LIMITS, max_steps=16)
    off = CorpusCampaign(STUB_CORPUS, worker_isolation="auto", **base)
    assert off.worker_isolation is False
    on = CorpusCampaign(STUB_CORPUS, worker_isolation="auto",
                        fleet_dir=str(tmp_path / "fl"), **base)
    assert on.worker_isolation is True
    with pytest.raises(ValueError):
        CorpusCampaign(STUB_CORPUS, worker_isolation="sometimes", **base)


# --- the headline acceptance scenario (real engine) -----------------------

# --- the worker says which device it got -----------------------------------

@pytest.mark.parametrize("expect,refused", [("cpu", False), ("tpu", True)])
def test_real_worker_reports_device_and_tier_mismatch_is_refused(
        expect, refused):
    """A REAL (non-stub) worker's init reply names the device its
    engine got — cpu under the tests' pin. A worker spawned for another
    tier than it came up on is a failed spawn (a death, feeding the
    breaker), not quiet CPU capacity."""
    sup = WorkerSupervisor(
        config={"limits": TEST_LIMITS, "batch_size": 2,
                "lanes_per_contract": 8, "max_steps": 16},
        backoff_base=0.01, spawn_timeout=240.0, expect_tier=expect)
    try:
        if refused:
            with pytest.raises(WorkerDied, match="spawned for the tpu"):
                sup._spawn_and_init()
            assert not sup.alive()
            assert "worker_death" in kinds(sup.events)
        else:
            sup._spawn_and_init()
            assert sup.alive()
        spawn = [e for e in sup.events if e["kind"] == "worker_spawn"][-1]
        assert spawn["device"]["platform"] == "cpu"
        assert spawn["device"]["count"] >= 1 and spawn["device"]["kind"]
        assert sup.status()["device"] == spawn["device"]
    finally:
        sup.close()


@pytest.mark.slow
def test_real_engine_segv_mid_superstep_survival(tmp_path):
    """ISSUE 10 acceptance: with worker_isolation=on, a SIGSEGV
    injected into the engine worker mid-superstep is survived by the
    parent — the batch replays through retry, the final issue set is
    byte-identical to an uninjected run, and the restart is counted."""
    from mythril_tpu.disassembler.asm import assemble

    kill = assemble(0, "SELFDESTRUCT")
    safe = assemble(1, 0, "SSTORE", "STOP")
    contracts = [(f"c{i:03d}", kill if i % 2 == 0 else safe)
                 for i in range(4)]

    def mk(**kw):
        return CorpusCampaign(contracts, batch_size=2,
                              lanes_per_contract=8, limits=TEST_LIMITS,
                              max_steps=64, transaction_count=1,
                              modules=["AccidentallyKillable"], **kw)

    ref = mk(worker_isolation="off").run()
    ref_issues = sorted(i["contract"] for i in ref.issues)
    assert ref_issues, "baseline must find issues to assert parity"

    os.environ["MYTHRIL_WORKER_FAULT"] = (
        f"segv:mid-superstep:1:once={tmp_path}/cookie")
    try:
        res = mk(worker_isolation="on").run()
    finally:
        del os.environ["MYTHRIL_WORKER_FAULT"]
    assert sorted(i["contract"] for i in res.issues) == ref_issues
    assert len(res.issues) == len(ref.issues)
    assert not res.quarantined
    ks = [e.get("kind") for e in res.backend_events]
    assert ks.count("worker_death") == 1
    assert ks.count("worker_restart") == 1
