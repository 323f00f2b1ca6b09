"""Where the host meets the device (docs/observability.md "Host/device
seam"): ``superstep`` spans that end when the device does and say how
many supersteps ran, every host read of a device array through
``obs.device.fetch``, and phase spans that split their wall clock into
CPU, waits for the device and the rest."""

import time

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.obs import device as obs_device
from mythril_tpu.obs import metrics as obs_metrics
from mythril_tpu.obs import trace as obs_trace

KILLABLE = assemble(0, "SELFDESTRUCT")
SAFE = assemble(1, 0, "SSTORE", "STOP")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs_trace.close()
    obs_metrics.REGISTRY.reset()
    yield
    obs_trace.close()
    obs_metrics.REGISTRY.reset()


def spans_of(records, name):
    return [r for r in records
            if r.get("kind") == "span" and r.get("name") == name]


# --- the helper ----------------------------------------------------------

def _leaf():
    import jax.numpy as jnp

    return jnp.arange(12, dtype=jnp.int32).reshape(3, 4)


def _pair():
    import jax.numpy as jnp

    return (jnp.ones(5, dtype=bool), jnp.arange(3, dtype=jnp.uint32))


@pytest.mark.parametrize("make", [_leaf, _pair], ids=["leaf", "tuple"])
def test_fetch_returns_what_asarray_returns_and_records_nothing_when_off(
        make):
    x = make()
    tr = obs_trace.configure(buffer=True)
    obs_trace.close()                    # tracing off again
    n0, w0, _ = obs_device.tally()
    got = obs_device.fetch(x, "x")
    want = ([np.asarray(a) for a in x] if isinstance(x, tuple)
            else [np.asarray(x)])
    got_l = list(got) if isinstance(x, tuple) else [got]
    assert isinstance(got, tuple) == isinstance(x, tuple)
    for g, w in zip(got_l, want):
        assert isinstance(g, np.ndarray)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)
    assert tr.drain_buffer() == []       # no device_fetch record
    n1, w1, _ = obs_device.tally()
    assert n1 == n0 + 1 and w1 >= w0     # one read, whatever it held
    snap = obs_metrics.REGISTRY.snapshot()["counters"]
    assert snap["device_fetches_total"] == 1.0
    assert snap["device_fetch_seconds_total"] >= 0.0


def test_fetch_emits_a_span_when_tracing_and_times_a_kernel_whole():
    x = _leaf()
    tr = obs_trace.configure(buffer=True)
    t0 = time.monotonic()
    row = obs_device.fetch(lambda: x[1], "kernel:x[1]")
    obs_device.fetch(x, "x")
    recs = spans_of(tr.drain_buffer(), "device_fetch")
    assert np.array_equal(row, np.asarray(x)[1])
    assert [r["what"] for r in recs] == ["kernel:x[1]", "x"]
    assert [r["bytes"] for r in recs] == [16, 48]
    assert all(t0 <= r["mono"] <= time.monotonic() for r in recs)


def test_fetch_spans_leave_room_in_a_worker_buffer(monkeypatch):
    """The one span a batch emits by the thousand stops at three
    quarters of a buffering tracer's cap: the phases' spans still fit,
    and what was dropped is counted."""
    monkeypatch.setattr(obs_trace, "BUFFER_CAP", 8)
    x = _leaf()
    tr = obs_trace.configure(buffer=True)
    with obs_device.phase_timer("host_phase", bi=0):
        for _ in range(10):
            obs_device.fetch(x, "x")
    names = [r["name"] for r in tr.drain_buffer()]
    assert names == ["device_fetch"] * 6 + ["host_phase"]
    snap = obs_metrics.REGISTRY.snapshot()["counters"]
    assert snap["obs_events_dropped_total"] == 4.0
    assert snap["device_fetches_total"] == 10.0


def test_phase_timer_splits_its_wall_clock():
    x = _leaf()
    tr = obs_trace.configure(buffer=True)
    with obs_device.phase_timer("host_phase", bi=0) as sp:
        obs_device.fetch(x, "x")
        time.sleep(0.05)                 # neither CPU nor a device wait
    assert sp.dur >= 0.05
    (rec,) = spans_of(tr.drain_buffer(), "host_phase")
    assert rec["device_fetches"] == 1 and rec["bi"] == 0
    assert 0.0 <= rec["cpu_s"] and 0.0 <= rec["device_wait_s"]
    assert rec["cpu_s"] + rec["device_wait_s"] <= rec["dur"] - 0.04
    # a read made on ANOTHER thread is not this phase's
    import threading

    with obs_device.phase_timer("host_phase", bi=1):
        t = threading.Thread(target=obs_device.fetch, args=(x, "x"))
        t.start()
        t.join()
    (rec,) = spans_of(tr.drain_buffer(), "host_phase")
    assert rec["device_fetches"] == 0
    assert obs_metrics.REGISTRY.snapshot()["counters"][
        "device_fetches_total"] == 2.0


# --- superstep spans -------------------------------------------------------

def _explore(**kw):
    from mythril_tpu.analysis import SymExecWrapper

    tr = obs_trace.configure(buffer=True)
    sym = SymExecWrapper([KILLABLE, SAFE], limits=TEST_LIMITS,
                         lanes_per_contract=4, max_steps=16,
                         deadline_chunk_steps=8, **kw)
    return sym, tr.drain_buffer()


@pytest.mark.parametrize("spill", [True, False],
                         ids=["chunked", "unchunked"])
def test_superstep_spans_end_with_the_device_and_count_what_ran(spill):
    sym, recs = _explore(spill=spill)
    steps = spans_of(recs, "superstep")
    assert steps
    for sp in steps:
        assert sp["steps"] == (8 if spill else 16)
        assert 0 <= sp["steps_run"] <= sp["steps"]
        assert sp["dur"] >= sp["enqueue_s"] >= 0.0
        assert 0.0 <= sp["device_wait_s"] <= sp["dur"]
        assert sp["tx"] == 0 and isinstance(sp["cold"], bool)
    # both programs halt in a handful of supersteps: quiescence, not
    # the budget, ended the first call
    assert 0 < steps[0]["steps_run"] < steps[0]["steps"]
    assert steps[0]["done"] == 0
    ran = sum(sp["steps_run"] for sp in steps)
    assert ran == int(np.asarray(sym.sf.steps_total))
    ctr = obs_metrics.REGISTRY.snapshot()
    assert ctr["counters"]["engine_supersteps_total"] == ran
    assert ctr["counters"]["engine_supersteps_budget_total"] == sum(
        sp["steps"] for sp in steps)
    # every result read of the call is ONE transfer inside the span
    reads = [r["what"] for r in spans_of(recs, "device_fetch")
             if r["what"].startswith("visited,steps_total")]
    assert len(reads) == len(steps)
    assert reads[0] == ("visited,steps_total,base.active,fork_req,"
                        "base.running" if spill
                        else "visited,steps_total")
    import jax

    assert ctr["gauges"]["frontier_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(sym.sf))


class _SlowArray:
    """A result that is ready ``seconds`` after it was enqueued, as a
    device array is: making it returns at once, reading it blocks."""

    def __init__(self, value, seconds):
        self.value = value
        self.ready_at = time.monotonic() + seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.ready_at - time.monotonic()))
        return self.value


def test_deadline_pacing_reads_the_devices_rate(monkeypatch):
    """``--execution-timeout``: the chunk loop falls to the small chunk
    when the remaining budget does not cover a full one. It sizes that
    from seconds per superstep, which is the DEVICE's rate only because
    the span ends when the device does (the enqueue alone takes
    microseconds, and the loop then overshot by whole chunks)."""
    from mythril_tpu.analysis import SymExecWrapper
    from mythril_tpu.analysis import symbolic as asym

    per_step = 0.05
    calls = []

    def runner(sf, env, corpus, spec, limits, max_steps, **kw):
        calls.append(max_steps)
        C, MC = corpus.code.shape
        vis = _SlowArray(np.zeros((C, MC), dtype=bool),
                         per_step * max_steps)
        return sf.replace(steps_total=sf.steps_total + max_steps), vis

    monkeypatch.setattr(asym, "sym_run", runner)
    kw = dict(limits=TEST_LIMITS, lanes_per_contract=4,
              deadline_chunk_steps=8, warm_shapes={8, 2})
    # one chunk without a deadline first: the seam's own small programs
    # (rebalance, harvest) compile here, not inside the timed run
    SymExecWrapper([SAFE], max_steps=8, **kw)
    del calls[:]
    tr = obs_trace.configure(buffer=True)
    # a chunk of 8 takes 0.4 s: two fit into 1 s, a third does not
    sym = SymExecWrapper([SAFE], max_steps=64, execution_timeout=1.0, **kw)
    assert sym.timed_out
    # full chunks while they fit, then the small chunk to the deadline;
    # timing the enqueue alone this read [8, 8, 8] and overshot
    assert calls[0] == 8 and calls.count(8) <= 2 and 2 in calls, calls
    assert calls == sorted(calls, reverse=True), calls
    steps = spans_of(tr.drain_buffer(), "superstep")
    assert [sp["steps"] for sp in steps] == calls
    for sp in steps:
        assert sp["steps_run"] == sp["steps"]
        assert sp["dur"] >= 0.9 * per_step * sp["steps"] > sp["enqueue_s"]


# --- phase spans of a pipelined campaign ------------------------------------

def test_host_phase_of_a_pipelined_campaign_says_where_its_time_went():
    from mythril_tpu.mythril.campaign import CorpusCampaign

    contracts = [(f"c{i:03d}", KILLABLE if i % 2 == 0 else SAFE)
                 for i in range(6)]
    tr = obs_trace.configure(buffer=True)
    res = CorpusCampaign(
        contracts, batch_size=4, lanes_per_contract=8, limits=TEST_LIMITS,
        max_steps=64, transaction_count=1,
        modules=["AccidentallyKillable"], pipeline=True).run()
    recs = tr.drain_buffer()
    assert res.batches == 2 and res.batch_status == ["ok", "ok"]
    hosts = spans_of(recs, "host_phase")
    devs = spans_of(recs, "device_phase")
    assert len(hosts) == 2 and len(devs) == 2
    for sp in hosts + devs:
        assert sp["device_fetches"] > 0
        assert sp["cpu_s"] >= 0.0 and sp["device_wait_s"] >= 0.0
        assert sp["cpu_s"] + sp["device_wait_s"] <= sp["dur"]
    # each read is on the stream, on the thread of the phase it is in
    reads = spans_of(recs, "device_fetch")
    for sp in hosts:
        mine = [r for r in reads if r["tid"] == sp["tid"]
                and sp["mono"] <= r["mono"] <= sp["mono"] + sp["dur"]]
        assert len(mine) == sp["device_fetches"]
        assert any(r["what"] == "tape_imm" for r in mine)
    # the supersteps' waits are inside their device phase's
    for sp in devs:
        inside = [s for s in spans_of(recs, "superstep")
                  if sp["mono"] <= s["mono"] <= sp["mono"] + sp["dur"]]
        assert inside
        assert sum(s["device_wait_s"] for s in inside) <= (
            sp["device_wait_s"] + 1e-5 * len(inside))


# --- checkpoints written before the counter ----------------------------------

def test_checkpoint_without_the_step_counter_resumes_at_zero(tmp_path):
    import jax.numpy as jnp

    from mythril_tpu.symbolic import make_sym_frontier
    from mythril_tpu.utils.checkpoint import load_frontier, save_frontier

    sf = make_sym_frontier(4, TEST_LIMITS)
    path = str(tmp_path / "old.npz")
    # a None leaf is no leaf: the file is what an older writer wrote
    save_frontier(path, sf.replace(steps_total=None), {"tx": 0})
    template = sf.replace(steps_total=jnp.int32(7))
    got, meta = load_frontier(path, template)
    assert meta == {"tx": 0}
    assert int(np.asarray(got.steps_total)) == 0
    assert np.asarray(got.steps_total).dtype == np.int32
    # and one written today carries it
    save_frontier(path, template, {"tx": 1})
    got, _ = load_frontier(path, sf)
    assert int(np.asarray(got.steps_total)) == 7
