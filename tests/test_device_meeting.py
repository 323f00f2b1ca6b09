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


# --- the leaves a context keeps on the host ----------------------------------

def _frontier(lanes=4):
    """A real frontier that no engine ran: building it compiles
    nothing, and its leaves are device arrays."""
    from mythril_tpu.symbolic import make_sym_frontier

    return make_sym_frontier(lanes, TEST_LIMITS)


def _context(sf):
    from mythril_tpu.analysis.symbolic import AnalysisContext

    return AnalysisContext(sf=sf, corpus=None, limits=TEST_LIMITS,
                           contract_names=["c0"])


def _leaf_reads():
    snap = obs_metrics.REGISTRY.snapshot()["counters"]
    return (snap.get('host_leaf_reads_total{result="copy"}', 0.0),
            snap.get('host_leaf_reads_total{result="hit"}', 0.0))


def test_context_copies_a_leaf_once_and_serves_the_copy_afterwards():
    import jax.numpy as jnp

    sf = _frontier()
    sf = sf.replace(base=sf.base.replace(
        contract_id=jnp.asarray([3, 2, 1, 0], dtype=jnp.int32)))
    ctx = _context(sf)
    tr = obs_trace.configure(buffer=True)
    first = ctx.host("st_val_sym")
    assert isinstance(first, np.ndarray)
    assert np.array_equal(first, np.asarray(sf.st_val_sym))
    assert ctx.host("st_val_sym") is first
    assert _leaf_reads() == (1.0, 1.0)
    # a dotted name resolves through sf.base, and is a leaf of its own
    cid = ctx.host("base.contract_id")
    assert cid.tolist() == [3, 2, 1, 0]
    assert ctx.host("base.contract_id") is cid
    assert [ctx.contract_of(lane) for lane in range(4)] == [3, 2, 1, 0]
    assert _leaf_reads() == (2.0, 6.0)
    # a scalar leaf too
    assert int(ctx.host("dropped_total")) == 0
    # what reached the device: one whole-leaf copy a name, no kernel
    whats = [r["what"] for r in spans_of(tr.drain_buffer(), "device_fetch")]
    assert whats == ["st_val_sym", "base.contract_id", "dropped_total"]
    snap = obs_metrics.REGISTRY.snapshot()["counters"]
    assert snap["device_fetches_total"] == 3.0
    assert "device_kernel_reads_total" not in snap
    # another context of the same frontier keeps copies of its own
    assert _context(sf).host("st_val_sym") is not first
    with pytest.raises(AttributeError):
        ctx.host("no_such_leaf")


def test_threads_racing_for_leaves_tear_nothing():
    """``--solver-workers`` > 1 runs the modules of one context on pool
    threads: a leaf that two of them copy at once is copied twice and
    either copy kept, every request is answered with the leaf's
    values, and every request is counted once."""
    import sys
    import threading
    from operator import attrgetter

    sf = _frontier()
    ctx = _context(sf)
    names = ["st_val_sym", "st_key_sym", "base.active", "base.error",
             "n_arith", "tape_op", "con_node", "dropped_total"]
    want = {n: np.asarray(attrgetter(n)(sf)) for n in names}
    n_threads, rounds = 16, 20
    wrong, start = [], threading.Barrier(n_threads)

    def worker():
        start.wait(timeout=30)
        for _ in range(rounds):
            for n in names:
                if not np.array_equal(ctx.host(n), want[n]):
                    wrong.append(n)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
    copies, hits = _leaf_reads()
    assert len(names) <= copies <= n_threads * len(names)
    assert copies + hits == n_threads * rounds * len(names)
    # the race over, every thread is served the one copy that was kept
    assert all(ctx.host(n) is ctx.host(n) for n in names)
    assert _leaf_reads()[0] == copies


def test_fetch_counts_a_kernel_read():
    x = _leaf()
    obs_device.fetch(x, "x")
    snap = obs_metrics.REGISTRY.snapshot()["counters"]
    assert "device_kernel_reads_total" not in snap
    obs_device.fetch(lambda: x[2], "kernel:x[2]")
    snap = obs_metrics.REGISTRY.snapshot()["counters"]
    assert snap["device_kernel_reads_total"] == 1.0
    assert snap["device_fetches_total"] == 2.0


def _lane_sinks_by_device_slices(sf, lane):
    """``IntegerArithmetics._lane_sinks`` as it was: each leaf indexed
    on the device per lane, the row read back."""
    out = []
    for name in ("st_val_sym", "st_key_sym"):
        row = np.asarray(getattr(sf, name)[lane])
        out.extend(int(x) for x in row[row > 0])
    return out


_SINK_CASES = {
    # (st_val_sym, st_key_sym), lane, the sinks in order
    "no_slots": (np.zeros((3, 0), np.int32), np.zeros((3, 0), np.int32),
                 1, []),
    "all_zeros": (np.zeros((3, 4), np.int32), np.zeros((3, 4), np.int32),
                  0, []),
    "mixed": (np.array([[9, 9, 9, 9], [0, 17, -1, 15], [8, 8, 8, 8]],
                       np.int32),
              np.array([[7, 7, 7, 7], [21, 0, 0, 14], [6, 6, 6, 6]],
                       np.int32),
              1, [17, 15, 21, 14]),
    "last_lane": (np.array([[1, 2], [3, 4], [0, 5]], np.int32),
                  np.array([[6, 7], [8, 9], [10, 0]], np.int32),
                  2, [5, 10]),
    "keys_only": (np.zeros((2, 3), np.int32),
                  np.array([[0, 0, 0], [0, 0, 12]], np.int32),
                  1, [12]),
}


@pytest.mark.parametrize("case", sorted(_SINK_CASES))
def test_lane_sinks_from_the_host_copy_are_the_device_slices(case):
    import jax.numpy as jnp

    from mythril_tpu.analysis.module.modules.integer import (
        IntegerArithmetics)

    val, key, lane, want = _SINK_CASES[case]
    sf = _frontier(val.shape[0]).replace(
        st_val_sym=jnp.asarray(val), st_key_sym=jnp.asarray(key))
    ctx = _context(sf)
    for ln in range(val.shape[0]):
        got = IntegerArithmetics._lane_sinks(ctx, ln)
        assert got == _lane_sinks_by_device_slices(sf, ln)
        assert all(isinstance(x, int) and x > 0 for x in got)
    assert IntegerArithmetics._lane_sinks(ctx, lane) == want
    # both leaves came over once, whatever the number of lanes asked
    assert _leaf_reads()[0] == 2.0


def _frontier_with_a_wrapping_add():
    """Lane 1 recorded ``calldata[4] + calldata[36]`` at pc 7 and stored
    the sum: every outlet of the lane is tracked, so the sink gate runs
    (``_lane_sinks``) and the solver finds the overflow."""
    import jax.numpy as jnp

    from mythril_tpu.symbolic.ops import SymOp

    sf = _frontier()
    n = int(np.asarray(sf.tape_len)[1])      # the preset leaves
    a, b, r = 11, 12, n                      # calldata words at 4 and 36
    return sf.replace(
        tape_len=sf.tape_len.at[1].set(n + 1),
        tape_op=sf.tape_op.at[1, r].set(int(SymOp.ADD)),
        tape_a=sf.tape_a.at[1, r].set(a),
        tape_b=sf.tape_b.at[1, r].set(b),
        n_arith=sf.n_arith.at[1].set(1),
        arith_op=sf.arith_op.at[1, 0].set(0x01),
        arith_a=sf.arith_a.at[1, 0].set(a),
        arith_b=sf.arith_b.at[1, 0].set(b),
        arith_r=sf.arith_r.at[1, 0].set(r),
        arith_pc=sf.arith_pc.at[1, 0].set(7),
        st_val_sym=sf.st_val_sym.at[1, 0].set(r),
        base=sf.base.replace(
            contract_id=jnp.zeros_like(sf.base.contract_id)))


def test_integer_module_dispatches_nothing_and_reads_no_leaf_twice():
    from mythril_tpu.analysis.module.modules.integer import (
        IntegerArithmetics)

    ctx = _context(_frontier_with_a_wrapping_add())
    tr = obs_trace.configure(buffer=True)
    mod = IntegerArithmetics()
    issues = mod._execute(ctx)
    assert [(i.swc_id, i.address, i.lane, i.contract) for i in issues] == [
        ("101", 7, 1, "c0")]
    assert ctx.contract_name(1) == "c0"
    # a second module pass over the same context copies nothing anew
    copies, _ = _leaf_reads()
    mod._cache.clear()
    assert len(mod._execute(ctx)) == 1
    assert _leaf_reads()[0] == copies
    whats = [r["what"] for r in spans_of(tr.drain_buffer(), "device_fetch")]
    assert len(whats) == len(set(whats)) == copies
    assert not [w for w in whats if w.startswith("kernel:")]
    assert {"st_val_sym", "st_key_sym", "base.contract_id", "tape_imm",
            "base.active", "n_arith"} <= set(whats)
    snap = obs_metrics.REGISTRY.snapshot()["counters"]
    assert "device_kernel_reads_total" not in snap
    assert snap["device_fetches_total"] == copies


def test_host_phase_code_hands_fetch_no_callable():
    """A callable is how a read says it dispatches a program first
    (``kernel:``): the layers that run beside a device phase have
    none."""
    import pathlib
    import re

    root = pathlib.Path(mythril_tpu.__file__).parent
    files = [p for d in ("analysis", "smt")
             for p in sorted((root / d).rglob("*.py"))]
    assert len(files) > 20
    bad = [str(p.relative_to(root)) for p in files
           if re.search(r"fetch\(\s*lambda", p.read_text())]
    assert bad == []


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
    # with spill the seam's masks and the scalar that says the call left
    # its loop at the pool's fixpoint ride it too: no read of their own
    assert reads[0] == ("visited,steps_total,copy_steps,base.active,"
                        "fork_req,base.running,base.home_contract,fixpoint"
                        if spill else "visited,steps_total,copy_steps")
    # neither program has a copy opcode: the cond was never taken
    assert [sp["copy_steps"] for sp in steps] == [0] * len(steps)
    assert int(np.asarray(sym.sf.copy_steps)) == 0
    assert not any("ended_in" in sp for sp in steps)
    assert (sym.sf.fixpoint is None) is (not spill)
    assert [sp["stuck"] for sp in steps] == [False] * len(steps)
    assert [sp.get("ended") for sp in steps] == (
        [None] * (len(steps) - 1) + ["quiescent"])
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


# --- the seam of a call that left its loop at the pool's fixpoint -----------

@pytest.mark.parametrize("case", [
    "an_idle_seam_ends_the_transaction", "a_seam_that_evicts_goes_on",
    "a_stuck_pool_without_the_flag_runs_its_budget",
    "the_drain_does_not_take_the_last_chunks_flag"])
def test_the_host_ends_a_transaction_only_on_the_devices_word(
        case, monkeypatch):
    """What ``explore`` makes of the frontier's ``fixpoint`` scalar, on
    a stand-in for ``sym_run`` that hands back a full pool, every lane
    parked, with the flag as the case wants it: the transaction ends at
    a seam only if the device said so AND the seam's scheduling step did
    nothing; the host has no rule of its own."""
    import jax.numpy as jnp

    from mythril_tpu.analysis import SymExecWrapper
    from mythril_tpu.analysis import symbolic as asym

    flag = case != "a_stuck_pool_without_the_flag_runs_its_budget"
    calls = []

    def runner(sf, env, corpus, spec, limits, max_steps, **kw):
        calls.append(max_steps)
        C, MC = corpus.code.shape
        P = sf.n_lanes
        full = jnp.ones(P, dtype=bool)
        return sf.replace(
            base=sf.base.replace(active=full,
                                 halted=jnp.zeros(P, dtype=bool)),
            fork_req=full, fixpoint=jnp.asarray(flag),
            steps_total=sf.steps_total + 2), np.zeros((C, MC), dtype=bool)

    evictions = iter([1] if case in (
        "a_seam_that_evicts_goes_on",
        "the_drain_does_not_take_the_last_chunks_flag") else [])
    monkeypatch.setattr(asym, "sym_run", runner)
    monkeypatch.setattr(asym, "relieve_starved",
                        lambda sf, *a: (sf, next(evictions, 0)))
    budget = 8 if case.startswith("the_drain") else 32
    tr = obs_trace.configure(buffer=True)
    SymExecWrapper([SAFE], limits=TEST_LIMITS, lanes_per_contract=4,
                   max_steps=budget, deadline_chunk_steps=8,
                   warm_shapes={8, 2})
    steps = spans_of(tr.drain_buffer(), "superstep")
    ctr = obs_metrics.REGISTRY.snapshot()["counters"]
    ends = ctr.get('engine_fixpoint_ends_total{tx="0"}', 0)
    skipped = ctr.get('engine_calls_skipped_total{tx="0"}', 0)
    exits = ctr.get('engine_inloop_fixpoint_exits_total{tx="0"}', 0)
    assert len(steps) == len(calls) == exits + (0 if flag else len(calls))
    assert [sp.get("ended_in") for sp in steps] == (
        ["fixpoint" if flag else None] * len(steps))
    if case == "an_idle_seam_ends_the_transaction":
        assert calls == [8]
        assert (ends, skipped) == (1, 3 + 4)     # 3 chunks, 4 drain rounds
        assert steps[0]["stuck"] and steps[0]["ended"] == "fixpoint"
        assert steps[0]["skipped"] == 7 and steps[0]["steps_run"] == 2
    elif case == "a_seam_that_evicts_goes_on":
        assert calls == [8, 8]
        assert (ends, skipped) == (1, 2 + 4)
        assert [sp["stuck"] for sp in steps] == [False, True]
        assert "ended" not in steps[0] and steps[1]["ended"] == "fixpoint"
    elif case == "a_stuck_pool_without_the_flag_runs_its_budget":
        assert calls == [8] * 4 + [8] * 4        # chunks, then the drain
        assert (ends, skipped) == (0, 0)
        assert all(sp["stuck"] for sp in steps)
        assert steps[-1]["ended"] == "budget"
        assert [bool(sp.get("drain")) for sp in steps] == (
            [False] * 4 + [True] * 4)
    else:
        # the budget's one chunk left on the rule, but its seam evicted:
        # the drain starts from a frontier that is no call's yet, runs a
        # round, and that round's word ends it
        assert calls == [8, 8]
        assert [bool(sp.get("drain")) for sp in steps] == [False, True]
        assert (ends, skipped) == (1, 3)
        assert steps[-1]["ended"] == "fixpoint"


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


# --- the report's split of a host phase -------------------------------------

@pytest.mark.parametrize("kernel_reads", [0, 2])
def test_trace_report_counts_the_kernel_reads_of_each_host_phase(
        kernel_reads, tmp_path):
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(root, "tools", "trace_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    def span(name, mono, dur, tid, **attrs):
        return dict(schema=1, kind="span", name=name, t=0.0, mono=mono,
                    dur=dur, tid=tid, **attrs)

    recs = [span("host_phase", 10.0, 5.0, 7, bi=0, device_fetches=3,
                 device_wait_s=0.5, cpu_s=4.0),
            span("host_phase", 20.0, 5.0, 7, bi=1, device_fetches=1,
                 device_wait_s=0.1, cpu_s=4.5),
            span("device_fetch", 11.0, 0.01, 7, what="st_val_sym",
                 bytes=256),
            # in batch 1's time, but on the device phase's thread
            span("device_fetch", 21.0, 0.2, 9, what="kernel:elsewhere",
                 bytes=4),
            span("device_fetch", 22.0, 0.01, 7, what="tape_imm",
                 bytes=64)]
    recs += [span("device_fetch", 12.0 + i, 0.2, 7,
                  what="kernel:base.contract_id[lane]", bytes=4)
             for i in range(kernel_reads)]
    path = tmp_path / "t.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    text = report.report(*report.load_trace(str(path)))
    (bi0,) = [ln for ln in text.splitlines() if ln.startswith("  bi 0:")]
    (bi1,) = [ln for ln in text.splitlines() if ln.startswith("  bi 1:")]
    assert f"3 fetches, {kernel_reads} kernel: reads)" in bi0
    assert ("dispatches on the device" in bi0) == bool(kernel_reads)
    assert "1 fetches, 0 kernel: reads)" in bi1
    assert "dispatches on the device" not in bi1


@pytest.mark.parametrize("trace", ["calls_leave_inside", "witness_calls"])
def test_trace_report_says_how_each_transaction_ended(tmp_path, trace):
    """Two batches: the second message call ends at its pool's fixpoint,
    in the call that filled it, which left its loop inside; the first
    runs its budget. (``witness_calls``: a trace from before the loop
    saw the fixpoint, whose transactions paid a whole call for it.)"""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(root, "tools", "trace_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    def call(mono, tx, stuck, steps_run=64, **attrs):
        return dict(schema=1, kind="span", name="superstep", t=0.0,
                    mono=mono, dur=2.0, tid=9, tx=tx, tx_kind="message",
                    steps=64, steps_run=steps_run, cold=False, stuck=stuck,
                    **attrs)

    recs = []
    for t0 in (0.0, 100.0):
        recs += [call(t0 + 1, 0, False, copy_steps=3),
                 call(t0 + 4, 0, False, ended="budget", copy_steps=2)]
        if trace == "witness_calls":
            recs += [call(t0 + 10, 1, True),
                     call(t0 + 13, 1, True, ended="fixpoint", skipped=6)]
        else:
            recs += [call(t0 + 10, 1, True, steps_run=40,
                          ended_in="fixpoint", ended="fixpoint",
                          skipped=7)]
    # emitted once their seams have spoken: not in time order
    recs.reverse()
    path = tmp_path / "t.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    text = report.report(*report.load_trace(str(path))).splitlines()
    head = text.index("== transactions (tx, tx_kind) ==")
    cols = text[head + 1].split()
    rows = [dict(zip(cols, ln.split(None, len(cols) - 1)))
            for ln in text[head + 2:head + 4]]
    # a trace from before the counter has no ``copy_steps``: none counted
    assert [r["copies"] for r in rows] == ["10", "0"]
    got = [(r["tx"], r["calls"], r["early"], r["unrun"], r["skipped"],
            r["spun"], r["ended"]) for r in rows]
    assert got[0] == ("0", "4", "0", "0", "0", "0", "budget x2")
    assert got[1] == (
        ("1", "4", "0", "0", "12", "2", "fixpoint x2")
        if trace == "witness_calls"
        else ("1", "2", "2", "48", "14", "0", "fixpoint x2"))


# --- checkpoints written before the counter ----------------------------------

@pytest.mark.parametrize("leaf", ["steps_total", "copy_steps"])
def test_checkpoint_without_the_step_counter_resumes_at_zero(tmp_path, leaf):
    import jax.numpy as jnp

    from mythril_tpu.symbolic import make_sym_frontier
    from mythril_tpu.utils.checkpoint import load_frontier, save_frontier

    sf = make_sym_frontier(4, TEST_LIMITS)
    path = str(tmp_path / "old.npz")
    # a None leaf is no leaf: the file is what an older writer wrote
    save_frontier(path, sf.replace(**{leaf: None}), {"tx": 0})
    template = sf.replace(**{leaf: jnp.int32(7)})
    got, meta = load_frontier(path, template)
    assert meta == {"tx": 0}
    assert int(np.asarray(getattr(got, leaf))) == 0
    assert np.asarray(getattr(got, leaf)).dtype == np.int32
    # and one written today carries it
    save_frontier(path, template, {"tx": 1})
    got, _ = load_frontier(path, sf)
    assert int(np.asarray(getattr(got, leaf))) == 7


@pytest.mark.parametrize("written", ["before_the_leaf", "with_the_leaf",
                                     "by_a_bare_run"])
def test_checkpoint_and_the_fixpoint_scalar(tmp_path, written):
    """A spill run's frontier carries ``fixpoint``; a checkpoint written
    before the leaf existed resumes with it clear (no call has left on
    the rule yet), one written with it brings it back, and a bare run's
    frontier has no such leaf on either side."""
    import jax.numpy as jnp

    from mythril_tpu.symbolic import make_sym_frontier
    from mythril_tpu.utils.checkpoint import load_frontier, save_frontier

    bare = make_sym_frontier(4, TEST_LIMITS)
    assert bare.fixpoint is None
    spill = bare.replace(fixpoint=jnp.ones((), dtype=bool))
    path = str(tmp_path / "f.npz")
    if written == "before_the_leaf":
        save_frontier(path, bare, {})
        got, _ = load_frontier(path, spill)
        assert np.asarray(got.fixpoint).dtype == np.bool_
        assert not bool(np.asarray(got.fixpoint))
    elif written == "with_the_leaf":
        save_frontier(path, spill, {})
        got, _ = load_frontier(
            path, bare.replace(fixpoint=jnp.zeros((), dtype=bool)))
        assert bool(np.asarray(got.fixpoint))
    else:
        save_frontier(path, bare, {})
        got, _ = load_frontier(path, bare)
        assert got.fixpoint is None
