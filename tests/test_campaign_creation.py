"""Campaigns that deploy: a corpus record may carry a contract's
creation code, and ``CorpusCampaign`` then analyses it as single-contract
``analyze --creation-code`` does (constructor from the creator account,
message calls from the storage it left). The record survives every seam a
batch can be replayed through: corpus directory, fingerprint and
checkpoint resume, the isolation worker's IPC, a fed fleet unit.
"""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu import engine_worker
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core.frontier import ATTACKER_ADDRESS, CREATOR_ADDRESS
from mythril_tpu.disassembler.asm import assemble, selector_prologue
from mythril_tpu.fleet import WorkLedger, corpus_fingerprint
from mythril_tpu.mythril.campaign import CorpusCampaign, load_corpus_dir
from mythril_tpu.mythril.orchestration import (MythrilAnalyzer,
                                               MythrilConfig,
                                               MythrilDisassembler)
from mythril_tpu.resilience import (FaultInjector, FaultSpec, InjectedKill,
                                    WorkerSupervisor)
from mythril_tpu.symbolic import SymSpec

CONCRETE = SymSpec(storage=False)
MODULES = ["AccidentallyKillable", "EtherThief"]

#: the wallet of parity_wallet_bug_2: ``init(address)`` sets the owner,
#: whoever calls; ``kill()`` is for the owner
WALLET = assemble(
    *selector_prologue(),
    "DUP1", 0x11111111, "EQ", ("ref", "init"), "JUMPI",
    "DUP1", 0x22222222, "EQ", ("ref", "kill"), "JUMPI",
    0, 0, "REVERT",
    ("label", "init"), "POP", 4, "CALLDATALOAD", 0, "SSTORE", "STOP",
    ("label", "kill"), "POP", "CALLER", 0, "SLOAD", "EQ",
    ("ref", "ok"), "JUMPI", 0, 0, "REVERT",
    ("label", "ok"), "CALLER", "SELFDESTRUCT",
)
#: ``kill()`` for the owner alone: safe once a constructor set the owner
GUARDED = assemble(
    "CALLER", 0, "SLOAD", "EQ", ("ref", "ok"), "JUMPI", 0, 0, "REVERT",
    ("label", "ok"), "CALLER", "SELFDESTRUCT",
)
CTOR_OWNER = assemble("CALLER", 0, "SSTORE", 0, 0, "RETURN")
#: a constructor that hands the contract to whoever calls it first
CTOR_OPEN = assemble("CALLER", 0, "SSTORE", 0, 0, "SSTORE", 0, 0, "RETURN")
assert ATTACKER_ADDRESS != 0


def campaign(contracts, **kw):
    kw.setdefault("transaction_count", 2)
    return CorpusCampaign(
        contracts, batch_size=1, lanes_per_contract=16, limits=TEST_LIMITS,
        spec=CONCRETE, max_steps=128, modules=MODULES, **kw)


def found(res):
    return sorted((i["contract"], str(i["swc-id"])) for i in res.issues)


def test_campaign_over_records_equals_analyze_with_creation_code():
    res = campaign([("wallet", WALLET, CTOR_OWNER)]).run()
    contract = MythrilDisassembler.load_from_bytecode(
        WALLET.hex(), creation_code=CTOR_OWNER.hex(), name="wallet")
    single = MythrilAnalyzer([contract], MythrilConfig(
        limits=TEST_LIMITS, spec=CONCRETE, lanes_per_contract=16,
        max_steps=128, transaction_count=2)).fire_lasers(MODULES)
    want = sorted((i.contract, i.swc_id, i.address,
                   json.dumps(i.transaction_sequence))
                  for i in single.issues)
    got = sorted((i["contract"], str(i["swc-id"]), i["address"],
                  json.dumps(i["tx_sequence"])) for i in res.issues)
    assert got == want and [g[:2] for g in got] == [("wallet", "106")]
    # creation from the creator, init(attacker), kill(): three steps
    seq = res.issues[0]["tx_sequence"]
    assert len(seq) == 3
    assert int(seq[0]["caller"], 16) == CREATOR_ADDRESS
    assert seq[1]["input"].startswith("0x11111111")
    assert int(seq[1]["input"][10:74], 16) == ATTACKER_ADDRESS
    assert seq[2]["input"].startswith("0x22222222")
    assert int(seq[2]["caller"], 16) == ATTACKER_ADDRESS


@pytest.mark.parametrize("record, want", [
    # the guard rests on the constructor's write
    (("guarded", GUARDED, CTOR_OWNER), []),
    # a constructor that leaves the owner open to the zero address
    (("wallet", WALLET, CTOR_OPEN), [("wallet", "106")]),
    # one transaction does not reach the flaw that needs two
    (("wallet", WALLET, CTOR_OWNER, 1), []),
])
def test_verdicts_rest_on_the_constructor(record, want):
    txs = record[3] if len(record) > 3 else 2
    assert found(campaign([record[:3]], transaction_count=txs).run()) == want


def test_mixed_batch_deploys_and_pairs_keep_their_shape_class():
    camp = CorpusCampaign(
        [("wallet", WALLET, CTOR_OWNER), ("plain", GUARDED)], batch_size=2,
        lanes_per_contract=16, limits=TEST_LIMITS, spec=CONCRETE,
        max_steps=128, transaction_count=2, modules=MODULES)
    assert found(camp.run()) == [("wallet", "106")]
    assert camp.shape_is_warm(deploys=True) and not camp.shape_is_warm()
    assert camp._shape_key() == (2, 16, 128, 2)
    assert camp._shape_key(deploys=True) == (2, 16, 128, 2, 1)


def _hog(n: int, salt: int) -> bytes:
    """``n`` functions that each write their own slot: every one of
    them is a state the second transaction starts from, and each of
    those walks the whole dispatcher again."""
    toks = list(selector_prologue())
    for i in range(n):
        toks += ["DUP1", 0x30000000 + i, "EQ", ("ref", f"f{i}"), "JUMPI"]
    toks += [0, 0, "REVERT"]
    for i in range(n):
        toks += [("label", f"f{i}"), "POP", 4, "CALLDATALOAD", i + 1,
                 "SSTORE", "STOP"]
    return assemble(*toks) + bytes([salt])


@pytest.mark.parametrize("floor", [True, False])
def test_small_contract_beside_hogs_is_served_at_the_pools_fixpoint(
        floor, monkeypatch):
    """Three 12-function neighbours fill the pool of 64 lanes at the
    first fork of the second transaction, and every lane parks. The
    wallet needs four lanes there and holds fewer: under its floor (a
    quarter of its share of 16), so the seam's ``relieve_starved`` has
    the neighbours give up their parked lanes. Without it the flaw that
    needs the second call is not found."""
    from mythril_tpu.analysis import symbolic
    from mythril_tpu.obs import metrics

    if not floor:
        monkeypatch.setattr(symbolic, "relieve_starved",
                            lambda sf, *a: (sf, 0))
    def evicted():
        return metrics.REGISTRY.snapshot()["counters"].get(
            "evicted_lanes_total", 0)

    before = evicted()
    res = CorpusCampaign(
        [(f"hog{i}", _hog(12, i), CTOR_OWNER) for i in range(3)]
        + [("wallet", WALLET, CTOR_OWNER)],
        batch_size=4, lanes_per_contract=16, limits=TEST_LIMITS,
        spec=CONCRETE, max_steps=128, transaction_count=2,
        modules=MODULES).run()
    evicted = evicted() - before
    assert found(res) == ([("wallet", "106")] if floor else [])
    assert (evicted > 0) is floor
    # the lanes given up are dropped forks, as those still parked are
    assert res.dropped_forks >= evicted


@pytest.fixture(scope="module")
def pools():
    """Two batches at the shape the test above compiles, each run with
    the rule that ends a transaction at its pool's fixpoint and with
    the host deaf to it (the product has no switch: ``sym_run`` is
    wrapped from here so that every frontier comes back with its
    ``fixpoint`` scalar cleared; the compiled program is the same, and
    each call still leaves its loop where the rule says): ``hogs`` fills
    its pool in the second message call and nobody is under the floor;
    in ``relieved`` the wallet is."""
    import jax.numpy as jnp

    from mythril_tpu.analysis import symbolic
    from mythril_tpu.obs import metrics as obs_metrics
    from mythril_tpu.obs import trace as obs_trace

    hogs = [(f"hog{i}", _hog(12, i), CTOR_OWNER) for i in range(4)]
    batches = {"hogs": hogs,
               "relieved": hogs[:3] + [("wallet", WALLET, CTOR_OWNER)]}
    ran = {}

    def deaf(*a, **kw):
        sf, vis = symbolic_sym_run(*a, **kw)
        return sf.replace(fixpoint=jnp.zeros((), dtype=bool)), vis

    symbolic_sym_run = symbolic.sym_run

    def run(name, rule):
        before = obs_metrics.REGISTRY.snapshot()["counters"]
        tracer = obs_trace.configure(buffer=True)
        with pytest.MonkeyPatch.context() as mp:
            if not rule:
                mp.setattr(symbolic, "sym_run", deaf)
            try:
                res = CorpusCampaign(
                    batches[name], batch_size=4, lanes_per_contract=16,
                    limits=TEST_LIMITS, spec=CONCRETE, max_steps=128,
                    transaction_count=2, modules=MODULES).run()
                spans = [s for s in tracer.drain_buffer()
                         if s.get("kind") == "span"
                         and s["name"] == "superstep"]
            finally:
                obs_trace.close()
        after = obs_metrics.REGISTRY.snapshot()["counters"]
        return {
            "report": (found(res), res.paths_total, res.dropped_forks),
            "counters": {k: v - before.get(k, 0) for k, v in after.items()
                         if k.startswith(("engine_", "evicted_"))
                         and v != before.get(k, 0)},
            "calls": [(s["tx"], s["steps"], s["steps_run"],
                       bool(s.get("drain"))) for s in spans],
            "spans": spans}

    def pool(name, rule):
        if (name, rule) not in ran:     # a worker runs what its cases read
            ran[name, rule] = run(name, rule)
        return ran[name, rule]

    return pool


def _per_tx(counters, family):
    return {k: v for k, v in counters.items() if k.startswith(family)}


@pytest.mark.parametrize("case", [
    "the_reports_are_the_rule_off_runs", "the_calls_that_go",
    "counters_and_span_attributes", "a_transaction_never_stuck",
    "a_pool_that_is_relieved", "no_call_spins_a_chunk_at_a_stuck_pool"])
def test_transaction_ends_where_its_pool_is_proven_stuck(pools, case):
    """Three 12-function hogs and a fourth fill the pool of 64 lanes in
    the first chunk of the second message call: every lane parks, the
    call leaves its loop at the second sweep after that, and the
    transaction ends at that call's seam: no witness call, no second
    chunk, none of the four drain rounds. Nothing any report reads
    moves."""
    name = "relieved" if case == "a_pool_that_is_relieved" else "hogs"
    on, off = pools(name, True), pools(name, False)
    sweep = TEST_LIMITS.propagate_every
    if case == "the_reports_are_the_rule_off_runs":
        assert on["report"] == off["report"]
        assert on["report"][2] > 0      # forks were lost: a full pool
        for family in ("engine_paths_total", "engine_dropped_forks_total"):
            assert _per_tx(on["counters"], family) == _per_tx(
                off["counters"], family) != {}
    elif case == "the_calls_that_go":
        # deaf to the flag the host starts every call the budget holds,
        # and each leaves at its second sweep: the frontier stands still
        last = [c for c in off["calls"] if c[0] == 2]
        filled = last[0][2]
        assert 2 * sweep <= filled < 64 and filled % sweep == 0
        assert last == ([(2, 64, filled, False),
                         (2, 64, 2 * sweep, False)]
                        + [(2, 64, 2 * sweep, True)] * 4)
        assert [c for c in on["calls"] if c[0] == 2] == last[:1]
        assert off["spans"][-1]["ended"] == "budget"
        assert on["counters"]["engine_supersteps_total"] == (
            off["counters"]["engine_supersteps_total"] - 5 * 2 * sweep)
    elif case == "counters_and_span_attributes":
        assert on["counters"]['engine_fixpoint_ends_total{tx="2"}'] == 1
        assert on["counters"]['engine_calls_skipped_total{tx="2"}'] == 5
        assert on["counters"][
            'engine_inloop_fixpoint_exits_total{tx="2"}'] == 1
        # (a deaf host counts none of the six calls that left early)
        assert not _per_tx(off["counters"],
                           "engine_inloop_fixpoint_exits_total")
        assert not _per_tx(off["counters"], "engine_fixpoint_ends_total")
        assert not _per_tx(off["counters"], "engine_calls_skipped_total")
        (last,) = [s for s in on["spans"] if s["tx"] == 2]
        assert last["stuck"] is True and last["ended_in"] == "fixpoint"
        assert last["ended"] == "fixpoint" and last["skipped"] == 5
        assert last["steps_run"] < last["steps"]
        # the predicate of ``stuck`` is the host's own, deaf or not
        assert all(s["stuck"] and "ended_in" not in s
                   for s in off["spans"] if s["tx"] == 2)
    elif case == "a_transaction_never_stuck":
        # the constructor and the first message call end at quiescence
        for run in (on, off):
            early = [s for s in run["spans"] if s["tx"] < 2]
            assert [c for c in run["calls"] if c[0] < 2] == [
                (0, 64, 6, False), (1, 64, 64, False), (1, 64, 8, False)]
            assert not any(s["stuck"] for s in early)
            assert not any("ended_in" in s for s in early)
            assert [s.get("ended") for s in early] == [
                "quiescent", None, "quiescent"]
            assert not any(k.startswith(
                ("engine_inloop_fixpoint_exits_total{tx=\"0\"",
                 "engine_inloop_fixpoint_exits_total{tx=\"1\""))
                for k in run["counters"])
    elif case == "no_call_spins_a_chunk_at_a_stuck_pool":
        # what the parent paid for its proof: a whole chunk from a
        # stuck seam. No call does, with the host listening or deaf
        for run in (on, off):
            before = None
            for s in run["spans"]:
                if before is not None and before["tx"] == s["tx"] \
                        and before["stuck"]:
                    assert s["steps_run"] <= 2 * sweep < s["steps"]
                before = s
    else:
        # the wallet is under its floor at the first seam of the second
        # call: that call left its loop at the fixpoint too, but
        # ``relieve_starved`` evicts at its seam, so the transaction goes
        # on from a new frontier, and runs every call it ran
        assert on["report"] == off["report"]
        assert on["report"][0] == [("wallet", "106")]
        assert on["calls"] == off["calls"]
        exits = _per_tx(on["counters"], "engine_inloop_fixpoint_exits_total")
        assert {k: v for k, v in on["counters"].items()
                if k not in exits} == off["counters"]
        assert on["counters"]["evicted_lanes_total"] > 0
        assert not _per_tx(on["counters"], "engine_fixpoint_ends_total")
        assert exits == {'engine_inloop_fixpoint_exits_total{tx="2"}': 1}
        first = [s for s in on["spans"] if s["tx"] == 2][0]
        assert first["ended_in"] == "fixpoint" and not first["stuck"]
        assert on["spans"][-1]["ended"] == "quiescent"


def test_deploy_epilogue_longer_than_memory_does_not_trap():
    """solc's epilogue copies the whole runtime code to memory and
    returns it; a runtime longer than the memory model must not cost the
    deploy (the payload is the image the caller supplies)."""
    limits = dataclasses.replace(TEST_LIMITS, mem_bytes=128)
    runtime = GUARDED + bytes(200)
    ctor = assemble("CALLER", 0, "SSTORE")
    at = len(ctor) + 13
    creation = (ctor + b"\x61" + len(runtime).to_bytes(2, "big") + b"\x80\x61"
                + at.to_bytes(2, "big") + b"\x60\x00\x39\x60\x00\xf3"
                + runtime)
    from mythril_tpu.analysis import SymExecWrapper

    sym = SymExecWrapper([runtime], creation_bytecodes=[creation],
                         limits=limits, spec=CONCRETE, lanes_per_contract=4,
                         max_steps=64, transaction_count=1)
    assert not sym.tx_contexts[0].trap_counts
    assert len(sym.tx_contexts) == 2, "the message call ran"
    # beyond the payload the cap still traps: MSTORE at 4096
    far = assemble(1, 4096, "MSTORE", 0, 0, "RETURN")
    sym = SymExecWrapper([runtime], creation_bytecodes=[far], limits=limits,
                         spec=CONCRETE, lanes_per_contract=4, max_steps=64,
                         transaction_count=1)
    assert sym.tx_contexts[0].trap_counts


def test_corpus_dir_pairs_bin_with_bin_runtime(tmp_path):
    (tmp_path / "Wallet.bin").write_text(CTOR_OWNER.hex())
    (tmp_path / "Wallet.bin-runtime").write_text("0x" + WALLET.hex())
    (tmp_path / "lone.hex").write_text(GUARDED.hex())
    (tmp_path / "legacy.bin").write_text(GUARDED.hex())
    (tmp_path / "empty.bin").write_text("")
    (tmp_path / "empty.bin-runtime").write_text(WALLET.hex())
    (tmp_path / "notes.txt").write_text("skipped")
    got = load_corpus_dir(str(tmp_path))
    assert got == [("Wallet", WALLET, CTOR_OWNER), ("empty", WALLET),
                   ("legacy", GUARDED), ("lone", GUARDED)]
    (tmp_path / "void").mkdir()
    with pytest.raises(ValueError):
        load_corpus_dir(str(tmp_path / "void"))


def test_fingerprint_covers_the_constructor_and_pairs_hash_as_before():
    pairs = [("a", WALLET), ("b", GUARDED)]
    h = hashlib.sha256()
    for name, code in pairs:
        h.update(name.encode() + b"\0" + hashlib.sha256(code).digest())
    assert corpus_fingerprint(pairs) == h.hexdigest()[:16]
    assert corpus_fingerprint([("a", WALLET, None), ("b", GUARDED)]) \
        == corpus_fingerprint(pairs)
    owner = corpus_fingerprint([("a", WALLET, CTOR_OWNER), ("b", GUARDED)])
    opened = corpus_fingerprint([("a", WALLET, CTOR_OPEN), ("b", GUARDED)])
    assert len({owner, opened, corpus_fingerprint(pairs)}) == 3


def test_checkpoint_resume_deploys_again_and_refuses_another_constructor(
        tmp_path):
    corpus = [("first", GUARDED, CTOR_OWNER), ("wallet", WALLET, CTOR_OWNER)]
    ck = str(tmp_path / "ck")
    with pytest.raises(InjectedKill):
        campaign(corpus, checkpoint_dir=ck, fault_injector=FaultInjector(
            [FaultSpec.parse("kill:batch=1")])).run()
    res = campaign(corpus, checkpoint_dir=ck).run()
    assert res.batches == 2 and found(res) == [("wallet", "106")]
    assert len(res.batch_wall) == 2, "batch 0 came from the checkpoint"
    # the same names and runtime codes under another constructor are
    # another corpus: the cursor is not resumed over it
    other = [corpus[0], ("wallet", WALLET, CTOR_OPEN)]
    camp = campaign(other, checkpoint_dir=ck)
    camp.run()
    assert "checkpoint_reset" in camp._event_kinds


class Spy:
    """A supervisor that answers like the stub worker and keeps what it
    was asked."""

    on_event = None
    device = None

    def __init__(self):
        self.asked = []

    def run_batch(self, bi, names, codes, **kw):
        self.asked.append((list(names), list(codes), kw))
        return {"issues": [], "paths": len(names), "dropped": 0,
                "iprof": {}}

    def close(self):
        pass


def test_isolation_worker_is_handed_the_creation_code():
    # parent side: the campaign gives the supervisor the record ...
    spy = Spy()
    campaign([("wallet", WALLET, CTOR_OWNER), ("plain", GUARDED)],
             worker_isolation="on", worker_supervisor=spy).run()
    assert [a[2]["creations"] for a in spy.asked] == [[CTOR_OWNER], None]
    # ... the supervisor puts it on the wire ...
    sup = WorkerSupervisor(stub=True, batch_timeout=30.0,
                           spawn_timeout=60.0)
    sent = []
    send = sup._send
    sup._send = lambda msg: (sent.append(msg), send(msg))[1]
    try:
        sup.run_batch(0, ["wallet"], [WALLET], creations=[CTOR_OWNER])
        sup.run_batch(1, ["plain"], [GUARDED])
    finally:
        sup.close()
    batches = [m for m in sent if m.get("op") == "batch"]
    assert [m["creations"] for m in batches] == [[CTOR_OWNER], None]
    # ... and the worker's batch handler deploys with it
    camp = campaign([])
    msg = {"bi": 0, "names": ["wallet"], "codes": [WALLET],
           "creations": [CTOR_OPEN], "lanes": None, "width": None}
    out = engine_worker._run_batch(camp, False, msg, None, 0)
    assert [(i["contract"], str(i["swc-id"])) for i in out["issues"]] \
        == [("wallet", "106")]
    assert len(out["issues"][0]["tx_sequence"]) == 3


def test_fed_fleet_unit_carries_the_record(tmp_path):
    ledger = WorkLedger(str(tmp_path / "ledger"))
    ledger.ensure_feed()
    uid = ledger.feed_unit([("wallet", WALLET, CTOR_OWNER),
                            ("plain", GUARDED)], config={"k": 1})
    items, cfg = ledger.read_unit_items(uid)
    assert items == [("wallet", WALLET, CTOR_OWNER), ("plain", GUARDED)]
    assert cfg == {"k": 1}
    assert ledger.read_unit(uid) == (["wallet", "plain"],
                                     [WALLET, GUARDED], {"k": 1})
    uid2 = ledger.feed_unit([("plain", GUARDED)])
    with open(ledger._unit_desc_path(uid2)) as fh:
        assert "creations" not in json.load(fh)


def test_deployed_counter_and_spans_say_which_transaction(tmp_path):
    from mythril_tpu.obs import metrics as obs_metrics
    from mythril_tpu.obs import trace as obs_trace

    reg = obs_metrics.REGISTRY
    before = reg.snapshot()["counters"]
    tracer = obs_trace.configure(buffer=True)
    try:
        campaign([("wallet", WALLET, CTOR_OWNER)]).run()
        spans = [s for s in tracer.drain_buffer() if s.get("kind") == "span"]
    finally:
        obs_trace.close()
    after = reg.snapshot()["counters"]

    def delta(key):
        return after.get(key, 0) - before.get(key, 0)

    assert delta("campaign_contracts_deployed_total") == 1
    kinds = {(s["name"], s.get("tx"), s.get("tx_kind")) for s in spans
             if s["name"] in ("superstep", "drain", "harvest", "tx_seam")}
    assert ("harvest", 0, "creation") in kinds
    assert ("tx_seam", 0, "creation") in kinds
    assert ("tx_seam", 1, "message") in kinds
    assert ("harvest", 2, "message") in kinds
    assert not any(k == "creation" for _, tx, k in kinds if tx)
    seams = {s["tx"]: s for s in spans if s["name"] == "tx_seam"}
    assert seams[0]["carried"] >= 1 and seams[1]["carried"] >= 1
    # one path deployed; what survives each message call is counted
    # under its own transaction index
    assert delta('engine_paths_total{tx="0"}') == 1
    assert delta('engine_paths_total{tx="1"}') >= 2
    assert delta('engine_paths_total{tx="2"}') >= 1
    assert delta('engine_dropped_forks_total{tx="2"}') == 0
    assert np.isfinite(seams[0]["dur"])
