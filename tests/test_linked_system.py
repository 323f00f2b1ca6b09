"""Linked systems: a unit of a campaign, a world of a lane, and what
crosses a hop.

- the corpus directory's manifest (``S.system.json``) and what
  ``load_corpus_dir`` makes of it; a directory without one reads as it
  always did;
- ``CorpusCampaign`` never splits a system: batches are cut on unit
  boundaries, bisection, quarantine, the checkpoint and the resume treat
  a system as one item (a stub runner: no engine);
- ``make_frontier``'s ``systems``: a member's lanes hold their own
  system at the manifest's addresses and nothing of the neighbour's;
- a symbolic word stored whole at ``ptr + 4`` and read by the callee's
  ``CALLDATALOAD(4)`` is the caller's tape node, the selector under it
  stays concrete, an overlapping partial write havocs;
- the plain multi-account EVM (``pyevm_world.py``) on calls, value,
  return data, STATICCALL, DELEGATECALL and revert rollback.
"""

import json
import os

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core.frontier import (ACCT_ATTACKER, ACCT_CONTRACT0,
                                       ACCT_CREATOR, ATTACKER_ADDRESS,
                                       contract_address, make_frontier)
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.fleet import corpus_fingerprint
from mythril_tpu.mythril.campaign import (CorpusCampaign, corpus_units,
                                          load_corpus_dir)
from mythril_tpu.ops import u256
from mythril_tpu.resilience import FaultInjector, InjectedKill
from mythril_tpu.symbolic import state as st
from mythril_tpu.utils.checkpoint import load_json_checkpoint

from pyevm_world import World
from test_calls import call_tokens, run_pair

L = TEST_LIMITS


# --- the manifest ------------------------------------------------------------

def write_dir(tmp_path, systems, singles=()):
    """``systems``: {name: [(member, address)]}; every member and single
    gets a ``.bin`` / ``.bin-runtime`` pair."""
    for name in [m for ms in systems.values() for m, _ in ms] + list(singles):
        (tmp_path / f"{name}.bin").write_text("6000")
        (tmp_path / f"{name}.bin-runtime").write_text("00")
    for sysname, members in systems.items():
        (tmp_path / f"{sysname}.system.json").write_text(json.dumps({
            "system": sysname,
            "members": [{"name": m, "address": hex(a)} for m, a in members]}))
    return str(tmp_path)


def test_manifest_members_come_together_in_deploy_order(tmp_path):
    d = write_dir(tmp_path, {"sys": [("zeta", 0x10), ("alpha", 0x20)]},
                  singles=("beta", "omega"))
    recs = load_corpus_dir(d)
    assert [r[0] for r in recs] == ["zeta", "alpha", "beta", "omega"]
    assert recs[0][3] == {"system": "sys", "address": 0x10}
    assert recs[1][3] == {"system": "sys", "address": 0x20}
    assert recs[0][2] == b"\x60\x00" and len(recs[2]) == 3
    assert [len(u) for u in corpus_units(recs)] == [2, 1, 1]
    # the link is content: another address is another corpus
    other = [recs[0][:3] + ({"system": "sys", "address": 0x11},)] + recs[1:]
    assert corpus_fingerprint(recs) != corpus_fingerprint(other)
    assert corpus_fingerprint(recs[2:]) == corpus_fingerprint(
        [r[:3] for r in recs[2:]])


def test_directory_without_manifest_reads_as_before(tmp_path):
    d = write_dir(tmp_path, {}, singles=("b", "a"))
    (tmp_path / "c.hex").write_text("0x00")
    assert load_corpus_dir(d) == [("a", b"\x00", b"\x60\x00"),
                                  ("b", b"\x00", b"\x60\x00"),
                                  ("c", b"\x00")]


@pytest.mark.parametrize("case, match", [
    ("too_many", r"5 members does not fit.*at most 2 members \(max_accounts"),
    ("missing", "without a code file.*ghost"),
    ("twice", "appears twice"),
])
def test_manifest_refusals_say_why(tmp_path, case, match):
    members = {"too_many": [(f"m{i}", i + 1) for i in range(5)],
               "missing": [("m0", 1)],
               "twice": [("m0", 1), ("m1", 1)]}[case]
    d = write_dir(tmp_path, {"sys": members})
    if case == "missing":
        doc = json.loads((tmp_path / "sys.system.json").read_text())
        doc["members"].append({"name": "ghost", "address": "0x9"})
        (tmp_path / "sys.system.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match=match):
        load_corpus_dir(d, max_members=L.max_accounts - 2)


def test_campaign_refuses_a_system_its_limits_cannot_hold():
    recs = [(f"m{i}", b"\x00", None, {"system": "s", "address": i + 1})
            for i in range(3)]
    with pytest.raises(ValueError, match=r"max_accounts - 2 = 2"):
        CorpusCampaign(recs, batch_size=4, limits=L, spec=object())
    with pytest.raises(ValueError, match="--num-hosts and --fleet"):
        CorpusCampaign(recs[:2], batch_size=4, limits=L, spec=object(),
                       fleet_dir="/nonexistent")


# --- a system is never split ----------------------------------------------------

def linked(system, k, n=3):
    return [(f"{system}_{j}", b"\x00", b"\x00",
             {"system": system, "address": 0x1000 * (k + 1) + j})
            for j in range(n)]


# A A A s | B B B | C C C s | s   (batch_size 4, systems of three)
CORPUS = (linked("A", 0) + [("s0", b"\x00")] + linked("B", 1)
          + linked("C", 2) + [("s1", b"\x00"), ("s2", b"\x00")])
SYSTEMS = {"A", "B", "C"}


def recording_runner(calls):
    def run(bi, names, codes):
        calls.append((bi, list(names)))
        return {"issues": [{"contract": n, "batch": bi} for n in names],
                "paths": len(names), "dropped": 0, "iprof": {}}
    return run


def stub(calls, ckpt=None, fault=None, **kw):
    import dataclasses

    return CorpusCampaign(
        CORPUS, batch_size=4, checkpoint_dir=ckpt, spec=object(),
        limits=dataclasses.replace(L, max_accounts=6),
        batch_timeout=5.0, fault_injector=FaultInjector.from_string(fault),
        batch_runner=recording_runner(calls), **kw)


def whole_systems(names) -> bool:
    return all(sum(n.startswith(s + "_") for n in names) in (0, 3)
               for s in SYSTEMS)


@pytest.mark.parametrize("pipeline", [False, True])
def test_batches_are_cut_on_system_boundaries(pipeline):
    calls = []
    camp = stub(calls, pipeline=pipeline)
    assert camp.n_batches == 4
    res = camp.run()
    assert [names for _, names in calls] == [
        ["A_0", "A_1", "A_2", "s0"], ["B_0", "B_1", "B_2"],
        ["C_0", "C_1", "C_2", "s1"], ["s2"]]
    assert all(whole_systems(names) for _, names in calls)
    assert res.contracts == len(CORPUS) and res.batch_status == [
        "ok"] * res.batches
    assert sorted(i["contract"] for i in res.issues) == sorted(
        c[0] for c in CORPUS)


def test_a_poisoned_member_quarantines_its_system_and_nothing_else(tmp_path):
    calls = []
    res = stub(calls, str(tmp_path / "q"), "raise:contract=B_1").run()
    assert all(whole_systems(names) for _, names in calls)
    assert sorted(q["name"] for q in res.quarantined) == [
        "B_0", "B_1", "B_2"]
    assert {q["system"] for q in res.quarantined} == {"B"}
    assert all("ResilienceError" in q["reason"] for q in res.quarantined)
    assert sorted(i["contract"] for i in res.issues) == sorted(
        c[0] for c in CORPUS if not c[0].startswith("B_"))
    assert "quarantined:3" in res.batch_status


def test_kill_and_resume_with_a_poisoned_member(tmp_path):
    ck = str(tmp_path / "k")
    calls = []
    with pytest.raises(InjectedKill):
        stub(calls, ck, "raise:contract=A_2;kill:batch=1").run()
    state = load_json_checkpoint(os.path.join(ck, "campaign.json"))
    assert state["next_batch"] == 1
    assert sorted(q["name"] for q in state["quarantined"]) == [
        "A_0", "A_1", "A_2"]
    calls2 = []
    resumed = stub(calls2, ck, "raise:contract=A_2").run()
    straight = stub([], str(tmp_path / "s"), "raise:contract=A_2").run()
    assert all(bi >= 1 for bi, _ in calls2)
    assert all(whole_systems(n) for _, n in calls + calls2)
    assert resumed.quarantined == straight.quarantined
    assert resumed.contracts == straight.contracts == len(CORPUS)
    assert (sorted(i["contract"] for i in resumed.issues)
            == sorted(i["contract"] for i in straight.issues))


def test_halved_batches_keep_systems_whole():
    subs = CorpusCampaign._sub_batches(CORPUS[:8], 4)
    assert [[c[0] for c in s] for s in subs] == [
        ["A_0", "A_1", "A_2", "s0"], ["B_0", "B_1", "B_2", "C_0"]]
    assert CorpusCampaign._sub_batches(CORPUS[:4], 2) == [
        list(CORPUS[:3]), [CORPUS[3]]]


# --- the lane's world -------------------------------------------------------------

def test_a_members_lanes_hold_their_own_system_at_its_addresses():
    addrs = [0xA1, 0xA2, 0xA3, 0xB1, 0xB2, contract_address(5)]
    systems = [[0, 1, 2]] * 3 + [[3, 4]] * 2 + [None]
    cid = np.repeat(np.arange(6, dtype=np.int32), 2)
    import dataclasses

    f = make_frontier(12, dataclasses.replace(L, max_accounts=6),
                      contract_id=cid, n_contracts=6, contract_addrs=addrs,
                      systems=systems)
    table = [[u256.to_int(a) for a in lane]
             for lane in np.asarray(f.acct_addr)]
    code, used = np.asarray(f.acct_code), np.asarray(f.acct_used)
    cur = np.asarray(f.cur_acct)
    for lane, c in enumerate(cid):
        members = systems[c] or [c]
        n = len(members)
        assert table[lane][ACCT_ATTACKER] == ATTACKER_ADDRESS
        assert used[lane].tolist() == [True] * (2 + n) + [False] * (4 - n)
        assert table[lane][2:2 + n] == [addrs[j] for j in members]
        assert code[lane][2:2 + n].tolist() == members
        assert cur[lane] == ACCT_CONTRACT0 + members.index(c)
        assert used[lane][ACCT_CREATOR]
    with pytest.raises(ValueError, match="max_accounts=4"):
        make_frontier(6, L, contract_id=cid[:6], n_contracts=3,
                      contract_addrs=addrs[:3], systems=[[0, 1, 2]] * 3)


# --- what crosses a hop ----------------------------------------------------------

def sym_storage(sf, lane=0):
    out = {}
    for k in np.flatnonzero(np.asarray(sf.base.st_used)[lane]):
        key = (int(np.asarray(sf.base.st_acct)[lane, k]),
               u256.to_int(np.asarray(sf.base.st_keys)[lane, k]))
        out[key] = (int(np.asarray(sf.st_val_sym)[lane, k]),
                    u256.to_int(np.asarray(sf.base.st_vals)[lane, k]))
    return out


CALLEE = assemble(4, "CALLDATALOAD", 1, "SSTORE",       # arg 0
                  36, "CALLDATALOAD", 3, "SSTORE",      # arg 1
                  0, "CALLDATALOAD", 0xE0, "SHR", 2, "SSTORE",
                  4, "CALLDATALOAD", 0, "MSTORE", 32, 0, "RETURN")
SELECTOR = 0xAABBCCDD


def caller(extra=(), second=77):
    """solc's encoding: the selector word, a symbolic argument at 4, a
    second one at 36; then the call, and the return word read back."""
    return assemble(("push32", SELECTOR << 224), 0, "MSTORE",
                    4, "CALLDATALOAD", "DUP1", 5, "SSTORE", 4, "MSTORE",
                    *([second] if isinstance(second, int) else second),
                    36, "MSTORE", *extra,
                    *call_tokens(args=(0, 68), ret=(0, 32)), "POP",
                    0, "MLOAD", 6, "SSTORE", "STOP")


ME, YOU = ACCT_CONTRACT0, ACCT_CONTRACT0 + 1


def test_a_word_stored_at_ptr_plus_4_is_the_callers_node_in_the_callee():
    out = run_pair(caller(), CALLEE)
    got = sym_storage(out)
    node = got[(ME, 5)][0]
    assert node != 0
    assert got[(YOU, 1)] == (node, 0) or got[(YOU, 1)][0] == node
    assert got[(YOU, 3)] == (0, 77)                 # a concrete neighbour
    assert got[(YOU, 2)] == (0, SELECTOR)           # the selector: concrete
    assert got[(ME, 6)][0] == node                  # and back, as a word
    hop = np.asarray(out.hop_stats)[0]
    assert hop[st.HOP_INTERNAL] == 1 and hop[st.HOP_MEMBER] == 1
    assert hop[st.HOP_CD_EXACT] == 2 and hop[st.HOP_CD_HAVOC] == 0
    assert hop[st.HOP_RET_EXACT] == 1 and hop[st.HOP_DEPTH] == 1


def test_two_symbolic_arguments_both_cross():
    out = run_pair(caller(second=[68, "CALLDATALOAD", "DUP1", 7, "SSTORE"]),
                   CALLEE)
    got = sym_storage(out)
    assert got[(YOU, 1)][0] == got[(ME, 5)][0] != 0
    assert got[(YOU, 3)][0] == got[(ME, 7)][0] != 0
    assert got[(YOU, 3)][0] != got[(YOU, 1)][0]
    assert got[(YOU, 2)] == (0, SELECTOR)


@pytest.mark.parametrize("extra", [
    (9, 10, "MSTORE8"),                     # a byte into the argument
    (5, 16, "MSTORE"),                      # a word at another shift
    (8, 0x20, "MSTORE"),                    # an aligned word over its tail
], ids=["mstore8", "other_shift", "aligned_over_tail"])
def test_a_partial_overwrite_of_the_argument_still_havocs(extra):
    out = run_pair(caller(extra), CALLEE)
    got = sym_storage(out)
    node = got[(ME, 5)][0]
    assert got[(YOU, 1)][0] not in (0, node)        # a fresh leaf
    assert np.asarray(out.hop_stats)[0][st.HOP_CD_HAVOC] >= 1


def test_an_unaligned_word_reads_back_whole_in_its_own_frame():
    code = assemble(4, "CALLDATALOAD", "DUP1", 1, "SSTORE", 0x24, "MSTORE",
                    0x24, "MLOAD", 2, "SSTORE",         # the same offset
                    0x25, "MLOAD", 3, "SSTORE",         # one byte on
                    5, 0x44, "MSTORE", 0x44, "MLOAD", 4, "SSTORE", "STOP")
    out = run_pair(code, b"\x00")
    got = sym_storage(out)
    node = got[(ME, 1)][0]
    assert got[(ME, 2)][0] == node != 0
    assert got[(ME, 3)][0] not in (0, node)
    assert got[(ME, 4)] == (0, 5)                   # concrete, and exact


# --- the plain multi-account EVM ----------------------------------------------------

A, B, EOA = 0xA0, 0xB0, 0xE0


def world(code_a, code_b):
    w = World(eoas=(EOA,))
    for addr, code in ((A, code_a), (B, code_b)):
        assert w.deploy(addr, assemble(
            len(code), "DUP1", 12, 0, "CODECOPY", 0, "RETURN") .ljust(
                12, b"\x00") + code, EOA)
        assert w.code(addr) == code
    return w


def call_b(op="CALL", value=0, args=(0, 0), ret=(0, 32)):
    head = [ret[1], ret[0], args[1], args[0]]
    if op in ("CALL", "CALLCODE"):
        head.append(value)
    return [*head, B, ("push2", 50_000), op]


def test_world_call_runs_the_callee_over_its_own_storage_and_memory():
    b = assemble(7, 1, "SSTORE", "CALLVALUE", 0, "MSTORE", 32, 0, "RETURN")
    a = assemble(99, 0, "MSTORE", *call_b(value=5), 2, "SSTORE",
                 0, "MLOAD", 3, "SSTORE", "RETURNDATASIZE", 4, "SSTORE",
                 "STOP")
    w = world(a, b)
    ok, _ = w.message(EOA, A, 0, b"", EOA)
    assert ok and w.storage() == {A: {2: 1, 3: 5, 4: 32}, B: {1: 7}}
    assert w.balance(B) == 10 ** 18 + 5 and w.balance(A) == 10 ** 18 - 5
    assert w.sent == [(A, B, 5)] and w.deepest == 1


def test_world_revert_rolls_storage_and_value_back():
    b = assemble(7, 1, "SSTORE", 0xBAD, 0, "MSTORE", 32, 0, "REVERT")
    a = assemble(*call_b(value=5), 2, "SSTORE", 0, "MLOAD", 3, "SSTORE",
                 "STOP")
    w = world(a, b)
    ok, _ = w.message(EOA, A, 0, b"", EOA)
    assert ok and w.storage() == {A: {2: 0, 3: 0xBAD}, B: {}}
    assert w.balance(B) == 10 ** 18 and not w.sent


def test_world_staticcall_forbids_writes_and_delegatecall_keeps_context():
    b = assemble("CALLER", 1, "SSTORE", "ADDRESS", 2, "SSTORE", "STOP")
    a = assemble(*call_b("STATICCALL"), 5, "SSTORE",
                 *call_b("DELEGATECALL"), 6, "SSTORE", "STOP")
    w = world(a, b)
    ok, _ = w.message(EOA, A, 0, b"", EOA)
    assert ok and w.storage() == {A: {5: 0, 6: 1, 1: EOA, 2: A}, B: {}}
