"""Dynamic ABI arguments: the floor under invalidated memory, and the
select over the transaction's bytes.

solc decodes an ``address[]`` or a ``bytes`` argument with a read at a
symbolic calldata offset and a ``CALLDATACOPY`` whose source and length
the caller chose. The copy invalidates memory from its destination's
word up (``SymFrontier.mem_floor``), not the scratch words below it
with which every mapping slot is hashed; the read is a ``CD_SELECT``
node that the evaluator answers from the transaction's bytes. Engine
against the plain EVM (``tests/pyevm_ref.py``) throughout, at the test
limits.
"""

import os
import random
import sys

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.core.frontier import ATTACKER_ADDRESS
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.ops import u256
from mythril_tpu.ops.keccak import keccak256_host_int
from mythril_tpu.smt.eval import Assignment, _evaluate_py, evaluate
from mythril_tpu.smt.solver import solve_lane
from mythril_tpu.smt.tape import extract_tape
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run
from mythril_tpu.symbolic.ops import FreeKind, SymOp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from pyevm_ref import RefEVM  # noqa: E402

L = TEST_LIMITS
PROLOGUE = [0x80, 0x40, "MSTORE"]
#: solc 0.4's decode of the ``bytes`` argument in head word 0, [] -> []
DECODE = [4, "CALLDATALOAD", 4, "ADD", "DUP1", "CALLDATALOAD",
          0x40, "MLOAD", "DUP2", "DUP2", "MSTORE",
          "DUP2", 0x1F, "ADD", 0x1F, "NOT", "AND",
          "DUP1", "DUP5", 0x20, "ADD", "DUP4", 0x20, "ADD", "CALLDATACOPY",
          "DUP2", 0x20, "ADD", "ADD", 0x40, "MSTORE", "POP", "POP", "POP"]
#: mapping[msg.sender] at slot 7: [] -> [keccak(caller . 7)]
SLOT = ["CALLER", 0, "MSTORE", 7, 0x20, "MSTORE", 0x40, 0, "SHA3"]


def srun(code: bytes, max_steps: int = 128, n_lanes: int = 4):
    img = ContractImage.from_bytecode(code, L.max_code)
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(n_lanes, L, active=active)
    return sym_run(sf, make_env(n_lanes), Corpus.from_images([img]),
                   SymSpec(), L, max_steps=max_steps, propagate_every=0)


def top(sf, lane=0, depth=0):
    """(value, tape node) of a stack slot, from the top."""
    at = int(sf.base.sp[lane]) - 1 - depth
    return (u256.to_int(np.asarray(sf.base.stack[lane, at])),
            int(sf.stack_sym[lane, at]))


def node(sf, i, lane=0):
    return (int(sf.tape_op[lane, i]), int(sf.tape_a[lane, i]),
            int(sf.tape_b[lane, i]))


def abi_bytes(data: bytes) -> bytes:
    """A call whose only argument is ``data`` as ``bytes``."""
    return (bytes(4) + (32).to_bytes(32, "big")
            + len(data).to_bytes(32, "big") + data.ljust(32, b"\0"))


# --- the floor -----------------------------------------------------------------

def test_a_copy_leaves_the_words_below_its_destination_exact():
    """After a decode the mapping slot hashed in the scratch words is
    the plain EVM's, the length stored below the copy reads back as the
    select, a word the copy filled is a havoc leaf, and the free pointer
    is a sum over the length."""
    code = assemble(*PROLOGUE, *DECODE, 0xA0, "MLOAD", 0x80, "MLOAD",
                    0x40, "MLOAD", *SLOT, "STOP")
    sf = srun(code)
    assert bool(sf.base.halted[0]) and not bool(sf.base.error[0])
    assert int(sf.mem_floor[0]) == 0xA0 // 32
    ref = RefEVM(code, abi_bytes(b"\x01\x02\x03")).run()
    slot, slot_sym = top(sf)
    assert slot_sym == 0 and slot == ref.stack[-1] == keccak256_host_int(
        ATTACKER_ADDRESS.to_bytes(32, "big") + (7).to_bytes(32, "big"))
    _, free = top(sf, depth=1)
    assert node(sf, free)[0] == int(SymOp.ADD)
    _, length = top(sf, depth=2)
    assert node(sf, length)[0] == int(SymOp.CD_SELECT)
    _, filled = top(sf, depth=3)
    assert node(sf, filled)[:2] == (int(SymOp.FREE), int(FreeKind.HAVOC))


@pytest.mark.parametrize("offset, floor", [
    # base + symbolic, either operand order, and nested as an allocation
    # after a decode is; a small concrete part counts, a huge one (a
    # subtraction in disguise) and any other operation do not
    ([0, "CALLDATALOAD", 0x100, "ADD"], 8),
    ([0x100, 0, "CALLDATALOAD", "ADD"], 8),
    ([0, "CALLDATALOAD", 0x100, "ADD", 0x20, "ADD"], 9),
    ([0x20, 0, "CALLDATALOAD", 0x20, "MUL", 0xA0, "ADD", "ADD"], 6),
    ([0, "CALLDATALOAD"], 0),
    ([0, "CALLDATALOAD", 0x100, "MUL"], 0),
    ([0, "CALLDATALOAD", ("push32", (1 << 256) - 32), "ADD"], 0),
    ([0x100, 0, "CALLDATALOAD", 0x200, "ADD", "SUB"], 0),
])
def test_a_store_at_a_symbolic_offset_floors_at_its_concrete_base(
        offset, floor):
    code = assemble(*PROLOGUE, 1, *offset, "MSTORE", 0x40, "MLOAD", *SLOT,
                    0x200, "MLOAD", "STOP")
    sf = srun(code)
    assert int(sf.mem_floor[0]) == floor
    # a word at or above the floor is unknown, one below it exact
    _, above = top(sf)
    assert node(sf, above)[:2] == (int(SymOp.FREE), int(FreeKind.HAVOC))
    slot, slot_sym = top(sf, depth=1)
    free, free_sym = top(sf, depth=2)
    if floor > 2:
        assert (free, free_sym) == (0x80, 0)
        assert slot_sym == 0 and slot == keccak256_host_int(
            ATTACKER_ADDRESS.to_bytes(32, "big") + (7).to_bytes(32, "big"))
    else:
        # no concrete base: the scratch words are gone, as they always
        # were, and the slot is a havoc digest
        for sym in (free_sym, slot_sym):
            assert node(sf, sym)[:2] == (int(SymOp.FREE),
                                         int(FreeKind.HAVOC))


def test_the_floor_is_saved_with_the_frame_and_comes_back():
    """The caller's memory is invalidated from word 0 before it calls;
    the callee starts exact (the hash over its own scratch word is the
    plain one), and back in the caller a read is a havoc leaf again."""
    from mythril_tpu.core.frontier import contract_address

    callee = assemble(5, 0, "MSTORE", 0x20, 0, "SHA3", 1, "SSTORE", "STOP")
    caller = assemble(1, 0, "CALLDATALOAD", "MSTORE",
                      0, 0, 0, 0, 0, ("push3", contract_address(1)),
                      ("push2", 50_000), "CALL", "POP",
                      0, "MLOAD", 2, "SSTORE", "STOP")
    imgs = [ContractImage.from_bytecode(c, L.max_code)
            for c in (caller, callee)]
    active = np.zeros(4, dtype=bool)
    active[0] = True
    # the shape of ``tests/test_calls.py``'s pairs: one compiled program
    sf = make_sym_frontier(4, L, contract_id=np.zeros(4, np.int32),
                           active=active, n_contracts=2, balance=10**18)
    sf = sym_run(sf, make_env(4), Corpus.from_images(imgs), SymSpec(), L,
                 max_steps=128)
    assert bool(sf.base.halted[0]) and int(sf.base.depth[0]) == 0
    assert int(sf.fr_mem_floor[0, 0]) == 0 and int(sf.mem_floor[0]) == 0
    keys = [u256.to_int(k) for k in np.asarray(sf.base.st_keys[0])]
    hashed, read_back = keys.index(1), keys.index(2)
    assert int(sf.st_val_sym[0, hashed]) == 0
    assert u256.to_int(np.asarray(sf.base.st_vals[0, hashed])) == \
        keccak256_host_int((5).to_bytes(32, "big"))
    assert node(sf, int(sf.st_val_sym[0, read_back]))[:2] == (
        int(SymOp.FREE), int(FreeKind.HAVOC))


# --- the select ------------------------------------------------------------------

#: the length of the dynamic argument in head word 0, left on the stack
LENGTH = assemble(4, "CALLDATALOAD", 4, "ADD", "CALLDATALOAD", "STOP")


def test_the_select_reads_what_the_plain_evm_reads():
    sf = srun(LENGTH)
    _, length = top(sf)
    op, offset, b = node(sf, length)
    assert (op, b) == (int(SymOp.CD_SELECT), 0)
    assert node(sf, offset)[0] == int(SymOp.ADD)
    assert tuple(int(x) for x in sf.cd_reads[0]) == (1, 0)
    tape = extract_tape(sf, 0)
    assert tape.nodes[length].imm == 0      # the first transaction's bytes
    rng = random.Random(40)
    for _ in range(32):
        off = rng.randrange(0, L.calldata_bytes - 36)
        data = bytearray(rng.randbytes(L.calldata_bytes))
        data[4:36] = off.to_bytes(32, "big")
        if off >= 32:   # a length of its own, where it is off the head
            data[4 + off:36 + off] = rng.randrange(1 << 256).to_bytes(
                32, "big")
        want = RefEVM(LENGTH, bytes(data)).run().stack[-1]
        asn = Assignment()
        asn.tx(0).calldata = bytearray(data)
        assert evaluate(tape, asn)[length] == want
        assert _evaluate_py(tape, asn)[length] == want


def test_a_read_beyond_the_window_is_still_a_havoc_leaf():
    sf = srun(assemble(L.calldata_bytes + 8, "CALLDATALOAD", "STOP"))
    _, leaf = top(sf)
    assert node(sf, leaf)[:2] == (int(SymOp.FREE), int(FreeKind.HAVOC))
    assert not np.asarray(sf.cd_reads).any()


def test_the_witness_search_sets_a_length_behind_the_head_and_it_replays():
    """``f(bytes, uint256 v)``: ``require(length == 3); require(v == 7)``
    then a store. The search has to point the offset off the two head
    words (all-zero calldata puts the length on the offset's own word)
    and write the length there; the transaction replays."""
    code = assemble(
        4, "CALLDATALOAD", 4, "ADD", "CALLDATALOAD", 3, "EQ",
        ("ref", "a"), "JUMPI", 0, 0, "REVERT", ("label", "a"),
        36, "CALLDATALOAD", 7, "EQ", ("ref", "b"), "JUMPI", 0, 0, "REVERT",
        ("label", "b"), 1, 0, "SSTORE", "STOP")
    sf = srun(code)
    wrote = np.flatnonzero(np.asarray(sf.base.active)
                           & np.asarray(sf.base.st_used).any(axis=1))
    assert len(wrote) == 1
    asn = solve_lane(sf, int(wrote[0]))
    assert asn is not None
    data = bytes(asn.tx(0).calldata)
    offset = int.from_bytes(data[4:36], "big")
    assert offset == 64, "the data starts behind the two head words"
    assert int.from_bytes(data[4 + offset:36 + offset], "big") == 3
    res = RefEVM(code, data).run()
    assert res.halted and not res.reverted and res.storage == {0: 1}


# --- this issue's twins, through the campaign's device phase -----------------------

def test_a_flaw_behind_a_decode_is_reported_wherever_its_static_twin_is():
    """``execute(address,uint256)`` behind ``require(members[msg.sender])``
    with a ``join()`` anyone may call, and ``kill()`` behind
    ``require(balances[msg.sender] != 0)`` with a ``deposit()``: each
    beside its twin that first decodes a ``bytes`` argument, and the
    four without the way in. Creation transaction and two calls from
    concrete storage, as the deploying cells run them."""
    from mythril_tpu.analysis import fire_lasers
    from mythril_tpu.mythril.campaign import CorpusCampaign

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    from bench_paths import load

    da = load("corpora/dynargs-v1.py", "dynargs_v1_for_the_twins")
    wild = da.wild

    def execute_static(c, L_):
        return [*da.nonpayable(L_), "CALLER", *da.map1(da.MEMBERS_SLOT),
                "SLOAD", *da.require(L_, "mem", "", True),
                0, 0, 0, 0, *da.arg(1), *da.arg_addr(0), "GAS", "CALL",
                *da.require(L_, "sent", "", True), "STOP"]

    def kill_funded(c, L_, decoded=False):
        return [*da.nonpayable(L_),
                *([*da.decode(0, False), "POP"] if decoded else []),
                "CALLER", *da.map1(c.balances), "SLOAD",
                *da.require(L_, "bal", "", True), "CALLER", "SELFDESTRUCT"]

    def kill_funded_decoded(c, L_):
        return kill_funded(c, L_, True)

    plan = [  # (functions, the id that has to be reported or may not be)
        ((da.join, execute_static), "105", True),
        ((da.join, da.execute), "105", True),
        ((wild.get_slot, execute_static), "105", False),
        ((wild.get_slot, da.execute), "105", False),
        ((wild.deposit, kill_funded), "106", True),
        ((wild.deposit, kill_funded_decoded), "106", True),
        ((wild.get_slot, kill_funded), "106", False),
        ((wild.get_slot, kill_funded_decoded), "106", False),
    ]
    rng = random.Random(40)
    codes = [da.dep.runtime(rng, fns, len(fns), True)[0]
             for fns, _, _ in plan]
    names = [f"twin{k}" for k in range(len(plan))]
    camp = CorpusCampaign([], batch_size=8, lanes_per_contract=16,
                          limits=L, spec=SymSpec(storage=False),
                          max_steps=128, transaction_count=2)
    sym = camp._explore_batch(
        0, names, codes,
        creations=[da.creation(code, True, False) for code in codes])
    reported = {name: set() for name in names}
    for issue in fire_lasers(sym).issues:
        reported[issue.contract].add(str(issue.swc_id))
        if str(issue.swc_id) == plan[names.index(issue.contract)][1]:
            assert len(issue.transaction_sequence) == 3
    got = [swc in reported[name]
           for name, (_, swc, _) in zip(names, plan)]
    assert got == [flawed for _, _, flawed in plan], reported
