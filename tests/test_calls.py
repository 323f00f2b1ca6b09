"""Sub-transaction layer: cross-contract CALL/DELEGATECALL/STATICCALL.

Real callee frames (save/restore, calldata/
returndata plumbing, storage + balance rollback on revert) replacing the
success-push stubs. Reference: ``mythril/laser/ethereum/call.py`` +
``transaction/transaction_models.py`` (⚠unv, SURVEY.md §3.2).
"""

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.core.frontier import (ACCT_ATTACKER, ACCT_CONTRACT0,
                                       contract_address)
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.ops import u256
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run
from mythril_tpu.analysis import SymExecWrapper, fire_lasers

L = TEST_LIMITS
ADDR1 = contract_address(1)


def run_pair(caller_code, callee_code, n_lanes=4, max_steps=128,
             spec=SymSpec(), balance=10**18):
    imgs = [ContractImage.from_bytecode(c, L.max_code)
            for c in (caller_code, callee_code)]
    corpus = Corpus.from_images(imgs)
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(
        n_lanes, L, contract_id=np.zeros(n_lanes, np.int32), active=active,
        n_contracts=2, balance=balance,
    )
    env = make_env(n_lanes)
    return sym_run(sf, env, corpus, spec, L, max_steps=max_steps)


def storage_of(sf, lane):
    out = {}
    used = np.asarray(sf.base.st_used)
    keys = np.asarray(sf.base.st_keys)
    vals = np.asarray(sf.base.st_vals)
    acct = np.asarray(sf.base.st_acct)
    for k in range(used.shape[1]):
        if used[lane, k]:
            out[(int(acct[lane, k]), u256.to_int(keys[lane, k]))] = \
                u256.to_int(vals[lane, k])
    return out


def call_tokens(value=0, args=(0, 0), ret=(0, 32), gas=50_000, addr=ADDR1):
    """Push CALL args: gas, to, value, argsOff/Len, retOff/Len (reversed)."""
    return [ret[1], ret[0], args[1], args[0], value,
            ("push3", addr), ("push2", gas), "CALL"]


def test_call_returndata_and_success():
    callee = assemble(42, 0, "MSTORE", 32, 0, "RETURN")
    caller = assemble(*call_tokens(), 1, "SSTORE",
                      0, "MLOAD", 2, "SSTORE", "STOP")
    out = run_pair(caller, callee)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0, 1)] == 1       # success
    assert st[(ACCT_CONTRACT0, 2)] == 42      # returned word
    assert bool(np.asarray(out.base.halted)[0])
    assert int(np.asarray(out.base.depth)[0]) == 0


def test_callee_storage_is_isolated():
    # callee writes ITS slot 7; caller writes its own slot 7 after the call
    callee = assemble(11, 7, "SSTORE", "STOP")
    caller = assemble(*call_tokens(), "POP", 22, 7, "SSTORE", "STOP")
    out = run_pair(caller, callee)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0 + 1, 7)] == 11  # callee account's storage
    assert st[(ACCT_CONTRACT0, 7)] == 22      # caller's own slot unharmed


def test_callee_revert_rolls_back_storage():
    callee = assemble(11, 7, "SSTORE", 0, 0, "REVERT")
    caller = assemble(*call_tokens(), 1, "SSTORE", "STOP")
    out = run_pair(caller, callee)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0, 1)] == 0       # success == 0
    assert (ACCT_CONTRACT0 + 1, 7) not in st  # write rolled back
    assert int(np.asarray(out.sub_revert_pc)[0]) >= 0


def test_callee_invalid_becomes_failure_not_lane_death():
    callee = bytes([0xFE])  # INVALID
    caller = assemble(*call_tokens(), 1, "SSTORE", "STOP")
    out = run_pair(caller, callee)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0, 1)] == 0
    assert bool(np.asarray(out.base.halted)[0])
    assert not bool(np.asarray(out.base.error)[0])


def test_value_transfer_moves_balances():
    callee = assemble("CALLVALUE", 3, "SSTORE", "STOP")
    caller = assemble(*call_tokens(value=1000), "POP", "STOP")
    out = run_pair(caller, callee)
    bal = np.asarray(out.base.acct_bal)
    assert u256.to_int(bal[0, ACCT_CONTRACT0]) == 10**18 - 1000
    assert u256.to_int(bal[0, ACCT_CONTRACT0 + 1]) == 10**18 + 1000
    # callee observed msg.value
    assert storage_of(out, 0)[(ACCT_CONTRACT0 + 1, 3)] == 1000


def test_insufficient_balance_returns_zero():
    callee = assemble("STOP")
    caller = assemble(*call_tokens(value=10), 1, "SSTORE", "STOP")
    out = run_pair(caller, callee, balance=5)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0, 1)] == 0  # call failed, lane continues


def test_delegatecall_writes_caller_storage():
    # callee code: SSTORE 5 at slot 9 — under DELEGATECALL this must land
    # in the CALLER's account
    callee = assemble(5, 9, "SSTORE", "STOP")
    caller = assemble(
        32, 0, 0, 0, ("push3", ADDR1), ("push2", 50000), "DELEGATECALL",
        "POP", "STOP",
    )
    out = run_pair(caller, callee)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0, 9)] == 5
    assert (ACCT_CONTRACT0 + 1, 9) not in st


def test_staticcall_blocks_sstore():
    callee = assemble(5, 9, "SSTORE", "STOP")
    caller = assemble(
        32, 0, 0, 0, ("push3", ADDR1), ("push2", 50000), "STATICCALL",
        1, "SSTORE", "STOP",
    )
    out = run_pair(caller, callee)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0, 1)] == 0   # callee failed (static write)
    assert (ACCT_CONTRACT0 + 1, 9) not in st


def test_callee_reads_calldata_from_caller_memory():
    # caller MSTOREs 0x1234 at 0 and passes [0, 32) as calldata;
    # callee stores CALLDATALOAD(0)
    callee = assemble(0, "CALLDATALOAD", 3, "SSTORE", "STOP")
    caller = assemble(0x1234, 0, "MSTORE",
                      *call_tokens(args=(0, 32)), "POP", "STOP")
    out = run_pair(caller, callee)
    assert storage_of(out, 0)[(ACCT_CONTRACT0 + 1, 3)] == 0x1234


def test_symbolic_fork_inside_callee():
    # callee: require(calldataword != 0) -> branches on caller-forwarded
    # SYMBOLIC data; both outcomes explored, revert one rolls back
    callee = assemble(
        0, "CALLDATALOAD", ("ref", "ok"), "JUMPI", 0, 0, "REVERT",
        ("label", "ok"), 1, 8, "SSTORE", "STOP",
    )
    # caller forwards ITS symbolic calldata word via memory
    caller = assemble(
        0, "CALLDATALOAD", 0, "MSTORE",
        *call_tokens(args=(0, 32)), 1, "SSTORE", "STOP",
    )
    out = run_pair(caller, callee)
    act = np.asarray(out.base.active)
    lanes = [i for i in range(act.shape[0]) if act[i]]
    assert len(lanes) == 2, "taken + fallthrough callee branches"
    succ = {storage_of(out, lane).get((ACCT_CONTRACT0, 1)) for lane in lanes}
    assert succ == {0, 1}
    for lane in lanes:
        st = storage_of(out, lane)
        if st[(ACCT_CONTRACT0, 1)] == 1:
            assert st.get((ACCT_CONTRACT0 + 1, 8)) == 1
        else:
            assert (ACCT_CONTRACT0 + 1, 8) not in st


def test_call_to_eoa_succeeds_and_transfers():
    from mythril_tpu.core.frontier import ATTACKER_ADDRESS
    caller = assemble(
        0, 0, 0, 0, 1000,
        ("push32", ATTACKER_ADDRESS), ("push2", 50000), "CALL",
        1, "SSTORE", "STOP",
    )
    callee = assemble("STOP")  # unused
    out = run_pair(caller, callee)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0, 1)] == 1
    bal = np.asarray(out.base.acct_bal)
    assert u256.to_int(bal[0, ACCT_ATTACKER]) == 10**20 + 1000
    assert u256.to_int(bal[0, ACCT_CONTRACT0]) == 10**18 - 1000


def test_unknown_callee_still_gets_symbolic_retval():
    # address not in the account table -> external fallback (havoc retval)
    caller = assemble(
        0, 0, 0, 0, 0, ("push3", 0xEEEEEE), ("push2", 50000), "CALL",
        ("ref", "yes"), "JUMPI", 1, 1, "SSTORE", "STOP",
        ("label", "yes"), 2, 1, "SSTORE", "STOP",
    )
    callee = assemble("STOP")
    out = run_pair(caller, callee)
    act = np.asarray(out.base.active)
    vals = {storage_of(out, i).get((ACCT_CONTRACT0, 1))
            for i in range(act.shape[0]) if act[i]}
    assert vals == {1, 2}, "both success outcomes explored for unknown callee"


def test_requirements_violation_fires_cross_contract():
    # two-contract fixture with a require in the
    # callee explored cross-contract, SWC-123 firing on it
    callee = assemble(
        0, "CALLDATALOAD", 100, "SWAP1", "LT",  # arg < 100 ?
        ("ref", "ok"), "JUMPI", 0, 0, "REVERT",
        ("label", "ok"), "STOP",
    )
    caller = assemble(
        0, "CALLDATALOAD", 0, "MSTORE",
        *call_tokens(args=(0, 32)), "POP",
        1, 0, "SSTORE", "STOP",
    )
    sym = SymExecWrapper(
        [caller, callee], limits=L, lanes_per_contract=8, max_steps=128,
    )
    report = fire_lasers(sym, white_list=["RequirementsViolation"])
    issues = [i for i in report.issues if i.swc_id == "123"]
    assert issues, "callee require() violation must be reported"
    assert issues[0].contract == "contract_0"  # reported on the caller


def test_reverting_value_call_rolls_back_transfer():
    # advisor r2 high: the value transfer must be undone when the callee
    # reverts — the rollback snapshot is taken PRE-transfer
    callee = assemble(0, 0, "REVERT")
    caller = assemble(*call_tokens(value=1000), 1, "SSTORE", "STOP")
    out = run_pair(caller, callee)
    st = storage_of(out, 0)
    assert st[(ACCT_CONTRACT0, 1)] == 0  # call failed
    bal = np.asarray(out.base.acct_bal)
    assert u256.to_int(bal[0, ACCT_CONTRACT0]) == 10**18, "payer refunded"
    assert u256.to_int(bal[0, ACCT_CONTRACT0 + 1]) == 10**18, "payee reverted"


def test_cross_contract_selfdestruct_attribution():
    # advisor r2 medium: a SELFDESTRUCT inside the CALLEE's code must be
    # attributed to the callee's contract id, not the caller's
    callee = assemble(0, "SELFDESTRUCT")
    caller = assemble(*call_tokens(), "POP", 1, 0, "SSTORE", "STOP")
    out = run_pair(caller, callee)
    assert bool(np.asarray(out.base.selfdestructed)[0])
    assert int(np.asarray(out.sd_pc)[0]) >= 0
    assert int(np.asarray(out.sd_cid)[0]) == 1, "recorded in the callee's code"
    assert int(np.asarray(out.base.contract_id)[0]) == 0  # lane back home


def test_delegatecall_propagates_symbolic_caller():
    # advisor r2 low: with a symbolic top-frame CALLER, a sender check
    # inside DELEGATECALLed code must stay symbolic (fork both ways), not
    # be decided concretely against the attacker address
    callee = assemble(
        "CALLER", ("push3", 0x123456), "EQ", ("ref", "own"), "JUMPI",
        1, 3, "SSTORE", "STOP",
        ("label", "own"), 2, 3, "SSTORE", "STOP",
    )
    caller = assemble(
        32, 0, 0, 0, ("push3", ADDR1), ("push2", 50000), "DELEGATECALL",
        "POP", "STOP",
    )
    out = run_pair(caller, callee, spec=SymSpec(caller=True))
    act = np.asarray(out.base.active)
    vals = {storage_of(out, i).get((ACCT_CONTRACT0, 3))
            for i in range(act.shape[0]) if act[i]}
    assert vals == {1, 2}, "both sender-check outcomes explored"


def test_balance_reads_not_forced_equal_across_transfer():
    # advisor r2 low: SELFBALANCE before and after a concrete value
    # transfer must yield DIFFERENT leaves (epoch-versioned), not one
    # hash-consed leaf forcing them equal
    from mythril_tpu.core.frontier import ATTACKER_ADDRESS
    caller = assemble(
        "SELFBALANCE", 1, "SSTORE",
        0, 0, 0, 0, 1000, ("push32", ATTACKER_ADDRESS), ("push2", 50000),
        "CALL", "POP",
        "SELFBALANCE", 2, "SSTORE", "STOP",
    )
    callee = assemble("STOP")  # unused
    out = run_pair(caller, callee)
    st_keys = np.asarray(out.base.st_keys)
    st_used = np.asarray(out.base.st_used)
    val_sym = np.asarray(out.st_val_sym)
    by_key = {}
    for k in range(st_used.shape[1]):
        if st_used[0, k]:
            by_key[u256.to_int(st_keys[0, k])] = int(val_sym[0, k])
    assert by_key[1] != 0 and by_key[2] != 0, "both reads symbolic leaves"
    assert by_key[1] != by_key[2], "pre/post-transfer reads independent"


def test_calldataload_beyond_window_havocs_not_zero():
    # a concrete-offset CALLDATALOAD past the modeled
    # window must havoc (both branches reachable), not read concrete 0
    off = L.calldata_bytes  # first byte past the window
    code = assemble(
        ("push2", off), "CALLDATALOAD", ("ref", "nz"), "JUMPI",
        1, 0, "SSTORE", "STOP",
        ("label", "nz"), 2, 0, "SSTORE", "STOP",
    )
    img = ContractImage.from_bytecode(code, L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(4, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(4, L, active=active)
    env = make_env(4)
    out = sym_run(sf, env, corpus, SymSpec(), L, max_steps=64)
    act = np.asarray(out.base.active)
    vals = {storage_of(out, i).get((ACCT_CONTRACT0, 0))
            for i in range(act.shape[0]) if act[i]}
    assert vals == {1, 2}, "read past the window must stay unconstrained"


def test_static_frame_blocks_symbolic_offset_log():
    # code-review r3: LOG with a SYMBOLIC offset inside a STATICCALL
    # frame must fail the callee like the concrete handler does
    # callee LOG0(off=calldataload(0), len=32): the caller forwards its
    # SYMBOLIC calldata word, so the LOG offset is symbolic (claimed path)
    callee = assemble(0, "CALLDATALOAD", 32, "SWAP1", "LOG0", "STOP")
    caller = assemble(
        0, "CALLDATALOAD", 0, "MSTORE",
        32, 0, 32, 0, ("push3", ADDR1), ("push2", 50000), "STATICCALL",
        1, "SSTORE", "STOP",
    )
    out = run_pair(caller, callee)
    act = np.asarray(out.base.active)
    for lane in np.where(act)[0]:
        st = storage_of(out, lane)
        assert st.get((ACCT_CONTRACT0, 1)) == 0, "static LOG must fail"


def test_selfdestruct_sweeps_balance_to_beneficiary():
    # SELFDESTRUCT(callee addr known in the table): executing account's
    # balance moves to the beneficiary, self zeroes (reference:
    # selfdestruct_ transfer semantics)
    caller = assemble(("push3", ADDR1), "SELFDESTRUCT")
    callee = assemble("STOP")  # just a known account to be credited
    out = run_pair(caller, callee)
    bal = np.asarray(out.base.acct_bal)
    assert u256.to_int(bal[0, ACCT_CONTRACT0]) == 0, "self swept"
    assert u256.to_int(bal[0, ACCT_CONTRACT0 + 1]) == 2 * 10**18, \
        "beneficiary credited"
    assert bool(np.asarray(out.base.selfdestructed)[0])


def test_selfdestruct_symbolic_beneficiary_only_zeroes_self():
    # symbolic beneficiary: funds leave the modeled world, no spurious
    # table credit from garbage limbs
    caller = assemble(0, "CALLDATALOAD", "SELFDESTRUCT")
    callee = assemble("STOP")
    out = run_pair(caller, callee)
    bal = np.asarray(out.base.acct_bal)
    assert u256.to_int(bal[0, ACCT_CONTRACT0]) == 0
    assert u256.to_int(bal[0, ACCT_CONTRACT0 + 1]) == 10**18, "unchanged"


def test_symbolic_callee_enumerates_account_table():
    """A CALL whose target word is SYMBOLIC (the proxy
    pattern — implementation address loaded from unconstrained storage)
    must fork one lane per candidate account instead of havocking: the
    lane constrained to the known implementation executes its code."""
    # proxy: to = sload(0); call(to); store success at slot 1
    caller = assemble(
        32, 0, 0, 0, 0,            # retLen retOff argsLen argsOff value
        0, "SLOAD",                # to (symbolic STORAGE leaf)
        ("push2", 50000), "CALL",
        1, "SSTORE", "STOP",
    )
    # implementation: writes 0x42 to ITS OWN slot 5
    callee = assemble(0x42, 5, "SSTORE", "STOP")
    out = run_pair(caller, callee, n_lanes=8)
    act = np.asarray(out.base.active)
    err = np.asarray(out.base.error)
    impl_lane = None
    for lane in np.where(act & ~err)[0]:
        st = storage_of(out, lane)
        if st.get((ACCT_CONTRACT0 + 1, 5)) == 0x42:
            impl_lane = lane
    assert impl_lane is not None, \
        "no lane explored the concrete implementation's paths"
    # the enumerating (fallback) lane took the external-havoc path and
    # carries the to != addr_k constraints; it must also survive
    assert (act & ~err).sum() >= 3, "candidate forks did not materialize"


def test_symbolic_callee_fallback_constraints():
    """The staying lane accumulates one negative EQ constraint per
    enumerated candidate (to != every known account)."""
    from mythril_tpu.symbolic.ops import SymOp

    caller = assemble(
        0, 0, 0, 0, 0,
        0, "SLOAD",
        ("push2", 50000), "CALL",
        "POP", "STOP",
    )
    callee = assemble("STOP")
    out = run_pair(caller, callee, n_lanes=12)
    # find a surviving lane with >= 4 negative constraints on EQ nodes
    act = np.asarray(out.base.active) & ~np.asarray(out.base.error)
    con_node = np.asarray(out.con_node)
    con_sign = np.asarray(out.con_sign)
    con_len = np.asarray(out.con_len)
    tape_op = np.asarray(out.tape_op)
    best = 0
    for lane in np.where(act)[0]:
        neg_eq = 0
        for c in range(con_len[lane]):
            node = con_node[lane, c]
            if not con_sign[lane, c] and tape_op[lane, node] == int(SymOp.EQ):
                neg_eq += 1
        best = max(best, neg_eq)
    assert best >= 4, f"fallback lane carries {best} != constraints, want 4"
