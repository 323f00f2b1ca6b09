"""Golden-style tests for the SWC detection-module suite: one
hand-assembled vulnerable fixture per module, plus guarded negatives.
(Reference analog: tests/testdata golden-report corpus, SURVEY.md §4.)
"""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.analysis import SymExecWrapper, fire_lasers


def analyze(code, **kw):
    kw.setdefault("limits", TEST_LIMITS)
    kw.setdefault("lanes_per_contract", 16)
    kw.setdefault("max_steps", 192)
    sym = SymExecWrapper([code], **kw)
    return fire_lasers(sym.ctx)


def swcs(report):
    return {i.swc_id for i in report.issues}


def test_unprotected_selfdestruct():
    code = assemble(4, "CALLDATALOAD", "SELFDESTRUCT")
    report = analyze(code)
    assert "106" in swcs(report)
    issue = [i for i in report.issues if i.swc_id == "106"][0]
    assert "beneficiary" in issue.description  # attacker-controlled target


def test_unreachable_selfdestruct_not_flagged():
    # JUMPI with concrete-false condition: the selfdestruct branch is dead
    code = assemble(0, ("ref", "kill"), "JUMPI", "STOP",
                    ("label", "kill"), "CALLER", "SELFDESTRUCT")
    report = analyze(code)
    assert "106" not in swcs(report)


def test_ether_thief_and_external_call():
    # call{value: calldata}(to=calldata): classic drain
    code = assemble(
        0, 0, 0, 0,                  # out_len out_off in_len in_off
        36, "CALLDATALOAD",          # value
        4, "CALLDATALOAD",           # to
        ("push2", 0xFFFF), "CALL",
        "POP", "STOP",
    )
    report = analyze(code)
    assert "105" in swcs(report)
    assert "107" in swcs(report)   # external call to user-supplied address
    assert "104" in swcs(report)   # retval popped, never branched on


def test_checked_retval_not_flagged_104():
    code = assemble(
        0, 0, 0, 0, 0,
        4, "CALLDATALOAD",
        ("push2", 0xFFFF), "CALL",
        ("ref", "ok"), "JUMPI",      # branches on success flag
        0, 0, "REVERT",
        ("label", "ok"), "STOP",
    )
    report = analyze(code)
    assert "104" not in swcs(report)


def test_arbitrary_jump():
    code = assemble(0, "CALLDATALOAD", "JUMP", ("label", "x"), "STOP")
    report = analyze(code)
    assert "127" in swcs(report)


def test_tx_origin():
    code = assemble(
        "ORIGIN", ("push3", 0xC0FFEE), "EQ", ("ref", "auth"), "JUMPI",
        0, 0, "REVERT",
        ("label", "auth"), ("push1", 1), ("push1", 0), "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "115" in swcs(report)
    assert "111" in swcs(report)   # ORIGIN is also a deprecated op


def test_reachable_assert():
    code = assemble(
        4, "CALLDATALOAD", ("push1", 100), "SWAP1", "LT",  # arg? 100<arg
        ("ref", "boom"), "JUMPI", "STOP",
        ("label", "boom"), "INVALID",
    )
    report = analyze(code)
    assert "110" in swcs(report)
    issue = [i for i in report.issues if i.swc_id == "110"][0]
    assert issue.transaction_sequence is not None


def test_delegatecall_to_calldata_address():
    code = assemble(
        0, 0, 0, 0,
        4, "CALLDATALOAD",
        ("push2", 0xFFFF), "DELEGATECALL",
        "POP", "STOP",
    )
    report = analyze(code)
    assert "112" in swcs(report)


def test_arbitrary_storage_write():
    code = assemble(
        36, "CALLDATALOAD", 4, "CALLDATALOAD", "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "124" in swcs(report)


def test_mapping_write_not_flagged_124():
    # keccak-derived key = solidity mapping: not an arbitrary write
    code = assemble(
        4, "CALLDATALOAD", 0, "MSTORE", 0, 32, "MSTORE",
        36, "CALLDATALOAD",
        64, 0, "SHA3",
        "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "124" not in swcs(report)


def test_mapping_write_then_raw_write_still_flagged_124():
    # a keccak mapping write earlier on the path must not mask the raw
    # attacker-keyed write that follows it
    code = assemble(
        4, "CALLDATALOAD", 0, "MSTORE", 0, 32, "MSTORE",
        1, 64, 0, "SHA3", "SSTORE",            # mapping[arg] = 1
        36, "CALLDATALOAD", 4, "CALLDATALOAD", "SSTORE",  # slots[arg1] = arg2
        "STOP",
    )
    report = analyze(code)
    assert "124" in swcs(report)


def test_state_change_after_call_and_multiple_sends():
    code = assemble(
        # two sends, then a storage write
        0, 0, 0, 0, 0, 4, "CALLDATALOAD", ("push2", 0xFFFF), "CALL", "POP",
        0, 0, 0, 0, 0, 4, "CALLDATALOAD", ("push2", 0xFFFF), "CALL", "POP",
        ("push1", 1), ("push1", 0), "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "107" in swcs(report)
    assert "113" in swcs(report)  # multiple sends
    state_change = [i for i in report.issues
                    if i.swc_id == "107" and "re-enter" in i.description]
    assert state_change, "StateChangeAfterCall must fire"


def test_timestamp_gated_transfer():
    code = assemble(
        "TIMESTAMP", ("push4", 0x65000000), "SWAP1", "GT",  # ts > const
        ("ref", "pay"), "JUMPI", "STOP",
        ("label", "pay"),
        0, 0, 0, 0, ("push1", 1), "CALLER", ("push2", 0xFFFF), "CALL",
        "POP", "STOP",
    )
    report = analyze(code)
    assert "116" in swcs(report)


def test_panic_revert_detected():
    panic_word = 0x4E487B71 << 224
    code = assemble(
        ("push32", panic_word), 0, "MSTORE",
        ("push1", 1), ("push1", 4), "MSTORE",
        ("push1", 36), ("push1", 0), "REVERT",
    )
    report = analyze(code)
    assert "110" in swcs(report)
    issue = [i for i in report.issues if "Panic" in i.title][0]
    assert "assert failure" in issue.description


def test_storage_gated_transfer_is_tod():
    # transfer guarded by a storage flag: front-runnable (SWC-114)
    code = assemble(
        0, "SLOAD", ("ref", "pay"), "JUMPI", "STOP",
        ("label", "pay"),
        0, 0, 0, 0, ("push1", 5), "CALLER", ("push2", 0xFFFF), "CALL",
        "POP", "STOP",
    )
    report = analyze(code)
    assert "114" in swcs(report)


# suite-wide undecided-rate bound: the snapshot fixture runs before the
# first test IN THIS FILE that this process runs (xdist --dist loadfile
# runs files whole, so the delta at the last test spans exactly this
# suite's queries; under --dist load the test makes up what is missing)
import pytest  # noqa: E402

from mythril_tpu.smt.solver import SOLVER_STATS  # noqa: E402

_stats0 = {}


@pytest.fixture(scope="module", autouse=True)
def _snapshot_solver_stats():
    _stats0["snap"] = SOLVER_STATS.snapshot()
    yield


def test_unknown_rate_bound_across_suite():
    """Across the SWC-suite fixtures the
    solver must DECIDE (sat or unsat) >= 90% of queries — every unknown is
    a silently dropped candidate finding. Runs last in this file (pytest
    preserves definition order)."""
    d = SOLVER_STATS.delta(_stats0["snap"])
    if d["sat"] + d["unsat"] + d["unknown"] < 10:
        # under `--dist load` the tests above ran in other workers:
        # run them here, so that the delta spans the suite again
        here = test_unknown_rate_bound_across_suite.__code__.co_firstlineno
        for name, fn in sorted(globals().items()):
            if (name.startswith("test_") and callable(fn)
                    and fn.__code__.co_firstlineno < here):
                fn()
        d = SOLVER_STATS.delta(_stats0["snap"])
    decided = d["sat"] + d["unsat"]
    total = decided + d["unknown"]
    assert total >= 10, f"suite exercised too few solver queries: {d}"
    assert d["unknown"] / total < 0.10, (
        f"undecided rate {d['unknown']}/{total} breaches the 10% bound: {d}")


# --- round-4 annotation channel: overflow must reach a sink ---

def test_unsunk_overflow_not_flagged_101():
    # the overflowable ADD result is POPped — it never reaches storage,
    # a call, a log, or a guard; the annotation channel drops it
    # (reference: OverUnderflowAnnotation reported only at sinks). The
    # unrelated store is SYMBOLIC so the lane has a recorded sink the
    # wrapped value provably cannot reach (a lane with no sinks at all
    # keeps the permissive behavior — RETURN flows aren't tracked).
    code = assemble(
        4, "CALLDATALOAD", ("push1", 1), "ADD", "POP",
        36, "CALLDATALOAD", ("push1", 0), "SSTORE",
        "STOP",
    )
    report = analyze(code)
    assert "101" not in swcs(report)


def test_sunk_overflow_still_flagged_101():
    code = assemble(
        4, "CALLDATALOAD", ("push1", 1), "ADD",
        ("push1", 0), "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "101" in swcs(report)


def test_overflow_through_mask_to_store_flagged_101():
    # the wrapped sum flows through AND before being stored: the
    # annotation must propagate through derived nodes, not just direct
    code = assemble(
        4, "CALLDATALOAD", 36, "CALLDATALOAD", "ADD",
        ("push32", (1 << 256) - 1), "AND",
        ("push1", 0), "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "101" in swcs(report)


def test_overflow_flowing_to_return_still_flagged_101():
    # RETURN data flows are untracked: a lane that halts returning data
    # keeps the permissive behavior, so an overflow whose only outlet is
    # the returned word is still reported (reference: _handle_return sink)
    code = assemble(
        4, "CALLDATALOAD", ("push1", 1), "ADD",
        ("push1", 0), "MSTORE",
        ("push1", 32), ("push1", 0), "RETURN",
    )
    report = analyze(code)
    assert "101" in swcs(report)


def test_exp_overflow_attacker_exponent_flagged_101():
    # storage = 3 ** calldata: exponent is attacker-chosen, the power
    # wraps for exp > 255 (sufficient-condition EXP predicate)
    code = assemble(
        4, "CALLDATALOAD", ("push1", 3), "EXP",
        ("push1", 0), "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "101" in swcs(report)


def test_exp_small_concrete_exponent_not_flagged_101():
    # storage = calldata ** 2: the exponent is the CONSTANT 2, the
    # GT(exp, 255) leg of the predicate is concretely false -> refuted
    code = assemble(
        ("push1", 2), 4, "CALLDATALOAD", "EXP",
        ("push1", 0), "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "101" not in swcs(report)


def test_overflow_as_storage_read_key_flagged_101():
    # storage[0] = SLOAD(calldata + 1): the wrapped sum's only use is as
    # a STORAGE-read key — which slot is read observably depends on it,
    # so cone() must traverse the FREE(STORAGE) leaf into its key node
    code = assemble(
        4, "CALLDATALOAD", ("push1", 1), "ADD", "SLOAD",
        ("push1", 0), "SSTORE", "STOP",
    )
    report = analyze(code)
    assert "101" in swcs(report)
