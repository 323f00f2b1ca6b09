"""Golden-report corpus: the in-repo behavioral spec for the SWC suite.

The reference's ``tests/testdata/outputs_expected``
oracle is unreachable (mount empty), so these goldens pin the suite's
behavior issue-for-issue: each fixture (vulnerable + safe sibling per
SWC class) has an expected-issue JSON under ``tests/fixtures/goldens/``;
refactors of the engine/solver/detectors cannot silently shift
detections past this file.

Regenerate after an INTENDED behavior change with
``MYTHRIL_REGEN_GOLDENS=1 python -m pytest tests/test_goldens.py`` and
review the diff like any other code change.

Witness-dependent fields (transaction_sequence, lane, description text)
are stripped: the pinned identity is (contract, swc-id, address, title,
severity).
"""

import json
import os

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.analysis import SymExecWrapper, fire_lasers

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "fixtures", "goldens")
REGEN = bool(os.environ.get("MYTHRIL_REGEN_GOLDENS"))


def _fixtures():
    """name -> (bytecode, kwargs). One vulnerable + one safe sibling per
    SWC class the suite covers (reference: input_contracts pairs ⚠unv)."""
    fx = {}

    def add(name, *tokens, **kw):
        fx[name] = (assemble(*tokens), kw)

    # SWC-106 unprotected / guarded SELFDESTRUCT
    add("swc106_killable", 4, "CALLDATALOAD", "SELFDESTRUCT")
    add("swc106_guarded",
        "CALLER", ("push20", 0xAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFE),
        "EQ", ("ref", "ok"), "JUMPI", 0, 0, "REVERT",
        ("label", "ok"), "CALLER", "SELFDESTRUCT")
    # SWC-105 / 107 / 104: ether drain + unchecked external call
    add("swc105_drain",
        0, 0, 0, 0, 36, "CALLDATALOAD", 4, "CALLDATALOAD",
        ("push2", 0xFFFF), "CALL", "POP", "STOP")
    add("swc104_checked",
        0, 0, 0, 0, 0, 4, "CALLDATALOAD", ("push2", 0xFFFF), "CALL",
        ("ref", "ok"), "JUMPI", 0, 0, "REVERT", ("label", "ok"), "STOP")
    # SWC-127 arbitrary jump + safe static jump
    add("swc127_arbitrary_jump", 0, "CALLDATALOAD", "JUMP",
        ("label", "x"), "STOP")
    add("swc127_static_jump", ("ref", "x"), "JUMP", ("label", "x"),
        ("push1", 1), ("push1", 0), "SSTORE", "STOP")
    # SWC-115 tx.origin auth + safe CALLER auth
    add("swc115_origin_auth",
        "ORIGIN", ("push3", 0xC0FFEE), "EQ", ("ref", "a"), "JUMPI",
        0, 0, "REVERT",
        ("label", "a"), 1, 0, "SSTORE", "STOP")
    add("swc115_caller_auth",
        "CALLER", ("push3", 0xC0FFEE), "EQ", ("ref", "a"), "JUMPI",
        0, 0, "REVERT",
        ("label", "a"), 1, 0, "SSTORE", "STOP")
    # SWC-101 integer overflow reaching a storage sink + guarded sibling
    add("swc101_add_overflow",
        0, "SLOAD", 4, "CALLDATALOAD", "ADD", 0, "SSTORE", "STOP")
    add("swc101_guarded_add",
        4, "CALLDATALOAD", ("push1", 100), "SWAP1", "GT",
        ("ref", "bad"), "JUMPI",
        0, "SLOAD", 4, "CALLDATALOAD", "ADD", 0, "SSTORE", "STOP",
        ("label", "bad"), 0, 0, "REVERT")
    # SWC-110 reachable INVALID + unreachable sibling
    add("swc110_assert_fail", 4, "CALLDATALOAD", ("ref", "ok"), "JUMPI",
        "INVALID", ("label", "ok"), 1, 0, "SSTORE", "STOP")
    add("swc110_dead_invalid", 0, ("ref", "bad"), "JUMPI",
        1, 0, "SSTORE", "STOP", ("label", "bad"), "INVALID")
    # SWC-124 arbitrary storage write + fixed-key sibling
    add("swc124_arbitrary_write",
        36, "CALLDATALOAD", 4, "CALLDATALOAD", "SSTORE", "STOP")
    add("swc124_fixed_write", 36, "CALLDATALOAD", 5, "SSTORE", "STOP")
    # SWC-112 delegatecall to user-supplied target + constant sibling
    add("swc112_deleg_user",
        0, 0, 0, 0, 4, "CALLDATALOAD", ("push2", 0xFFFF),
        "DELEGATECALL", "POP", "STOP")
    # SWC-116 timestamp-gated transfer
    add("swc116_timestamp",
        "TIMESTAMP", ("push4", 0x5F5E1000), "GT", ("ref", "w"), "JUMPI",
        "STOP",
        ("label", "w"), 0, 0, 0, 0, 1, "CALLER",
        ("push2", 0xFFFF), "CALL", "POP", "STOP")
    # SWC-107 state change after external call (reentrancy pattern)
    add("swc107_sstore_after_call",
        0, 0, 0, 0, 0, 4, "CALLDATALOAD", ("push2", 0xFFFF), "CALL",
        "POP", 1, 0, "SSTORE", "STOP")
    # multi-send (SWC-113 family)
    add("swc113_multi_send",
        0, 0, 0, 0, 1, 4, "CALLDATALOAD", ("push2", 0xFFFF), "CALL", "POP",
        0, 0, 0, 0, 1, 36, "CALLDATALOAD", ("push2", 0xFFFF), "CALL", "POP",
        "STOP")
    # deprecated op (SWC-111)
    add("swc111_origin_read", "ORIGIN", 0, "SSTORE", "STOP")
    # clean ERC20-ish storage write: must stay issue-free
    add("clean_store", 4, "CALLDATALOAD", 1, "SSTORE", "STOP")
    return fx


def _issue_key(d):
    return {
        "contract": d["contract"], "swc-id": d["swc-id"],
        "address": d["address"], "title": d["title"],
        "severity": d["severity"],
    }


def _analyze(code, **kw):
    kw.setdefault("limits", TEST_LIMITS)
    kw.setdefault("lanes_per_contract", 16)
    kw.setdefault("max_steps", 192)
    sym = SymExecWrapper([code], **kw)
    report = fire_lasers(sym.ctx)
    return sorted((_issue_key(i.as_dict()) for i in report.issues),
                  key=lambda d: (d["swc-id"], d["address"], d["title"]))


@pytest.mark.parametrize("name", sorted(_fixtures()))
def test_golden(name):
    code, kw = _fixtures()[name]
    got = _analyze(code, **kw)
    path = os.path.join(GOLDEN_DIR, f"{name}.json")
    if REGEN:
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(got, fh, indent=1, sort_keys=True)
        return
    assert os.path.exists(path), (
        f"golden missing for {name}; run MYTHRIL_REGEN_GOLDENS=1 "
        f"pytest tests/test_goldens.py and review the new file")
    with open(path) as fh:
        want = json.load(fh)
    assert got == want, (
        f"{name}: issue set diverged from golden\n got: {got}\nwant: {want}")
