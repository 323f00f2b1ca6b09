"""Real-world-shaped smoke corpus through the full suite: EIP-1167 proxy (exact spec bytes) delegating to a full ERC-20,
plus ERC-721 and a 2-of-3 multisig — the largest, most solc-shaped
bytecodes in the tree. Issue sets pinned as a golden; any trap storm
these expose is visible in the pinned coverage numbers.
"""

import dataclasses
import json
import os

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.analysis import SymExecWrapper, fire_lasers
from mythril_tpu.config import TEST_LIMITS

from realworld_fixture import build_realworld, eip1167_proxy

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "fixtures", "realworld")
GOLDEN = os.path.join(os.path.dirname(__file__), "fixtures", "goldens",
                      "realworld.json")
REGEN = bool(os.environ.get("MYTHRIL_REGEN_GOLDENS"))

# proxy -> erc20 delegatecall needs the 4-contract batch in the account
# table; the ERC-20's nested-mapping paths want a little more code room
LIMITS = dataclasses.replace(TEST_LIMITS, max_accounts=8, call_depth=3,
                             max_code=1024)


def test_eip1167_bytes_are_spec_exact():
    """The proxy fixture is the EIP-1167 byte sequence, not an
    approximation: prefix/suffix around the embedded address match the
    spec exactly."""
    code = eip1167_proxy(0xBEEF)
    assert code.hex().startswith("363d3d373d3d3d363d73")
    assert code.hex().endswith("5af43d82803e903d91602b57fd5bf3")
    assert len(code) == 45


def test_fixture_files_match_builder():
    if REGEN:
        os.makedirs(FIXTURE_DIR, exist_ok=True)
    for name, runtime in build_realworld():
        p = os.path.join(FIXTURE_DIR, f"{name.lower()}.bin-runtime")
        if REGEN:
            with open(p, "w") as fh:
                fh.write(runtime.hex())
            continue
        assert os.path.exists(p), f"fixture missing: {p} (regen)"
        assert bytes.fromhex(open(p).read().strip()) == runtime


def _issue_key(d):
    return {"contract": d["contract"], "swc-id": d["swc-id"],
            "address": d["address"], "title": d["title"],
            "severity": d["severity"]}


def test_realworld_golden():
    system = build_realworld()
    sym = SymExecWrapper(
        [code for _, code in system],
        contract_names=[n for n, _ in system],
        limits=LIMITS, lanes_per_contract=16, max_steps=192,
        transaction_count=2,
    )
    report = fire_lasers(sym)
    got = sorted((_issue_key(i.as_dict()) for i in report.issues),
                 key=lambda d: (d["contract"], d["swc-id"], d["address"],
                                d["title"]))
    cov = report.coverage or {}
    doc = {"issues": got,
           "coverage": {
               "surviving_paths": cov.get("surviving_paths"),
               "lanes_errored": cov.get("lanes_errored", {}),
               "dropped_forks": cov.get("dropped_forks"),
           }}
    # the pre-0.8 unchecked credit must be caught in the ERC-20 — checked
    # on `got` BEFORE the regen early-return, so a detector regression
    # cannot be silently pinned into a fresh golden
    assert any(d["contract"] == "Erc20Full" and d["swc-id"] == "101"
               for d in got)
    if REGEN:
        with open(GOLDEN, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
        return
    assert os.path.exists(GOLDEN), "golden missing; regen and review"
    with open(GOLDEN) as fh:
        want = json.load(fh)
    assert doc == want, (
        f"realworld issue/coverage set diverged\n got: "
        f"{json.dumps(doc, indent=1)}\nwant: {json.dumps(want, indent=1)}")
