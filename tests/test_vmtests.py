"""Consensus-style VM test vectors vs the concrete interpreter.

The independent oracle: fixtures in
``tests/fixtures/vmtests.json`` were generated with machinery deliberately
disjoint from the engine (raw-byte mini-assembler + Python big-int formula
expectations — see ``tests/fixtures/gen_vmtests.py``). The whole suite
runs as ONE batched frontier — each vector is a lane — mirroring how the
reference drives the official ``ethereum/tests`` VMTests JSON through
LASER (``tests/laser/evm_testsuite`` ⚠unv, SURVEY.md §4).
"""

import json
import os

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env, make_frontier
from mythril_tpu.core.interpreter import run
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.ops import u256

_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "vmtests.json")
with open(_FIXTURE) as fh:
    _DOC = json.load(fh)
GAS_LIMIT = _DOC["gasLimit"]  # the GAS vectors' expectations assume this
VECTORS = _DOC["tests"]
NAMES = sorted(VECTORS)


class _SuiteRun:
    """Run every vector once (one lane each), cache the final frontier."""

    def __init__(self):
        P = len(NAMES)
        L = TEST_LIMITS
        images, calldata, cd_len = [], np.zeros((P, L.calldata_bytes), np.uint8), \
            np.zeros(P, np.int32)
        for i, name in enumerate(NAMES):
            v = VECTORS[name]
            images.append(ContractImage.from_bytecode(
                bytes.fromhex(v["exec"]["code"]), L.max_code))
            data = bytes.fromhex(v["exec"].get("data", ""))
            calldata[i, : len(data)] = np.frombuffer(data, dtype=np.uint8)
            cd_len[i] = len(data)
        corpus = Corpus.from_images(images)
        f = make_frontier(
            P, L, contract_id=np.arange(P, dtype=np.int32),
            calldata=calldata, calldata_len=cd_len, gas_limit=GAS_LIMIT,
        )
        env = make_env(P)
        f = run(f, env, corpus, max_steps=64)
        self.f = f
        self.storage = []
        st_keys = np.asarray(f.st_keys)
        st_vals = np.asarray(f.st_vals)
        st_used = np.asarray(f.st_used)
        for i in range(P):
            d = {}
            for k in range(st_keys.shape[1]):
                if st_used[i, k]:
                    d[u256.to_int(st_keys[i, k])] = u256.to_int(st_vals[i, k])
            self.storage.append(d)
        self.error = np.asarray(f.error)
        self.reverted = np.asarray(f.reverted)
        self.halted = np.asarray(f.halted)
        self.retval = np.asarray(f.retval)
        self.retval_len = np.asarray(f.retval_len)


@pytest.fixture(scope="module")
def suite():
    return _SuiteRun()


@pytest.mark.parametrize("name", NAMES)
def test_vector(suite, name):
    lane = NAMES.index(name)
    expect = VECTORS[name]["expect"]
    if expect.get("error"):
        assert bool(suite.error[lane]), f"{name}: expected exceptional halt"
        return
    assert not bool(suite.error[lane]), f"{name}: unexpected error"
    if expect.get("reverted"):
        assert bool(suite.reverted[lane]), f"{name}: expected REVERT"
    else:
        assert bool(suite.halted[lane]), f"{name}: did not halt"
        assert not bool(suite.reverted[lane]), f"{name}: unexpected revert"
    # exact storage comparison (zero values filtered on both sides, since
    # an unwritten slot and a written zero are indistinguishable in the
    # EVM's post-state): spurious extra writes fail the vector too
    want = {
        int(k, 16): int(v, 16)
        for k, v in expect.get("storage", {}).items() if int(v, 16) != 0
    }
    got = {k: v for k, v in suite.storage[lane].items() if v != 0}
    assert got == want, f"{name}: storage {got} != expected {want}"
    if "out" in expect:
        n = int(suite.retval_len[lane])
        got = bytes(suite.retval[lane][:n]).hex()
        assert got == expect["out"], f"{name}: out {got} != {expect['out']}"
