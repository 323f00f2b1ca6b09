"""Independent CALL-frame vectors vs the symbolic engine.

The frame machinery gets an oracle whose bytecode and
expectations share NO code with the engine (see
``tests/fixtures/gen_calltests.py`` — raw-byte assembler + integer
formulas). Every vector runs the same 4-lane shape so the whole suite
compiles once.
"""

import json
import os

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.core.frontier import ACCT_CONTRACT0
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.ops import u256
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

L = TEST_LIMITS
_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "calltests.json")
with open(_FIXTURE) as fh:
    _DOC = json.load(fh)
VECTORS = _DOC["tests"]
NAMES = sorted(VECTORS)

ACCT_SLOT = {"caller": ACCT_CONTRACT0, "callee": ACCT_CONTRACT0 + 1,
             "attacker": 0}


def run_vector(v):
    imgs = [ContractImage.from_bytecode(bytes.fromhex(v["caller_code"]),
                                        L.max_code),
            ContractImage.from_bytecode(bytes.fromhex(v["callee_code"]),
                                        L.max_code)]
    corpus = Corpus.from_images(imgs)
    active = np.zeros(4, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(4, L, contract_id=np.zeros(4, np.int32),
                           active=active, n_contracts=2)
    env = make_env(4)
    # max_steps uniform so every vector reuses one compiled executable
    return sym_run(sf, env, corpus, SymSpec(), L, max_steps=128)


@pytest.mark.parametrize("name", NAMES)
def test_call_vector(name):
    v = VECTORS[name]
    out = run_vector(v)
    lane = 0
    assert bool(np.asarray(out.base.active)[lane])
    assert bool(np.asarray(out.base.halted)[lane]), f"{name}: lane not halted"
    assert not bool(np.asarray(out.base.error)[lane]), f"{name}: lane errored"
    assert int(np.asarray(out.base.depth)[lane]) == 0

    # exact storage comparison per account
    used = np.asarray(out.base.st_used)
    keys = np.asarray(out.base.st_keys)
    vals = np.asarray(out.base.st_vals)
    acct = np.asarray(out.base.st_acct)
    got = {}
    for k in range(used.shape[1]):
        if used[lane, k]:
            got.setdefault(int(acct[lane, k]), {})[
                u256.to_int(keys[lane, k])] = u256.to_int(vals[lane, k])
    for role, slots in v["expect_storage"].items():
        want = {int(s): int(x, 16) for s, x in slots.items()}
        assert got.get(ACCT_SLOT[role], {}) == want, (
            f"{name}: {role} storage {got.get(ACCT_SLOT[role], {})} != {want}")

    bal = np.asarray(out.base.acct_bal)
    for role, x in v["expect_balances"].items():
        assert u256.to_int(bal[lane, ACCT_SLOT[role]]) == int(x, 16), (
            f"{name}: {role} balance mismatch")
