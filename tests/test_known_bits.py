"""Known-bits propagation domain + solver observability.

The kills asserted here are ones INTERVALS ALONE CANNOT make: the OR
lower bound / AND alignment facts live in bit positions, not magnitudes.
"""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run
from mythril_tpu.analysis import SymExecWrapper, fire_lasers

L = TEST_LIMITS


def run_one(code, n_lanes=8, max_steps=64):
    img = ContractImage.from_bytecode(code, L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(n_lanes, L, active=active)
    env = make_env(n_lanes)
    return sym_run(sf, env, corpus, SymSpec(), L, max_steps=max_steps)


def surviving_slot0(out):
    act = np.asarray(out.base.active) & ~np.asarray(out.base.error)
    used = np.asarray(out.base.st_used)
    keys = np.asarray(out.base.st_keys)
    vals = np.asarray(out.base.st_vals)
    got = set()
    for lane in np.where(act)[0]:
        for k in range(used.shape[1]):
            if used[lane, k] and not keys[lane, k].any():
                got.add(int(vals[lane, k, 0]))
    return got


def test_or_low_bit_eq_is_killed():
    # (calldataload(0) | 1) == 2 is unsat: bit 0 of the LHS is known 1.
    # The taken branch must be pruned on-device, never reaching the SSTORE.
    code = assemble(
        0, "CALLDATALOAD", 1, "OR", 2, "EQ", ("ref", "t"), "JUMPI",
        9, 0, "SSTORE", "STOP",
        ("label", "t"), 1, 0, "SSTORE", "STOP",
    )
    out = run_one(code)
    assert 1 not in surviving_slot0(out), "infeasible branch explored"
    assert 9 in surviving_slot0(out), "feasible fallthrough lost"
    assert int(np.asarray(out.killed_total)) >= 1


def test_and_alignment_eq_is_killed():
    # (x & ~0xFF) == 5: the low 8 bits of the LHS are known zero
    code = assemble(
        0, "CALLDATALOAD", ("push32", (2**256 - 1) ^ 0xFF), "AND",
        5, "EQ", ("ref", "t"), "JUMPI",
        9, 0, "SSTORE", "STOP",
        ("label", "t"), 1, 0, "SSTORE", "STOP",
    )
    out = run_one(code)
    assert 1 not in surviving_slot0(out)
    assert 9 in surviving_slot0(out)
    assert int(np.asarray(out.killed_total)) >= 1


def test_feasible_masked_eq_survives():
    # control: (x & ~0xFF) == 0x100 IS satisfiable — both branches live
    code = assemble(
        0, "CALLDATALOAD", ("push32", (2**256 - 1) ^ 0xFF), "AND",
        ("push2", 0x100), "EQ", ("ref", "t"), "JUMPI",
        9, 0, "SSTORE", "STOP",
        ("label", "t"), 1, 0, "SSTORE", "STOP",
    )
    out = run_one(code)
    assert surviving_slot0(out) == {1, 9}


def test_solver_stats_in_report():
    code = assemble(0, "SELFDESTRUCT")
    sym = SymExecWrapper([code], limits=L, lanes_per_contract=4,
                         max_steps=64, transaction_count=1)
    report = fire_lasers(sym, white_list=["AccidentallyKillable"])
    stats = report.coverage["solver"]["total"]
    assert stats["attempts"] >= 1 and stats["sat"] >= 1
    assert "AccidentallyKillable" in report.coverage["solver"]["by_module"]
