"""Per-opcode instruction profiler (reference:
``--enable-iprof``'s InstructionProfiler table ⚠unv, SURVEY §5.1).

The histogram rides the frontier as an optional ``[P, 256]`` leaf
(sharding-compatible: lane-leading like every other leaf) and must count
each executed instruction EXACTLY once — in particular a fork copy's row
starts empty, so pre-fork instructions are not double-counted the way
summing ``n_steps`` over surviving lanes would.
"""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env, make_frontier, run
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.analysis import SymExecWrapper

L = TEST_LIMITS


def test_concrete_exact_counts():
    code = assemble(1, 2, "ADD", "POP", "STOP")
    img = ContractImage.from_bytecode(code, L.max_code)
    P = 8
    f = make_frontier(P, L).attach_iprof()
    out = run(f, make_env(P), Corpus.from_images([img]), max_steps=32)
    hist = np.asarray(out.op_hist).sum(axis=0)
    counts = {op: int(n) for op, n in enumerate(hist) if n}
    # assemble() emits minimal-width pushes: two PUSH1 (0x60), ADD, POP, STOP
    assert counts == {0x60: 2 * P, 0x01: P, 0x50: P, 0x00: P}
    assert hist.sum() == np.asarray(out.n_steps).sum()


def test_symbolic_fork_counts_each_instruction_once():
    # one symbolic JUMPI -> two paths sharing the SSTORE/STOP tail; the
    # branch-point instructions must be counted ONCE, the tail twice
    code = assemble(0, "CALLDATALOAD", ("ref", "T"), "JUMPI",
                    ("label", "T"), 1, 0, "SSTORE", "STOP")
    sym = SymExecWrapper([code], limits=L, lanes_per_contract=4,
                         max_steps=64, transaction_count=1,
                         enable_iprof=True)
    prof = sym.iprof
    assert prof["JUMPI"] == 1
    assert prof["CALLDATALOAD"] == 1
    assert prof["SSTORE"] == 2  # both admitted paths run the tail
    assert prof["STOP"] == 2
    # n_steps DOES double-count the shared prefix on the fork copy
    assert sum(prof.values()) < int(np.asarray(sym.sf.base.n_steps).sum())
    table = sym.iprof_table()
    assert "JUMPI" in table and "TOTAL" in table


def test_disabled_by_default():
    sym = SymExecWrapper([assemble("STOP")], limits=L, lanes_per_contract=4,
                         max_steps=16, transaction_count=1)
    assert sym.sf.base.op_hist is None
    assert sym.iprof == {}
