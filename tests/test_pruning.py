"""Bounded loops + dependency pruner.

Reference: ``strategy/extensions/bounded_loops.py`` (drop states past
--loop-bound) and ``laser/plugin/plugins/dependency_pruner.py`` (skip
tx-N paths whose read-set no prior tx wrote) — SURVEY.md §5.7 calls these
"the single biggest algorithmic speedup".
"""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.core.frontier import Trap
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run
from mythril_tpu.analysis import SymExecWrapper

L = TEST_LIMITS  # loop_bound=4


def run_one(code, n_lanes=8, max_steps=128, limits=L):
    img = ContractImage.from_bytecode(code, limits.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(n_lanes, limits, active=active)
    env = make_env(n_lanes)
    return sym_run(sf, env, corpus, SymSpec(), limits, max_steps=max_steps)


def test_infinite_concrete_loop_quiesces_at_bound():
    # for(;;){} — a pure backward JUMP spin must retire at the bound, not
    # burn the whole max_steps budget for the frontier
    code = assemble(("label", "top"), ("ref", "top"), "JUMP")
    out = run_one(code, max_steps=128)
    err = np.asarray(out.base.err_code)
    assert int(err[0]) == Trap.LOOP_BOUND
    # quiesced long before max_steps (bound + small constant)
    assert int(np.asarray(out.base.n_steps)[0]) < 40


def test_symbolic_loop_forks_bounded():
    # while (calldataload(0) != i) i++ — symbolic JUMPI back-edge: each
    # iteration forks an exit path; the spinning lane retires at the bound
    # and the exit paths survive
    code = assemble(
        0,                                  # i
        ("label", "top"),
        "DUP1", 0, "CALLDATALOAD", "EQ", ("ref", "done"), "JUMPI",
        1, "ADD",
        ("ref", "top"), "JUMP",
        ("label", "done"), 1, 0, "SSTORE", "STOP",
    )
    out = run_one(code, n_lanes=16, max_steps=128)
    err = np.asarray(out.base.err_code)
    act = np.asarray(out.base.active)
    halted = np.asarray(out.base.halted)
    assert (err == Trap.LOOP_BOUND).sum() >= 1, "spinner retired"
    assert (act & halted & (err == 0)).sum() >= 2, "exit paths survived"


def test_loop_under_bound_unaffected():
    # a 3-iteration concrete loop (< bound 4) completes normally
    code = assemble(
        3,                                   # counter
        ("label", "top"),
        1, "SWAP1", "SUB",                   # counter -= 1
        "DUP1", ("ref", "top"), "JUMPI",
        1, 0, "SSTORE", "STOP",
    )
    out = run_one(code)
    assert bool(np.asarray(out.base.halted)[0])
    assert int(np.asarray(out.base.err_code)[0]) == 0


def test_dependency_pruner_retires_nonreading_tx2():
    # writes slot 1 every tx, never reads: tx-2 paths read nothing tx-1
    # wrote -> retired at the tx2->tx3 boundary, tx3 never runs
    writer = assemble(42, 1, "SSTORE", "STOP")
    sym = SymExecWrapper([writer], limits=L, lanes_per_contract=4,
                         max_steps=64, transaction_count=3)
    assert len(sym.tx_contexts) == 2, "tx3 had no surviving lanes"
    assert not bool(np.asarray(sym.sf.base.active).any())


def test_dependency_reader_survives_all_txs():
    # counter: slot1 = sload(1) + 1 — tx N reads tx N-1's write, survives
    counter = assemble(0x1, "SLOAD", 1, "ADD", 1, "SSTORE", "STOP")
    sym = SymExecWrapper([counter], limits=L, spec=SymSpec(storage=False),
                         lanes_per_contract=4, max_steps=64,
                         transaction_count=3)
    assert len(sym.tx_contexts) == 3
    assert bool(np.asarray(sym.sf.base.active).any())


def test_dependency_pruner_exempts_first_message_tx_after_creation():
    # code-review r3: with a creation tx the FIRST message call is tx_id 1
    # — it must not be retired for reading nothing the constructor wrote
    ctor = assemble(0, 0, "RETURN")  # empty-effect constructor
    writer = assemble(42, 1, "SSTORE", "STOP")
    sym = SymExecWrapper([writer], creation_bytecodes=[ctor], limits=L,
                         lanes_per_contract=4, max_steps=64,
                         transaction_count=2)
    # creation ctx + first message ctx + second message ctx: the first
    # message tx (writes, reads nothing) must still reach tx 2
    assert len(sym.tx_contexts) == 3
