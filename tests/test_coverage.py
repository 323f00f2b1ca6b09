"""Lost-coverage accounting: masked traps are attributed and reported.

A lane tripping a static cap must not vanish
silently — the report carries a coverage block saying what was lost.
"""

import json

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env, make_frontier
from mythril_tpu.core.frontier import Trap
from mythril_tpu.core.interpreter import run
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.analysis import SymExecWrapper, fire_lasers


def _run_concrete(code: bytes, max_steps: int = 64):
    img = ContractImage.from_bytecode(code, TEST_LIMITS.max_code)
    corpus = Corpus.from_images([img])
    f = make_frontier(4, TEST_LIMITS)
    env = make_env(4)
    return run(f, env, corpus, max_steps=max_steps)


def test_bad_jump_trap_attributed():
    f = _run_concrete(assemble(3, "JUMP", "STOP"))
    assert bool(np.asarray(f.error).all())
    assert np.asarray(f.err_code)[0] == Trap.BAD_JUMP


def test_invalid_opcode_trap_attributed():
    f = _run_concrete(bytes([0xFE]))
    assert np.asarray(f.err_code)[0] == Trap.INVALID_OP


def test_stack_cap_trip_is_warned_in_report():
    # an unrolled push sequence deeper than TEST_LIMITS.max_stack (32)
    blower = assemble(*([1] * (TEST_LIMITS.max_stack + 4)), "STOP")
    sym = SymExecWrapper([blower], limits=TEST_LIMITS,
                         lanes_per_contract=4, max_steps=64)
    report = fire_lasers(sym)
    cov = report.coverage
    assert cov is not None
    assert cov["lanes_errored"].get("stack_cap", 0) >= 1
    assert cov["lanes_lost_to_caps"] >= 1
    assert any("capacity caps" in w for w in report.coverage_warnings())
    assert "WARNING" in report.as_text()
    assert json.loads(report.as_json())["coverage"]["lanes_lost_to_caps"] >= 1


def test_clean_run_has_no_warnings():
    clean = assemble(1, ("push1", 0), "SSTORE", "STOP")
    sym = SymExecWrapper([clean], limits=TEST_LIMITS,
                         lanes_per_contract=4, max_steps=64)
    report = fire_lasers(sym)
    assert report.coverage["lanes_lost_to_caps"] == 0
    assert report.coverage_warnings() == []
    assert report.coverage["surviving_paths"] >= 1
