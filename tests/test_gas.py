"""Gas fidelity: EIP-2929 warm/cold accounting + EIP-150 63/64 forwarding.

Vmtests-style vectors with cold/warm
SLOAD / EXTCODE* and a CALL match hand-computed gas exactly. Expected
values are derived from the yellow-paper/EIP schedules in the comments —
NOT from the implementation's own tables.
"""

import dataclasses

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.core.frontier import contract_address
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

BERLIN = dataclasses.replace(TEST_LIMITS, gas_schedule="berlin")
# fully concrete runs: gas must be a single exact number (min == max)
CONC = SymSpec(calldata=False, callvalue=False, caller=False,
               storage=False, block_env=False)


def run_one(code, limits, n_contracts=1, max_steps=64, gas_limit=10_000_000):
    imgs = [ContractImage.from_bytecode(code, limits.max_code)]
    if n_contracts > 1:
        imgs += [ContractImage.from_bytecode(assemble("STOP"), limits.max_code)
                 for _ in range(n_contracts - 1)]
    corpus = Corpus.from_images(imgs)
    active = np.zeros(4, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(4, limits, active=active, n_contracts=n_contracts,
                           gas_limit=gas_limit, balance=10**18)
    env = make_env(4)
    return sym_run(sf, env, corpus, CONC, limits, max_steps=max_steps)


def gas_of(out):
    gmin = int(np.asarray(out.base.gas_min)[0])
    gmax = int(np.asarray(out.base.gas_max)[0])
    b = out.base
    assert bool(np.asarray(b.halted)[0]) and not bool(np.asarray(b.error)[0])
    return gmin, gmax


def test_berlin_sload_cold_then_warm():
    # PUSH1(3) SLOAD(cold 2100 TOTAL — EIP-2929 cold replaces warm) POP(2)
    # PUSH1(3) SLOAD(warm 100) POP(2) STOP(0)  => 2210
    code = assemble(0, "SLOAD", "POP", 0, "SLOAD", "POP", "STOP")
    gmin, gmax = gas_of(run_one(code, BERLIN))
    assert gmin == gmax == 2210, (gmin, gmax)


def test_istanbul_sload_flat():
    # same code, istanbul: 3 + 800 + 2 + 3 + 800 + 2 = 1610
    code = assemble(0, "SLOAD", "POP", 0, "SLOAD", "POP", "STOP")
    gmin, gmax = gas_of(run_one(code, TEST_LIMITS))
    assert gmin == gmax == 1610, (gmin, gmax)


def test_berlin_extcodesize_cold_then_warm():
    # target: the OTHER corpus contract (in the account table, not
    # pre-warmed; self/origin are warm at tx start)
    # PUSH3(3) EXTCODESIZE(cold 2600 TOTAL) POP(2)
    # PUSH3(3) EXTCODESIZE(warm 100) POP(2) STOP => 2710
    addr = contract_address(1)
    code = assemble(("push3", addr), "EXTCODESIZE", "POP",
                    ("push3", addr), "EXTCODESIZE", "POP", "STOP")
    gmin, gmax = gas_of(run_one(code, BERLIN, n_contracts=2))
    assert gmin == gmax == 2710, (gmin, gmax)


def test_berlin_self_is_prewarmed():
    # EXTCODESIZE(self): tx.to is in the EIP-2929 pre-warmed set
    # PUSH3(3) EXTCODESIZE(100) POP(2) STOP => 105
    code = assemble(("push3", contract_address(0)),
                    "EXTCODESIZE", "POP", "STOP")
    gmin, gmax = gas_of(run_one(code, BERLIN))
    assert gmin == gmax == 105, (gmin, gmax)


# straight-line gas burner (a loop would trip the bounded-loops policy):
# 13 x [PUSH32 max(3) PUSH1 2(3) EXP(10 + 50*32) POP(2)] = 1618 gas each
BURNER = assemble(*sum(
    [[("push32", (1 << 256) - 1), 2, "EXP", "POP"] for _ in range(13)], []),
    "STOP")


def test_gas_63_64_forwarding_burns_forwarded_on_oog():
    """Callee burns past its forwarded ceiling; the caller loses exactly
    min(gas operand, 63/64 remaining) + its own costs and continues
    (exceptional sub-call halt != lane death)."""
    callee = BURNER
    caller = assemble(
        0, 0, 0, 0, 0,                       # retLen retOff argsLen argsOff value
        ("push3", contract_address(1)),      # to (table account, code = callee)
        ("push2", 5000),                     # gas operand
        "CALL", "POP", "STOP",
    )
    limits = TEST_LIMITS
    imgs = [ContractImage.from_bytecode(c, limits.max_code)
            for c in (caller, callee)]
    corpus = Corpus.from_images(imgs)
    active = np.zeros(4, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(4, limits, active=active, n_contracts=2,
                           gas_limit=100_000, balance=10**18)
    env = make_env(4)
    out = sym_run(sf, env, corpus, CONC, limits, max_steps=128)
    b = out.base
    assert bool(np.asarray(b.halted)[0]) and not bool(np.asarray(b.error)[0])
    gmin = int(np.asarray(b.gas_min)[0])
    gmax = int(np.asarray(b.gas_max)[0])
    # caller prefix: 5*PUSH1(3) + PUSH3(3) + PUSH2(3) = 21; CALL base 700
    # (istanbul, no value); forwarded = min(5000, 63/64*(100000-721)) =
    # 5000, burned whole by the callee's OOG; then POP(2) + STOP(0).
    assert gmin == gmax == 21 + 700 + 5000 + 2, (gmin, gmax)
    # the call pushed 0 (failure) and execution continued to STOP
    assert int(np.asarray(b.pc)[0]) == len(caller) - 1


def test_gas_63_64_cap_applies_when_operand_exceeds_remaining():
    """Gas operand larger than 63/64 of what remains: the callee ceiling
    is capped, and its OOG burns exactly the cap."""
    callee = BURNER
    caller = assemble(
        0, 0, 0, 0, 0,
        ("push3", contract_address(1)),
        ("push3", 0xFFFFFF),                 # absurd gas operand
        "CALL", "POP", "STOP",
    )
    limits = TEST_LIMITS
    imgs = [ContractImage.from_bytecode(c, limits.max_code)
            for c in (caller, callee)]
    corpus = Corpus.from_images(imgs)
    active = np.zeros(4, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(4, limits, active=active, n_contracts=2,
                           gas_limit=20_000, balance=10**18)
    env = make_env(4)
    out = sym_run(sf, env, corpus, CONC, limits, max_steps=128)
    b = out.base
    assert bool(np.asarray(b.halted)[0]) and not bool(np.asarray(b.error)[0])
    gmin = int(np.asarray(b.gas_min)[0])
    gmax = int(np.asarray(b.gas_max)[0])
    # prefix 21 + CALL 700 = 721 used; remaining 19279; cap = 19279 -
    # 19279//64 = 19279 - 301 = 18978; total = 721 + 18978 + 2
    assert gmin == gmax == 721 + 18978 + 2, (gmin, gmax)
