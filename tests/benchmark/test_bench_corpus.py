"""Corpus ``wild-v1``: deterministic in the seed, the same work for every
seed in another order, and bytecode that does what its labels say (run
concretely through the repo's plain reference EVM)."""

import hashlib
import os
import re
import sys

import pytest

from bench_paths import ROOT, load

wild = load("corpora/wild-v1.py", "bench_wild_v1")
asm = load("asm.py", "bench_asm")

SELECTOR = re.compile(rb"\x80\x63(....)\x14\x61(..)\x57", re.S)


def selectors(code: bytes) -> dict:
    """selector -> entry offset, read from the dispatcher's leaves."""
    return {int.from_bytes(m.group(1), "big"): int.from_bytes(
        m.group(2), "big") for m in SELECTOR.finditer(code)}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_same_seed_same_stream(seed):
    a, b = wild.batch(seed, 3), wild.batch(seed, 3)
    assert a == b
    other = wild.batch(seed + 1, 3)
    assert [c["code"] for c in a] != [c["code"] for c in other]
    assert [c["name"] for c in a] != [c["name"] for c in wild.batch(seed, 4)]
    # every seed gets the same set of contracts, in another order
    assert sorted(c["kind"] for c in a) == sorted(c["kind"] for c in other)
    assert sorted(len(selectors(c["code"])) for c in a) == sorted(
        len(selectors(c["code"])) for c in other)
    orders = {tuple(c["kind"] for c in wild.batch(s, 3)) for s in range(6)}
    assert len(orders) > 1


def test_batches_take_the_two_sets_in_turn_and_bytes_are_distinct():
    seen, kinds = set(), []
    for bi in range(4):
        batch = wild.batch(21, bi)
        assert len(batch) == wild.BATCH == 8
        kinds.append(sorted(c["kind"] for c in batch))
        for c in batch:
            assert c["code"] not in seen
            seen.add(c["code"])
            assert c["code"][-43:-34] == b"\xa1\x65bzzr0\x58\x20"
    assert kinds[0] == kinds[2] and kinds[1] == kinds[3]
    assert kinds[0] != kinds[1]
    both = kinds[0] + kinds[1]
    assert both.count("precompile_gate") == 1
    assert both.count("precompile_gate_safe") == 1
    assert both.count("safe") == 8


def test_contracts_have_the_sizes_and_dispatchers_of_mainnet_code():
    sizes, counts = [], []
    for bi in range(2):
        for c in wild.batch(5, bi):
            sizes.append(len(c["code"]))
            counts.append(len(selectors(c["code"])))
            assert c["code"][:5] == bytes.fromhex("6080604052")
    assert 3000 <= min(sizes) and max(sizes) <= 24576
    assert max(sizes) >= 15000
    assert min(counts) >= 20 and max(counts) == 60
    small = wild.batch(5, 0, max_code=512) + wild.batch(5, 1, max_code=512)
    assert all(len(c["code"]) <= 512 for c in small)
    assert sorted(c["kind"] for c in small) == sorted(
        c["kind"] for bi in range(2) for c in wild.batch(5, bi))


def test_labels_follow_the_flaws():
    for c in wild.batch(9, 0) + wild.batch(9, 1):
        ids = {wild.FLAWS[f] for f in c["kind"].split("+")
               if f != "safe"} - {None}
        assert set(c["must_report"]) == ids
        assert set(c["must_not_report"]) == {"101", "106", "115"} - ids


def _call(code, selector, caller, storage=None, value=0):
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from pyevm_ref import RefEnv, RefEVM

    env = RefEnv()
    env.caller = env.origin = caller
    env.callvalue = value
    evm = RefEVM(code, selector.to_bytes(4, "big") + (1 << 255).to_bytes(
        32, "big") * 3, env=env, storage=storage or {})
    res = evm.run(max_steps=2000)
    assert not res.error
    return res


@pytest.mark.parametrize("max_code", [24576, 512])
def test_the_bytecode_does_what_the_labels_say(max_code):
    """Every function of every contract runs to an end without a stack
    or jump fault; a stranger can destroy exactly the contracts labelled
    SWC-106 (the reference EVM computes no precompile, so the gate pair
    is left to the digest test below), and the unchecked mint wraps
    where SafeMath reverts."""
    stranger = 0xBADC0DE
    for c in wild.batch(13, 0, max_code) + wild.batch(13, 1, max_code):
        killed = False
        for sel in selectors(c["code"]):
            res = _call(c["code"], sel, stranger,
                        storage={k: 1 << 255 for k in range(3, 44)})
            assert res.halted
            killed |= res.selfdestructed
        if "precompile_gate" not in c["kind"]:
            assert killed == ("106" in c["must_report"]), c["name"]
    # minting 2**255 twice wraps the balance to 0 without a revert
    mint = next(c for c in wild.batch(13, 0, 512)
                if c["kind"] == "mint_unchecked")
    wrapped = 0
    for sel in selectors(mint["code"]):
        once = _call(mint["code"], sel, stranger).storage
        twice = _call(mint["code"], sel, stranger, storage=once)
        wrapped += (1 << 255 in once.values() and not twice.reverted
                    and 0 in twice.storage.values())
    assert wrapped == 1


def test_ripemd160_is_the_real_one():
    for msg in (b"", b"abc", b"a" * 55, b"a" * 56, b"x" * 200,
                (42).to_bytes(32, "big")):
        assert asm.ripemd160(msg) == hashlib.new("ripemd160", msg).digest()


def test_precompile_gate_compares_with_the_true_digest():
    both = wild.batch(9, 0) + wild.batch(9, 1)
    gate = next(c for c in both if c["kind"] == "precompile_gate")["code"]
    safe = next(c for c in both
                if c["kind"] == "precompile_gate_safe")["code"]
    for code, reachable in ((gate, True), (safe, False)):
        at = code.index(bytes.fromhex("7f00000000009a7e"))
        digest = asm.ripemd160(code[at + 1:at + 33])
        flipped = digest[:-1] + bytes([digest[-1] ^ 1])
        assert (digest in code) == reachable
        assert (flipped in code) == (not reachable)


def test_assembler_matches_the_programs_own():
    from mythril_tpu.disassembler.asm import assemble

    toks = [0, "CALLDATALOAD", 0xE0, "SHR", 255, "SLOAD", "ADD",
            ("push20", 77), ("ref", "x"), "JUMPI", 1 << 200, "POP",
            "CALLDATASIZE", "NOT", 3, "SHL", "OR", "XOR", "MOD", "GAS",
            0, 0, "LOG1", "LOG2", "LOG3", "NUMBER", "EXP", "STOP",
            ("label", "x"), "CALLER", "SELFDESTRUCT"]
    # the program's assembler emits the JUMPDEST for a label too
    assert asm.assemble(*toks) == assemble(*toks)
