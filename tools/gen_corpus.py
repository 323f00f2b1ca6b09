#!/usr/bin/env python
"""Synthesize a BASELINE-config-3-style contract corpus.

The reference's corpora are Etherscan-verified contracts; with no network
in this image, the campaign dress run (SURVEY §6 / BASELINE config 3) uses a synthetic mix authored with the in-repo
assembler: per-index constant variation keeps every contract distinct
(different storage slots, selectors, thresholds), and the mix covers
vulnerable + safe shapes across several SWC classes so detection work is
representative, not degenerate.

Usage:  python tools/gen_corpus.py OUT_DIR [N] [TRIO_BATCH=32]
Then:   python -m mythril_tpu analyze --corpus OUT_DIR --batch-size 32 ...
(TRIO_BATCH wires the inter-contract trio's callee addresses for that
--batch-size; use 6 with default limits for real in-batch call resolution)
"""

from __future__ import annotations

import os
import sys

# host-side tool: never let the imports below (asm → package __init__ →
# u256 device tables) initialize a TPU backend — a wedged one
# hangs the process before the first file is written. Only
# when run AS the tool: an importer (the tests take MIX from here)
# keeps its own backend choice.
if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mythril_tpu.disassembler.asm import assemble


def killable(i: int) -> bytes:
    """SWC-106: caller-reachable SELFDESTRUCT (sweeps to the caller).
    The dead PUSH keeps every instance byte-distinct like the other
    generators (a constant body would let dedup collapse 1/8 of the
    corpus and skew the dress-run numbers)."""
    return assemble(i % 251, "POP", "CALLER", "SELFDESTRUCT")


def guarded_killable(i: int) -> bytes:
    """Safe sibling: only the owner baked into the code can kill. (A
    STORED owner is no guard to an analysis of the runtime image alone:
    initial storage is unknown, so the owner may be the attacker, and
    SWC-106 is then reported for this contract too.)"""
    return assemble(
        ("push20", 0xA11CE00000000000000000000000000000000000 + i),
        "CALLER", "EQ", ("ref", "ok"), "JUMPI",
        0, 0, "REVERT",
        ("label", "ok"), "JUMPDEST", "CALLER", "SELFDESTRUCT")


def add_overflow(i: int) -> bytes:
    """SWC-101: unchecked add of calldata into storage."""
    return assemble(
        0, "CALLDATALOAD", i % 251, "SLOAD", "ADD", i % 251, "SSTORE",
        "STOP")


def checked_add(i: int) -> bytes:
    """Safe sibling: SafeMath-style overflow guard (revert when the
    old value is greater than the sum)."""
    return assemble(
        0, "CALLDATALOAD", i % 251, "SLOAD", "ADD",
        "DUP1", i % 251, "SLOAD", "GT", ("ref", "bad"), "JUMPI",
        i % 251, "SSTORE", "STOP",
        ("label", "bad"), "JUMPDEST", 0, 0, "REVERT")


def timestamp_gate(i: int) -> bytes:
    """SWC-116: block.timestamp conditions a storage write."""
    return assemble(
        "TIMESTAMP", 1_700_000_000 + i, "LT", ("ref", "skip"), "JUMPI",
        1, i % 251, "SSTORE",
        ("label", "skip"), "JUMPDEST", "STOP")


def origin_auth(i: int) -> bytes:
    """SWC-115: tx.origin used for authorization."""
    return assemble(
        "ORIGIN", i % 251, "SLOAD", "EQ", ("ref", "ok"), "JUMPI",
        0, 0, "REVERT",
        ("label", "ok"), "JUMPDEST", 2, i % 251, "SSTORE", "STOP")


def branchy_store(i: int) -> bytes:
    """Path-explosion shape: 4 calldata branches into distinct writes."""
    toks = []
    for b in range(4):
        toks += [32 * b, "CALLDATALOAD", ("ref", f"L{b}"), "JUMPI",
                 ("label", f"L{b}"), "JUMPDEST"]
    toks += [i & 0xFF, (i >> 8) % 251, "SSTORE", "STOP"]
    return assemble(*toks)


def plain_store(i: int) -> bytes:
    """Quiet filler: single concrete write, no findings."""
    return assemble(1 + (i % 254), i % 251, "SSTORE", "STOP")


MIX = [killable, guarded_killable, add_overflow, checked_add,
       timestamp_gate, origin_auth, branchy_store, plain_store]

def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "corpus_synth"
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 1000
    # campaign batch size the inter-contract trio is wired for: the
    # trio's hardcoded callee addresses are ``contract_address(pos)``,
    # and a contract's account index inside one compiled batch IS its
    # position in that batch. For the calls to RESOLVE at analysis time
    # the whole batch must also fit the frontier account table
    # (2 + batch_size <= limits.max_accounts, so batch 6 at the default
    # limits). Mismatched batch sizes stay sound — the calls just hit no
    # known account and degrade to havoc leaves.
    trio_batch = int(sys.argv[3]) if len(sys.argv) > 3 else 32
    trio_base = max(trio_batch - 3, 0)
    os.makedirs(out_dir, exist_ok=True)
    # config-4 shape (BASELINE configs[3]): one
    # caller→router→vault trio per 32-contract batch, wired for its
    # in-batch account indices. Filenames are index-first so the sorted
    # corpus order load_corpus_dir uses EQUALS generation order.
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))
    from config4_fixture import build_system

    trio_codes = [(name, runtime) for name, _, runtime
                  in build_system(base=trio_base)]
    n_trio = 0
    for i in range(n):
        pos = i % trio_batch
        if pos >= trio_base:
            name, code = trio_codes[pos - trio_base]
            fname = f"c{i:05d}_inter_{name.lower()}.hex"
            n_trio += 1
        else:
            gen = MIX[i % len(MIX)]
            code = gen(i)
            fname = f"c{i:05d}_{gen.__name__}.hex"
        with open(os.path.join(out_dir, fname), "w") as fh:
            fh.write(code.hex())
    print(f"{n} contracts -> {out_dir} "
          f"({len(MIX)} shapes + {n_trio} inter-contract trio members, "
          f"per-index constants)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
