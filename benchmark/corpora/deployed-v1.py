"""Corpus ``deployed-v1``: a seeded, endless stream of labelled contracts
that come as ``solc --bin --bin-runtime`` gives them: creation code and
the runtime code it returns.

The source is SmartBugs (Durieux et al., arXiv:1910.10601), which hands
Mythril each contract's Solidity source, so Mythril runs the
constructor and starts its message calls from the storage it left. Of
sb-curated it is ``dataset/access_control`` (DASP 2), whose flaws need
two calls from a deployed state. Nothing can be fetched here, so the
contracts are generated; the building blocks are ``wild-v1``'s. Every
contract is

- creation code as solc lays it out: the constructor body (free-memory
  prologue, the non-payable check, ``owner = msg.sender`` to slot 0,
  ``initialized = true`` to slot 43, ``totalSupply`` to slot 44 and
  ``balances[msg.sender]``, both ``SUPPLY``),
  then ``CODECOPY`` + ``RETURN`` of the runtime code, which follows it;
- runtime code whose ``onlyOwner`` reads the owner with ``SLOAD`` (no
  immutable baked into the code, as in ``wild-v1``), so every guard of
  every safe contract rests on what the constructor wrote.

Batches of 8 take the two sets of ``SLOTS`` in turn, 4 flawed and 4 safe
each, so every seed gives the same work in another order:

- 5 of 8 at ``wild-v1``'s shapes (20-60 external functions, binary-split
  dispatcher, SafeMath subroutines, reason strings, trailer) with its
  one-transaction flaws and safe contracts. The labels are those that
  hold from a deployed state (``tests/benchmark/test_bench_deployed.py``
  runs each witness in the plain EVM). ``wild-v1``'s ``mint_unchecked``
  is left out: from balances of zero its add wraps only in a second
  call. The SWC-101 flaw here is ``mint_supply_unchecked``, an
  unchecked ``totalSupply += amount`` over the constructor's value;
- 3 of 8 at the shapes of the curated set's own files (2-20 external
  functions): two with a flaw that needs TWO calls from a stranger
  (``reinit_kill``: parity_wallet_bug_2, SWC-106;
  ``owner_change_unprotected``: unprotected0, SWC-105) and the safe
  sibling ``init_once_safe``, whose ``require(!initialized)`` holds only
  because the constructor ran. ``incorrect_constructor_name1-3`` are left
  out: their misnamed constructor makes ``msg.sender`` the owner, so
  ``owner.transfer`` pays the attacker's own concrete address, which
  the analyzer's EtherThief does not count as attacker-controlled (a
  rule that did would flag every ``msg.sender.transfer`` of a caller's
  own deposit; upstream compares the attacker's balances).

Each flawed contract carries ``witness``: for each SWC id it must be
reported for, the calldata of the attack from ``STRANGER``, in order. ``max_code`` under 3072 (the test limits
hold 512 bytes of creation code) gives the same contracts cut to their
flaws, their safe siblings and a filler, without reason strings.

The same ``seed`` gives the same stream; nothing here imports the
program under test or JAX.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from asm import assemble  # noqa: E402


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


wild = _load("deployed_v1_wild_blocks", os.path.join(HERE, "wild-v1.py"))

BATCH = 8
#: upstream Mythril's accounts: who deploys, and who attacks
CREATOR = 0xAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFE
STRANGER = 0xDEADBEEFDEADBEEFDEADBEEFDEADBEEFDEADBEEF
# wild-v1's bodies use slots 1..42
OWNER_SLOT, INIT_SLOT, SUPPLY_SLOT = 0, 43, 44
SUPPLY = 10 ** 27
M256 = (1 << 256) - 1

require, arg, arg_addr, nonpayable = (wild.require, wild.arg,
                                      wild.arg_addr, wild.nonpayable)


# --- bodies that read what the constructor wrote ----------------------------

def owner_only(c, L):
    mask = [] if c.lean else [wild.ADDR_MASK, "AND"]
    return ["CALLER", OWNER_SLOT, "SLOAD", *mask, "EQ",
            *require(L, "own", "Ownable: caller is not the owner", c.lean)]


def set_guarded(c, L):
    return [*nonpayable(L), *owner_only(c, L), *arg(0), c.slot(), "SSTORE",
            "STOP"]


def toggle_guarded(c, L):
    s = c.slot()
    return [*nonpayable(L), *owner_only(c, L), s, "SLOAD", "ISZERO", s,
            "SSTORE", "STOP"]


def kill_guarded(c, L):
    return [*nonpayable(L), *owner_only(c, L), "CALLER", "SELFDESTRUCT"]


def withdraw_guarded(c, L):
    """``owner.transfer(this.balance)``, for the owner to call."""
    mask = [] if c.lean else [wild.ADDR_MASK, "AND"]
    return [*nonpayable(L), *owner_only(c, L), 0, 0, 0, 0, "SELFBALANCE",
            OWNER_SLOT, "SLOAD", *mask, "GAS", "CALL",
            *require(L, "sent", "Wallet: transfer failed", c.lean), "STOP"]


def mint_supply_unchecked(c, L):
    """SWC-101: ``totalSupply += amount`` without SafeMath, over the
    supply the constructor wrote; the balance is credited safely."""
    return [*nonpayable(L), *arg(1), SUPPLY_SLOT, "SLOAD", "ADD",
            SUPPLY_SLOT, "SSTORE",
            *wild._move(c, L, "r", arg_addr(0), "safe_add", arg(1)),
            1, *wild.ret_word()]


def init_wallet(c, L):
    """``initWallet(address)`` of parity_wallet_bug_2: sets the owner,
    whoever calls and however often."""
    return [*nonpayable(L), *arg_addr(0), OWNER_SLOT, "SSTORE", "STOP"]


def init_wallet_once(c, L):
    """The repaired ``initWallet``: ``require(!initialized)``."""
    return [*nonpayable(L), INIT_SLOT, "SLOAD", "ISZERO",
            *require(L, "once", "Wallet: already initialized", c.lean),
            *arg_addr(0), OWNER_SLOT, "SSTORE", 1, INIT_SLOT, "SSTORE",
            "STOP"]


def change_owner(c, L):
    """``changeOwner(address)`` of unprotected0: no ``onlyOwner``."""
    return [*nonpayable(L), *arg_addr(0), OWNER_SLOT, "SSTORE", "STOP"]


FILLERS = [{"set_guarded": set_guarded,
            "toggle_guarded": toggle_guarded}.get(f.__name__, f)
           for f in wild.FILLERS]

#: the curated kinds: (functions, must_not_report, the attack on each
#: id that must be reported, as (function, arguments) steps)
CURATED = {
    "reinit_kill": ((init_wallet, kill_guarded), [], {
        "106": [("init_wallet", (STRANGER,)), ("kill_guarded", ())]}),
    "owner_change_unprotected": ((change_owner, withdraw_guarded), ["106"], {
        "105": [("change_owner", (STRANGER,)), ("withdraw_guarded", ())]}),
    "init_once_safe": ((init_wallet_once, kill_guarded, withdraw_guarded),
                       ["105", "106"], {}),
}

#: the one-transaction flaws (wild-v1's, and the one above) with their
#: SWC ids, and the attack on each: one call from a deployed state
FLAWS = {**wild.FLAWS, "mint_supply_unchecked": "101"}
FLAW_FNS = {**wild._FLAW_FNS,
            "mint_supply_unchecked": mint_supply_unchecked}
ATTACK = {
    "mint_supply_unchecked": [("mint_supply_unchecked", (STRANGER, M256))],
    "kill": [("kill", ())],
    "exec_unchecked": [("exec_unchecked", ())],
    "sweep": [("sweep", ())],
}

#: the two sets of 8 contracts, (kind or wild-v1 flaws, selectors) each
SLOTS = (
    (("reinit_kill", 6), ("init_once_safe", 9),
     (("exec_unchecked",), 58), ((), 29),
     ("owner_change_unprotected", 3), (("mint_supply_unchecked",), 46),
     ((), 20), ((), 57)),
    (("reinit_kill", 5), ("init_once_safe", 20),
     (("sweep",), 49), ((), 26),
     ("owner_change_unprotected", 2), (("mint_supply_unchecked", "kill"), 44),
     (("precompile_gate_safe",), 35), ((), 60)))
assert all(len(s) == BATCH for s in SLOTS)


def runtime(rng, fns, n_sel: int, lean: bool):
    """(runtime code without trailer, selector of each named function):
    ``fns`` filled up to ``n_sel`` external functions, laid out as
    ``wild-v1`` lays a contract out."""
    c = wild._Ctx(rng, lean, n_sel >= 40)
    fns = list(fns)
    k = 0
    while len(fns) < n_sel:
        f = FILLERS[k % len(FILLERS)]
        if c.heavy and f.__name__ in ("get_slot", "balance_of",
                                      "toggle_guarded", "branchy"):
            f = wild.update_position if k % 2 else wild.transfer_from
        fns.append(f)
        k += 1
    rng.shuffle(fns)
    sels = rng.sample(range(1 << 32), len(fns))
    body = []
    for i, f in enumerate(fns):
        body += [("label", f"f{i}"), "POP", *f(c, f"f{i}_")]
    toks = [0x80, 0x40, "MSTORE", 4, "CALLDATASIZE", "LT",
            ("ref", "fallback"), "JUMPI", 0, "CALLDATALOAD", 0xE0, "SHR",
            *wild.dispatcher(sorted((s, f"f{i}")
                                    for i, s in enumerate(sels))),
            ("label", "fallback"), 0, "DUP1", "REVERT",
            *body, *wild.subroutines(lean)]
    named = {}
    for f, s in zip(fns, sels):
        named.setdefault(f.__name__, s)
    return assemble(*toks), named


def creation(code: bytes, lean: bool) -> bytes:
    """The constructor, then solc's deploy epilogue (``PUSH2 len DUP1
    PUSH2 offset PUSH1 0 CODECOPY PUSH1 0 RETURN INVALID``), then
    ``code``."""
    head = [] if lean else [0x80, 0x40, "MSTORE", *nonpayable("ctor_")]
    ctor = assemble(
        *head, "CALLER", OWNER_SLOT, "SSTORE", 1, INIT_SLOT, "SSTORE",
        SUPPLY, "DUP1", SUPPLY_SLOT, "SSTORE", "CALLER", *wild.map1(1),
        "SSTORE")
    at = len(ctor) + 14
    tail = (b"\x61" + len(code).to_bytes(2, "big") + b"\x80\x61"
            + at.to_bytes(2, "big") + b"\x60\x00\x39\x60\x00\xf3\xfe")
    return ctor + tail + code


def calldata(selector: int, args=()) -> bytes:
    return selector.to_bytes(4, "big") + b"".join(
        int(a).to_bytes(32, "big") for a in args)


def contract(rng, what, n_sel: int, lean: bool):
    """(runtime code, kind, must_report, must_not_report, witness)"""
    if isinstance(what, str):
        fns, must_not, attack = CURATED[what]
        n = len(fns) + 1 if lean else n_sel
        code, named = runtime(rng, fns, n, lean)
        kind = what
    else:
        fns = [FLAW_FNS[f] for f in what]
        ids = {FLAWS[f] for f in what} - {None}
        if "106" not in ids:
            fns.append(kill_guarded)
        if lean:
            fns += [wild.get_slot, wild.deposit]
        code, named = runtime(rng, fns, len(what) + 3 if lean else n_sel,
                              lean)
        must_not = sorted({"101", "106", "115"} - ids)
        attack = {FLAWS[f]: ATTACK[f] for f in what if FLAWS[f]}
        kind = "+".join(what) or "safe"
    witness = {swc: [calldata(named[f], a) for f, a in steps]
               for swc, steps in attack.items()}
    return code, kind, sorted(witness), list(must_not), witness


def _trailer(seed: int, idx: int) -> bytes:
    """Solidity's bzzr0 metadata, as ``wild-v1``'s."""
    h = hashlib.sha256(f"deployed-v1:{seed}:{idx}".encode()).digest()
    return b"\xa1\x65bzzr0\x58\x20" + h + b"\x00\x29"


def batch(seed: int, bi: int, max_code: int = 24576) -> list:
    """Batch ``bi`` of the stream for ``seed``: 8 dicts with ``name``,
    ``code`` (runtime bytes), ``creation`` (bytes), ``kind``,
    ``must_report``, ``must_not_report`` and ``witness`` (SWC id ->
    the attack's calldata, in order; empty for a safe contract)."""
    rng = random.Random(f"deployed-v1:{int(seed)}:{bi}")
    lean = max_code < 3072
    slots = list(SLOTS[bi % len(SLOTS)])
    rng.shuffle(slots)
    out = []
    for pos, (what, n_sel) in enumerate(slots):
        idx = bi * BATCH + pos
        code, kind, must, must_not, witness = contract(
            rng, what, n_sel, lean)
        code += _trailer(seed, idx)
        init = creation(code, lean)
        assert len(init) <= max_code, (kind, len(init), max_code)
        out.append({"name": f"d{idx:06d}_{kind}", "code": code,
                    "creation": init, "kind": kind, "must_report": must,
                    "must_not_report": must_not, "witness": witness})
    return out
