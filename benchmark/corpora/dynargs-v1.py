"""Corpus ``dynargs-v1``: a seeded, endless stream of labelled contracts
whose functions take DYNAMIC ABI arguments (``address[]``, ``bytes``,
``string``), at the size such contracts have on mainnet.

The source is smartbugs-curated: ``dataset/arithmetic/BECToken.sol``,
whose ``batchTransfer(address[] _receivers, uint256 _value)`` computes
``amount = uint256(cnt) * _value`` unchecked and then loops over the
array (CVE-2018-10299, "batchOverflow"), and
``dataset/access_control/parity_wallet_bug_1.sol``, whose
``WalletLibrary`` has ``execute(address _to, uint _value, bytes _data)``
behind a check that reads a mapping; both run as SmartBugs runs Mythril,
from sources, so the constructor runs first (Durieux et al.,
arXiv:1910.10601). Nothing can be fetched here, so the contracts are
generated, from ``deployed-v1``'s and ``wild-v1``'s blocks. The
assembler of the benchmark has no ``CALLDATACOPY``: this file brings the
opcode to its table.

A dynamic argument is decoded as solc 0.4 decodes a ``public``
function's (:func:`decode`): the head word is an offset, the length is
read at ``4 + offset``, the data is copied to the free memory pointer
with ``CALLDATACOPY`` and the pointer moves on by a length the caller
chose. Every contract is creation code plus runtime code at 20-60
external functions, laid out as ``wild-v1`` lays a contract out, and a
quarter of its fillers take a dynamic argument
(``approve_with_data(address,uint256,bytes)``, ``set_name(string)``,
which stores the length, ``multi_transfer(address[],uint256[])`` over
SafeMath), so that decodes are on most explored paths and not only in
the flaw. Those three have no outlet but storage (no event, no return
value, no call): the analyzer's IntegerArithmetics reports any
satisfiable wrap on a path that logs, returns or calls, the decode's own
``length + 31`` included, and a filler has to be safe against SWC-101.

Batches of 8 take the two sets of ``SLOTS`` in turn, each

- 2 ``batch_overflow``: BECToken's ``batchTransfer`` (SWC-101, one call);
- 2 ``batch_checked``: its repaired sibling, ``SafeMath.mul(cnt, _value)``;
- 2 ``member_execute``: ``join()`` sets ``members[msg.sender]`` for
  anyone, ``execute(address,uint256,bytes)`` decodes its ``bytes``, then
  requires ``members[msg.sender]`` and sends ``_value`` to ``_to``
  (SWC-105 through TWO calls);
- 1 ``member_execute_safe``: the same ``execute``; ``members[]`` is
  written by the constructor and an ``onlyOwner`` ``add_member`` only;
- 1 with ``wild-v1``'s one-call ``kill`` at ~30 functions.

Every contract is labelled, and a flawed one carries ``witness``: the
calldata of its attack from ``STRANGER``, in order. The seed orders the
batch, the functions, the selectors, the constants and the trailers.
``max_code`` under 3072 gives the lean contracts the test limits hold
(the flaw or its sibling and a filler, no reason strings).

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
import asm  # noqa: E402

asm.OPCODES.setdefault("CALLDATACOPY", 0x37)


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dep = _load("dynargs_v1_deployed_blocks",
            os.path.join(HERE, "deployed-v1.py"))
wild = dep.wild

BATCH = dep.BATCH
CREATOR, STRANGER, M256 = dep.CREATOR, dep.STRANGER, dep.M256
OWNER_SLOT, INIT_SLOT, SUPPLY_SLOT, SUPPLY = (
    dep.OWNER_SLOT, dep.INIT_SLOT, dep.SUPPLY_SLOT, dep.SUPPLY)
MEMBERS_SLOT = 45
calldata = dep.calldata
require, arg, arg_addr, nonpayable, map1, map2, call_sub = (
    wild.require, wild.arg, wild.arg_addr, wild.nonpayable, wild.map1,
    wild.map2, wild.call_sub)
#: the kinds whose flaw needs both calls, and the SWC id of each
TWO_CALL = {"member_execute": "105"}
#: BECToken's cap on the receivers of one batchTransfer
MAX_RECEIVERS = 20


def decode(k: int, words: bool) -> list:
    """[] -> [ptr]: the dynamic argument in head word ``k`` copied to
    memory, as solc 0.4 does for a ``public`` function: ``ptr`` holds
    the length, the data follows, and the free pointer moves past it.
    ``words``: an array of 32-byte elements; else ``bytes``/``string``,
    padded to whole words."""
    size = [0x20, "MUL"] if words else [0x1F, "ADD", 0x1F, "NOT", "AND"]
    return [*arg(k), 4, "ADD",              # [pos]: where the length is
            "DUP1", "CALLDATALOAD",         # [pos, len]
            0x40, "MLOAD",                  # [pos, len, ptr]
            "DUP2", "DUP2", "MSTORE",       # mstore(ptr, len)
            "DUP2", *size,                  # [pos, len, ptr, size]
            "DUP1", "DUP5", 0x20, "ADD",    # [.., size, size, pos + 32]
            "DUP4", 0x20, "ADD",            # [.., size, size, src, ptr + 32]
            "CALLDATACOPY",                 # [pos, len, ptr, size]
            "DUP2", 0x20, "ADD", "ADD",     # [pos, len, ptr, ptr + 32 + size]
            0x40, "MSTORE",                 # the free pointer moves on
            "SWAP2", "POP", "POP"]          # [ptr]


def element(ptr_at: int) -> list:
    """[.., i] -> [.., i, mem[ptr + 32 + 32 * i]]; ``ptr`` is ``ptr_at``
    deep once ``32 * i + 32`` is on the stack."""
    return ["DUP1", 0x20, "MUL", 0x20, "ADD", f"DUP{ptr_at}", "ADD",
            "MLOAD"]


# --- BECToken -----------------------------------------------------------------

def _batch_transfer(c, L, checked: bool) -> list:
    mask = [] if c.lean else [wild.ADDR_MASK, "AND"]
    # the Transfer event; a lean contract has no room for its topic
    event = [] if c.lean else wild.log_transfer(
        c.topic(), ["CALLER"], ["DUP1"], arg(1))
    amount = ([("ref", L + "am"), "DUP2", *arg(1), ("ref", "safe_mul"),
               "JUMP", ("label", L + "am")] if checked
              else [*arg(1), "DUP2", "MUL"])
    return [
        *nonpayable(L), *decode(0, True),
        "DUP1", "MLOAD",                    # [ptr, cnt]
        *amount,                            # [ptr, cnt, amount]
        # require(cnt > 0 && cnt <= 20)
        0, "DUP3", "GT", "DUP1", "ISZERO", ("ref", L + "c1"), "JUMPI",
        "POP", MAX_RECEIVERS, "DUP3", "GT", "ISZERO", ("label", L + "c1"),
        *require(L, "cnt", "BEC: receivers out of range", c.lean),
        # require(_value > 0 && balances[msg.sender] >= amount)
        0, *arg(1), "GT", "DUP1", "ISZERO", ("ref", L + "c2"), "JUMPI",
        "POP", "DUP1", "CALLER", *map1(c.balances), "SLOAD", "LT", "ISZERO",
        ("label", L + "c2"),
        *require(L, "bal", "BEC: balance too low", c.lean),
        # balances[msg.sender] = balances[msg.sender].sub(amount)
        "CALLER", *map1(c.balances), "DUP1", "SLOAD",
        *call_sub(L, "s", "safe_sub", ["DUP4"]), "SWAP1", "SSTORE",
        0,                                  # [ptr, cnt, amount, i]
        ("label", L + "lp"),
        "DUP3", "DUP2", "LT", "ISZERO", ("ref", L + "end"), "JUMPI",
        *element(5), *mask,                 # [.., i, _receivers[i]]
        "DUP1", *map1(c.balances), "DUP1", "SLOAD",
        *call_sub(L, "a", "safe_add", arg(1)), "SWAP1", "SSTORE",
        *event, "POP", 1, "ADD", ("ref", L + "lp"), "JUMP",
        ("label", L + "end"), "POP", "POP", "POP", "POP",
        1, *wild.ret_word()]


def batch_transfer(c, L):
    """SWC-101: ``amount = cnt * _value`` unchecked (CVE-2018-10299)."""
    return _batch_transfer(c, L, False)


def batch_transfer_checked(c, L):
    return _batch_transfer(c, L, True)


# --- the wallet -----------------------------------------------------------------

def join(c, L):
    """``members[msg.sender] = true``, for anyone."""
    return [*nonpayable(L), 1, "CALLER", *map1(MEMBERS_SLOT), "SSTORE",
            "STOP"]


def add_member(c, L):
    return [*nonpayable(L), *dep.owner_only(c, L), 1, *arg_addr(0),
            *map1(MEMBERS_SLOT), "SSTORE", "STOP"]


def execute(c, L):
    """``execute(address _to, uint256 _value, bytes _data)``: the
    decode runs before the guard, as in any ``public`` function;
    ``_to.call.value(_value)()`` (the data is not passed on)."""
    return [*nonpayable(L), *decode(2, False), "POP",
            "CALLER", *map1(MEMBERS_SLOT), "SLOAD",
            *require(L, "mem", "Wallet: caller is not a member", c.lean),
            0, 0, 0, 0, *arg(1), *arg_addr(0), "GAS", "CALL",
            *require(L, "sent", "Wallet: transfer failed", c.lean), "STOP"]


# --- fillers with a dynamic argument -------------------------------------------

def approve_with_data(c, L):
    """``approveAndCall(address,uint256,bytes)`` up to the call."""
    return [*nonpayable(L), *decode(2, False), "POP",
            *arg(1), *arg_addr(0), "CALLER", *map2(c.allowances), "SSTORE",
            "STOP"]


def set_name(c, L):
    """A ``string`` setter; what it keeps is the length."""
    return [*nonpayable(L), *decode(0, False), "MLOAD", c.slot(), "SSTORE",
            "STOP"]


def multi_transfer(c, L):
    """``multiTransfer(address[] _to, uint256[] _values)`` over
    SafeMath, the lengths required equal, no cap on them."""
    mask = [] if c.lean else [wild.ADDR_MASK, "AND"]
    return [
        *nonpayable(L), *decode(0, True), *decode(1, True),   # [to, vals]
        "DUP2", "MLOAD", "DUP2", "MLOAD", "EQ",
        *require(L, "len", "Token: lengths differ", c.lean),
        0,                                  # [to, vals, i]
        ("label", L + "lp"),
        "DUP3", "MLOAD", "DUP2", "LT", "ISZERO", ("ref", L + "end"), "JUMPI",
        *element(3),                        # [to, vals, i, v]
        "CALLER", *map1(c.balances), "DUP1", "SLOAD",
        *call_sub(L, "s", "safe_sub", ["DUP4"]), "SWAP1", "SSTORE",
        "SWAP1", *element(5), *mask,        # [to, vals, v, i, to[i]]
        *map1(c.balances), "DUP1", "SLOAD",
        *call_sub(L, "a", "safe_add", ["DUP5"]), "SWAP1", "SSTORE",
        "SWAP1", "POP", 1, "ADD", ("ref", L + "lp"), "JUMP",
        ("label", L + "end"), "STOP"]


DYNAMIC = (approve_with_data, set_name, multi_transfer)
_OWN_DYNAMIC = {"batch_transfer", "batch_transfer_checked", "execute"}

#: kind -> (functions, must_not_report, the attack on each id that must
#: be reported, as (function, arguments) steps)
R1, R2 = (0x1111 << 144) + 1, (0x2222 << 144) + 2
KINDS = {
    "batch_overflow": ((batch_transfer,), ["105", "106"], {
        "101": [("batch_transfer", ([R1, R2], 1 << 255))]}),
    "batch_checked": ((batch_transfer_checked,), ["101", "105", "106"], {}),
    "member_execute": ((join, execute), ["106"], {
        "105": [("join", ()), ("execute", (STRANGER, 1, b""))]}),
    "member_execute_safe": ((add_member, execute), ["101", "105", "106"],
                            {}),
}

#: the two sets of 8 contracts, (kind or wild-v1 flaws, selectors) each
SLOTS = (
    (("batch_overflow", 20), ("batch_overflow", 41),
     ("batch_checked", 27), ("batch_checked", 56),
     ("member_execute", 23), ("member_execute", 48),
     ("member_execute_safe", 35), (("kill",), 30)),
    (("batch_overflow", 24), ("batch_overflow", 52),
     ("batch_checked", 21), ("batch_checked", 60),
     ("member_execute", 20), ("member_execute", 44),
     ("member_execute_safe", 38), (("kill",), 31)))
assert all(len(s) == BATCH for s in SLOTS)


def abi(selector: int, args=()) -> bytes:
    """Calldata as the ABI lays it out: a head word an argument (a
    dynamic one's is the offset of its tail), then the tails: a list is
    an array of words, ``bytes`` a byte string."""
    head, tail = [], b""
    for a in args:
        if isinstance(a, int):
            head.append(a)
            continue
        head.append(32 * len(args) + len(tail))
        if isinstance(a, bytes):
            tail += len(a).to_bytes(32, "big") + a.ljust(-(-len(a) // 32) * 32,
                                                         b"\0")
        else:
            tail += b"".join(int(w).to_bytes(32, "big")
                             for w in [len(a), *a])
    return calldata(selector, head) + tail


def filled(fns, n_sel: int) -> list:
    """``fns`` filled up to ``n_sel`` functions: every fourth filler one
    of ``DYNAMIC``, the rest ``deployed-v1``'s in their order (the light
    ones of a contract of 40 or more replaced as there)."""
    fns, k, heavy = list(fns), 0, n_sel >= 40
    while len(fns) < n_sel:
        if k % 4 == 1:
            f = DYNAMIC[(k // 4) % len(DYNAMIC)]
        else:
            f = dep.FILLERS[(k - (k + 2) // 4) % len(dep.FILLERS)]
            if heavy and f.__name__ in ("get_slot", "balance_of",
                                        "toggle_guarded", "branchy"):
                f = wild.update_position if k % 2 else wild.transfer_from
        fns.append(f)
        k += 1
    return fns


def creation(code: bytes, lean: bool, members: bool) -> bytes:
    """``deployed-v1``'s constructor and deploy epilogue; with
    ``members`` the constructor also makes its sender a member."""
    head = [] if lean else [0x80, 0x40, "MSTORE", *nonpayable("ctor_")]
    more = [1, "CALLER", *map1(MEMBERS_SLOT), "SSTORE"] if members else []
    ctor = asm.assemble(
        *head, "CALLER", OWNER_SLOT, "SSTORE", 1, INIT_SLOT, "SSTORE",
        SUPPLY, "DUP1", SUPPLY_SLOT, "SSTORE", "CALLER", *map1(1),
        "SSTORE", *more)
    at = len(ctor) + 14
    tail = (b"\x61" + len(code).to_bytes(2, "big") + b"\x80\x61"
            + at.to_bytes(2, "big") + b"\x60\x00\x39\x60\x00\xf3\xfe")
    return ctor + tail + code


def contract(rng, what, n_sel: int, lean: bool):
    """(runtime code, kind, must_report, must_not_report, witness,
    functions with a dynamic argument)"""
    if isinstance(what, str):
        fns, must_not, attack = KINDS[what]
        fns = filled(fns, len(fns) + 1 if lean else n_sel)
        kind = what
    else:
        own = [dep.FLAW_FNS[f] for f in what]
        ids = {dep.FLAWS[f] for f in what} - {None}
        fns = filled(own + ([wild.get_slot, wild.deposit] if lean else []),
                     len(own) + 2 if lean else n_sel)
        must_not = sorted({"101", "106", "115"} - ids)
        attack = {dep.FLAWS[f]: dep.ATTACK[f] for f in what if dep.FLAWS[f]}
        kind = "+".join(what) or "safe"
    code, named = dep.runtime(rng, fns, len(fns), lean)
    witness = {swc: [abi(named[f], a) for f, a in steps]
               for swc, steps in attack.items()}
    dynamic = sum(f in DYNAMIC or f.__name__ in _OWN_DYNAMIC for f in fns)
    return code, kind, sorted(witness), list(must_not), witness, dynamic


def _trailer(seed: int, idx: int) -> bytes:
    """Solidity's bzzr0 metadata, as ``wild-v1``'s."""
    h = hashlib.sha256(f"dynargs-v1:{seed}:{idx}".encode()).digest()
    return b"\xa1\x65bzzr0\x58\x20" + h + b"\x00\x29"


def batch(seed: int, bi: int, max_code: int = 24576) -> list:
    """Batch ``bi`` of the stream for ``seed``: 8 dicts with ``name``,
    ``code`` (runtime bytes), ``creation`` (bytes), ``kind``,
    ``must_report``, ``must_not_report``, ``witness`` (SWC id -> the
    attack's calldata, in order; empty for a safe contract) and
    ``dynamic`` (how many functions decode a dynamic argument)."""
    rng = random.Random(f"dynargs-v1:{int(seed)}:{bi}")
    lean = max_code < 3072
    slots = list(SLOTS[bi % len(SLOTS)])
    rng.shuffle(slots)
    out = []
    for pos, (what, n_sel) in enumerate(slots):
        idx = bi * BATCH + pos
        code, kind, must, must_not, witness, dynamic = contract(
            rng, what, n_sel, lean)
        code += _trailer(seed, idx)
        init = creation(code, lean, kind.startswith("member"))
        assert len(init) <= max_code, (kind, len(init), max_code)
        out.append({"name": f"a{idx:06d}_{kind}", "code": code,
                    "creation": init, "kind": kind, "must_report": must,
                    "must_not_report": must_not, "witness": witness,
                    "dynamic": dynamic})
    return out
