"""Corpus ``wild-v1``: a seeded, endless stream of labelled contracts at
the sizes and shapes of verified mainnet code.

What is known of the source (SmartBugs' sb-wild: 47k Etherscan-verified
contracts; the EIP-170 cap of 24576 bytes) is the shape, not the bytes:
nothing can be fetched here, so the contracts are generated, the way
Solidity lays them out. Every contract is

- the free-memory-pointer prologue, a calldata-size check and a
  binary-split selector dispatcher over 20 to 60 external functions (as
  solc emits above four selectors), with a reverting fallback;
- function bodies drawn from the templates below: getters, mapping and
  nested-mapping reads through keccak chains, owner-guarded setters
  (the owner an immutable baked into the code), ERC-20 style
  ``approve`` / ``transfer`` / ``transferFrom`` over SafeMath
  subroutines called through return addresses on the stack, packed-slot
  updates, timestamp gates, payable deposits, multi-stage position
  updates; ``require`` failures revert with ``Error(string)`` reasons,
  events are logged;
- shared internal subroutines (``safe_add``, ``safe_sub``, ``safe_mul``);
- a 43-byte Solidity metadata trailer, which makes every instance
  byte-distinct.

Labels hold by construction. A contract's flaws are functions with the
flaw written into them (``FLAWS``); every other function is written safe
against the same ids, so a contract without a flaw of an id carries that
id under ``must_not_report`` (SWC-101: all arithmetic on values goes
through SafeMath; SWC-106: the only SELFDESTRUCT is owner-guarded, or
gated by a RIPEMD-160 digest that cannot match; SWC-115: authorisation
compares ``msg.sender``). The ``precompile_gate`` pair is decided only
by an analyzer that computes precompile 3 concretely.

Batches of 8 take the two sets of (flaws, selector count) slots in
``SLOTS`` in turn, so every seed gives the same work; the seed sets the
order of a batch, the order of functions in each contract, selectors,
constants and trailers. ``max_code`` under 3072 (the test limits hold
512 bytes) gives the same contracts cut to their flaws, their safe
siblings and two fillers, without reason strings.

The same ``seed`` gives the same stream; nothing here imports the
program under test or JAX.
"""

from __future__ import annotations

import hashlib
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from asm import assemble, ripemd160  # noqa: E402

BATCH = 8
ADDR_MASK = ("push20", (1 << 160) - 1)
ERROR_SIG = ("push32", 0x08C379A0 << 224)


# --- building blocks (stack effects in the comments, top on the right) ----

def nonpayable(L):
    return ["CALLVALUE", "ISZERO", ("ref", L + "np"), "JUMPI",
            0, "DUP1", "REVERT", ("label", L + "np")]


def reason(msg: str, lean: bool):
    """``revert(Error(msg))`` as solc 0.5/0.6 lays it out."""
    if lean:
        return [0, "DUP1", "REVERT"]
    data = msg.encode()
    t = [0x40, "MLOAD", ERROR_SIG, "DUP2", "MSTORE", 4, "ADD",
         0x20, "DUP2", "MSTORE", 0x20, "ADD",
         len(data), "DUP2", "MSTORE", 0x20, "ADD"]
    for i in range(0, len(data), 32):
        word = int.from_bytes(data[i:i + 32].ljust(32, b"\0"), "big")
        t += [("push32", word), "DUP2", "MSTORE", 0x20, "ADD"]
    return t + [0x40, "MLOAD", "DUP1", "SWAP2", "SUB", "SWAP1", "REVERT"]


def require(L, tag, msg, lean):
    """[cond] -> []: falls through when cond is non-zero."""
    return [("ref", L + tag), "JUMPI", *reason(msg, lean),
            ("label", L + tag)]


def arg(k):
    return [4 + 32 * k, "CALLDATALOAD"]


def arg_addr(k):
    return [*arg(k), ADDR_MASK, "AND"]


def map1(slot):
    """[key] -> [keccak(key . slot)]"""
    return [0, "MSTORE", slot, 0x20, "MSTORE", 0x40, 0, "SHA3"]


def map2(slot):
    """[k2, k1] -> [keccak(k2 . keccak(k1 . slot))]"""
    return [*map1(slot), "SWAP1", 0, "MSTORE", 0x20, "MSTORE",
            0x40, 0, "SHA3"]


def ret_word():
    """[v] -> return v"""
    return [0x40, "MLOAD", "SWAP1", "DUP2", "MSTORE", 0x20, "SWAP1",
            "RETURN"]


def call_sub(L, tag, sub, operand):
    """[a] -> [sub(a, operand)] through an internal call."""
    return [("ref", L + tag), "SWAP1", *operand, ("ref", sub), "JUMP",
            ("label", L + tag)]


def owner_only(c, L):
    return ["CALLER", ("push20", c.owner), "EQ",
            *require(L, "own", "Ownable: caller is not the owner", c.lean)]


def log_transfer(topic, t2, t3, amount):
    return [*amount, 0x40, "MLOAD", "MSTORE",
            *t3, *t2, ("push32", topic), 0x20, 0x40, "MLOAD", "LOG3"]


def subroutines(lean):
    """``safe_add``, ``safe_sub``, ``safe_mul``: [ret, a, b] -> [r]."""
    return [
        ("label", "safe_add"), "DUP2", "DUP2", "ADD", "DUP3", "DUP2", "LT",
        "ISZERO", *require("safe_add", "ok", "SafeMath: addition overflow",
                           lean),
        "SWAP3", "SWAP2", "POP", "POP", "JUMP",
        ("label", "safe_sub"), "DUP2", "DUP2", "GT", "ISZERO",
        *require("safe_sub", "ok", "SafeMath: subtraction overflow", lean),
        "SWAP1", "SUB", "SWAP1", "JUMP",
        ("label", "safe_mul"), "DUP2", "ISZERO", ("ref", "safe_mulz"),
        "JUMPI", "DUP2", "DUP2", "MUL", "DUP3", "DUP2", "DIV", "DUP3", "EQ",
        *require("safe_mul", "ok", "SafeMath: multiplication overflow",
                 lean),
        "SWAP3", "SWAP2", "POP", "POP", "JUMP",
        ("label", "safe_mulz"), "POP", "POP", 0, "SWAP1", "JUMP",
    ]


# --- safe function bodies: f(c, L) -> tokens, entered with [] ---------------

def get_slot(c, L):
    return [*nonpayable(L), c.slot(), "SLOAD", *ret_word()]


def balance_of(c, L):
    return [*nonpayable(L), *arg_addr(0), *map1(c.balances), "SLOAD",
            *ret_word()]


def allowance(c, L):
    return [*nonpayable(L), *arg_addr(1), *arg_addr(0),
            *map2(c.allowances), "SLOAD", *ret_word()]


def set_guarded(c, L):
    return [*nonpayable(L), *owner_only(c, L), *arg(0), c.slot(), "SSTORE",
            "STOP"]


def toggle_guarded(c, L):
    s = c.slot()
    return [*nonpayable(L), *owner_only(c, L), s, "SLOAD", "ISZERO", s,
            "SSTORE", "STOP"]


def approve(c, L):
    return [*nonpayable(L), *arg(1), *arg_addr(0), "CALLER",
            *map2(c.allowances), "SSTORE",
            *log_transfer(c.topic(), ["CALLER"], arg_addr(0), arg(1)),
            1, *ret_word()]


def _move(c, L, tag, who, sub, amount, slot=None):
    """balances[who] = sub(balances[who], amount)"""
    return [*who, *map1(c.balances if slot is None else slot), "DUP1",
            "SLOAD", *call_sub(L, tag, sub, amount), "SWAP1", "SSTORE"]


def transfer(c, L):
    return [*nonpayable(L),
            *_move(c, L, "r1", ["CALLER"], "safe_sub", arg(1)),
            *_move(c, L, "r2", arg_addr(0), "safe_add", arg(1)),
            *log_transfer(c.topic(), ["CALLER"], arg_addr(0), arg(1)),
            1, *ret_word()]


def transfer_from(c, L):
    return [*nonpayable(L),
            "CALLER", *arg_addr(0), *map2(c.allowances), "DUP1", "SLOAD",
            *call_sub(L, "r0", "safe_sub", arg(2)), "SWAP1", "SSTORE",
            *_move(c, L, "r1", arg_addr(0), "safe_sub", arg(2)),
            *_move(c, L, "r2", arg_addr(1), "safe_add", arg(2)),
            *log_transfer(c.topic(), arg_addr(0), arg_addr(1), arg(2)),
            1, *ret_word()]


def time_gate(c, L):
    return [*nonpayable(L), 1_500_000_000 + c.rng.randrange(10 ** 8),
            "TIMESTAMP", "LT", "ISZERO",
            *require(L, "t", "Crowdsale: not open yet", c.lean),
            *arg(0), c.slot(), "SSTORE", "STOP"]


def set_packed(c, L):
    s = c.slot()
    keep = ((1 << 256) - 1) ^ (0xFFFFFFFFFFFFFFFF << 64)
    return [*nonpayable(L), *arg(0), ("push8", 0xFFFFFFFFFFFFFFFF), "AND",
            0x40, "SHL", s, "SLOAD", ("push32", keep), "AND", "OR", s,
            "SSTORE", "STOP"]


def branchy(c, L):
    t = [*nonpayable(L)]
    for b in range(2):
        t += [*arg(b), "ISZERO", ("ref", f"{L}b{b}"), "JUMPI",
              1 + c.rng.randrange(250), c.slot(), "SSTORE",
              ("label", f"{L}b{b}")]
    return t + ["STOP"]


def mul_div(c, L):
    return [*nonpayable(L), ("ref", L + "r"), *arg(0), *arg(1),
            ("ref", "safe_mul"), "JUMP", ("label", L + "r"),
            *arg(2), "DUP1", "ISZERO", "ISZERO",
            *require(L, "d", "SafeMath: division by zero", c.lean),
            "SWAP1", "DIV", *ret_word()]


def deposit(c, L):
    return [*_move(c, L, "r", ["CALLER"], "safe_add", ["CALLVALUE"]),
            "STOP"]


def update_position(c, L):
    """A long body: k capped, SafeMath-guarded mapping updates."""
    t = [*nonpayable(L)]
    for i in range(c.rng.randrange(4, 8) if c.heavy
                   else c.rng.randrange(2, 4)):
        sub = "safe_add" if c.rng.random() < 0.6 else "safe_sub"
        cap = 10 ** c.rng.randrange(18, 30)
        t += [*(["CALLER"] if i % 2 == 0 else arg_addr(0)),
              *map1(c.slot()), "DUP1", "SLOAD",
              *call_sub(L, f"r{i}", sub, arg(1 + i % 2)),
              "DUP1", cap, "LT", "ISZERO",
              *require(L, f"c{i}", "Pool: position exceeds the cap",
                       c.lean),
              "SWAP1", "SSTORE"]
    return t + [1, *ret_word()]


FILLERS = [get_slot, balance_of, allowance, set_guarded, toggle_guarded,
           approve, transfer, transfer_from, time_gate, set_packed, branchy,
           mul_div, deposit, update_position]


def kill_guarded(c, L):
    """Safe sibling of ``kill``: only the immutable owner."""
    return [*nonpayable(L), *owner_only(c, L), "CALLER", "SELFDESTRUCT"]


# --- flaws: f(c, L) -> tokens; FLAWS maps the name to its SWC id -------------

def mint_unchecked(c, L):
    """SWC-101: an unchecked add into a balance."""
    return [*nonpayable(L), *arg_addr(0), *map1(c.balances), "DUP1",
            "SLOAD", *arg(1), "ADD", "SWAP1", "SSTORE", 1, *ret_word()]


def kill(c, L):
    """SWC-106: anyone can SELFDESTRUCT."""
    return [*nonpayable(L), "CALLER", "SELFDESTRUCT"]


def origin_auth(c, L):
    """SWC-115: tx.origin compared with the stored owner."""
    return [*nonpayable(L), "ORIGIN", c.slot(), "SLOAD", "EQ",
            *require(L, "o", "Auth: not the owner", c.lean),
            *arg(0), c.slot(), "SSTORE", "STOP"]


def exec_unchecked(c, L):
    """SWC-104: the result of a value-bearing CALL is dropped."""
    return [*nonpayable(L), 0, 0, 0, 0, c.slot(), "SLOAD", c.slot(),
            "SLOAD", ("push3", 100_000), "CALL", "POP", 1, *ret_word()]


def sweep(c, L):
    """SWC-105: the whole balance goes to tx.origin, unguarded."""
    return [*nonpayable(L), 0, 0, 0, 0, "SELFBALANCE", "ORIGIN",
            ("push3", 200_000), "CALL", "POP", "STOP"]


def _gate(c, L, reachable: bool):
    """mem[0:32] = K; CALL precompile 3 over it into mem[32:64]; the
    word read back is compared with RIPEMD-160(K), or with that word
    with its lowest bit flipped; equality leads to a SELFDESTRUCT."""
    k = (0x9A7E << 200) + c.rng.randrange(1 << 64)
    word = int.from_bytes(ripemd160(k.to_bytes(32, "big")), "big")
    if not reachable:
        word ^= 1
    return [("push32", k), 0, "MSTORE",
            32, 32, 32, 0, 0, 3, ("push2", 0xFFFF), "CALL", "POP",
            32, "MLOAD", ("push20", word), "EQ", ("ref", L + "k"), "JUMPI",
            "STOP", ("label", L + "k"), "CALLER", "SELFDESTRUCT"]


def precompile_gate(c, L):
    return _gate(c, L, True)


def precompile_gate_safe(c, L):
    return _gate(c, L, False)


FLAWS = {"mint_unchecked": "101", "kill": "106", "origin_auth": "115",
         "exec_unchecked": "104", "sweep": "105", "precompile_gate": "106",
         "precompile_gate_safe": None}
_FLAW_FNS = {f.__name__: f for f in (
    mint_unchecked, kill, origin_auth, exec_unchecked, sweep,
    precompile_gate, precompile_gate_safe)}

def _slots(flawed, safe):
    return tuple([(f, n) for f, n in flawed] + [((), n) for n in safe])


#: the two sets of 8 contracts, (flaws, selectors) each, that batches
#: take in turn
SLOTS = (
    _slots([(("mint_unchecked",), 46), (("kill",), 24),
            (("exec_unchecked",), 58), (("precompile_gate",), 35)],
           (20, 29, 45, 57)),
    _slots([(("origin_auth",), 22), (("sweep",), 49),
            (("precompile_gate_safe",), 35), (("mint_unchecked", "kill"), 44)],
           (26, 32, 40, 60)))
assert all(len(s) == BATCH for s in SLOTS)


class _Ctx:
    def __init__(self, rng, lean, heavy):
        self.rng, self.lean, self.heavy = rng, lean, heavy
        self.owner = (0xA11CE << 140) + rng.randrange(1 << 128)
        self.balances, self.allowances = 1, 2

    def slot(self):
        return 3 + self.rng.randrange(40)

    def topic(self):
        return self.rng.randrange(1 << 256)


def dispatcher(sels: list):
    """``sels``: sorted (selector, label). solc's binary split."""
    if len(sels) <= 4:
        t = []
        for s, lab in sels:
            t += ["DUP1", ("push4", s), "EQ", ("ref", lab), "JUMPI"]
        return t + [("ref", "fallback"), "JUMP"]
    mid = len(sels) // 2
    lo = f"split{sels[mid][0]:08x}"
    return ["DUP1", ("push4", sels[mid][0]), "GT", ("ref", lo), "JUMPI",
            *dispatcher(sels[mid:]), ("label", lo), *dispatcher(sels[:mid])]


def contract(rng, flaws, n_sel: int, lean: bool):
    """(code without trailer, must_report, must_not_report)"""
    c = _Ctx(rng, lean, n_sel >= 40)
    fns = [_FLAW_FNS[f] for f in flaws]
    ids = {FLAWS[f] for f in flaws} - {None}
    if "106" not in ids:
        fns.append(kill_guarded)
    if lean:
        fns += [get_slot, deposit]
    k = 0
    while len(fns) < n_sel:
        f = FILLERS[k % len(FILLERS)]
        if c.heavy and f in (get_slot, balance_of, toggle_guarded, branchy):
            f = update_position if k % 2 else transfer_from
        fns.append(f)
        k += 1
    rng.shuffle(fns)
    sels = rng.sample(range(1 << 32), len(fns))
    body = []
    for i, f in enumerate(fns):
        body += [("label", f"f{i}"), "POP", *f(c, f"f{i}_")]
    toks = [0x80, 0x40, "MSTORE", 4, "CALLDATASIZE", "LT",
            ("ref", "fallback"), "JUMPI", 0, "CALLDATALOAD", 0xE0, "SHR",
            *dispatcher(sorted((s, f"f{i}") for i, s in enumerate(sels))),
            ("label", "fallback"), 0, "DUP1", "REVERT",
            *body, *subroutines(lean)]
    must_not = sorted({"101", "106", "115"} - ids)
    return assemble(*toks), sorted(ids), must_not


def _trailer(seed: int, idx: int) -> bytes:
    """Solidity's bzzr0 metadata: 0xa1 0x65 'bzzr0' 0x58 0x20 <32-byte
    hash> 0x00 0x29 (43 bytes), after the code's last terminator."""
    h = hashlib.sha256(f"wild-v1:{seed}:{idx}".encode()).digest()
    return b"\xa1\x65bzzr0\x58\x20" + h + b"\x00\x29"


def batch(seed: int, bi: int, max_code: int = 24576) -> list:
    """Batch ``bi`` of the stream for ``seed``: 8 dicts with ``name``,
    ``code`` (bytes), ``kind``, ``must_report``, ``must_not_report``."""
    rng = random.Random(f"wild-v1:{int(seed)}:{bi}")
    lean = max_code < 3072
    slots = list(SLOTS[bi % len(SLOTS)])
    rng.shuffle(slots)
    out = []
    for pos, (flaws, n_sel) in enumerate(slots):
        idx = bi * BATCH + pos
        code, must, must_not = contract(
            rng, flaws, len(flaws) + 3 if lean else n_sel, lean)
        code += _trailer(seed, idx)
        assert len(code) <= max_code, (len(code), max_code)
        kind = "+".join(flaws) or "safe"
        out.append({"name": f"c{idx:06d}_{kind}", "code": code,
                    "kind": kind, "must_report": must,
                    "must_not_report": must_not})
    return out
