"""Corpus ``linked-v1``: a seeded, endless stream of labelled SYSTEMS of
four contracts that call each other, as a deployed DeFi protocol does.

The source is Uniswap V2 as it stands on mainnet: ``Uniswap/v2-periphery``
``UniswapV2Router02.sol`` (``swapExactTokensForETH``,
``removeLiquidityETH``), which calls ``UniswapV2Pair.swap`` / ``burn`` in
``Uniswap/v2-core``, which calls the ERC-20's ``transfer``, and then
``WETH9.withdraw`` and the ether sent on to ``to``: three contracts deep,
``to`` and the amount crossing every hop as ABI arguments. It is run as
SmartBugs runs Mythril (Durieux et al., arXiv:1910.10601): from sources,
so every constructor first, then two message calls. Nothing can be
fetched here and the image has no solc, so the systems are generated
from the blocks ``wild-v1`` and ``deployed-v1`` have; what is taken from
the public files is written from memory, and what is set here is listed
under ``assumed`` in ``benchmark/configs/corpus-linked.json``.

A system is four members, deployed in this order:

- ``weth`` (WETH9's shape, 8-11 external functions, 1.5-3 KB):
  ``deposit`` payable, a ``withdraw`` that pays ether, the ERC-20 rest;
- ``token`` (an ERC-20 over SafeMath, 9-14 functions, 2-5 KB);
- ``pair`` (core: an LP ERC-20 plus ``mint`` / ``burn`` / ``swap`` /
  ``skim`` / ``sync`` / ``getReserves`` behind a ``lock`` word, 22-28
  functions, 8-12 KB); its two assets and the router are constructor
  arguments, bytes at the end of its creation code, where the factory
  calls ``initialize`` in the deploying transaction;
- ``router`` (periphery, 18-24 functions, 16-24 KB): the other members'
  addresses are baked into its runtime code, as Router02's
  ``immutable``s are.

Calls are encoded as solc encodes a high-level call: the selector word
at the free pointer, argument ``k`` at ``ptr + 4 + 32k``, an
``EXTCODESIZE`` check, ``CALL`` or ``STATICCALL`` (``getReserves``,
``balanceOf``), ``require(success)``, ``returndatasize`` checked and the
return word read from the aligned window at ``ptr``. The opcodes
``benchmark/asm.py``'s table lacks are added to it here.

A batch is two systems (8 contracts), one flawed and one safe; two sets
take turns, so there are four kinds:

- ``depth3_theft``: a router function anyone may call goes router ->
  ``pair`` -> ``weth``, and the ether leaves ``weth`` (frames at depth
  2) to the ``to`` the transaction named. Every guard on the way passes
  only for a member's address as ``msg.sender``. ``weth``: SWC-105, one
  call;
- ``depth3_guarded``: the same chain; the router's entry demands
  ``msg.sender == owner``, which the router's constructor wrote;
- ``hop_flag_twocall``: call 1 through the router writes an operator
  into the PAIR's storage, call 2 passes through the router to the
  pair's payout, which pays the stored operator once there is one.
  ``pair``: SWC-105, two calls;
- ``hop_flag_ctor_safe``: the same two functions, safe only because the
  pair's constructor set ``initialized``, which ``setOperator`` reads,
  from a router lane, through the hop.

Every member is labelled; a flawed one carries ``witness`` (SWC id ->
the attack's calldata from ``STRANGER``, in order) and ``entry``, the
name of the member the calls go to (the router). Every contract names
its ``system`` and its ``address``; :func:`manifest` gives the manifest
a corpus directory holds a system. The seed orders the systems of a
batch, the functions, selectors, constants, addresses and trailers; a
system's members stay together, in deploy order. ``max_code`` under
3072 gives lean systems (the labelled functions and a filler each, no
reason strings): what the test limits hold.

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

for _name, _op in (("CODECOPY", 0x39), ("EXTCODESIZE", 0x3B),
                   ("RETURNDATASIZE", 0x3D), ("RETURNDATACOPY", 0x3E),
                   ("STATICCALL", 0xFA)):
    asm.OPCODES.setdefault(_name, _op)
assemble = asm.assemble


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dep = _load("linked_v1_deployed_blocks", os.path.join(HERE, "deployed-v1.py"))
wild = dep.wild

BATCH = 8
ROLES = ("weth", "token", "pair", "router")     # the deploy order
CREATOR, STRANGER, M256 = dep.CREATOR, dep.STRANGER, dep.M256
OWNER_SLOT, INIT_SLOT, SUPPLY_SLOT, SUPPLY = (
    dep.OWNER_SLOT, dep.INIT_SLOT, dep.SUPPLY_SLOT, dep.SUPPLY)
#: storage the members' links live in (``wild-v1``'s bodies use 1..42,
#: ``deployed-v1``'s constructor 0, 43 and 44)
PEER_A, PEER_B, ROUTER_SLOT, OPERATOR_SLOT, LOCK_SLOT, RESERVE0, RESERVE1 = (
    45, 46, 47, 48, 49, 50, 51)
RESERVES = (5 * 10 ** 21, 3 * 10 ** 20)
KINDS = ("depth3_theft", "depth3_guarded", "hop_flag_twocall",
         "hop_flag_ctor_safe")
FLAWED = {"depth3_theft": "weth", "hop_flag_twocall": "pair"}
#: the two sets of two systems that batches take in turn
SETS = (("depth3_theft", "hop_flag_ctor_safe"),
        ("hop_flag_twocall", "depth3_guarded"))
#: external functions of each member: (set one, set two) by kind's place
COUNTS = {"weth": (8, 11, 9, 10), "token": (9, 14, 12, 10),
          "pair": (22, 28, 25, 24), "router": (18, 24, 21, 20)}
#: the router's size, reached with the library subroutines its swap
#: family inlines (bytes, drawn per system)
ROUTER_BYTES = (16384, 23000)

require, arg, arg_addr, nonpayable, map1, call_sub, ret_word = (
    wild.require, wild.arg, wild.arg_addr, wild.nonpayable, wild.map1,
    wild.call_sub, wild.ret_word)
calldata = dep.calldata


# --- a high-level call, as solc lays it out ---------------------------------

def hop(c, L, tag, target, sel, args=(), value=None, static=False,
        ret=False):
    """[] -> [] (or [word] with ``ret``): ``target.f(args)``. ``target``
    and each of ``args`` are token lists that push one word; ``value``
    likewise (a CALL that sends ether)."""
    n = 4 + 32 * len(args)
    word = ([("push4", sel), 0xE0, "SHL"] if c.lean
            else [("push32", sel << 224)])
    t = [0x40, "MLOAD", *word, "DUP2", "MSTORE"]            # [ptr]
    for k, a in enumerate(args):
        t += [*a, "DUP2", 4 + 32 * k, "ADD", "MSTORE"]
    if not c.lean:
        t += [*target, "EXTCODESIZE",
              *require(L, tag + "x", "Address: call to non-contract",
                       c.lean)]
    t += [0x20 if ret else 0, "DUP2", n, "DUP4"]
    t += ([] if static else [*(value or [0])])
    t += [*target, "GAS", "STATICCALL" if static else "CALL",
          *require(L, tag + "s", "TransferHelper: CALL_FAILED", c.lean)]
    if not ret:
        return t + ["POP"]
    if not c.lean:
        t += [0x1F, "RETURNDATASIZE", "GT",
              *require(L, tag + "r", "TransferHelper: NO_RETURN_DATA",
                       c.lean)]
    return t + ["MLOAD"]


def slot_addr(c, slot):
    return [slot, "SLOAD"] + ([] if c.lean else [wild.ADDR_MASK, "AND"])


def only(c, L, who, msg):
    """``require(msg.sender == who)``"""
    return ["CALLER", *who, "EQ", *require(L, "only", msg, c.lean)]


def send(c, L, to, amount):
    """``to.call.value(amount)("")`` and ``require(success)``"""
    return [0, 0, 0, 0, *amount, *to, "GAS", "CALL",
            *require(L, "sent", "WETH: ETH transfer failed", c.lean)]


def locked(c, L, body):
    """The pair's ``lock`` modifier around ``body`` ([] -> [])."""
    return [LOCK_SLOT, "SLOAD", 1, "EQ",
            *require(L, "lk", "UniswapV2: LOCKED", c.lean),
            0, LOCK_SLOT, "SSTORE", *body, 1, LOCK_SLOT, "SSTORE"]


# --- weth ---------------------------------------------------------------------

def weth_deposit(c, L):
    return wild.deposit(c, L)


def weth_withdraw_to(c, L):
    """``withdraw(to, wad)``: pays ``to``, on the pair's call only
    (WETH9's own pays ``msg.sender`` back what it deposited)."""
    return [*nonpayable(L),
            *only(c, L, slot_addr(c, PEER_A), "WETH: caller is not the pair"),
            *send(c, L, arg_addr(0), arg(1)), "STOP"]


# --- pair ---------------------------------------------------------------------

def pair_release(c, L):
    """The ``swap`` / ``burn`` leg that ends in ether: the router's call
    only, then ``weth.withdraw(to, amount)``."""
    return [*nonpayable(L),
            *only(c, L, slot_addr(c, ROUTER_SLOT), "UniswapV2: FORBIDDEN"),
            *hop(c, L, "w", slot_addr(c, PEER_B), c.sel["weth_withdraw_to"],
                 [arg_addr(0), arg(1)]),
            "STOP"]


def pair_set_operator(c, L):
    once = ([INIT_SLOT, "SLOAD", "ISZERO",
             *require(L, "once", "UniswapV2: ALREADY_INITIALIZED", c.lean)]
            if c.kind == "hop_flag_ctor_safe" else [])
    return [*nonpayable(L),
            *only(c, L, slot_addr(c, ROUTER_SLOT), "UniswapV2: FORBIDDEN"),
            *once, *arg_addr(0), OPERATOR_SLOT, "SSTORE",
            1, INIT_SLOT, "SSTORE", "STOP"]


def pair_pay_operator(c, L):
    """The protocol fee's payout: to the stored operator, once there is
    one (``feeTo``'s shape: the zero address means off)."""
    return [*nonpayable(L),
            *only(c, L, slot_addr(c, ROUTER_SLOT), "UniswapV2: FORBIDDEN"),
            *slot_addr(c, OPERATOR_SLOT), "ISZERO", "ISZERO",
            *require(L, "op", "UniswapV2: NO_OPERATOR", c.lean),
            *send(c, L, slot_addr(c, OPERATOR_SLOT), arg(0)), "STOP"]


def pair_get_reserves(c, L):
    return [*nonpayable(L), 0x40, "MLOAD", RESERVE0, "SLOAD", "DUP2",
            "MSTORE", RESERVE1, "SLOAD", "DUP2", 0x20, "ADD", "MSTORE",
            "TIMESTAMP", "DUP2", 0x40, "ADD", "MSTORE", 0x60, "SWAP1",
            "RETURN"]


def _balance_here(c, L, tag):
    """[] -> [token.balanceOf(this)] through a STATICCALL."""
    return hop(c, L, tag, slot_addr(c, PEER_A), c.sel["token_balance_of"],
               [["ADDRESS"]], static=True, ret=True)


def pair_sync(c, L):
    return [*nonpayable(L), *locked(c, L, [
        *_balance_here(c, L, "b"), RESERVE0, "SSTORE"]), "STOP"]


def pair_skim(c, L):
    return [*nonpayable(L), *locked(c, L, [
        *_balance_here(c, L, "b"),
        *call_sub(L, "ex", "safe_sub", [RESERVE0, "SLOAD"]),    # [excess]
        0x20, "MSTORE",
        *hop(c, L, "t", slot_addr(c, PEER_A), c.sel["token_transfer"],
             [arg_addr(0), [0x20, "MLOAD"]], ret=True),
        *require(L, "ok", "UniswapV2: TRANSFER_FAILED", c.lean)]), "STOP"]


def pair_mint(c, L):
    return [*nonpayable(L), *locked(c, L, [
        *_balance_here(c, L, "b"),
        *call_sub(L, "am", "safe_sub", [RESERVE0, "SLOAD"]),    # [amount]
        "DUP1", "ISZERO", "ISZERO",
        *require(L, "nz", "UniswapV2: INSUFFICIENT_LIQUIDITY_MINTED",
                 c.lean),
        *arg_addr(0), *map1(c.balances), "DUP1", "SLOAD",
        *call_sub(L, "ad", "safe_add", ["DUP4"]), "SWAP1", "SSTORE",
        "POP"]), 1, *ret_word()]


def pair_burn(c, L):
    return [*nonpayable(L), *locked(c, L, [
        "ADDRESS", *map1(c.balances), "SLOAD",                 # [liquidity]
        "DUP1", "ISZERO", "ISZERO",
        *require(L, "nz", "UniswapV2: INSUFFICIENT_LIQUIDITY_BURNED",
                 c.lean),
        0x20, "MSTORE",
        *hop(c, L, "t", slot_addr(c, PEER_A), c.sel["token_transfer"],
             [arg_addr(0), [0x20, "MLOAD"]], ret=True),
        *require(L, "ok", "UniswapV2: TRANSFER_FAILED", c.lean),
        0, "ADDRESS", *map1(c.balances), "SSTORE"]), 1, *ret_word()]


def pair_swap(c, L):
    return [*nonpayable(L), *locked(c, L, [
        *arg(0), "ISZERO", "ISZERO",
        *require(L, "nz", "UniswapV2: INSUFFICIENT_OUTPUT_AMOUNT", c.lean),
        RESERVE0, "SLOAD", *arg(0), "LT",
        *require(L, "lq", "UniswapV2: INSUFFICIENT_LIQUIDITY", c.lean),
        *hop(c, L, "t", slot_addr(c, PEER_A), c.sel["token_transfer"],
             [arg_addr(1), arg(0)], ret=True),
        *require(L, "ok", "UniswapV2: TRANSFER_FAILED", c.lean),
        *_balance_here(c, L, "b"), RESERVE0, "SSTORE"]), "STOP"]


# --- router -------------------------------------------------------------------

def router_sweep_eth(c, L):
    """``swapExactTokensForETH`` / ``removeLiquidityETH`` cut to the leg
    that ends in ether: ``pair.release(to, amount)``."""
    guard = (only(c, L, slot_addr(c, OWNER_SLOT),
                  "Ownable: caller is not the owner")
             if c.kind == "depth3_guarded" else [])
    return [*nonpayable(L), *guard,
            *hop(c, L, "p", c.addr("pair"), c.sel["pair_release"],
                 [arg_addr(0), arg(1)]), "STOP"]


def router_nominate(c, L):
    return [*nonpayable(L),
            *hop(c, L, "p", c.addr("pair"), c.sel["pair_set_operator"],
                 [arg_addr(0)]), "STOP"]


def router_payout(c, L):
    return [*nonpayable(L),
            *hop(c, L, "p", c.addr("pair"), c.sel["pair_pay_operator"],
                 [arg(0)]), "STOP"]


def router_reserves(c, L):
    return [*nonpayable(L),
            *hop(c, L, "p", c.addr("pair"), c.sel["pair_get_reserves"],
                 static=True, ret=True), *ret_word()]


def router_balance(c, L):
    return [*nonpayable(L),
            *hop(c, L, "t", c.addr("token"), c.sel["token_balance_of"],
                 [arg_addr(0)], static=True, ret=True), *ret_word()]


def router_quote(c, L):
    """``UniswapV2Library.quote``"""
    return [*nonpayable(L), *arg(0), "ISZERO", "ISZERO",
            *require(L, "a", "UniswapV2Library: INSUFFICIENT_AMOUNT",
                     c.lean),
            *arg(1), "ISZERO", "ISZERO",
            *require(L, "l", "UniswapV2Library: INSUFFICIENT_LIQUIDITY",
                     c.lean),
            ("ref", L + "m"), *arg(0), *arg(2), ("ref", "safe_mul"), "JUMP",
            ("label", L + "m"), *arg(1), "SWAP1", "DIV", *ret_word()]


def router_add_liquidity(c, L):
    """``addLiquidity`` cut to one asset: pull the tokens to the pair,
    then ``pair.mint(to)``."""
    return [*nonpayable(L), *arg(2), "TIMESTAMP", "GT", "ISZERO",
            *require(L, "d", "UniswapV2Router: EXPIRED", c.lean),
            *hop(c, L, "f", c.addr("token"), c.sel["token_transfer_from"],
                 [["CALLER"], c.addr("pair"), arg(0)], ret=True),
            *require(L, "ok", "TransferHelper: TRANSFER_FROM_FAILED",
                     c.lean),
            *hop(c, L, "m", c.addr("pair"), c.sel["pair_mint"],
                 [arg_addr(1)], ret=True), *ret_word()]


def router_skim(c, L):
    return [*nonpayable(L),
            *hop(c, L, "p", c.addr("pair"), c.sel["pair_skim"],
                 [arg_addr(0)]), "STOP"]


def router_sync(c, L):
    return [*nonpayable(L),
            *hop(c, L, "p", c.addr("pair"), c.sel["pair_sync"]), "STOP"]


def router_wrap(c, L):
    """The ETH leg in: ``weth.deposit.value(msg.value)()``"""
    return [*hop(c, L, "w", c.addr("weth"), c.sel["weth_deposit"],
                 value=["CALLVALUE"]), "STOP"]


ROUTER_FILLERS = [router_reserves, router_balance, router_quote,
                  router_add_liquidity, router_skim, router_sync,
                  router_wrap, dep.set_guarded, dep.toggle_guarded,
                  wild.get_slot, wild.time_gate, wild.set_packed]
PAIR_CORE = [pair_get_reserves, pair_sync, pair_skim, pair_mint, pair_burn,
             pair_swap]
ERC20 = [wild.balance_of, wild.allowance, wild.approve, wild.transfer,
         wild.transfer_from]
#: what each kind's labels rest on, by member
NEEDED = {
    "depth3_theft": {"router": [router_sweep_eth], "pair": [pair_release],
                     "weth": [weth_withdraw_to]},
    "hop_flag_twocall": {"router": [router_nominate, router_payout],
                         "pair": [pair_set_operator, pair_pay_operator],
                         "weth": [weth_withdraw_to]},
}
NEEDED["depth3_guarded"] = NEEDED["depth3_theft"]
NEEDED["hop_flag_ctor_safe"] = NEEDED["hop_flag_twocall"]
#: functions other members call, whose selectors are fixed a system
CALLED = ("weth_deposit", "weth_withdraw_to", "token_balance_of",
          "token_transfer", "token_transfer_from", "pair_release",
          "pair_set_operator", "pair_pay_operator", "pair_get_reserves",
          "pair_sync", "pair_skim", "pair_mint")
_ALIAS = {("token", "balance_of"): "token_balance_of",
          ("token", "transfer"): "token_transfer",
          ("token", "transfer_from"): "token_transfer_from"}


class _Ctx(wild._Ctx):
    def __init__(self, rng, lean, kind, sel, addrs):
        super().__init__(rng, lean, False)
        self.kind, self.sel, self.addrs = kind, sel, addrs

    def addr(self, role):
        return [("push20", self.addrs[role])]


def _library(rng, n_bytes: int) -> list:
    """Internal subroutines of UniswapV2Library's and TransferHelper's
    shape ([ret, a, b] -> [r], SafeMath-guarded with reason strings),
    as many as fill ``n_bytes``: what Router02's swap family inlines."""
    def one(k):
        L = f"lib{k}_"
        return [
            ("label", L + "in"), "DUP2", "ISZERO", "ISZERO",
            *require(L, "a", "UniswapV2Library: INSUFFICIENT_INPUT_AMOUNT",
                     False),
            "DUP1", "ISZERO", "ISZERO",
            *require(L, "l", "UniswapV2Library: INSUFFICIENT_LIQUIDITY",
                     False),
            ("ref", L + "m"), "DUP3", 990 + rng.randrange(10),
            ("ref", "safe_mul"), "JUMP", ("label", L + "m"),
            ("ref", L + "n"), "SWAP1", "DUP3", ("ref", "safe_mul"), "JUMP",
            ("label", L + "n"), "SWAP2", "POP", "POP", "SWAP1", "JUMP"]

    # every subroutine assembles to the same length
    each = len(assemble(("label", "safe_mul"), *one(0))) - 1
    toks = []
    for k in range(-(-n_bytes // each)):
        toks += one(k)
    return toks


def _functions(rng, role, kind, n_sel, lean) -> list:
    """The member's external functions, in the order they are laid out."""
    fns = list(NEEDED[kind].get(role, ()))
    base = {"weth": [weth_deposit, *ERC20],
            "token": list(ERC20),
            "pair": [*PAIR_CORE, *ERC20],
            "router": []}[role]
    pool = {"router": ROUTER_FILLERS}.get(role, dep.FILLERS)
    if lean:
        fns += {"weth": [weth_deposit],
                "token": [wild.balance_of, wild.transfer],
                "pair": [pair_get_reserves],
                "router": [router_reserves]}[role]
    else:
        fns += [f for f in base if f not in fns]
        k = 0
        while len(fns) < n_sel:
            fns.append(pool[k % len(pool)])
            k += 1
    rng.shuffle(fns)
    return fns


def _selectors(rng, role, fns, sel) -> list:
    """A selector a function; one that other members call has the
    system's selector for it (``sel``)."""
    free = iter(rng.sample(range(1 << 32), len(fns)))
    sels, fixed = [], set()
    for f in fns:
        name = f.__name__
        key = name if name in sel else _ALIAS.get((role, name))
        if key is not None and key not in fixed:
            fixed.add(key)
            sels.append(sel[key])
        else:
            sels.append(next(free))
    return sels


def _member(rng, role, kind, fns, sels, lean, sel, addrs, size=0):
    """(runtime code without trailer, selectors by function name)"""
    c = _Ctx(rng, lean, kind, sel, addrs)
    body = []
    for i, f in enumerate(fns):
        body += [("label", f"f{i}"), "POP", *f(c, f"f{i}_")]
    toks = [0x80, 0x40, "MSTORE", 4, "CALLDATASIZE", "LT",
            ("ref", "fallback"), "JUMPI", 0, "CALLDATALOAD", 0xE0, "SHR",
            *wild.dispatcher(sorted((s, f"f{i}")
                                    for i, s in enumerate(sels))),
            ("label", "fallback"), 0, "DUP1", "REVERT", *body]
    # a lean member that calls none of them has no room for SafeMath
    if not lean or any(t in (("ref", "safe_add"), ("ref", "safe_sub"),
                             ("ref", "safe_mul")) for t in body):
        toks += wild.subroutines(lean)
    if size:
        toks += _library(rng, max(0, size - len(assemble(*toks))))
    return assemble(*toks), _named(fns, sels)


def _named(fns, sels) -> dict:
    named = {}
    for f, s in zip(fns, sels):
        named.setdefault(f.__name__, s)
    return named


def creation(role, code: bytes, lean: bool, args=()) -> bytes:
    """The member's constructor, solc's deploy epilogue, ``code`` and
    the constructor's arguments (32-byte words), which the constructor
    copies from the end of the creation code."""
    head = [] if lean else [0x80, 0x40, "MSTORE", *nonpayable("ctor_")]
    own = {
        "weth": ["CALLER", OWNER_SLOT, "SSTORE"],
        "token": ["CALLER", OWNER_SLOT, "SSTORE", SUPPLY, "DUP1",
                  SUPPLY_SLOT, "SSTORE", "CALLER", *map1(1), "SSTORE"],
        "pair": [1, INIT_SLOT, "SSTORE"],
        "router": ["CALLER", OWNER_SLOT, "SSTORE"],
    }[role]
    if lean and role == "weth":
        own = []
    if not lean and role == "token":
        own += [1, INIT_SLOT, "SSTORE"]
    if not lean and role == "pair":
        own += ["CALLER", OWNER_SLOT, "SSTORE", 1, LOCK_SLOT, "SSTORE",
                RESERVES[0], RESERVE0, "SSTORE", RESERVES[1], RESERVE1,
                "SSTORE"]
    slots = {"weth": (PEER_A,),
             "pair": (PEER_A, PEER_B, ROUTER_SLOT)}.get(role, ())
    assert len(slots) == len(args)

    def ctor(at):
        copy = ([32 * len(args), ("push2", at), 0x80, "CODECOPY"]
                if args else [])
        loads = []
        for k, s in enumerate(slots):
            loads += [0x80 + 32 * k, "MLOAD", s, "SSTORE"]
        return assemble(*head, *own, *copy, *loads)

    n = len(ctor(0)) + 14
    tail = (b"\x61" + len(code).to_bytes(2, "big") + b"\x80\x61"
            + n.to_bytes(2, "big") + b"\x60\x00\x39\x60\x00\xf3\xfe")
    return (ctor(n + len(code)) + tail + code
            + b"".join(int(a).to_bytes(32, "big") for a in args))


def dispatch_steps(sels: list, s: int) -> int:
    """Instructions from the contract's first to the JUMPI that enters
    the function with selector ``s``, by ``wild-v1``'s layout."""
    def walk(sels):
        if len(sels) <= 4:
            return 5 * (sels.index(s) + 1)
        mid = len(sels) // 2
        return (6 + walk(sels[:mid]) if s < sels[mid]
                else 5 + walk(sels[mid:]))
    return 12 + walk(sorted(sels))


#: the labelled paths of each kind, as (role, function) legs
PATHS = {
    "depth3_theft": [[("router", "router_sweep_eth"),
                      ("pair", "pair_release"),
                      ("weth", "weth_withdraw_to")]],
    "hop_flag_twocall": [[("router", "router_nominate"),
                          ("pair", "pair_set_operator")],
                         [("router", "router_payout"),
                          ("pair", "pair_pay_operator")]],
}
PATHS["depth3_guarded"] = PATHS["depth3_theft"]
PATHS["hop_flag_ctor_safe"] = PATHS["hop_flag_twocall"]
#: what the three dispatchers of a labelled path may take together (the
#: bodies of the longest take 135 steps with every check, and 256 are
#: the transaction's)
DISPATCH_BUDGET = 96


def _trailer(seed: int, idx: int) -> bytes:
    """Solidity's bzzr0 metadata, as ``wild-v1``'s."""
    h = hashlib.sha256(f"linked-v1:{seed}:{idx}".encode()).digest()
    return b"\xa1\x65bzzr0\x58\x20" + h + b"\x00\x29"


def system(rng, seed: int, sys_idx: int, kind: str, place: int,
           lean: bool, max_code: int) -> list:
    """The four members of one system, in deploy order."""
    name = f"s{sys_idx:06d}_{kind}"
    addrs = {r: (0x51A7 << 144) + rng.randrange(1 << 128) for r in ROLES}
    fns = {role: _functions(rng, role, kind, COUNTS[role][place], lean)
           for role in ROLES}
    while True:
        # selectors, drawn again until the three dispatchers of every
        # labelled path fit their share of the step budget
        sel = dict(zip(CALLED, rng.sample(range(1 << 32), len(CALLED))))
        sels = {role: _selectors(rng, role, fns[role], sel)
                for role in ROLES}
        named = {role: _named(fns[role], sels[role]) for role in ROLES}
        if len({s for r in ROLES for s in sels[r]}) == sum(map(len,
                                                               sels.values())) \
                and all(sum(dispatch_steps(sels[r], named[r][f])
                            for r, f in path) <= DISPATCH_BUDGET
                        for path in PATHS[kind]):
            break
    size = 0 if lean else rng.randrange(*ROUTER_BYTES)
    built = {role: _member(rng, role, kind, fns[role], sels[role], lean,
                           sel, addrs, size if role == "router" else 0)
             for role in ROLES}
    router = built["router"][1]
    attack = {
        "depth3_theft": [("router_sweep_eth", (STRANGER, 10 ** 15))],
        "hop_flag_twocall": [("router_nominate", (STRANGER,)),
                             ("router_payout", (10 ** 15,))],
    }.get(kind, [])
    witness = [calldata(router[f], a) for f, a in attack]
    out = []
    for k, role in enumerate(ROLES):
        code = built[role][0] + _trailer(seed, sys_idx * len(ROLES) + k)
        args = {"weth": (addrs["pair"],),
                "pair": (addrs["token"], addrs["weth"],
                         addrs["router"])}.get(role, ())
        init = creation(role, code, lean, args)
        assert len(init) <= max_code, (role, kind, len(init), max_code)
        flawed = FLAWED.get(kind) == role
        must_not = {"weth": ["101", "106"], "token": ["101", "105", "106"],
                    "pair": ["106"], "router": ["105", "106"]}[role]
        if not flawed and "105" not in must_not:
            must_not = sorted(must_not + ["105"])
        out.append({
            "name": f"{name}__{role}", "code": code, "creation": init,
            "kind": f"{kind}.{role}", "role": role, "system": name,
            "address": addrs[role],
            "must_report": ["105"] if flawed else [],
            "must_not_report": must_not,
            "witness": {"105": witness} if flawed else {},
            "entry": f"{name}__router",
            "selectors": built[role][1]})
    return out


def manifest(members: list) -> dict:
    """What a corpus directory holds a system, beside its members'
    ``X.bin`` / ``X.bin-runtime`` pairs (``<system>.system.json``)."""
    return {"system": members[0]["system"],
            "members": [{"name": m["name"],
                         "address": f"0x{m['address']:040x}"}
                        for m in members]}


def batch(seed: int, bi: int, max_code: int = 24576) -> list:
    """Batch ``bi`` of the stream for ``seed``: two systems, 8 dicts with
    ``name``, ``code``, ``creation``, ``kind``, ``role``, ``system``,
    ``address``, ``must_report``, ``must_not_report``, ``witness`` and
    ``entry``; a system's four members stand together, in deploy
    order."""
    rng = random.Random(f"linked-v1:{int(seed)}:{bi}")
    lean = max_code < 3072
    kinds = list(SETS[bi % len(SETS)])
    rng.shuffle(kinds)
    out = []
    for pos, kind in enumerate(kinds):
        place = 2 * (bi % len(SETS)) + SETS[bi % len(SETS)].index(kind)
        out += system(rng, seed, 2 * bi + pos, kind, place, lean, max_code)
    return out
