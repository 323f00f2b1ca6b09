"""Real-world-shaped smoke corpus.

This image has zero network egress, so genuine Etherscan bytecode cannot
be vendored. What CAN be, faithfully:

- **EIP-1167 minimal proxy** — the exact spec byte sequence every real
  clone deployment uses (only the embedded implementation address varies
  per deployment; here it's the in-corpus ERC-20 so the
  DELEGATECALL resolves in-batch).
- **Pre-0.8-Solidity-shaped contracts** assembled at real scale: a full
  ERC-20 (transfer/transferFrom/approve/allowance/balanceOf/totalSupply/
  decimals, canonical keccak event topics, nested-mapping allowance
  slots), an ERC-721 (ownerOf/mint/approve/transferFrom with auth
  checks), and a 2-of-3 multisig (owner set, confirmation bitmap,
  value-bearing execute). Structure mirrors solc output: selector
  dispatcher, keccak mapping keys, LOG3 events with the canonical
  topics, revert-on-failure guards.

The canonical topics are the real ones (keccak of the event
signatures): Transfer(address,address,uint256) =
0xddf252ad..., Approval(address,address,uint256) = 0x8c5be1e5....
"""

from mythril_tpu.core.frontier import contract_address
from mythril_tpu.disassembler.asm import (assemble, mapping_key,
                                          selector_prologue)

TRANSFER_TOPIC = 0xDDF252AD1BE2C89B69C2B068FC378DAA952BA7F163C4A11628F55A4DF523B3EF
APPROVAL_TOPIC = 0x8C5BE1E5EBEC7D5BD14F71427D1E84F3DD0314C0F7B2291E5B200AC8C7C3B925


def eip1167_proxy(impl: int) -> bytes:
    """EIP-1167 minimal proxy runtime, exact spec bytes around the
    20-byte implementation address."""
    return (bytes.fromhex("363d3d373d3d3d363d73")
            + impl.to_bytes(20, "big")
            + bytes.fromhex("5af43d82803e903d91602b57fd5bf3"))


_mapkey = mapping_key  # shared slot convention (disassembler/asm.py)


def _revert():
    return [0, 0, "REVERT"]


def _ret_true():
    return [1, 0, "MSTORE", 32, 0, "RETURN"]


def _log3(topic0: int):
    """LOG3(mem[0:32], topic0, t1, t2) with t1/t2 already on stack as
    [.., t1, t2]; data word must be at memory 0."""
    # LOG3 pops off, len, t0, t1, t2 — push reversed
    return [("push32", topic0), 32, 0, "LOG3"]


def erc20_full() -> bytes:
    """Pre-0.8-style token: unchecked add on credit (the classic real-
    world SWC-101 shape), canonical events, nested allowance mapping
    allowance[owner][spender] = keccak(spender . keccak(owner . 1))."""
    return assemble(
        *selector_prologue(),
        "DUP1", 0xA9059CBB, "EQ", ("ref", "transfer"), "JUMPI",
        "DUP1", 0x23B872DD, "EQ", ("ref", "transferFrom"), "JUMPI",
        "DUP1", 0x095EA7B3, "EQ", ("ref", "approve"), "JUMPI",
        "DUP1", 0x70A08231, "EQ", ("ref", "balanceOf"), "JUMPI",
        "DUP1", 0xDD62ED3E, "EQ", ("ref", "allowance"), "JUMPI",
        "DUP1", 0x18160DDD, "EQ", ("ref", "totalSupply"), "JUMPI",
        "DUP1", 0x313CE567, "EQ", ("ref", "decimals"), "JUMPI",
        *_revert(),

        # -- transfer(to, amount): caller pays --
        ("label", "transfer"), "POP",
        4, "CALLDATALOAD", 36, "CALLDATALOAD",   # [to, amt]
        "CALLER", ("ref", "xfer"), "JUMP",       # [to, amt, from] -> common

        # -- transferFrom(from, to, amount): spend allowance first --
        ("label", "transferFrom"), "POP",
        36, "CALLDATALOAD", 68, "CALLDATALOAD",  # [to, amt]
        4, "CALLDATALOAD",                       # [to, amt, from]
        # allowance key = keccak(caller . keccak(from . 1))
        "DUP1", *_mapkey(1),                     # [to, amt, from, k1]
        "CALLER", *_mapkey_dyn(),                # [to, amt, from, akey]
        "DUP1", "SLOAD",                         # [to, amt, from, akey, al]
        "DUP4", "DUP2", "LT", ("ref", "nope"), "JUMPI",  # al < amt -> revert
        "DUP4", "SWAP1", "SUB",                  # [to, amt, from, akey, al-amt]
        "SWAP1", "SSTORE",                       # [to, amt, from]
        ("ref", "xfer"), "JUMP",

        # -- common transfer body: [to, amt, from] --
        ("label", "xfer"),
        "DUP1", *_mapkey(0),                     # [to, amt, from, fkey]
        "DUP1", "SLOAD",                         # [to, amt, from, fkey, fbal]
        "DUP4", "DUP2", "LT", ("ref", "nope"), "JUMPI",
        "DUP4", "SWAP1", "SUB", "SWAP1", "SSTORE",  # balances[from] -= amt; [to, amt, from]
        "DUP3", *_mapkey(0),                     # [to, amt, from, tkey]
        "DUP1", "SLOAD",                         # [.., tkey, tbal]
        "DUP4", "ADD",                           # unchecked credit (pre-0.8)
        "SWAP1", "SSTORE",                       # [to, amt, from]
        # Transfer(from, to, amt): data word = amt, topics t2=from t3=to
        # (LOG3 pops off, len, t1, then t2 from the stack TOP — so the
        # stack must be [to, from] with `from` on top)
        "DUP2", 0, "MSTORE",                     # mem[0]=amt; [to, amt, from]
        "SWAP1", "POP",                          # [to, from]
        *_log3(TRANSFER_TOPIC),
        *_ret_true(),
        ("label", "nope"), *_revert(),

        # -- approve(spender, amount) --
        ("label", "approve"), "POP",
        36, "CALLDATALOAD",                      # [amt]
        "CALLER", *_mapkey(1),                   # [amt, k1=keccak(caller.1)]
        4, "CALLDATALOAD", *_mapkey_dyn(),       # [amt, akey]
        "DUP2", "SWAP1", "SSTORE",               # allowance[caller][sp]=amt; [amt]
        0, "MSTORE",                             # mem[0]=amt
        4, "CALLDATALOAD", "CALLER",             # [spender, caller]: t2=owner t3=spender
        *_log3(APPROVAL_TOPIC),
        *_ret_true(),

        # -- views --
        ("label", "balanceOf"), "POP",
        4, "CALLDATALOAD", *_mapkey(0), "SLOAD",
        0, "MSTORE", 32, 0, "RETURN",
        ("label", "allowance"), "POP",
        4, "CALLDATALOAD", *_mapkey(1),
        36, "CALLDATALOAD", *_mapkey_dyn(), "SLOAD",
        0, "MSTORE", 32, 0, "RETURN",
        ("label", "totalSupply"), "POP",
        2, "SLOAD", 0, "MSTORE", 32, 0, "RETURN",
        ("label", "decimals"), "POP",
        18, 0, "MSTORE", 32, 0, "RETURN",
    )


def _mapkey_dyn():
    """[.., slotword, key] -> keccak(key . slotword) — nested-mapping
    second hop where the 'slot' is itself a computed keccak."""
    return ["SWAP1", 32, "MSTORE", 0, "MSTORE", 64, 0, "SHA3"]


def erc721_like() -> bytes:
    """owners[tokenId] @ keccak(id.0), approvals @ keccak(id.1),
    contract owner @ slot 2 (set by constructor)."""
    return assemble(
        *selector_prologue(),
        "DUP1", 0x6352211E, "EQ", ("ref", "ownerOf"), "JUMPI",
        "DUP1", 0x40C10F19, "EQ", ("ref", "mint"), "JUMPI",
        "DUP1", 0x095EA7B3, "EQ", ("ref", "approve"), "JUMPI",
        "DUP1", 0x23B872DD, "EQ", ("ref", "transferFrom"), "JUMPI",
        *_revert(),

        ("label", "ownerOf"), "POP",
        4, "CALLDATALOAD", *_mapkey(0), "SLOAD",
        "DUP1", "ISZERO", ("ref", "nope"), "JUMPI",   # nonexistent -> revert
        0, "MSTORE", 32, 0, "RETURN",

        # mint(to, id): onlyOwner, must not exist
        ("label", "mint"), "POP",
        "CALLER", 2, "SLOAD", "EQ", "ISZERO", ("ref", "nope"), "JUMPI",
        36, "CALLDATALOAD", "DUP1", *_mapkey(0),      # [id, okey]
        "DUP1", "SLOAD", "ISZERO", "ISZERO", ("ref", "nope"), "JUMPI",
        4, "CALLDATALOAD", "SWAP1", "SSTORE",         # owners[id]=to; [id]
        0, "MSTORE",                                   # mem[0]=id (event data)
        4, "CALLDATALOAD", 0,                          # [to, 0]: t2=from=0 t3=to
        *_log3(TRANSFER_TOPIC),
        *_ret_true(),

        # approve(to, id): only current owner
        ("label", "approve"), "POP",
        36, "CALLDATALOAD", "DUP1", *_mapkey(0), "SLOAD",  # [id, owner]
        "DUP1", "CALLER", "EQ", "ISZERO", ("ref", "nope"), "JUMPI",
        "POP",                                         # [id]
        "DUP1", *_mapkey(1),                           # [id, akey]
        4, "CALLDATALOAD", "SWAP1", "SSTORE",          # approvals[id]=to; [id]
        0, "MSTORE",
        4, "CALLDATALOAD", "CALLER",                   # t2=owner t3=approved
        *_log3(APPROVAL_TOPIC),
        *_ret_true(),

        # transferFrom(from, to, id): caller is owner or approved
        ("label", "transferFrom"), "POP",
        68, "CALLDATALOAD",                            # [id]
        "DUP1", *_mapkey(0), "DUP1", "SLOAD",          # [id, okey, owner]
        "DUP1", 4, "CALLDATALOAD", "EQ", "ISZERO", ("ref", "nope"), "JUMPI",
        "CALLER", "EQ",                                # owner == caller ?
        ("ref", "auth_ok"), "JUMPI",
        # else need approvals[id] == caller
        "DUP2", *_mapkey(1), "SLOAD", "CALLER", "EQ", "ISZERO",
        ("ref", "nope"), "JUMPI",
        ("label", "auth_ok"),
        36, "CALLDATALOAD", "SWAP1", "SSTORE",         # owners[id]=to; [id]
        "DUP1", *_mapkey(1), 0, "SWAP1", "SSTORE",     # approvals[id]=0; [id]
        0, "MSTORE",
        36, "CALLDATALOAD", 4, "CALLDATALOAD",         # [to, from]: t2=from t3=to
        *_log3(TRANSFER_TOPIC),
        *_ret_true(),
        ("label", "nope"), *_revert(),
    )


def multisig_2of3() -> bytes:
    """Owners at slots 0-2; pending tx (to@10, value@11, confirm
    bitmap@12); execute fires on the 2nd confirmation with a real
    value-bearing CALL — the realistic multi-send/depth shape."""
    def owner_index():
        # [..] -> [idx] (0,1,2) or revert; also leaves nothing else
        return [
            "CALLER", 0, "SLOAD", "EQ", ("ref", "own0"), "JUMPI",
            "CALLER", 1, "SLOAD", "EQ", ("ref", "own1"), "JUMPI",
            "CALLER", 2, "SLOAD", "EQ", ("ref", "own2"), "JUMPI",
            *_revert(),
        ]

    return assemble(
        *selector_prologue(),
        "DUP1", 0xC6427474, "EQ", ("ref", "submit"), "JUMPI",
        "DUP1", 0xC01A8C84, "EQ", ("ref", "confirm"), "JUMPI",
        "DUP1", 0x784547A7, "EQ", ("ref", "isConfirmed"), "JUMPI",
        *_revert(),

        # submit(to, value): any owner; resets bitmap to caller's bit
        ("label", "submit"), "POP",
        *owner_index(),
        ("label", "own0"), 1, ("ref", "subgo"), "JUMP",
        ("label", "own1"), 2, ("ref", "subgo"), "JUMP",
        ("label", "own2"), 4,
        ("label", "subgo"),                         # [bit]
        4, "CALLDATALOAD", 10, "SSTORE",            # to
        36, "CALLDATALOAD", 11, "SSTORE",           # value
        12, "SSTORE",                               # bitmap = caller's bit
        *_ret_true(),

        # confirm(): set bit; if two distinct bits -> execute
        ("label", "confirm"), "POP",
        *_confirm_tail(),

        ("label", "isConfirmed"), "POP",
        12, "SLOAD", 0, "MSTORE", 32, 0, "RETURN",
    )


def _confirm_tail():
    return [
        "CALLER", 0, "SLOAD", "EQ", ("ref", "c0"), "JUMPI",
        "CALLER", 1, "SLOAD", "EQ", ("ref", "c1"), "JUMPI",
        "CALLER", 2, "SLOAD", "EQ", ("ref", "c2"), "JUMPI",
        *_revert(),
        ("label", "c0"), 1, ("ref", "cgo"), "JUMP",
        ("label", "c1"), 2, ("ref", "cgo"), "JUMP",
        ("label", "c2"), 4,
        ("label", "cgo"),                            # [bit]
        12, "SLOAD", "OR", "DUP1", 12, "SSTORE",     # bitmap |= bit; [bm]
        # popcount(bm) >= 2 over 3 bits: bm in {3,5,6,7}
        "DUP1", 3, "EQ",
        "DUP2", 5, "EQ", "OR",
        "DUP2", 6, "EQ", "OR",
        "DUP2", 7, "EQ", "OR",
        "ISZERO", ("ref", "pend"), "JUMPI",
        # execute: CALL(to=slot10, value=slot11), clear state
        0, 0, 0, 0,
        11, "SLOAD", 10, "SLOAD", ("push3", 100000), "CALL",
        "POP",
        0, 12, "SSTORE", 0, 11, "SSTORE", 0, 10, "SSTORE",
        ("label", "pend"), "POP", *_ret_true(),
    ]


def build_realworld():
    """[(name, runtime)] — the smoke corpus. Proxy delegates to the
    ERC-20 at corpus index 1 (pair the two in that order)."""
    return [
        ("Eip1167Proxy", eip1167_proxy(contract_address(1))),
        ("Erc20Full", erc20_full()),
        ("Erc721", erc721_like()),
        ("Multisig2of3", multisig_2of3()),
    ]
