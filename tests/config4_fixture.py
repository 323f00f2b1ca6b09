"""BASELINE config-4 fixture: a 3-contract system at call depth 3.

"Uniswap-V2 core+periphery, inter-contract call depth 3 (multi-tx
symbolic)" — BASELINE.json configs[3]. No solc exists in this image, so
this is the hand-assembled structural equivalent:

  caller (periphery user entry)
    └─ CALL → router (periphery)
         └─ CALL → vault (core, holds balances + ether)
              └─ CALL → value send (depth 3)

- ``vault``: keccak-mapping balances[caller] (slot-1 keyed), payable
  deposit, guarded withdraw that sends ether back to msg.sender, and a
  BUG: ``sweep()`` sends the whole contract balance to ``tx.origin``
  with no authorization — the classic origin-drain, reachable through
  the full caller→router→vault chain, so only an engine whose frames
  carry calldata/value/returndata across two hops can witness it from
  the caller entry point.
- ``router``: builds sub-call calldata in memory (selector + forwarded
  arg), forwards value on deposit.
- ``caller``: user entry; ``pump()`` deposits via the router,
  ``attack()`` reaches vault.sweep() via the router.
- constructors store the deployer (CALLER) at slot 0 — creation tx +
  message txs is the reference's ``execute_contract_creation`` →
  ``execute_message_call`` sequence (⚠unv, SURVEY §3.2).

Addresses are the corpus defaults (``contract_address(i)``): the trio
must sit at corpus indices (caller=0, router=1, vault=2). The builder
takes a base index so tools/gen_corpus.py can instantiate the shape at
any batch-aligned position.
"""

from mythril_tpu.core.frontier import contract_address
from mythril_tpu.disassembler.asm import (assemble, mapping_key,
                                          selector_prologue)

# selectors (fixed, arbitrary 4-byte ids)
VAULT_DEPOSIT = 0xD0E30DB0    # deposit()
VAULT_WITHDRAW = 0x2E1A7D4D   # withdraw(uint256)
VAULT_SWEEP = 0x6EA056A9      # sweep()  — the unguarded drain
ROUTER_DEPOSIT = 0xB6B55F25
ROUTER_WITHDRAW = 0x38D07436
ROUTER_SWEEP = 0x35FAA416
CALLER_PUMP = 0xD96A094A
CALLER_ATTACK = 0x9E5FAAFC

GAS = ("push3", 200000)


_mapkey = mapping_key  # shared slot convention (disassembler/asm.py)


def _sel_word(selector: int) -> int:
    """selector left-aligned in a 32-byte word (MSTORE at offset 0)."""
    return selector << 224


def vault_runtime() -> bytes:
    return assemble(
        *selector_prologue(),
        "DUP1", VAULT_DEPOSIT, "EQ", ("ref", "deposit"), "JUMPI",
        "DUP1", VAULT_WITHDRAW, "EQ", ("ref", "withdraw"), "JUMPI",
        "DUP1", VAULT_SWEEP, "EQ", ("ref", "sweep"), "JUMPI",
        0, 0, "REVERT",
        # -- deposit(): balances[caller] += callvalue --
        ("label", "deposit"), "POP",
        "CALLVALUE", "CALLER", *_mapkey(1),     # [cv, key]
        "DUP1", "SLOAD",                        # [cv, key, bal]
        "DUP3", "ADD",                          # [cv, key, bal+cv]
        "SWAP1", "SSTORE", "POP", "STOP",
        # -- withdraw(amount): guarded send back to msg.sender --
        ("label", "withdraw"), "POP",
        4, "CALLDATALOAD",                      # [amt]
        "CALLER", *_mapkey(1),                  # [amt, key]
        "DUP1", "SLOAD",                        # [amt, key, bal]
        "DUP3", "DUP2", "LT",                   # bal < amt ?
        ("ref", "insufficient"), "JUMPI",
        "DUP3", "SWAP1", "SUB",                 # [amt, key, bal-amt]
        "SWAP1", "SSTORE",                      # [amt]
        0, 0, 0, 0, "DUP5", "CALLER", GAS, "CALL",
        "POP", "POP", "STOP",
        ("label", "insufficient"), 0, 0, "REVERT",
        # -- sweep(): BUG — whole balance to tx.origin, no auth --
        ("label", "sweep"), "POP",
        0, 0, 0, 0, "SELFBALANCE", "ORIGIN", GAS, "CALL",
        "POP", "STOP",
    )


def router_runtime(base: int = 0) -> bytes:
    vault = contract_address(base + 2)

    def forward(selector: int, args_len: int, value_tokens):
        # calldata in memory: selector word at 0 (+ forwarded arg at 4)
        head = [_sel_word(selector), 0, "MSTORE"]
        if args_len > 4:
            head += [4, "CALLDATALOAD", 4, "MSTORE"]
        return head + [0, 0, args_len, 0, *value_tokens,
                       ("push3", vault), GAS, "CALL", "POP", "STOP"]

    return assemble(
        *selector_prologue(),
        "DUP1", ROUTER_DEPOSIT, "EQ", ("ref", "deposit"), "JUMPI",
        "DUP1", ROUTER_WITHDRAW, "EQ", ("ref", "withdraw"), "JUMPI",
        "DUP1", ROUTER_SWEEP, "EQ", ("ref", "sweep"), "JUMPI",
        0, 0, "REVERT",
        ("label", "deposit"), "POP",
        *forward(VAULT_DEPOSIT, 4, ["CALLVALUE"]),
        ("label", "withdraw"), "POP",
        *forward(VAULT_WITHDRAW, 36, [0]),
        ("label", "sweep"), "POP",
        *forward(VAULT_SWEEP, 4, [0]),
    )


def caller_runtime(base: int = 0) -> bytes:
    router = contract_address(base + 1)

    def forward(selector: int, value_tokens):
        return [_sel_word(selector), 0, "MSTORE",
                0, 0, 4, 0, *value_tokens,
                ("push3", router), GAS, "CALL", "POP", "STOP"]

    return assemble(
        *selector_prologue(),
        "DUP1", CALLER_PUMP, "EQ", ("ref", "pump"), "JUMPI",
        "DUP1", CALLER_ATTACK, "EQ", ("ref", "attack"), "JUMPI",
        0, 0, "REVERT",
        ("label", "pump"), "POP", *forward(ROUTER_DEPOSIT, ["CALLVALUE"]),
        ("label", "attack"), "POP", *forward(ROUTER_SWEEP, [0]),
    )


def constructor() -> bytes:
    """Store the deployer at slot 0, return (runtime supplied by the
    artifact, as solc standard-JSON does — SURVEY §3.1)."""
    return assemble("CALLER", 0, "SSTORE", 0, 0, "RETURN")


def build_system(base: int = 0):
    """[(name, creation, runtime)] for corpus indices base..base+2."""
    return [
        ("Caller", constructor(), caller_runtime(base)),
        ("Router", constructor(), router_runtime(base)),
        ("Vault", constructor(), vault_runtime()),
    ]
