"""Generate independent CALL-frame test vectors (calltests.json).

The CALL/frame machinery — the riskiest part of the
engine — was tested only against the author's own expectations. These
vectors use deliberately independent machinery (same philosophy as
``gen_vmtests.py``):

- bytecode emitted by the raw-byte mini-assembler below (NOT
  ``mythril_tpu.disassembler.asm``);
- every expected storage slot and balance is an explicit Python integer
  FORMULA evaluated at generation time — never an interpreter;
- account keys are symbolic names ("caller" / "callee" / "attacker")
  resolved to account-table slots by the runner.

Each vector: caller (contract 0) + callee (contract 1); the runner seeds
one lane on the caller with concrete calldata and runs the SYMBOLIC
engine (frames live there). Balance conventions of
``make_sym_frontier``: contracts start at 10**18, EOAs at 10**20.

Run: ``python tests/fixtures/gen_calltests.py`` (rewrites calltests.json).
"""

import json
import os

M = (1 << 256) - 1
B0 = 10**18                 # contract starting balance
ATTACKER = 0xDEADBEEFDEADBEEFDEADBEEFDEADBEEFDEADBEEF
CALLEE_ADDR = 0xAFFE + 0x10000  # contract_address(1) convention


def push(v, width=None):
    v &= M
    if width is None:
        width = max(1, (v.bit_length() + 7) // 8)
    return bytes([0x5F + width]) + v.to_bytes(width, "big")


OPS = {
    "STOP": 0x00, "ADD": 0x01, "SUB": 0x03, "CALLER": 0x33,
    "CALLVALUE": 0x34, "CALLDATALOAD": 0x35, "RETURNDATASIZE": 0x3D,
    "POP": 0x50, "MLOAD": 0x51, "MSTORE": 0x52, "SLOAD": 0x54,
    "SSTORE": 0x55, "JUMP": 0x56, "JUMPI": 0x57, "JUMPDEST": 0x5B,
    "DUP1": 0x80, "SWAP1": 0x90, "CALL": 0xF1, "CALLCODE": 0xF2,
    "RETURN": 0xF3, "DELEGATECALL": 0xF4, "STATICCALL": 0xFA,
    "REVERT": 0xFD, "INVALID": 0xFE,
}


def op(*names):
    return bytes(OPS[n] for n in names)


def call(kind="CALL", value=None, args=(0, 0), ret=(0, 32), gas=0xFFFF,
         to=CALLEE_ADDR):
    """Raw bytes pushing a full CALL-family argument list."""
    out = push(ret[1]) + push(ret[0]) + push(args[1]) + push(args[0])
    if kind in ("CALL", "CALLCODE"):
        out += push(value or 0)
    out += push(to) + push(gas) + op(kind)
    return out


def sstore(slot):
    return push(slot) + op("SSTORE")


VECTORS = {}


def vec(name, caller, callee, expect_storage, expect_balances=None,
        max_steps=96):
    VECTORS[name] = {
        "caller_code": caller.hex(),
        "callee_code": callee.hex(),
        # expected storage: {account: {slot: value}} — EXACT (all written
        # slots listed); accounts by role name
        "expect_storage": {
            acct: {str(k): hex(v) for k, v in slots.items()}
            for acct, slots in expect_storage.items()
        },
        "expect_balances": {
            acct: hex(v) for acct, v in (expect_balances or {}).items()
        },
        "max_steps": max_steps,
    }


# 1. returndata plumbing: callee returns 42; caller stores success + word
vec(
    "call_returndata",
    call() + sstore(1) + push(0) + op("MLOAD") + sstore(2) + op("STOP"),
    push(42) + push(0) + op("MSTORE") + push(32) + push(0) + op("RETURN"),
    {"caller": {1: 1, 2: 42}},
)

# 2. reverting value call: transfer fully undone, success 0
vec(
    "revert_undoes_transfer",
    call(value=12345) + sstore(1) + op("STOP"),
    push(7) + sstore(9) + push(0) + push(0) + op("REVERT"),
    {"caller": {1: 0}, "callee": {}},
    {"caller": B0, "callee": B0},
)

# 3. successful value transfer: payer/payee formula; callee sees value
vec(
    "value_transfer",
    call(value=98765) + sstore(1) + op("STOP"),
    op("CALLVALUE") + sstore(3),
    {"caller": {1: 1}, "callee": {3: 98765}},
    {"caller": B0 - 98765, "callee": B0 + 98765},
)

# 4. DELEGATECALL writes the CALLER's storage under the caller's balance
vec(
    "delegatecall_storage_ctx",
    call("DELEGATECALL") + sstore(1) + op("STOP"),
    push(5) + sstore(9),
    {"caller": {1: 1, 9: 5}, "callee": {}},
)

# 5. STATICCALL: callee write traps -> success 0, nothing written
vec(
    "staticcall_blocks_write",
    call("STATICCALL") + sstore(1) + op("STOP"),
    push(5) + sstore(9),
    {"caller": {1: 0}, "callee": {}},
)

# 6. CALLCODE: callee code under CALLER storage; self-value net zero
vec(
    "callcode_self_value",
    call("CALLCODE", value=777) + sstore(1) + op("STOP"),
    push(6) + sstore(9),
    {"caller": {1: 1, 9: 6}, "callee": {}},
    {"caller": B0, "callee": B0},
)

# 7. insufficient balance: success 0, no transfer, caller continues
vec(
    "insufficient_balance",
    call(value=2 * B0) + sstore(1) + push(11) + sstore(2) + op("STOP"),
    op("STOP"),
    {"caller": {1: 0, 2: 11}},
    {"caller": B0, "callee": B0},
)

# 8. callee INVALID: becomes success 0; callee's pre-fault write rolled back
vec(
    "callee_invalid_rolls_back",
    call() + sstore(1) + op("STOP"),
    push(3) + sstore(4) + op("INVALID"),
    {"caller": {1: 0}, "callee": {}},
)

# 9. nested self-call: callee calls itself (depth 2) writing 11 then 5
#    callee: if calldataload(0) != 0 {sstore(7, 11)} else {self-call with
#    data=1; sstore(8, 5)} — both writes land in the CALLEE account.
#    Layout (byte offsets audited by hand):
#      0  push(0)            2 bytes
#      2  CALLDATALOAD       1
#      3  push(37)           2
#      5  JUMPI              1
#      6  push(1) push(0) MSTORE            5   (marker word for the inner)
#     11  call(args=(0,32), ret=(0,0))     18   (6 pushes + to + gas + CALL)
#     29  POP                1
#     30  push(5) push(8) SSTORE            5
#     35  STOP               1
#     36  (padding none) -> JUMPDEST at 37? NO: next byte IS 36
#    Recount: 6+5=11; 11+18=29; POP at 29; 30..34 store; STOP 35;
#    JUMPDEST 36 — target 36.
_callee_nested = (
    push(0) + op("CALLDATALOAD")
    + push(36) + op("JUMPI")
    + push(1) + push(0) + op("MSTORE")
    + call(args=(0, 32), ret=(0, 0), to=CALLEE_ADDR) + op("POP")
    + push(5) + sstore(8) + op("STOP")
    + op("JUMPDEST") + push(11) + sstore(7) + op("STOP")
)
assert _callee_nested[36] == OPS["JUMPDEST"], \
    f"nested vector JUMPDEST drifted: {_callee_nested.hex()}"
vec(
    "nested_self_call",
    call() + sstore(1) + op("STOP"),
    _callee_nested,
    {"caller": {1: 1}, "callee": {7: 11, 8: 5}},
    max_steps=128,
)

# 10. RETURNDATASIZE reflects the callee's payload even past the ret window
vec(
    "returndatasize_full",
    call(ret=(0, 0)) + op("POP") + op("RETURNDATASIZE") + sstore(1)
    + op("STOP"),
    push(0) + push(0) + op("MSTORE") + push(64) + push(0) + op("RETURN"),
    {"caller": {1: 64}},
)


def main():
    out = {
        "comment": "independent CALL-frame vectors; see gen_calltests.py",
        "callee_address": hex(CALLEE_ADDR),
        "tests": VECTORS,
    }
    path = os.path.join(os.path.dirname(__file__), "calltests.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1, sort_keys=True)
    print(f"wrote {len(VECTORS)} vectors to {path}")


if __name__ == "__main__":
    main()
