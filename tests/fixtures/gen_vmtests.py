"""Generate consensus-style VM test vectors (tests/fixtures/vmtests.json).

The Ethereum consensus VMTests (``tests/laser/evm_testsuite`` in the
reference ⚠unv, SURVEY.md §4 — "the key correctness oracle") cannot be
vendored in this image (no network). This generator hand-transcribes the
same *style* of vector with deliberately independent machinery so the
fixtures do not share code — or misconceptions — with the interpreter
under test:

- bytecode is emitted by the 10-line mini-assembler below (NOT
  ``mythril_tpu.disassembler.asm``);
- every expected value is an explicit Python big-int formula evaluated at
  generation time (NOT an EVM interpreter) — Python ints are the
  independent arbiter for 256-bit arithmetic;
- the two keccak digests are well-known literals (empty string and
  32 zero bytes), not computed by our kernel.

Vectors follow the official shape: ``exec.code``/``exec.data`` in, then
``expect.storage`` (slot -> value) and optional ``expect.out``. Results
are stored via the official tests' ``...600055`` SSTORE idiom.

Run: ``python tests/fixtures/gen_vmtests.py`` (rewrites vmtests.json).
"""

import json
import os

M = (1 << 256) - 1  # word mask


def neg(x):  # two's-complement encoding of -x
    return (-x) & M


# --- independent mini-assembler (opcode bytes spelled out) ---------------

def push(v, width=None):
    v &= M
    if width is None:
        width = max(1, (v.bit_length() + 7) // 8)
    return bytes([0x5F + width]) + v.to_bytes(width, "big")


def op(*names):
    TBL = {
        "STOP": 0x00, "ADD": 0x01, "MUL": 0x02, "SUB": 0x03, "DIV": 0x04,
        "SDIV": 0x05, "MOD": 0x06, "SMOD": 0x07, "ADDMOD": 0x08,
        "MULMOD": 0x09, "EXP": 0x0A, "SIGNEXTEND": 0x0B, "LT": 0x10,
        "GT": 0x11, "SLT": 0x12, "SGT": 0x13, "EQ": 0x14, "ISZERO": 0x15,
        "AND": 0x16, "OR": 0x17, "XOR": 0x18, "NOT": 0x19, "BYTE": 0x1A,
        "SHL": 0x1B, "SHR": 0x1C, "SAR": 0x1D, "SHA3": 0x20,
        "CALLDATALOAD": 0x35, "CALLDATASIZE": 0x36, "CALLDATACOPY": 0x37,
        "CODESIZE": 0x38, "CODECOPY": 0x39, "POP": 0x50, "MLOAD": 0x51,
        "MSTORE": 0x52, "MSTORE8": 0x53, "SLOAD": 0x54, "SSTORE": 0x55,
        "JUMP": 0x56, "JUMPI": 0x57, "PC": 0x58, "MSIZE": 0x59,
        "GAS": 0x5A, "JUMPDEST": 0x5B, "RETURN": 0xF3, "REVERT": 0xFD,
        "INVALID": 0xFE,
    }
    return bytes(TBL[n] for n in names)


def dup(n):
    return bytes([0x80 + n - 1])


def swap(n):
    return bytes([0x90 + n - 1])


def store0(code):  # append: SSTORE result (on stack) to slot 0, STOP
    return code + push(0) + op("SSTORE", "STOP")


# --- vector builders ------------------------------------------------------

TESTS = {}


def binop(name, opname, a, b, expect):
    # stack order: op pops top as first operand -> push b, push a, OP
    TESTS[name] = {
        "exec": {"code": (push(b) + push(a) + op(opname) + push(0)
                          + op("SSTORE", "STOP")).hex()},
        "expect": {"storage": {"0x00": hex(expect & M)}},
    }


def triop(name, opname, a, b, c, expect):
    TESTS[name] = {
        "exec": {"code": (push(c) + push(b) + push(a) + op(opname) + push(0)
                          + op("SSTORE", "STOP")).hex()},
        "expect": {"storage": {"0x00": hex(expect & M)}},
    }


# arithmetic (expected values: direct Python-int formulas)
binop("add_simple", "ADD", 3, 4, 3 + 4)
binop("add_wrap", "ADD", M, 2, (M + 2) & M)
binop("sub_simple", "SUB", 10, 4, 10 - 4)
binop("sub_underflow", "SUB", 0, 1, (0 - 1) & M)
binop("mul_simple", "MUL", 7, 8, 7 * 8)
binop("mul_wrap", "MUL", 1 << 128, 1 << 128, ((1 << 128) ** 2) & M)
binop("div_simple", "DIV", 100, 7, 100 // 7)
binop("div_by_zero", "DIV", 5, 0, 0)
binop("sdiv_neg", "SDIV", neg(6), 2, neg(3))
binop("sdiv_both_neg", "SDIV", neg(6), neg(2), 3)
binop("sdiv_minint_by_neg1", "SDIV", 1 << 255, M, 1 << 255)
binop("sdiv_by_zero", "SDIV", neg(5), 0, 0)
binop("mod_simple", "MOD", 100, 7, 100 % 7)
binop("mod_by_zero", "MOD", 5, 0, 0)
binop("smod_neg_dividend", "SMOD", neg(8), 3, neg(2))
binop("smod_neg_divisor", "SMOD", 8, neg(3), 2)
triop("addmod_wide", "ADDMOD", M, M, 12, ((M % 12) + (M % 12)) % 12)
triop("addmod_mod_zero", "ADDMOD", 4, 5, 0, 0)
triop("mulmod_wide", "MULMOD", M, M, 12, ((M % 12) * (M % 12)) % 12)
triop("mulmod_mod_one", "MULMOD", 39, 41, 1, 0)
binop("exp_simple", "EXP", 2, 10, 2 ** 10)
binop("exp_large", "EXP", 3, 200, pow(3, 200, 1 << 256))
binop("exp_zero_exponent", "EXP", 7, 0, 1)
binop("exp_zero_base", "EXP", 0, 0, 1)  # 0**0 == 1 in the EVM
binop("signextend_byte0_neg", "SIGNEXTEND", 0, 0xFF, M)
binop("signextend_byte0_pos", "SIGNEXTEND", 0, 0x7F, 0x7F)
binop("signextend_byte1", "SIGNEXTEND", 1, 0x8123, (0x8123 | (M ^ 0xFFFF)))
binop("signextend_idx31_identity", "SIGNEXTEND", 31, 0xDEAD, 0xDEAD)
binop("signextend_idx_big", "SIGNEXTEND", 64, 0xBEEF, 0xBEEF)

# comparisons
binop("lt_true", "LT", 1, 2, 1)
binop("lt_false_eq", "LT", 2, 2, 0)
binop("gt_true", "GT", 5, 2, 1)
binop("slt_neg_lt_zero", "SLT", neg(1), 0, 1)
binop("sgt_neg_gt_zero", "SGT", neg(1), 0, 0)
binop("sgt_pos_gt_neg", "SGT", 1, neg(1), 1)
binop("eq_true", "EQ", 42, 42, 1)
binop("eq_false", "EQ", 42, 43, 0)

# bitwise
binop("and_mask", "AND", 0xFF00FF, 0x0F0F0F, 0xFF00FF & 0x0F0F0F)
binop("or_mask", "OR", 0xF0, 0x0F, 0xFF)
binop("xor_self", "XOR", 0xABCDEF, 0xABCDEF, 0)
binop("byte_top", "BYTE", 0, 0xAB << 248, 0xAB)
binop("byte_last", "BYTE", 31, 0x12345, 0x45)
binop("byte_oob", "BYTE", 32, M, 0)
binop("shl_one", "SHL", 1, 1, 2)
binop("shl_overflow", "SHL", 256, 1, 0)
binop("shl_edge255", "SHL", 255, 3, (3 << 255) & M)
binop("shr_one", "SHR", 1, 4, 2)
binop("shr_big", "SHR", 256, M, 0)
binop("sar_neg", "SAR", 4, neg(16), M)  # -16 >> 4 == -1
binop("sar_pos", "SAR", 4, 16, 1)
binop("sar_big_neg", "SAR", 300, 1 << 255, M)

TESTS["iszero_zero"] = {
    "exec": {"code": (push(0) + op("ISZERO") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": "0x1"}},
}
TESTS["not_zero"] = {
    "exec": {"code": (push(0) + op("NOT") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(M)}},
}

# keccak (well-known digest literals, NOT computed here)
KECCAK_EMPTY = 0xC5D2460186F7233C927E7DB2DCC703C0E500B653CA82273B7BFAD8045D85A470
KECCAK_32ZERO = 0x290DECD9548B62A8D60345A988386FC84BA6BC95484008F6362F93160EF3E563
TESTS["sha3_empty"] = {
    "exec": {"code": (push(0) + push(0) + op("SHA3") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(KECCAK_EMPTY)}},
}
TESTS["sha3_32_zero_bytes"] = {
    "exec": {"code": (push(32) + push(0) + op("SHA3") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(KECCAK_32ZERO)}},
}

# memory
TESTS["mstore_mload_roundtrip"] = {
    "exec": {"code": (push(0xDEADBEEF) + push(64) + op("MSTORE")
                      + push(64) + op("MLOAD") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(0xDEADBEEF)}},
}
TESTS["mstore8_writes_one_byte"] = {
    # MSTORE8 0xfffe at offset 31 keeps only the low byte (0xfe) -> the
    # word at 0 reads as 0xfe in its least significant byte
    "exec": {"code": (push(0xFFFE) + push(31) + op("MSTORE8")
                      + push(0) + op("MLOAD") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(0xFE)}},
}
TESTS["msize_after_mstore"] = {
    "exec": {"code": (push(1) + push(64) + op("MSTORE") + op("MSIZE")
                      + push(0) + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(96)}},
}
TESTS["mload_cold_is_zero"] = {
    "exec": {"code": (push(128) + op("MLOAD") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": "0x0"}},
}

# control flow — offsets computed from the emitted byte layout
_jump_code = push(4, 1) + op("JUMP") + op("INVALID") + op("JUMPDEST") \
    + push(1) + push(0) + op("SSTORE", "STOP")
assert _jump_code[4] == 0x5B  # JUMPDEST really is at offset 4
TESTS["jump_over_invalid"] = {
    "exec": {"code": _jump_code.hex()},
    "expect": {"storage": {"0x00": "0x1"}},
}
_jumpi_taken = push(1, 1) + push(6, 1) + op("JUMPI") + op("INVALID") \
    + op("JUMPDEST") + push(1) + push(0) + op("SSTORE", "STOP")
assert _jumpi_taken[6] == 0x5B
TESTS["jumpi_taken"] = {
    "exec": {"code": _jumpi_taken.hex()},
    "expect": {"storage": {"0x00": "0x1"}},
}
_jumpi_not = push(0, 1) + push(8, 1) + op("JUMPI") + push(2) + push(0) \
    + op("SSTORE", "STOP") + op("JUMPDEST", "INVALID")
TESTS["jumpi_not_taken"] = {
    "exec": {"code": _jumpi_not.hex()},
    "expect": {"storage": {"0x00": "0x2"}},
}
TESTS["pc_value"] = {
    # PUSH1 0 (2 bytes) POP, then PC at offset 3 pushes 3
    "exec": {"code": (push(0, 1) + op("POP", "PC") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": "0x3"}},
}

# stack ops
TESTS["dup2_swap1"] = {
    # [7, 9] -> DUP2 -> [7, 9, 7] -> ADD -> [7, 16] -> SWAP1 -> [16, 7]
    # -> SUB -> 7 - 16 = -9
    "exec": {"code": (push(7) + push(9) + dup(2) + op("ADD") + swap(1)
                      + op("SUB") + push(0) + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(neg(9))}},
}
TESTS["pop_discards"] = {
    "exec": {"code": (push(1) + push(2) + op("POP") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": "0x1"}},
}

# calldata
TESTS["calldataload_word"] = {
    "exec": {
        "code": (push(2) + op("CALLDATALOAD") + push(0)
                 + op("SSTORE", "STOP")).hex(),
        "data": "00" * 2 + "11" * 32,
    },
    "expect": {"storage": {"0x00": "0x" + "11" * 32}},
}
TESTS["calldataload_past_end_zero_fill"] = {
    "exec": {
        "code": (push(4) + op("CALLDATALOAD") + push(0)
                 + op("SSTORE", "STOP")).hex(),
        "data": "0000000012345678",  # bytes 4..7 then zeros
    },
    "expect": {"storage": {"0x00": hex(0x12345678 << (28 * 8))}},
}
TESTS["calldatasize"] = {
    "exec": {
        "code": (op("CALLDATASIZE") + push(0) + op("SSTORE", "STOP")).hex(),
        "data": "aa" * 9,
    },
    "expect": {"storage": {"0x00": "0x9"}},
}
TESTS["calldatacopy_then_mload"] = {
    "exec": {
        "code": (push(4, 1) + push(0, 1) + push(0, 1)
                 + op("CALLDATACOPY") + push(0) + op("MLOAD") + push(0)
                 + op("SSTORE", "STOP")).hex(),
        "data": "c0fefe11",
    },
    "expect": {"storage": {"0x00": hex(0xC0FEFE11 << (28 * 8))}},
}

# code introspection
_codesize_code = op("CODESIZE") + push(0) + op("SSTORE", "STOP")
TESTS["codesize"] = {
    "exec": {"code": _codesize_code.hex()},
    "expect": {"storage": {"0x00": hex(len(_codesize_code))}},
}
_codecopy_code = push(2, 1) + push(0, 1) + push(0, 1) + op("CODECOPY") \
    + push(0) + op("MLOAD") + push(0) + op("SSTORE", "STOP")
TESTS["codecopy_first_bytes"] = {
    # copies its own first 2 bytes (0x60 0x02) into memory word 0
    "exec": {"code": _codecopy_code.hex()},
    "expect": {"storage": {"0x00": hex(0x6002 << (30 * 8))}},
}

# storage
TESTS["sstore_sload_roundtrip"] = {
    "exec": {"code": (push(0x77) + push(5) + op("SSTORE") + push(5)
                      + op("SLOAD") + push(1) + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x05": "0x77", "0x01": "0x77"}},
}
TESTS["sload_cold_is_zero"] = {
    "exec": {"code": (push(9) + op("SLOAD") + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": "0x0"}},
}
TESTS["sstore_overwrite"] = {
    "exec": {"code": (push(1) + push(0) + op("SSTORE") + push(2) + push(0)
                      + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": "0x2"}},
}

# return data
TESTS["return_word"] = {
    "exec": {"code": (push(0xCAFE) + push(0) + op("MSTORE") + push(32)
                      + push(0) + op("RETURN")).hex()},
    "expect": {"out": "00" * 30 + "cafe"},
}
TESTS["revert_flags_and_returns"] = {
    "exec": {"code": (push(0xBAD) + push(0) + op("MSTORE") + push(32)
                      + push(0) + op("REVERT")).hex()},
    "expect": {"out": "00" * 30 + "0bad", "reverted": True},
}

# gas accounting via the GAS opcode (deterministic: concrete lanes have
# min == max). gas_limit is fixed by the runner at 100000.
GL = 100_000
TESTS["gas_after_pushes"] = {
    # PUSH1(3) + PUSH1(3) + ADD(3) + GAS(2) = 11 used when GAS executes
    "exec": {"code": (push(1, 1) + push(2, 1) + op("ADD", "GAS") + swap(1)
                      + op("POP") + push(0) + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(GL - 11)}},
}
TESTS["gas_after_mstore_expansion"] = {
    # PUSH1(3) PUSH1(3) MSTORE(3 + 3-word expansion 3*3+9*9//512=9) GAS(2)
    # offset 64 -> words 3 -> expansion cost 3*3 + 9//512 = 9
    "exec": {"code": (push(1, 1) + push(64, 1) + op("MSTORE", "GAS")
                      + push(0) + op("SSTORE", "STOP")).hex()},
    "expect": {"storage": {"0x00": hex(GL - (3 + 3 + 3 + 9 + 2))}},
}

# exceptional halts
TESTS["invalid_op_errors"] = {
    "exec": {"code": op("INVALID").hex()},
    "expect": {"error": True},
}
TESTS["bad_jump_errors"] = {
    "exec": {"code": (push(3, 1) + op("JUMP", "STOP")).hex()},
    "expect": {"error": True},
}
TESTS["stack_underflow_errors"] = {
    "exec": {"code": op("ADD").hex()},
    "expect": {"error": True},
}


def main():
    out = os.path.join(os.path.dirname(__file__), "vmtests.json")
    with open(out, "w") as fh:
        json.dump({"gasLimit": GL, "tests": TESTS}, fh, indent=1, sort_keys=True)
    print(f"wrote {len(TESTS)} vectors to {out}")


if __name__ == "__main__":
    main()
