"""Precompile dispatch 0x1-0x9.

Reference: ``mythril/laser/ethereum/natives.py`` + the dispatch in
``call.py`` (⚠unv). sha256/identity/modexp compute on device; ecrecover
is an uninterpreted leaf; the rest havoc soundly.
"""

import hashlib

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.core.frontier import ACCT_CONTRACT0
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.ops import u256
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

L = TEST_LIMITS


def run_one(code, n_lanes=4, max_steps=128):
    img = ContractImage.from_bytecode(code, L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(n_lanes, L, active=active)
    env = make_env(n_lanes)
    return sym_run(sf, env, corpus, SymSpec(), L, max_steps=max_steps)


def storage_map(sf, lane=0):
    out = {}
    used = np.asarray(sf.base.st_used)
    keys = np.asarray(sf.base.st_keys)
    vals = np.asarray(sf.base.st_vals)
    for k in range(used.shape[1]):
        if used[lane, k]:
            out[u256.to_int(keys[lane, k])] = u256.to_int(vals[lane, k])
    return out


def sym_storage_map(sf, lane=0):
    out = {}
    used = np.asarray(sf.base.st_used)
    keys = np.asarray(sf.base.st_keys)
    syms = np.asarray(sf.st_val_sym)
    for k in range(used.shape[1]):
        if used[lane, k]:
            out[u256.to_int(keys[lane, k])] = int(syms[lane, k])
    return out


def call_pre(addr, args=(0, 0), ret=(0, 32)):
    """Push CALL to precompile `addr`: gas,to,value,aOff,aLen,rOff,rLen."""
    return [ret[1], ret[0], args[1], args[0], 0, addr, ("push2", 0xFFFF), "CALL"]


def test_sha256_concrete():
    # sha256 of the 32-byte word 0x...2a stored at memory 0
    code = assemble(
        42, 0, "MSTORE",
        *call_pre(2, args=(0, 32), ret=(32, 32)),
        1, "SSTORE",            # success flag
        32, "MLOAD", 2, "SSTORE", "STOP",
    )
    out = run_one(code)
    st = storage_map(out)
    assert st[1] == 1
    expected = int.from_bytes(
        hashlib.sha256((42).to_bytes(32, "big")).digest(), "big")
    assert st[2] == expected


def test_identity_copies_bytes():
    code = assemble(
        0x1234, 0, "MSTORE",
        *call_pre(4, args=(0, 32), ret=(64, 32)),
        "POP", 64, "MLOAD", 1, "SSTORE", "STOP",
    )
    out = run_one(code)
    assert storage_map(out)[1] == 0x1234


def test_modexp_small_operands():
    # 3 ** 5 mod 100 = 43; header lengths 32/32/32, operands at 96/128/160
    code = assemble(
        32, 0, "MSTORE", 32, 32, "MSTORE", 32, 64, "MSTORE",
        3, 96, "MSTORE", 5, 128, "MSTORE", 100, 160, "MSTORE",
        *call_pre(5, args=(0, 192), ret=(192, 32)),
        "POP", ("push1", 192), "MLOAD", 1, "SSTORE", "STOP",
    )
    out = run_one(code, max_steps=128)
    assert storage_map(out)[1] == 43


def test_ecrecover_symbolic_input_is_leaf():
    # SYMBOLIC signature bytes: the result must be an uninterpreted leaf
    # (round 4 computes CONCRETE inputs for real — see the vector test)
    code = assemble(
        0, "CALLDATALOAD", 0, "MSTORE",   # symbolic word into the window
        *call_pre(1, args=(0, 128), ret=(0, 32)),
        "POP", 0, "MLOAD", 1, "SSTORE", "STOP",
    )
    out = run_one(code)
    sym = sym_storage_map(out)
    assert sym[1] != 0, "ecrecover result must be an uninterpreted leaf"


def test_ecrecover_concrete_invalid_returns_empty():
    # all-zero signature: the precompile returns EMPTY output; the
    # output word stays concrete zero
    code = assemble(
        *call_pre(1, args=(0, 128), ret=(0, 32)),
        "POP", 0, "MLOAD", 1, "SSTORE", "STOP",
    )
    out = run_one(code)
    assert storage_map(out)[1] == 0
    assert sym_storage_map(out)[1] == 0, "invalid recovery must be concrete"


# the canonical ethereum/tests CallEcrecover0 vector
_ECR_HASH = 0x456E9AEA5E197A1F1AF7A3E85A3212FA4049A3BA34C2289B4C860FC0B0C64EF3
_ECR_V = 28
_ECR_R = 0x9242685BF161793CC25603C231BC2F568EB630EA16AA137D2664AC8038825608
_ECR_S = 0x4F8AE3BD7535248D0BD448298CC2E2071E56992D0774DC340C368AE950852ADA
_ECR_ADDR = 0x7156526FBD7A3C72969B54F64E42C10FBB768C8A


def test_ecrecover_host_vector():
    from mythril_tpu.ops.secp256k1 import ecrecover

    assert ecrecover(_ECR_HASH, _ECR_V, _ECR_R, _ECR_S) == _ECR_ADDR
    assert ecrecover(_ECR_HASH, 29, _ECR_R, _ECR_S) is None
    assert ecrecover(_ECR_HASH, _ECR_V, 0, _ECR_S) is None


def test_ecrecover_concrete_vector_on_device():
    # the engine's concrete path recovers the signer address end-to-end
    code = assemble(
        ("push32", _ECR_HASH), 0, "MSTORE",
        _ECR_V, 32, "MSTORE",
        ("push32", _ECR_R), 64, "MSTORE",
        ("push32", _ECR_S), 96, "MSTORE",
        *call_pre(1, args=(0, 128), ret=(128, 32)),
        1, "SSTORE",
        ("push1", 128), "MLOAD", 2, "SSTORE", "STOP",
    )
    out = run_one(code)
    st = storage_map(out)
    assert st[1] == 1
    assert st[2] == _ECR_ADDR, hex(st.get(2, 0))


def test_ripemd_symbolic_input_havocs():
    # 0x3 with SYMBOLIC input bytes: success=1, result unconstrained —
    # the branch on the output must explore both sides (concrete inputs
    # compute for real below)
    code = assemble(
        0, "CALLDATALOAD", 0, "MSTORE",
        *call_pre(3, args=(0, 32), ret=(0, 32)),
        "POP", 0, "MLOAD", ("ref", "nz"), "JUMPI",
        1, 0, "SSTORE", "STOP",
        ("label", "nz"), 2, 0, "SSTORE", "STOP",
    )
    out = run_one(code, n_lanes=8)
    act = np.asarray(out.base.active)
    vals = {storage_map(out, i).get(0) for i in range(act.shape[0]) if act[i]}
    assert vals == {1, 2}


# --- round-4: the remaining natives compute concretely (ripemd160,
# alt_bn128 add/mul/pairing, blake2f) -------------------------------------


def test_blake2_f_matches_hashlib():
    # full BLAKE2b rebuilt on our F == hashlib.blake2b — external oracle
    # for the compression function the precompile exposes
    from mythril_tpu.ops.blake2 import blake2b_hash

    for msg in (b"", b"abc", b"a" * 128, b"xyz" * 100, bytes(range(129))):
        assert blake2b_hash(msg) == hashlib.blake2b(msg).digest(), msg


def test_blake2f_precompile_bytes():
    from mythril_tpu.ops.blake2 import IV, blake2f_precompile

    # single-block blake2b("abc") expressed as one F call (the EIP-152
    # vector-5 shape): h = param-tweaked IV, m = "abc" padded, t = 3
    h = list(IV)
    h[0] ^= 0x01010040
    inp = (
        (12).to_bytes(4, "big")
        + b"".join(x.to_bytes(8, "little") for x in h)
        + b"abc".ljust(128, b"\x00")
        + (3).to_bytes(8, "little") + (0).to_bytes(8, "little")
        + b"\x01"
    )
    assert blake2f_precompile(inp) == hashlib.blake2b(b"abc").digest()
    assert blake2f_precompile(inp[:-1]) is None          # bad length
    assert blake2f_precompile(inp[:-1] + b"\x02") is None  # bad final flag


def test_bn128_module():
    from mythril_tpu.ops import bn128 as bn

    assert bn.on_curve_g1(bn.G1)
    assert bn.on_curve_g2(bn.G2)
    # external anchor: the standard generators have the standard order
    assert bn._pt_mul(bn.G1, bn.CURVE_ORDER) is None
    assert bn.in_g2_subgroup(bn.G2)
    assert bn._pt_add(bn.G1, bn.G1) == bn._pt_mul(bn.G1, 2)
    # byte-level add/mul agree with the group law
    g1b = bn._write_g1(bn.G1)
    assert bn.ecadd(g1b + g1b) == bn._write_g1(bn._pt_mul(bn.G1, 2))
    assert bn.ecmul(g1b + (5).to_bytes(32, "big")) == bn._write_g1(
        bn._pt_mul(bn.G1, 5))
    # invalid points fail
    assert bn.ecadd(b"\x00" * 31 + b"\x01" + b"\x00" * 31 + b"\x01"
                    + b"\x00" * 64) is None
    assert bn.ecmul(bytes(32) + (1).to_bytes(32, "big")
                    + (1).to_bytes(32, "big")) is None


def test_bn128_pairing_bilinear():
    from mythril_tpu.ops import bn128 as bn

    e1 = bn.pairing(bn.G1, bn.G2)
    assert e1 != bn.Fq12.one(), "pairing must be non-degenerate"
    e2 = bn.pairing(bn._pt_mul(bn.G1, 2), bn.G2)
    assert e2 == e1 * e1, "bilinearity in the G1 slot"
    # the product-check shape the precompile actually runs
    assert bn.pairing_check([(bn.G1, bn.G2), (bn._pt_neg(bn.G1), bn.G2)])
    assert not bn.pairing_check([(bn.G1, bn.G2), (bn.G1, bn.G2)])


def _g2_calldata(pt) -> bytes:
    x, y = pt
    return (x.c1.to_bytes(32, "big") + x.c0.to_bytes(32, "big")
            + y.c1.to_bytes(32, "big") + y.c0.to_bytes(32, "big"))


def _mstore_words(data: bytes, base: int = 0):
    """Assembler ops writing `data` to memory word-by-word from `base`."""
    ops = []
    for i in range(0, len(data), 32):
        w = int.from_bytes(data[i:i + 32].ljust(32, b"\x00"), "big")
        ops += [("push32", w), base + i, "MSTORE"]
    return ops


def test_ripemd_concrete_on_device():
    code = assemble(
        42, 0, "MSTORE",
        *call_pre(3, args=(0, 32), ret=(32, 32)),
        1, "SSTORE",
        32, "MLOAD", 2, "SSTORE", "STOP",
    )
    out = run_one(code)
    st = storage_map(out)
    assert st[1] == 1
    digest = hashlib.new("ripemd160", (42).to_bytes(32, "big")).digest()
    assert st[2] == int.from_bytes(digest, "big")
    assert sym_storage_map(out)[2] == 0, "concrete result must stay concrete"


def test_bn128_add_concrete_on_device():
    from mythril_tpu.ops import bn128 as bn

    g1b = bn._write_g1(bn.G1)
    expected = bn.ecadd(g1b + g1b)
    code = assemble(
        *_mstore_words(g1b + g1b),
        *call_pre(6, args=(0, 128), ret=(128, 64)),
        1, "SSTORE",
        ("push1", 128), "MLOAD", 2, "SSTORE",
        ("push1", 160), "MLOAD", 3, "SSTORE", "STOP",
    )
    out = run_one(code, max_steps=128)
    st = storage_map(out)
    assert st[1] == 1
    assert st[2] == int.from_bytes(expected[:32], "big")
    assert st[3] == int.from_bytes(expected[32:], "big")


def test_bn128_invalid_point_fails_call():
    # (1, 1) is not on the curve: the CALL itself must fail (success=0,
    # empty returndata) — the one precompile-failure channel the EVM has
    code = assemble(
        1, 0, "MSTORE", 1, 32, "MSTORE",
        *call_pre(6, args=(0, 128), ret=(128, 64)),
        1, "SSTORE",
        "RETURNDATASIZE", 2, "SSTORE",
        ("push1", 128), "MLOAD", 3, "SSTORE", "STOP",
    )
    out = run_one(code, max_steps=128)
    st = storage_map(out)
    assert st[1] == 0, "invalid input must fail the precompile call"
    assert st[2] == 0 and st[3] == 0


def test_bn128_pairing_concrete_on_device():
    from mythril_tpu.ops import bn128 as bn

    g1b = bn._write_g1(bn.G1)
    neg = bn._write_g1(bn._pt_neg(bn.G1))
    g2b = _g2_calldata(bn.G2)
    inp = g1b + g2b + neg + g2b  # e(P,Q) * e(-P,Q) == 1
    code = assemble(
        *_mstore_words(inp),
        *call_pre(8, args=(0, len(inp)), ret=(384, 32)),
        1, "SSTORE",
        ("push2", 384), "MLOAD", 2, "SSTORE", "STOP",
    )
    out = run_one(code, max_steps=160)
    st = storage_map(out)
    assert st[1] == 1
    assert st[2] == 1, "pairing product must verify"


def test_blake2f_concrete_on_device():
    from mythril_tpu.ops.blake2 import IV, blake2f_precompile

    h = list(IV)
    h[0] ^= 0x01010040
    inp = (
        (12).to_bytes(4, "big")
        + b"".join(x.to_bytes(8, "little") for x in h)
        + b"abc".ljust(128, b"\x00")
        + (3).to_bytes(8, "little") + (0).to_bytes(8, "little")
        + b"\x01"
    )
    expected = blake2f_precompile(inp)
    code = assemble(
        *_mstore_words(inp),  # trailing pad bytes beyond 213 are ignored
        *call_pre(9, args=(0, 213), ret=(224, 64)),
        1, "SSTORE",
        ("push1", 224), "MLOAD", 2, "SSTORE",
        ("push2", 256), "MLOAD", 3, "SSTORE", "STOP",
    )
    out = run_one(code, max_steps=160)
    st = storage_map(out)
    assert st[1] == 1
    assert st[2] == int.from_bytes(expected[:32], "big")
    assert st[3] == int.from_bytes(expected[32:], "big")
