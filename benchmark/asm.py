"""What the corpus generators need to author bytecode without the
program under test: a two-pass assembler for the opcodes the
corpora use, and RIPEMD-160 (the expected word of the ``precompile_gate``
pair is computed here, not by the analyzer's own native).

Token forms of :func:`assemble`: ``"ADD"`` (opcode by name), ``int``
(PUSH of the minimal width; 0 is ``PUSH1 0``), ``("pushN", value)``,
``("label", name)`` (a JUMPDEST) and ``("ref", name)`` (``PUSH2`` of the
label's offset).
"""

from __future__ import annotations

import struct

OPCODES = {
    "STOP": 0x00, "ADD": 0x01, "MUL": 0x02, "SUB": 0x03, "DIV": 0x04,
    "MOD": 0x06, "EXP": 0x0A,
    "LT": 0x10, "GT": 0x11, "EQ": 0x14, "ISZERO": 0x15, "AND": 0x16,
    "OR": 0x17, "XOR": 0x18, "NOT": 0x19, "SHL": 0x1B, "SHR": 0x1C,
    "SHA3": 0x20, "ADDRESS": 0x30, "BALANCE": 0x31, "ORIGIN": 0x32,
    "CALLER": 0x33, "CALLVALUE": 0x34, "CALLDATALOAD": 0x35,
    "CALLDATASIZE": 0x36, "TIMESTAMP": 0x42, "NUMBER": 0x43, "SELFBALANCE": 0x47, "POP": 0x50, "MLOAD": 0x51,
    "MSTORE": 0x52, "SLOAD": 0x54, "SSTORE": 0x55, "JUMP": 0x56,
    "JUMPI": 0x57, "GAS": 0x5A, "JUMPDEST": 0x5B,
    "LOG1": 0xA1, "LOG2": 0xA2, "LOG3": 0xA3, "CALL": 0xF1, "RETURN": 0xF3,
    "REVERT": 0xFD, "INVALID": 0xFE, "SELFDESTRUCT": 0xFF,
    **{f"DUP{i}": 0x7F + i for i in range(1, 17)},
    **{f"SWAP{i}": 0x8F + i for i in range(1, 17)},
}


def assemble(*tokens) -> bytes:
    out = bytearray()
    labels: dict = {}
    refs: list = []
    for t in tokens:
        if isinstance(t, str):
            out.append(OPCODES[t.upper()])
        elif isinstance(t, int):
            width = max(1, (t.bit_length() + 7) // 8)
            if t < 0 or width > 32:
                raise ValueError(f"push value out of range: {t!r}")
            out.append(0x5F + width)
            out += t.to_bytes(width, "big")
        elif t[0] == "label":
            labels[t[1]] = len(out)
            out.append(OPCODES["JUMPDEST"])
        elif t[0] == "ref":
            out.append(0x61)
            refs.append((len(out), t[1]))
            out += b"\x00\x00"
        elif t[0].lower().startswith("push"):
            width = int(t[0][4:])
            out.append(0x5F + width)
            out += int(t[1]).to_bytes(width, "big")
        else:
            raise ValueError(f"bad token: {t!r}")
    for off, name in refs:
        out[off:off + 2] = labels[name].to_bytes(2, "big")
    return bytes(out)


# --- RIPEMD-160 (Dobbertin, Bosselaers, Preneel 1996) ----------------------

_RL = (list(range(16))
       + [7, 4, 13, 1, 10, 6, 15, 3, 12, 0, 9, 5, 2, 14, 11, 8]
       + [3, 10, 14, 4, 9, 15, 8, 1, 2, 7, 0, 6, 13, 11, 5, 12]
       + [1, 9, 11, 10, 0, 8, 12, 4, 13, 3, 7, 15, 14, 5, 6, 2]
       + [4, 0, 5, 9, 7, 12, 2, 10, 14, 1, 3, 8, 11, 6, 15, 13])
_RR = ([5, 14, 7, 0, 9, 2, 11, 4, 13, 6, 15, 8, 1, 10, 3, 12]
       + [6, 11, 3, 7, 0, 13, 5, 10, 14, 15, 8, 12, 4, 9, 1, 2]
       + [15, 5, 1, 3, 7, 14, 6, 9, 11, 8, 12, 2, 10, 0, 4, 13]
       + [8, 6, 4, 1, 3, 11, 15, 0, 5, 12, 2, 13, 9, 7, 10, 14]
       + [12, 15, 10, 4, 1, 5, 8, 7, 6, 2, 13, 14, 0, 3, 9, 11])
_SL = ([11, 14, 15, 12, 5, 8, 7, 9, 11, 13, 14, 15, 6, 7, 9, 8]
       + [7, 6, 8, 13, 11, 9, 7, 15, 7, 12, 15, 9, 11, 7, 13, 12]
       + [11, 13, 6, 7, 14, 9, 13, 15, 14, 8, 13, 6, 5, 12, 7, 5]
       + [11, 12, 14, 15, 14, 15, 9, 8, 9, 14, 5, 6, 8, 6, 5, 12]
       + [9, 15, 5, 11, 6, 8, 13, 12, 5, 12, 13, 14, 11, 8, 5, 6])
_SR = ([8, 9, 9, 11, 13, 15, 15, 5, 7, 7, 8, 11, 14, 14, 12, 6]
       + [9, 13, 15, 7, 12, 8, 9, 11, 7, 7, 12, 7, 6, 15, 13, 11]
       + [9, 7, 15, 11, 8, 6, 6, 14, 12, 13, 5, 14, 13, 13, 7, 5]
       + [15, 5, 8, 11, 14, 14, 6, 14, 6, 9, 12, 9, 12, 5, 15, 8]
       + [8, 5, 12, 9, 12, 5, 14, 6, 8, 13, 6, 5, 15, 13, 11, 11])
_KL = (0x00000000, 0x5A827999, 0x6ED9EBA1, 0x8F1BBCDC, 0xA953FD4E)
_KR = (0x50A28BE6, 0x5C4DD124, 0x6D703EF3, 0x7A6D76E9, 0x00000000)
_M = 0xFFFFFFFF


def _f(j: int, x: int, y: int, z: int) -> int:
    if j < 16:
        return x ^ y ^ z
    if j < 32:
        return (x & y) | (~x & _M & z)
    if j < 48:
        return (x | (~y & _M)) ^ z
    if j < 64:
        return (x & z) | (y & ~z & _M)
    return x ^ (y | (~z & _M))


def _rol(x: int, n: int) -> int:
    return ((x << n) | (x >> (32 - n))) & _M


def ripemd160(data: bytes) -> bytes:
    h = [0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0]
    msg = data + b"\x80" + b"\x00" * ((55 - len(data)) % 64) \
        + struct.pack("<Q", 8 * len(data))
    for off in range(0, len(msg), 64):
        x = struct.unpack("<16I", msg[off:off + 64])
        al, bl, cl, dl, el = h
        ar, br, cr, dr, er = h
        for j in range(80):
            t = (_rol((al + _f(j, bl, cl, dl) + x[_RL[j]] + _KL[j // 16])
                      & _M, _SL[j]) + el) & _M
            al, el, dl, cl, bl = el, dl, _rol(cl, 10), bl, t
            t = (_rol((ar + _f(79 - j, br, cr, dr) + x[_RR[j]]
                       + _KR[j // 16]) & _M, _SR[j]) + er) & _M
            ar, er, dr, cr, br = er, dr, _rol(cr, 10), br, t
        t = (h[1] + cl + dr) & _M
        h[1] = (h[2] + dl + er) & _M
        h[2] = (h[3] + el + ar) & _M
        h[3] = (h[4] + al + br) & _M
        h[4] = (h[0] + bl + cr) & _M
        h[0] = t
    return struct.pack("<5I", *h)
