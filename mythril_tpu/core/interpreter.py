"""Vectorized concrete EVM superstep.

Counterpart of the reference's per-opcode ``Instruction.evaluate`` +
``LaserEVM.execute_state`` (``mythril/laser/ethereum/{instructions,svm}.py``
⚠unv, SURVEY.md §3.2), re-designed frontier-first:

- Handlers operate on the WHOLE frontier with a lane mask (no vmap of a
  scalar interpreter): every update is `jnp.where(mask, new, old)`.
- Dispatch is per opcode *class* behind `lax.cond(jnp.any(mask))` — a
  superstep pays only for classes present in the frontier. This matters
  because DIV/EXP/MODARITH are 256-step `fori_loop`s that must not run
  when no lane needs them.
- Stack-arity validation and min/max gas accounting happen once per step
  from dense tables (reference: the ``StateTransition`` decorator).

CALL/CREATE are stubbed at this layer (success push); real sub-transaction
semantics live in the symbolic VM layer above.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..config import LimitsConfig, DEFAULT_LIMITS
from ..disassembler import opcodes as oc
from ..ops import u256
from ..ops.keccak import keccak256_device
from .frontier import Frontier, Env, Corpus, Trap

I64 = jnp.int64
I32 = jnp.int32
U32 = jnp.uint32
U8 = jnp.uint8

# ---------------------------------------------------------------------------
# Opcode classes (dispatch granularity)
# ---------------------------------------------------------------------------

CLS_STACK, CLS_ALU, CLS_MUL, CLS_DIVMOD, CLS_MODARITH, CLS_EXP, CLS_SHA3, CLS_ENV, \
    CLS_COPY, CLS_MEM, CLS_STORAGE, CLS_JUMP, CLS_HALT, CLS_LOG, CLS_CALL, CLS_CREATE = range(16)

N_CLASSES = 16


def _build_class_table() -> np.ndarray:
    t = np.full(256, CLS_HALT, dtype=np.int32)  # invalid opcodes -> filtered by IS_VALID
    def s(codes, cls):
        for c in codes:
            t[c] = cls

    s([0x50, 0x58, 0x59, 0x5A, 0x5B] + list(range(0x5F, 0xA0)), CLS_STACK)  # POP PC MSIZE GAS JUMPDEST PUSH* DUP* SWAP*
    s([0x01, 0x03, 0x10, 0x11, 0x12, 0x13, 0x14, 0x15, 0x16, 0x17, 0x18, 0x19,
       0x0B, 0x1A, 0x1B, 0x1C, 0x1D], CLS_ALU)
    s([0x02], CLS_MUL)
    s([0x04, 0x05, 0x06, 0x07], CLS_DIVMOD)
    s([0x08, 0x09], CLS_MODARITH)
    s([0x0A], CLS_EXP)
    s([0x20], CLS_SHA3)
    s([0x30, 0x31, 0x32, 0x33, 0x34, 0x35, 0x36, 0x38, 0x3A, 0x3B, 0x3D, 0x3F,
       0x40, 0x41, 0x42, 0x43, 0x44, 0x45, 0x46, 0x47, 0x48], CLS_ENV)
    s([0x37, 0x39, 0x3C, 0x3E], CLS_COPY)
    s([0x51, 0x52, 0x53], CLS_MEM)
    s([0x54, 0x55], CLS_STORAGE)
    s([0x56, 0x57], CLS_JUMP)
    s([0x00, 0xF3, 0xFD, 0xFE, 0xFF], CLS_HALT)
    s(list(range(0xA0, 0xA5)), CLS_LOG)
    s([0xF1, 0xF2, 0xF4, 0xFA], CLS_CALL)
    s([0xF0, 0xF5], CLS_CREATE)
    return t


CLASS_TABLE = _build_class_table()

# jnp views of the metadata tables (built once at import)
_J_STACK_IN = jnp.asarray(oc.STACK_IN)
_J_STACK_OUT = jnp.asarray(oc.STACK_OUT)
# every EVM op with sout > 0 rewrites the post-op top of stack; the
# shared writeback lands it at sp - sin + sout - 1 (pre-step sp)
_J_PUSHES = jnp.asarray(oc.STACK_OUT > 0)
_J_D_SP = jnp.asarray(oc.STACK_OUT - oc.STACK_IN)
_J_GAS_MIN = jnp.asarray(oc.GAS_MIN)
_J_GAS_MAX = jnp.asarray(oc.GAS_MAX)
_J_GAS_MIN_BERLIN = jnp.asarray(oc.GAS_MIN_BERLIN)
_J_GAS_MAX_BERLIN = jnp.asarray(oc.GAS_MAX_BERLIN)
_J_PUSH_WIDTH = jnp.asarray(oc.PUSH_WIDTH)
_J_IS_VALID = jnp.asarray(oc.IS_VALID)
_J_CLASS = jnp.asarray(CLASS_TABLE)

# keccak256(b"") — EXTCODEHASH of an existing account without code
_EMPTY_KECCAK_INT = 0xC5D2460186F7233C927E7DB2DCC703C0E500B653CA82273B7BFAD8045D85A470
_J_EMPTY_KECCAK = jnp.asarray(
    [(_EMPTY_KECCAK_INT >> (32 * i)) & 0xFFFFFFFF for i in range(8)],
    dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# Stack helpers (frontier-level)
# ---------------------------------------------------------------------------


def _peek(f: Frontier, i) -> jnp.ndarray:
    """Stack slot i from the top (i static int or i32[P]); u32[P, 8]."""
    idx = jnp.clip(f.sp - 1 - i, 0, f.max_stack - 1)
    return jnp.take_along_axis(f.stack, idx[:, None, None].astype(I32), axis=1)[:, 0]


def _use_scatter() -> bool:
    """Slot-write strategy, resolved once at trace time: XLA:CPU lowers
    per-lane dynamic scatters well and the O(P) index write beats
    touching the whole array; TPU lowers them as serialized updates —
    measured on the SAME chip, the round-3 scatter rewrite took the
    concrete interpreter from 1.05M to 0.149M lane-steps/s (7x). Dense
    one-hot compare-selects keep every write a fusable vector op on TPU.
    The same holds for reads of single elements (the TPU serializes an
    element gather as it does a scatter: PR 37's feasibility sweep, PR
    41's :func:`_gather_bytes`), so this one test also picks between the
    CPU's element gather and the TPU's row reads there.
    To trace the other backend's choice, patch this function (as
    tests/test_write_paths.py and tools/scaling_report.py do)."""
    return jax.default_backend() == "cpu"


def _set_slot(stack, pos, val, mask):
    """stack[P,S,8] with stack[lane, pos[lane]] = val[lane] where mask.
    Lanes with mask off — or pos outside [0, S) — write nowhere."""
    P, S = stack.shape[0], stack.shape[1]
    idx = jnp.where(mask & (pos >= 0), pos, S).astype(I32)
    if _use_scatter():
        return stack.at[jnp.arange(P), idx].set(val, mode="drop")
    sel = jnp.arange(S, dtype=I32)[None, :] == idx[:, None]
    return jnp.where(sel[:, :, None], val[:, None, :], stack)


def _write_slot(arr, widx, val):
    """arr[P, K, ...] with arr[lane, widx[lane]] = val[lane]; widx == K
    (or beyond) writes nowhere. Backend-adaptive like :func:`_set_slot`;
    ``val`` may be scalar, [P], or [P, ...] matching arr's tail dims."""
    P, K = arr.shape[0], arr.shape[1]
    widx = widx.astype(I32)
    if _use_scatter():
        # same explicit dtype cast as the dense path: XLA's implicit
        # unsafe scatter cast is deprecated (FutureWarning today, error
        # in future JAX) and the two formulations must stay equivalent
        return arr.at[jnp.arange(P), widx].set(
            jnp.asarray(val, arr.dtype), mode="drop")
    tail = arr.shape[2:]
    sel = jnp.arange(K, dtype=I32)[None, :] == widx[:, None]
    val = jnp.broadcast_to(jnp.asarray(val, arr.dtype), (P,) + tail)
    return jnp.where(sel.reshape((P, K) + (1,) * len(tail)),
                     jnp.expand_dims(val, 1), arr)


def _hist_add(hist, op, delta):
    """hist[P, 256] += delta[P] at column op[P] (backend-adaptive like
    :func:`_write_slot`; iprof's accumulate and the engine's retry
    netting share this so neither reintroduces a TPU scatter)."""
    if _use_scatter():
        return hist.at[jnp.arange(op.shape[0]), op].add(delta)
    sel = jnp.arange(256, dtype=I32)[None, :] == op[:, None]
    return hist + sel * delta[:, None]


def _word_to_be_bytes(val) -> jnp.ndarray:
    """u256 limbs [P,8] -> big-endian bytes u8[P,32] (byte 0 most significant)."""
    k = jnp.arange(32)
    limb = (31 - k) // 4
    shift = (8 * ((31 - k) % 4)).astype(U32)
    return ((jnp.take(val, limb, axis=-1) >> shift) & U32(0xFF)).astype(U8)


def _be_bytes_to_word(b) -> jnp.ndarray:
    """big-endian bytes u8/u32[P,32] -> u256 limbs u32[P,8]."""
    b = b.astype(U32)
    limb_ids = jnp.arange(8)
    k_base = 28 - 4 * limb_ids  # most-significant byte index per limb
    gather = (k_base[:, None] + jnp.arange(4)[None, :]).reshape(-1)
    bb = jnp.take(b, gather, axis=-1).reshape(b.shape[:-1] + (8, 4))
    w = U32(1) << (U32(8) * (3 - jnp.arange(4)).astype(U32))
    return jnp.sum(bb * w, axis=-1).astype(U32)


_ROW_BYTES = 128  # one lane tile of u8: the row `_gather_bytes` reads on the TPU


def _gather_bytes(buf, start, n_static: int, limit):
    """buf[P, L] bytes; read n_static bytes from per-lane offset start,
    zero-filled past `limit` (per-lane logical length), before 0, past L
    and where ``start + k`` wraps in int64. Returns u8[P, n].

    Backend-adaptive like :func:`_set_slot` (one result, two lowerings).
    The CPU gathers the P x n single bytes. The TPU serializes an element
    gather (~12 ns a byte: seven of these reads were 22-23% of its busy
    time in every cell until PR 41) and reads a row for about the price
    of an element, so there the window is read as the ``ceil(n / 128) + 1``
    rows of 128 bytes that hold it and shifted left by ``start % 128`` in
    seven compare-select stages. A buffer with fewer rows than that (a
    copy's source under the whole memory's window: ``out[:, j] =
    buf[:, j + start]`` with ``start = src - dst``) is not gathered from:
    each row it has is selected into its place among zero rows."""
    n = n_static
    P, L = buf.shape
    W = _ROW_BYTES
    with jax.named_scope("gather_bytes"):
        if _use_scatter():
            idx = start[:, None].astype(I64) + jnp.arange(n, dtype=I64)[None, :]
            safe = jnp.clip(idx, 0, L - 1).astype(I32)
            vals = jnp.take_along_axis(buf, safe, axis=1)
            ok = (idx >= 0) & (idx < limit[:, None].astype(I64)) & (idx < L)
            return jnp.where(ok, vals, 0)
        R = -(-L // W)
        if R * W != L:
            buf = jnp.pad(buf, ((0, 0), (0, R * W - L)))
        K = -(-n // W) + 1
        # a window that starts before -n or past L holds no byte of the
        # buffer (nor does one that wraps): clipping moves no byte of it
        s = jnp.clip(start.astype(I64), -n, L).astype(I32)
        row0 = s // W  # floor: a negative start reads from row -1 (masked)
        rows = row0[:, None] + jnp.arange(K, dtype=I32)[None, :]
        view = buf.reshape(P, R, W)
        if R < K:
            x = jnp.zeros((P, K, W), buf.dtype)
            for r in range(R):
                x = jnp.where((rows == r)[:, :, None], view[:, r, None, :], x)
        else:
            x = jnp.take_along_axis(
                view, jnp.clip(rows, 0, R - 1)[:, :, None], axis=1,
                mode="promise_in_bounds")
        x = x.reshape(P, K * W)
        shift = s - row0 * W  # in [0, W)
        b = W >> 1
        while b:  # x[:, j] <- x[:, j + shift], a bit of `shift` a stage
            x = jnp.where(((shift & b) != 0)[:, None], x[:, b:], x[:, :-b])
            b >>= 1
        idx = s[:, None] + jnp.arange(n, dtype=I32)[None, :]
        end = jnp.clip(limit.astype(I64), 0, L).astype(I32)
        return jnp.where((idx >= 0) & (idx < end[:, None]), x[:, :n], 0)


def _scatter_bytes(memory, start, vals, n_static: int, mask):
    """memory[P,M]; write vals[P,n] at per-lane offset start where mask."""
    P, M = memory.shape
    idx = start[:, None].astype(I64) + jnp.arange(n_static, dtype=I64)[None, :]
    idx = jnp.where(mask[:, None] & (idx >= 0) & (idx < M), idx, M)  # M = dropped
    lanes = jnp.broadcast_to(jnp.arange(P)[:, None], idx.shape)
    return memory.at[lanes, idx.astype(I32)].set(vals, mode="drop")


# ---------------------------------------------------------------------------
# Memory expansion (EVM yellow-paper cost: 3w + w^2/512)
# ---------------------------------------------------------------------------


def _mem_cost(words):
    w = words.astype(I64)
    return 3 * w + (w * w) // 512


def _expand_memory(f: Frontier, mask, end_bytes) -> Tuple[Frontier, jnp.ndarray]:
    """Charge expansion to end_bytes (i64[P]); flags error past the cap.
    Returns (frontier, oob_mask)."""
    M = f.memory.shape[1]
    end = jnp.maximum(end_bytes.astype(I64), 0)
    oob = mask & (end > M)
    words = (jnp.clip(end, 0, M) + 31) // 32
    new_words = jnp.where(mask, jnp.maximum(f.mem_words.astype(I64), words), f.mem_words.astype(I64))
    delta = _mem_cost(new_words) - _mem_cost(f.mem_words.astype(I64))
    return (
        f.replace(
            mem_words=new_words.astype(I32),
            gas_min=f.gas_min + jnp.where(mask, delta, 0),
            gas_max=f.gas_max + jnp.where(mask, delta, 0),
        ).trap(oob, Trap.OOB_MEM),
        oob,
    )


def _charge(f: Frontier, mask, amount) -> Frontier:
    amt = jnp.where(mask, amount.astype(I64), 0)
    return f.replace(gas_min=f.gas_min + amt, gas_max=f.gas_max + amt)


# ---------------------------------------------------------------------------
# Class handlers — each: (f, env, corpus, op, mask, old_pc) -> (f, aux)
#
# Handlers DO NOT write ``stack`` or ``sp``. A value-producing class
# returns its result word in ``aux["r"]`` (u32[P,8]) and the shared
# writeback in ``dispatch`` lands it at ``sp - sin + sout - 1`` once per
# superstep; ``sp`` advances centrally by the STACK_OUT-STACK_IN table.
# This keeps the 16 per-class ``lax.cond`` boundaries free of the [P,S,8]
# stack array — the round-4 profile showed the untaken conds' stack
# copies dominating the superstep. Optional aux keys: ``ok`` (bool[P],
# vetoes the write for lanes that trapped mid-handler) and the SWAP
# second write port ``w2_idx``/``w2_val``/``w2_mask``.
# ---------------------------------------------------------------------------


def _h_stack(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    is_push = (op >= 0x5F) & (op <= 0x7F)
    is_dup = (op >= 0x80) & (op <= 0x8F)
    is_swap = (op >= 0x90) & (op <= 0x9F)

    # PUSH immediate: big-endian `width` bytes following pc
    width = jnp.where(is_push, op.astype(I32) - 0x5F, 0)
    code_row = corpus.code[f.contract_id]  # u8[P, MC]
    code_len = corpus.code_len[f.contract_id]
    raw = _gather_bytes(code_row, old_pc + 1, 32, code_len)  # u8[P,32]
    ei = f.exec_init
    raw_ini = _gather_bytes(f.init_code, old_pc + 1, 32, f.init_len)
    raw = jnp.where(ei[:, None], raw_ini, raw)
    j = jnp.arange(32)
    sig = width[:, None] - 1 - j[None, :]  # byte significance (bytes); <0 = beyond width
    in_range = sig >= 0
    limb_idx = jnp.clip(sig, 0, 255) // 4  # [P,32]
    shift = (8 * (jnp.clip(sig, 0, 255) % 4)).astype(U32)
    contrib = jnp.where(in_range, raw.astype(U32) << shift, 0)
    onehot = limb_idx[:, :, None] == jnp.arange(8)[None, None, :]
    push_val = jnp.sum(jnp.where(onehot, contrib[:, :, None], 0), axis=1).astype(U32)

    dup_n = jnp.where(is_dup, op.astype(I32) - 0x7F, 1)
    dup_val = _peek(f, dup_n - 1)
    pc_val = u256.from_u64_scalar(old_pc.astype(jnp.uint64))
    msize_val = u256.from_u64_scalar((f.mem_words.astype(jnp.uint64)) * 32)
    gas_val = u256.from_u64_scalar(jnp.maximum(f.gas_limit - f.gas_max, 0).astype(jnp.uint64))

    # SWAP n: top goes to slot n below top via the second write port;
    # the slot-(n) value lands at the post-op top (sp-1) via `r` — the
    # shared writeback's sp - sin + sout - 1 is exactly sp-1 for SWAPs.
    swap_n = jnp.where(is_swap, op.astype(I32) - 0x8F, 1)
    top = _peek(f, 0)
    deep = _peek(f, swap_n)

    val = jnp.where(
        is_push[:, None], push_val,
        jnp.where(is_dup[:, None], dup_val,
                  jnp.where((op == 0x58)[:, None], pc_val,
                            jnp.where((op == 0x59)[:, None], msize_val,
                                      jnp.where(is_swap[:, None], deep,
                                                gas_val)))))
    # POP/JUMPDEST have sout == 0, so _J_PUSHES masks their write off
    return f, {
        "r": val,
        "w2_idx": f.sp - 1 - swap_n,
        "w2_val": top,
        "w2_mask": m & is_swap,
    }


def _h_alu(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    a = _peek(f, 0)
    b = _peek(f, 1)

    r = u256.add(a, b)
    r = jnp.where((op == 0x03)[:, None], u256.sub(a, b), r)
    r = jnp.where((op == 0x10)[:, None], u256.bool_to_word(u256.lt(a, b)), r)
    r = jnp.where((op == 0x11)[:, None], u256.bool_to_word(u256.gt(a, b)), r)
    r = jnp.where((op == 0x12)[:, None], u256.bool_to_word(u256.slt(a, b)), r)
    r = jnp.where((op == 0x13)[:, None], u256.bool_to_word(u256.sgt(a, b)), r)
    r = jnp.where((op == 0x14)[:, None], u256.bool_to_word(u256.eq(a, b)), r)
    r = jnp.where((op == 0x15)[:, None], u256.bool_to_word(u256.is_zero(a)), r)
    r = jnp.where((op == 0x16)[:, None], a & b, r)
    r = jnp.where((op == 0x17)[:, None], a | b, r)
    r = jnp.where((op == 0x18)[:, None], a ^ b, r)
    r = jnp.where((op == 0x19)[:, None], ~a, r)
    r = jnp.where((op == 0x0B)[:, None], u256.signextend(a, b), r)
    r = jnp.where((op == 0x1A)[:, None], u256.byte_op(a, b), r)
    r = jnp.where((op == 0x1B)[:, None], u256.shl(a, b), r)
    r = jnp.where((op == 0x1C)[:, None], u256.shr(a, b), r)
    r = jnp.where((op == 0x1D)[:, None], u256.sar(a, b), r)
    return f, {"r": r}


def _h_mul(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    return f, {"r": u256.mul(_peek(f, 0), _peek(f, 1))}


def _h_divmod(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    a, b = _peek(f, 0), _peek(f, 1)
    signed = (op == 0x05) | (op == 0x07)  # SDIV SMOD
    aa, na = u256.abs_signed(a)
    ab, nb = u256.abs_signed(b)
    da = jnp.where(signed[:, None], aa, a)
    db = jnp.where(signed[:, None], ab, b)
    q, rem = u256.divmod_u(da, db)  # one shared 256-step division
    q_signed = jnp.where((na != nb)[:, None], u256.neg(q), q)
    rem_signed = jnp.where(na[:, None], u256.neg(rem), rem)
    bz = u256.is_zero(b)[:, None]
    is_div = (op == 0x04) | (op == 0x05)
    r = jnp.where(
        is_div[:, None],
        jnp.where(signed[:, None], q_signed, q),
        jnp.where(signed[:, None], rem_signed, rem),
    )
    r = jnp.where(bz, 0, r).astype(U32)
    return f, {"r": r}


def _h_modarith(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    a, b, n = _peek(f, 0), _peek(f, 1), _peek(f, 2)
    is_add = op == 0x08
    wide_mul = u256.mul_wide(a, b)  # u32[P,16]
    s, carry = u256.add_carry(a, b)
    wide_add = jnp.concatenate(
        [s, carry.astype(U32)[:, None], jnp.zeros_like(s)[:, :7]], axis=-1
    )
    wide = jnp.where(is_add[:, None], wide_add, wide_mul)
    r = u256._mod_wide(wide, n)
    return f, {"r": r}


def _h_exp(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    base, e = _peek(f, 0), _peek(f, 1)
    r = u256.exp(base, e)
    # dynamic gas: 50 per significant exponent byte
    e_bytes = _word_to_be_bytes(e)
    nz = e_bytes != 0
    first_nz = jnp.argmax(nz, axis=1)  # 0 if none
    any_nz = jnp.any(nz, axis=1)
    n_bytes = jnp.where(any_nz, 32 - first_nz, 0).astype(I64)
    f = _charge(f, m, 50 * n_bytes)
    return f, {"r": r}


MAX_HASH_BYTES = 200  # SHA3 input cap (mapping keys need 64; see LimitsConfig)


def _h_sha3(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    off = u256.to_u64_saturating(_peek(f, 0)).astype(I64)
    ln = u256.to_u64_saturating(_peek(f, 1)).astype(I64)
    H = f.memory.shape[1]  # gather window limited by memory size
    max_hash = min(MAX_HASH_BYTES, H)
    too_long = m & (ln > max_hash)
    f, oob = _expand_memory(f, m & (ln > 0), off + ln)
    ok = m & ~too_long & ~oob
    data = _gather_bytes(f.memory, off, max_hash, jnp.full_like(off, H))
    # zero bytes past ln
    data = jnp.where(jnp.arange(max_hash)[None, :] < ln[:, None], data, 0)
    digest = keccak256_device(data, jnp.clip(ln, 0, max_hash).astype(I32))
    words = (ln + 31) // 32
    f = _charge(f, ok, 6 * words)
    return f.trap(too_long, Trap.HASH_LIMIT), {"r": digest, "ok": ok}


def _h_env(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    a = _peek(f, 0)  # operand for the 1-in ops
    # CODESIZE inside a constructor is the INIT code's size
    code_len = jnp.where(f.exec_init, f.init_len,
                         corpus.code_len[f.contract_id])

    cd_load = _be_bytes_to_word(
        _gather_bytes(f.calldata, u256.to_u64_saturating(a).astype(I64), 32, f.calldata_len)
    )
    self_addr = f.self_address
    # BALANCE / EXTCODESIZE answered from the per-lane account table;
    # unknown addresses read 0 concretely (the symbolic layer havocs them)
    found, slot = f.acct_lookup(a)
    acct_bal = f.acct_field(f.acct_bal, slot)
    balance_val = jnp.where(found[:, None], acct_bal, 0).astype(U32)
    ext_code = f.acct_field(f.acct_code, slot)
    ext_len = jnp.where(
        found & (ext_code >= 0),
        corpus.code_len[jnp.clip(ext_code, 0, corpus.code_len.shape[0] - 1)],
        0,
    )
    extsize = u256.from_u64_scalar(ext_len.astype(jnp.uint64))

    r = self_addr
    r = jnp.where((op == 0x31)[:, None], balance_val, r)
    r = jnp.where((op == 0x32)[:, None], env.origin, r)
    r = jnp.where((op == 0x33)[:, None], f.caller_addr, r)
    r = jnp.where((op == 0x34)[:, None], f.callvalue, r)
    r = jnp.where((op == 0x35)[:, None], cd_load, r)
    r = jnp.where((op == 0x36)[:, None], u256.from_u64_scalar(f.calldata_len.astype(jnp.uint64)), r)
    r = jnp.where((op == 0x38)[:, None], u256.from_u64_scalar(code_len.astype(jnp.uint64)), r)
    r = jnp.where((op == 0x3A)[:, None], env.gasprice, r)
    r = jnp.where((op == 0x3B)[:, None], extsize, r)
    r = jnp.where((op == 0x3D)[:, None], u256.from_u64_scalar(f.returndata_len.astype(jnp.uint64)), r)
    # EXTCODEHASH: corpus accounts answer the precomputed image hash,
    # codeless-but-existing accounts the empty-code hash, missing
    # accounts 0 (EIP-1052). CODE_UNKNOWN (-2) reads 0 concretely — the
    # symbolic layer havocs it (engine: never a wrong concrete value).
    ext_hash = corpus.code_hash[
        jnp.clip(ext_code, 0, corpus.code_hash.shape[0] - 1)]
    ehash = jnp.where((found & (ext_code >= 0))[:, None], ext_hash, 0)
    ehash = jnp.where((found & (ext_code == -1))[:, None],
                      _J_EMPTY_KECCAK[None, :], ehash).astype(U32)
    r = jnp.where((op == 0x3F)[:, None], ehash, r)
    r = jnp.where((op == 0x40)[:, None], jnp.zeros_like(r), r)  # BLOCKHASH stub
    r = jnp.where((op == 0x41)[:, None], env.coinbase, r)
    r = jnp.where((op == 0x42)[:, None], env.timestamp, r)
    r = jnp.where((op == 0x43)[:, None], env.number, r)
    r = jnp.where((op == 0x44)[:, None], env.prevrandao, r)
    r = jnp.where((op == 0x45)[:, None], env.blk_gaslimit, r)
    r = jnp.where((op == 0x46)[:, None], env.chainid, r)
    r = jnp.where((op == 0x47)[:, None], f.self_balance, r)
    r = jnp.where((op == 0x48)[:, None], env.basefee, r)
    return f, {"r": r}


def _h_copy(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    is_ext = op == 0x3C  # EXTCODECOPY: (addr, dst, src, len)
    dst = jnp.where(is_ext[:, None], _peek(f, 1), _peek(f, 0))
    src = jnp.where(is_ext[:, None], _peek(f, 2), _peek(f, 1))
    ln = jnp.where(is_ext[:, None], _peek(f, 3), _peek(f, 2))
    dst64 = u256.to_u64_saturating(dst).astype(I64)
    src64 = u256.to_u64_saturating(src).astype(I64)
    ln64 = u256.to_u64_saturating(ln).astype(I64)

    # (a corpus without creation images lowers exactly as it did before
    # ``Corpus.deploys``, operand order included: the persistent
    # compile cache keys on the program's text)
    if corpus.deploys is None:
        f, oob = _expand_memory(f, m & (ln64 > 0), dst64 + ln64)
    else:
        f, oob = _expand_memory(
            f, m & (ln64 > 0),
            _deploy_end(f, corpus, op == 0x39, dst64 + ln64))
    ok = m & ~oob

    P, M = f.memory.shape
    jpos = jnp.arange(M, dtype=I64)[None, :]
    in_window = (jpos >= dst64[:, None]) & (jpos < (dst64 + ln64)[:, None])
    # source byte per target position: memory[:, j] <- source[:, j + shift],
    # the whole memory's window of each source (the sum wraps in int64 as
    # ``j - dst + src`` did)
    shift = src64 - dst64

    cd = _gather_bytes(f.calldata, shift, M, f.calldata_len)
    code_row = corpus.code[f.contract_id]
    code = _gather_bytes(code_row, shift, M, corpus.code_len[f.contract_id])
    # CODECOPY inside a constructor copies from the INIT code (this is how
    # constructors materialize the runtime image they RETURN)
    code = jnp.where(
        f.exec_init[:, None],
        _gather_bytes(f.init_code, shift, M, f.init_len),
        code,
    )
    rd = _gather_bytes(f.returndata, shift, M, f.returndata_len)
    # EXTCODECOPY: resolve the address against the account table; unknown
    # or codeless accounts copy zeros (EVM: empty code)
    found, slot = f.acct_lookup(_peek(f, 0))
    ext_cid = f.acct_field(f.acct_code, slot)
    have_ext = found & (ext_cid >= 0)
    ext_row = corpus.code[jnp.clip(ext_cid, 0, corpus.code.shape[0] - 1)]
    ext_limit = jnp.where(
        have_ext,
        corpus.code_len[jnp.clip(ext_cid, 0, corpus.code_len.shape[0] - 1)],
        0,
    )
    ext = _gather_bytes(ext_row, shift, M, ext_limit)
    srcb = jnp.where((op == 0x37)[:, None], cd,
                     jnp.where((op == 0x39)[:, None], code,
                               jnp.where((op == 0x3E)[:, None], rd,
                                         jnp.where((op == 0x3C)[:, None], ext, 0))))
    memory = jnp.where(in_window & ok[:, None], srcb, f.memory)
    words = (ln64 + 31) // 32
    f = _charge(f, ok, 3 * words)
    return f.replace(memory=memory.astype(U8)), {}


def _deploy_end(f: Frontier, corpus: Corpus, is_payload, end_bytes):
    """The end of a memory window of a deploy's runtime payload
    (``Corpus.deploys``): the CODECOPY of top-level creation code and
    its RETURN reach no further than the memory model. What lies past
    it is not kept, and nothing can read it: every read there traps."""
    cut = is_payload & corpus.deploys[f.contract_id] & (f.depth == 0)
    return jnp.where(cut, jnp.minimum(end_bytes, f.memory.shape[1]),
                     end_bytes)


def _h_mem(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    off = u256.to_u64_saturating(_peek(f, 0)).astype(I64)
    val = _peek(f, 1)
    is_load = op == 0x51
    is_store8 = op == 0x53
    end = jnp.where(is_store8, off + 1, off + 32)
    f, oob = _expand_memory(f, m, end)
    ok = m & ~oob

    # MLOAD
    loaded = _be_bytes_to_word(
        _gather_bytes(f.memory, off, 32, jnp.full_like(off, f.memory.shape[1]))
    )

    # MSTORE / MSTORE8
    bytes32 = _word_to_be_bytes(val)
    mem = _scatter_bytes(f.memory, off, bytes32, 32, ok & (op == 0x52))
    low_byte = (val[:, 0] & U32(0xFF)).astype(U8)[:, None]
    mem = _scatter_bytes(mem, off, low_byte, 1, ok & is_store8)
    return f.replace(memory=mem), {"r": loaded, "ok": ok}


def _storage_lookup(f: Frontier, key):
    """(hit bool[P], value u32[P,8], hit_slot i32[P]) — scoped to the
    executing account (``cur_acct``), so cross-contract frames see their
    own storage (reference: ``Account.storage`` per account ⚠unv)."""
    match = (
        f.st_used
        & (f.st_acct == f.cur_acct[:, None])
        & jnp.all(f.st_keys == key[:, None, :], axis=-1)
    )  # [P,K]
    hit = jnp.any(match, axis=1)
    slot = jnp.argmax(match, axis=1).astype(I32)
    val = jnp.sum(jnp.where(match[:, :, None], f.st_vals, 0), axis=1).astype(U32)
    return hit, val, slot


def storage_alloc(f: Frontier, hit, hit_slot, m_store):
    """Matching-or-first-free slot for an SSTORE under `m_store`.
    Returns (widx i32[P] scatter index — K = dropped/no-write — and
    overflow bool[P]). Shared by the concrete and symbolic storage
    handlers so the allocation/overflow policy can't drift between them."""
    free = ~f.st_used
    has_free = jnp.any(free, axis=1)
    free_slot = jnp.argmax(free, axis=1).astype(I32)
    target = jnp.where(hit, hit_slot, free_slot)
    overflow = m_store & ~hit & ~has_free
    wmask = m_store & ~overflow
    K = f.st_used.shape[1]
    widx = jnp.where(wmask, target, K).astype(I32)
    return widx, overflow


def validate_jump_dest(f: Frontier, corpus: Corpus, dest_w):
    """(dest i64[P], valid bool[P]): saturating target + JUMPDEST check.
    Shared by the concrete and symbolic jump handlers. Init frames check
    against the per-lane init-buffer jumpdest map."""
    dest = u256.to_u64_saturating(dest_w).astype(I64)
    MC = corpus.code.shape[1]
    idx = jnp.clip(dest, 0, MC - 1).astype(I32)
    valid = (dest < MC) & jnp.take_along_axis(
        corpus.is_jumpdest[f.contract_id], idx[:, None], axis=1
    )[:, 0]
    IC = f.init_jd.shape[1]
    valid_ini = (dest < IC) & jnp.take_along_axis(
        f.init_jd, jnp.clip(dest, 0, IC - 1).astype(I32)[:, None], axis=1
    )[:, 0]
    return dest, jnp.where(f.exec_init, valid_ini, valid)


def _h_storage(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    key = _peek(f, 0)
    val = _peek(f, 1)
    is_store = op == 0x55
    static_viol = m & is_store & f.static
    m = m & ~static_viol
    hit, cur, slot = _storage_lookup(f, key)

    # SLOAD: miss -> 0 (clean storage; unconstrained/world storage in sym layer)
    loaded = jnp.where(hit[:, None], cur, 0).astype(U32)

    widx, overflow = storage_alloc(f, hit, slot, m & is_store)
    st_keys = _write_slot(f.st_keys, widx, key)
    st_vals = _write_slot(f.st_vals, widx, val)
    st_used = _write_slot(f.st_used, widx, True)
    st_written = _write_slot(f.st_written, widx, True)
    st_acct = _write_slot(f.st_acct, widx, f.cur_acct)

    return f.replace(
        st_keys=st_keys, st_vals=st_vals,
        st_used=st_used, st_written=st_written, st_acct=st_acct,
    ).trap(overflow, Trap.STORAGE_SLOTS).trap(static_viol, Trap.STATIC_WRITE), {
        "r": loaded, "ok": m & ~is_store,
    }


def _h_jump(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    dest_w = _peek(f, 0)
    cond = _peek(f, 1)
    is_jumpi = op == 0x57
    dest, valid_dest = validate_jump_dest(f, corpus, dest_w)
    taken = ~u256.is_zero(cond) | ~is_jumpi  # JUMP always taken
    bad = m & taken & ~valid_dest
    new_pc = jnp.where(taken, dest.astype(I32), old_pc + 1)
    pc = jnp.where(m & ~bad, new_pc, f.pc)
    return f.replace(pc=pc).trap(bad, Trap.BAD_JUMP), {}


def _h_halt(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    is_return = op == 0xF3
    is_revert = op == 0xFD
    is_invalid = op == 0xFE
    is_sd = op == 0xFF
    static_viol = m & is_sd & f.static
    m = m & ~static_viol
    has_data = is_return | is_revert

    off = u256.to_u64_saturating(_peek(f, 0)).astype(I64)
    ln = u256.to_u64_saturating(_peek(f, 1)).astype(I64)
    if corpus.deploys is None:
        f, oob = _expand_memory(f, m & has_data & (ln > 0), off + ln)
    else:
        f, oob = _expand_memory(f, m & has_data & (ln > 0),
                                _deploy_end(f, corpus, is_return, off + ln))
    RD = f.retval.shape[1]
    cap_len = jnp.clip(ln, 0, RD).astype(I32)
    data = _gather_bytes(f.memory, off, RD, jnp.full_like(off, f.memory.shape[1]))
    data = jnp.where(jnp.arange(RD)[None, :] < cap_len[:, None], data, 0)
    wmask = m & has_data & ~oob
    retval = jnp.where(wmask[:, None], data, f.retval)
    retval_len = jnp.where(wmask, cap_len, f.retval_len)

    # INVALID consumes all remaining gas
    gas_min = jnp.where(m & is_invalid, f.gas_limit, f.gas_min)
    gas_max = jnp.where(m & is_invalid, f.gas_limit, f.gas_max)

    return f.trap(m & is_invalid, Trap.INVALID_OP).trap(
        static_viol, Trap.STATIC_WRITE
    ).replace(
        halted=f.halted | (m & ~is_invalid),
        reverted=f.reverted | (m & is_revert),
        selfdestructed=f.selfdestructed | (m & is_sd),
        retval=retval,
        retval_len=retval_len,
        gas_min=gas_min,
        gas_max=gas_max,
    ), {}


def _h_log(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    static_viol = m & f.static
    m = m & ~static_viol
    off = u256.to_u64_saturating(_peek(f, 0)).astype(I64)
    ln = u256.to_u64_saturating(_peek(f, 1)).astype(I64)
    f, _ = _expand_memory(f, m & (ln > 0), off + ln)
    f = _charge(f, m, 8 * ln)
    # bounded event record: pc, executing contract, topic count, topic0,
    # first payload word (reference keeps full logs on GlobalState ⚠unv;
    # overflow beyond log_slots still counts in n_logs)
    LS = f.log_pc.shape[1]
    n_topics = op.astype(I32) - 0xA0
    topic0 = _peek(f, 2)
    raw0 = _gather_bytes(f.memory, off, 32, jnp.full_like(off, f.memory.shape[1]))
    # bytes past the log's data length are NOT part of the payload
    raw0 = jnp.where(jnp.arange(32)[None, :] < ln[:, None], raw0, 0)
    data0 = _be_bytes_to_word(raw0).astype(U32)
    widx = jnp.where(m & (f.n_logs < LS), jnp.minimum(f.n_logs, LS - 1), LS)
    return f.replace(
        n_logs=jnp.where(m, f.n_logs + 1, f.n_logs),
        log_pc=_write_slot(f.log_pc, widx, old_pc),
        log_cid=_write_slot(f.log_cid, widx, f.contract_id),
        log_ntopics=_write_slot(f.log_ntopics, widx, n_topics),
        log_topic0=_write_slot(
            f.log_topic0, widx,
            jnp.where((n_topics >= 1)[:, None], topic0, 0).astype(U32)),
        log_data0=_write_slot(f.log_data0, widx, data0),
    ).trap(static_viol, Trap.STATIC_WRITE), {}


def _h_call(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    """CALL family stub: success=1, empty returndata. Real sub-transactions
    are orchestrated by the symbolic VM layer (reference: call_ raising
    TransactionStartSignal ⚠unv)."""
    one = jnp.zeros_like(_peek(f, 0)).at[:, 0].set(1)
    return f.replace(
        returndata_len=jnp.where(m, 0, f.returndata_len),
    ), {"r": one}


def _h_create(f: Frontier, env: Env, corpus: Corpus, op, m, old_pc):
    """CREATE/CREATE2 stub: pushes zero address (creation semantics live in
    the tx layer)."""
    zero = jnp.zeros_like(_peek(f, 0))
    off = u256.to_u64_saturating(_peek(f, 1)).astype(I64)
    ln = u256.to_u64_saturating(_peek(f, 2)).astype(I64)
    f, _ = _expand_memory(f, m & (ln > 0), off + ln)
    return f, {"r": zero}


_HANDLERS = [
    _h_stack, _h_alu, _h_mul, _h_divmod, _h_modarith, _h_exp, _h_sha3, _h_env,
    _h_copy, _h_mem, _h_storage, _h_jump, _h_halt, _h_log, _h_call, _h_create,
]


# ---------------------------------------------------------------------------
# Superstep
# ---------------------------------------------------------------------------


def prologue(f: Frontier, corpus: Corpus, berlin: bool = False):
    """Fetch + validate the next instruction for every running lane.

    Returns ``(f, op, run, old_pc)``: frontier with arity/validity traps and
    base gas applied, the per-lane opcode (STOP past code end), the lanes
    that execute this step, and the pre-step pc. Shared by the concrete
    superstep and the symbolic engine (reference: the ``StateTransition``
    decorator checks in ``mythril/laser/ethereum/instructions.py`` ⚠unv).
    ``berlin`` charges the EIP-2929 WARM base costs — the symbolic engine
    adds cold surcharges from its per-lane warm sets.
    """
    running = f.running
    MC = corpus.code.shape[1]
    pc_idx = jnp.clip(f.pc, 0, MC - 1)
    op_raw = jnp.take_along_axis(corpus.code[f.contract_id], pc_idx[:, None], axis=1)[:, 0]
    in_code = f.pc < corpus.code_len[f.contract_id]
    # CREATE init frames fetch from the per-lane init buffer (a single-byte
    # per-lane gather — cheap enough to run unconditionally)
    ei = f.exec_init
    IC = f.init_code.shape[1]
    op_ini = jnp.take_along_axis(
        f.init_code, jnp.clip(f.pc, 0, IC - 1)[:, None], axis=1
    )[:, 0]
    op_raw = jnp.where(ei, op_ini, op_raw)
    in_code = jnp.where(ei, f.pc < f.init_len, in_code)
    op = jnp.where(running & in_code, op_raw, 0).astype(I32)  # off-end = STOP

    sin = _J_STACK_IN[op]
    sout = _J_STACK_OUT[op]
    invalid = running & ~_J_IS_VALID[op]
    # arity is checked against the CURRENT frame's stack region: sub-call
    # frames own [sp_base, sp) of the shared stack array
    stack_bad = running & _J_IS_VALID[op] & (
        (f.sp - f.sp_base < sin) | (f.sp - sin + sout > f.max_stack)
    )
    f = f.trap(invalid, Trap.INVALID_OP).trap(stack_bad, Trap.STACK)
    run = running & ~invalid & ~stack_bad

    gmin = _J_GAS_MIN_BERLIN if berlin else _J_GAS_MIN
    gmax = _J_GAS_MAX_BERLIN if berlin else _J_GAS_MAX
    f = f.replace(
        gas_min=f.gas_min + jnp.where(run, gmin[op], 0),
        gas_max=f.gas_max + jnp.where(run, gmax[op], 0),
    )
    return f, op, run, f.pc


# Dispatch granularity: EVERY class hides behind a `lax.cond`, so a
# superstep pays only for the classes present in the frontier. Measured
# on the chip (round 4, P=4096, ERC-20 mix): all gated 3.88 ms/superstep,
# cheap classes ungated 23.06 ms, nothing gated 763 ms — an un-taken
# cond skips its handler's whole-frontier reads and writes.


# Fields each class handler may WRITE. A gated class's `lax.cond`
# returns ONLY these leaves — the rest of the frontier never becomes a
# cond output, so XLA cannot be forced to materialize it at the
# boundary. NOTE `stack` and `sp` appear in NO write set: handlers
# return result words through the aux channel and the shared writeback
# below touches the [P,S,8] stack exactly once per superstep (round 4:
# with stack in ten classes' write sets, the untaken conds' stack
# copies were ~85% of superstep traffic and scaled superlinearly with
# P). The declaration is enforced at trace time: an undeclared write
# raises AssertionError during the first jit.
WRITE_FIELDS = {
    CLS_STACK: (),
    CLS_ALU: (),
    CLS_MUL: (),
    CLS_DIVMOD: (),
    CLS_MODARITH: (),
    CLS_EXP: ("gas_min", "gas_max"),
    CLS_SHA3: ("gas_min", "gas_max", "mem_words", "error", "err_code"),
    CLS_ENV: (),
    CLS_COPY: ("memory", "gas_min", "gas_max", "mem_words",
               "error", "err_code"),
    CLS_MEM: ("memory", "gas_min", "gas_max", "mem_words",
              "error", "err_code"),
    CLS_STORAGE: ("st_keys", "st_vals", "st_used",
                  "st_written", "st_acct", "error", "err_code"),
    CLS_JUMP: ("pc", "error", "err_code"),
    CLS_HALT: ("halted", "reverted", "selfdestructed", "retval",
               "retval_len", "gas_min", "gas_max", "mem_words",
               "error", "err_code"),
    CLS_LOG: ("n_logs", "log_pc", "log_cid", "log_ntopics", "log_topic0",
              "log_data0", "gas_min", "gas_max", "mem_words",
              "error", "err_code"),
    CLS_CALL: ("returndata_len",),
    CLS_CREATE: ("gas_min", "gas_max", "mem_words", "error", "err_code"),
}

# Aux outputs each class hands to the shared writeback: "r" the result
# word (u32[P,8]), "ok" a per-lane write veto (lanes that trapped inside
# the handler), and STACK's SWAP second write port.
AUX_KEYS = {
    CLS_STACK: ("r", "w2_idx", "w2_val", "w2_mask"),
    CLS_ALU: ("r",),
    CLS_MUL: ("r",),
    CLS_DIVMOD: ("r",),
    CLS_MODARITH: ("r",),
    CLS_EXP: ("r",),
    CLS_SHA3: ("r", "ok"),
    CLS_ENV: ("r",),
    CLS_COPY: (),
    CLS_MEM: ("r", "ok"),
    CLS_STORAGE: ("r", "ok"),
    CLS_JUMP: (),
    CLS_HALT: (),
    CLS_LOG: (),
    CLS_CALL: ("r",),
    CLS_CREATE: ("r",),
}

_FRONTIER_FIELDS: Tuple[str, ...] = ()


def _frontier_fields(f: Frontier):
    global _FRONTIER_FIELDS
    if not _FRONTIER_FIELDS:
        import dataclasses

        _FRONTIER_FIELDS = tuple(fl.name for fl in dataclasses.fields(f))
    return _FRONTIER_FIELDS


def _key_name(k) -> str:
    for attr in ("name", "key", "idx"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


def narrow_cond(pred, fn, obj, declared, aux_defaults=None):
    """``lax.cond(pred, fn, identity, obj)`` whose cond OUTPUTS are only
    the leaves under the ``declared`` dotted field paths — the rest of the
    pytree bypasses the cond entirely, so XLA cannot be forced to
    materialize untouched state at the boundary (same trick as
    ``dispatch``'s WRITE_FIELDS, generalized to nested pytrees like
    SymFrontier where writes land both on ``base.stack`` and on overlay
    fields). ``fn`` must write ONLY under ``declared``; an undeclared
    write raises at first trace.

    With ``aux_defaults`` (an ordered dict of default arrays), ``fn``
    returns ``(new_obj, aux_dict)`` and this returns ``(obj, aux)`` —
    the aux arrays ride the cond boundary (defaults when untaken), which
    is how a claimed handler hands a result word to a shared writeback
    without putting the whole stack in its write set (cf. dispatch's
    AUX_KEYS)."""
    import jax.tree_util as jtu

    kl, treedef = jtu.tree_flatten_with_path(obj)
    names = [".".join(_key_name(k) for k in path) for path, _ in kl]

    def is_declared(n: str) -> bool:
        return any(n == d or n.startswith(d + ".") for d in declared)

    idxs = [i for i, n in enumerate(names) if is_declared(n)]
    akeys = tuple(aux_defaults) if aux_defaults else ()

    def _true():
        if aux_defaults is None:
            new, aux = fn(obj), {}
        else:
            new, aux = fn(obj)
            for k in aux:
                if k not in akeys:
                    raise AssertionError(
                        f"{getattr(fn, '__name__', fn)} returned undeclared "
                        f"aux {k!r}; add it to aux_defaults")
        new_kl, _ = jtu.tree_flatten_with_path(new)
        for (_, b), (_, a), n in zip(new_kl, kl, names):
            if b is not a and not is_declared(n):
                raise AssertionError(
                    f"{getattr(fn, '__name__', fn)} wrote undeclared leaf "
                    f"{n!r}; add it to the declared write set")
        return tuple(new_kl[i][1] for i in idxs) + tuple(
            aux.get(k, aux_defaults[k]) for k in akeys)

    def _false():
        return tuple(kl[i][1] for i in idxs) + tuple(
            aux_defaults[k] for k in akeys)

    outs = lax.cond(pred, _true, _false)
    leaves = [leaf for _, leaf in kl]
    for j, i in enumerate(idxs):
        leaves[i] = outs[j]
    out_obj = jtu.tree_unflatten(treedef, leaves)
    if aux_defaults is None:
        return out_obj
    return out_obj, dict(zip(akeys, outs[len(idxs):]))


def dispatch(f: Frontier, env: Env, corpus: Corpus, op, run, old_pc,
             skip=None) -> Frontier:
    """Run the per-class handlers over the frontier. ``skip`` masks lanes
    out of concrete handling (the symbolic engine claims them).

    Handlers return ``(frontier, aux)``; the stack is written HERE, once:
    each value class's result word rides the aux channel through its
    (narrow) cond boundary, and one shared ``_set_slot`` pass lands every
    class's result at ``sp - sin + sout - 1`` (plus the SWAP second
    port). ``sp`` advances centrally from the arity tables."""
    cls = _J_CLASS[op]
    if skip is not None:
        run = run & ~skip
    # one O(P) pass computing every class-present predicate at once,
    # instead of one whole-frontier `jnp.any` reduction per gated class.
    # Formulated as a [P, 16] compare + OR-reduction, NOT a segment_sum:
    # TPU lowers data-dependent scatters poorly (serialized updates),
    # while this shape fuses into one vectorized pass.
    present = jnp.any(
        (cls[:, None] == jnp.arange(N_CLASSES, dtype=cls.dtype)[None, :])
        & run[:, None], axis=0)
    all_fields = _frontier_fields(f)
    P = f.pc.shape[0]
    zero_word = jnp.zeros((P, 8), dtype=U32)
    aux_defaults = {
        "r": zero_word,
        "ok": jnp.zeros(P, dtype=bool),
        "w2_idx": jnp.zeros(P, dtype=I32),
        "w2_val": zero_word,
        "w2_mask": jnp.zeros(P, dtype=bool),
    }
    pre_sp = f.sp
    val = zero_word
    veto = jnp.zeros(P, dtype=bool)
    w2_idx = aux_defaults["w2_idx"]
    w2_val = zero_word
    w2_mask = aux_defaults["w2_mask"]
    for cid, handler in enumerate(_HANDLERS):
        mask = run & (cls == cid)
        names = WRITE_FIELDS[cid]
        akeys = AUX_KEYS[cid]

        def _run_handler(fr=f, h=handler, mk=mask, names=names,
                         akeys=akeys):
            fr2, aux = h(fr, env, corpus, op, mk, old_pc)
            for fld in all_fields:
                if fld not in names and \
                        getattr(fr2, fld) is not getattr(fr, fld):
                    raise AssertionError(
                        f"{h.__name__} wrote undeclared field {fld!r}; "
                        f"add it to WRITE_FIELDS[{cid}]")
            for k in aux:
                if k not in akeys:
                    raise AssertionError(
                        f"{h.__name__} returned undeclared aux {k!r}; "
                        f"add it to AUX_KEYS[{cid}]")
            return tuple(getattr(fr2, n) for n in names) + tuple(
                aux.get(k, aux_defaults[k]) for k in akeys)

        outs = lax.cond(
            present[cid],
            _run_handler,
            lambda fr=f, names=names, akeys=akeys: tuple(
                getattr(fr, n) for n in names) + tuple(
                aux_defaults[k] for k in akeys),
        )
        f = f.replace(**dict(zip(names, outs[:len(names)])))
        aux = dict(zip(akeys, outs[len(names):]))
        if "r" in akeys:
            val = jnp.where(mask[:, None], aux.get("r", zero_word), val)
        if "ok" in akeys:
            veto = veto | (mask & ~aux.get("ok", aux_defaults["ok"]))
        if "w2_mask" in akeys:
            w2_idx = aux.get("w2_idx", aux_defaults["w2_idx"])
            w2_val = aux.get("w2_val", zero_word)
            w2_mask = aux.get("w2_mask", aux_defaults["w2_mask"])
    # shared writeback: ONE stack pass for every value class + SWAP port
    w1_mask = run & _J_PUSHES[op] & ~veto
    w1_idx = pre_sp - _J_STACK_IN[op] + _J_STACK_OUT[op] - 1
    stack = _set_slot(f.stack, w1_idx, val, w1_mask)
    stack = _set_slot(stack, w2_idx, w2_val, w2_mask)
    sp = jnp.where(run, pre_sp + _J_D_SP[op], pre_sp)
    return f.replace(stack=stack, sp=sp)


def epilogue(f: Frontier, op, run, old_pc) -> Frontier:
    """Default pc advance + out-of-gas trap after the handlers ran.
    Lanes with ``pc_hold`` set (a handler placed pc explicitly — e.g. a
    sub-call frame push pointing at the callee's entry) are left alone;
    the flag is consumed here."""
    cls = _J_CLASS[op]
    advanced = run & (cls != CLS_JUMP) & ~f.halted & ~f.error & ~f.pc_hold
    next_pc = old_pc + 1 + _J_PUSH_WIDTH[op]
    f = f.replace(
        pc=jnp.where(advanced, next_pc, f.pc),
        pc_hold=jnp.zeros_like(f.pc_hold),
        n_steps=f.n_steps + run.astype(I32),
    )
    if f.op_hist is not None:  # iprof: one masked histogram update per step
        f = f.replace(op_hist=_hist_add(f.op_hist, op, run.astype(I32)))
    oog = run & (f.gas_min > f.gas_limit)
    return f.trap(oog, Trap.OOG)


def superstep(f: Frontier, env: Env, corpus: Corpus) -> Frontier:
    """Advance every running lane by one instruction."""
    f, op, run, old_pc = prologue(f, corpus)
    f = dispatch(f, env, corpus, op, run, old_pc)
    return epilogue(f, op, run, old_pc)


@functools.partial(jax.jit, static_argnames=("max_steps",))
def run(f: Frontier, env: Env, corpus: Corpus, max_steps: int = 256) -> Frontier:
    """Run until every lane halts/errors or max_steps supersteps elapse."""

    def cond(state):
        i, fr = state
        return (i < max_steps) & jnp.any(fr.running)

    def body(state):
        i, fr = state
        return i + 1, superstep(fr, env, corpus)

    _, f = lax.while_loop(cond, body, (jnp.int32(0), f))
    return f
