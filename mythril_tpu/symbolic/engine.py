"""The symbolic superstep: concrete dispatch + sym-id overlay + forking.

Counterpart of the reference's symbolic ``Instruction.evaluate`` over Z3
expressions and ``jumpi_``'s state forking
(``mythril/laser/ethereum/instructions.py`` ⚠unv, SURVEY.md §3.2), but
frontier-first:

- lanes whose current opcode touches symbolic control/addresses are
  *claimed* out of the concrete dispatch and handled by sym-aware
  handlers (storage, jumps, calls, symbolic-offset memory ops);
- everything else runs the concrete handler unchanged, and a vectorized
  overlay keeps ``stack_sym``/``mem_sym`` in sync and appends tape nodes;
- a symbolic JUMPI records a fork request; :func:`expand_forks` performs
  masked lane duplication + prefix-sum compaction into free lanes
  (the reference's ``work_list.append`` of forked GlobalStates).

Over-approximation policy: wherever byte-exact symbolic tracking is not
worth the shapes, the result is a fresh unconstrained HAVOC leaf, never
a wrong value. Exact: aligned words at concrete offsets; a word below
the lane's ``mem_floor`` after a store or a copy at a symbolic offset
invalidated memory from its destination's concrete base up (solc's free
pointer after it decoded a dynamic argument: the scratch words that hash
every mapping slot stay exact); a ``CALLDATALOAD`` at a symbolic offset
in the top frame (a ``CD_SELECT`` node over the transaction's bytes);
**a word stored whole by one ``MSTORE`` at an unaligned offset and read
back whole at the same offset**, on both sides of a hop (``mem_usym`` /
``cd_usym``: one byte shift a lane, which is how solc's ABI encoder lays
a call out, the selector word at ``ptr`` and argument ``k`` at ``ptr + 4
+ 32k``): an ``MLOAD`` there, the callee's ``CALLDATALOAD(4 + 32k)`` of
an aligned call window, and a concrete argument among symbolic ones; the
callee's selector, a ``SHR`` of calldata word 0 that keeps only the
bytes that were concrete under the first argument (``head_node``); a
return word through an aligned window (``rv_sym``, as before).
Still a havoc leaf: an unaligned access that meets a symbolic word
anywhere else (another shift, a word that an aligned store, an
``MSTORE8``, a copy or a call's output has written into since, a word
the caller keeps across a call: its own whole words end when the frame
pops); the ALIGNED words an unaligned symbolic store covers, and the
partly covered tail word of a call window (one leaf for that word, no
longer the whole frame's calldata); a call window at an unaligned
offset that holds a symbol; ``ADDMOD`` / ``MULMOD`` over symbols; a
calldata read beyond the modelled window, or at a symbolic offset inside
a sub-frame; any word at or above the floor, those a ``CALLDATACOPY``
filled among them; all of memory after a store or a copy whose
destination has no concrete base.
A havoc leaf is tied to nothing, so the engine may explore infeasible
paths; it misses no feasible one AS LONG AS what the leaf stands for is
not read back through concrete state: from concrete storage a mapping
slot hashed out of havoc memory is a key that no earlier write matches,
and a flaw behind such a guard was a false negative until the floor
(``tests/test_dynamic_args.py``). A destination below its concrete base
(a sum that wraps) is not modelled: ``_concrete_base``.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..config import LimitsConfig, DEFAULT_LIMITS
from ..core import interpreter as ci
from ..core.frontier import (Frontier, Env, Corpus, Trap, CAP_TRAPS,
                             KILL_TRAPS, ACCT_ATTACKER, ATTACKER_ADDRESS,
                             CODE_UNKNOWN)
from ..ops import u256
from .ops import SymOp, FreeKind, TX_STRIDE, BAL_STRIDE
from .state import (MEM_EXACT, USYM_CONCRETE, SymFrontier, SymSpec,
                    HOP_INTERNAL, HOP_EOA, HOP_PRECOMPILE, HOP_EXTERNAL,
                    HOP_MEMBER, HOP_TRAPPED, HOP_CD_EXACT, HOP_CD_HAVOC,
                    HOP_RET_EXACT, HOP_RET_HAVOC, HOP_DEPTH, N_HOP)
# imported here, outside any trace, for its module-level jnp constants:
# ``_sym_run_impl`` imports from it while it is being traced, and a
# first import there would build them as tracers of that trace
from . import propagate  # noqa: F401

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32

# EVM opcode -> SymOp for plain binary/unary value ops (0 = no mapping)
def _binop_table() -> np.ndarray:
    t = np.zeros(256, dtype=np.int32)
    m = {
        0x01: SymOp.ADD, 0x02: SymOp.MUL, 0x03: SymOp.SUB, 0x04: SymOp.DIV,
        0x05: SymOp.SDIV, 0x06: SymOp.MOD, 0x07: SymOp.SMOD, 0x0A: SymOp.EXP,
        0x0B: SymOp.SIGNEXTEND, 0x10: SymOp.LT, 0x11: SymOp.GT,
        0x12: SymOp.SLT, 0x13: SymOp.SGT, 0x14: SymOp.EQ, 0x15: SymOp.ISZERO,
        0x16: SymOp.AND, 0x17: SymOp.OR, 0x18: SymOp.XOR, 0x19: SymOp.NOT,
        0x1A: SymOp.BYTE, 0x1B: SymOp.SHL, 0x1C: SymOp.SHR, 0x1D: SymOp.SAR,
    }
    for k, v in m.items():
        t[k] = int(v)
    return t


_J_BINOP = jnp.asarray(_binop_table())


# ---------------------------------------------------------------------------
# Tape + sym-stack helpers
# ---------------------------------------------------------------------------


def _peek_sym(sf: SymFrontier, i) -> jnp.ndarray:
    sp = sf.base.sp
    S = sf.stack_sym.shape[1]
    idx = jnp.clip(sp - 1 - i, 0, S - 1)
    return jnp.take_along_axis(sf.stack_sym, idx[:, None].astype(I32), axis=1)[:, 0]


def _set_sym_slot(stack_sym, pos, val, mask):
    """Masked single-slot write (backend-adaptive, see
    interpreter._set_slot / _write_slot)."""
    S = stack_sym.shape[1]
    idx = jnp.where(mask & (pos >= 0), pos, S).astype(I32)
    return ci._write_slot(stack_sym, idx, val)


def append_node(sf: SymFrontier, mask, op, a, b, imm=None):
    """Hash-consed tape append. op/a/b scalar or i32[P]; imm u32[P,8]|None.
    Returns (sf, ids) — id per lane (0 where ~mask). Overflow errors lane.

    The dedup scan compares one u32 fingerprint per entry
    (``tape_row_hash``) and verifies only the first hash-matching row —
    12x less scan traffic than comparing full rows (this scan runs
    several times per superstep and reads the whole tape each time). A
    collision on the first match degrades to a missed dedup: a duplicate
    node, never a wrong id."""
    from .state import tape_row_hash

    P, T = sf.tape_op.shape
    op = jnp.broadcast_to(jnp.asarray(op, I32), (P,))
    a = jnp.broadcast_to(jnp.asarray(a, I32), (P,))
    b = jnp.broadcast_to(jnp.asarray(b, I32), (P,))
    if imm is None:
        imm = jnp.zeros((P, 8), dtype=U32)
    h = tape_row_hash(op, a, b, imm)
    live = jnp.arange(T)[None, :] < sf.tape_len[:, None]
    match = live & (sf.tape_hash == h[:, None])
    hit0 = jnp.any(match, axis=1)
    hit_id = jnp.argmax(match, axis=1).astype(I32)
    # verify the candidate row (per-lane gather, not a full-tape compare)
    g1 = lambda arr: jnp.take_along_axis(arr, hit_id[:, None], axis=1)[:, 0]
    c_imm = jnp.take_along_axis(sf.tape_imm, hit_id[:, None, None], axis=1)[:, 0]
    hit = (hit0 & (g1(sf.tape_op) == op) & (g1(sf.tape_a) == a)
           & (g1(sf.tape_b) == b) & jnp.all(c_imm == imm, axis=-1))
    overflow = mask & ~hit & (sf.tape_len >= T)
    write = mask & ~hit & ~overflow
    widx = jnp.where(write, jnp.minimum(sf.tape_len, T), T)  # T = dropped
    ids = jnp.where(mask, jnp.where(hit, hit_id, jnp.where(write, sf.tape_len, 0)), 0)
    return (
        sf.replace(
            tape_op=ci._write_slot(sf.tape_op, widx, op),
            tape_a=ci._write_slot(sf.tape_a, widx, a),
            tape_b=ci._write_slot(sf.tape_b, widx, b),
            tape_imm=ci._write_slot(sf.tape_imm, widx, imm),
            tape_hash=ci._write_slot(sf.tape_hash, widx, h),
            tape_len=sf.tape_len + write.astype(I32),
            base=sf.base.trap(overflow, Trap.TAPE_LIMIT),
        ),
        ids,
    )


def _sym_or_const(sf: SymFrontier, mask, sym, limbs):
    """Operand id: existing sym, id 0 for concrete zero, CONST node else."""
    need = mask & (sym == 0) & ~u256.is_zero(limbs)
    sf, cid = append_node(sf, need, int(SymOp.CONST), 0, 0, limbs)
    return sf, jnp.where(sym != 0, sym, cid)


def _havoc(sf: SymFrontier, mask):
    """Fresh unconstrained leaf per lane (unique via per-lane counter)."""
    sf2, ids = append_node(
        sf, mask, int(SymOp.FREE), int(FreeKind.HAVOC), sf.havoc_cnt
    )
    return sf2.replace(havoc_cnt=sf2.havoc_cnt + mask.astype(I32)), ids


def _floor_word(off64):
    """The memory word of a byte offset, as ``mem_floor`` counts them."""
    return jnp.clip(off64 // 32, 0, MEM_EXACT).astype(I32)


def _reaches_floor(sf: SymFrontier, off64, ln64):
    """Does the window ``[off, off + ln)`` hold a word at or above the
    lane's ``mem_floor``, i.e. one that may be unknown?"""
    return (ln64 > 0) & (_floor_word(off64 + ln64 + 31) > sf.mem_floor)


def _lower_floor(floor, mask, word):
    return jnp.where(mask, jnp.minimum(floor, word), floor)


_BASE_DEPTH = 3


def _concrete_base(sf: SymFrontier, node):
    """A byte offset that the value of ``node`` does not lie below, read
    off the tape: the concrete parts of a sum ``ADD(CONST c, x)``, nested
    up to ``_BASE_DEPTH`` deep (solc's free pointer after a decode is
    ``ADD(0xa0, MUL(32, len))``, an allocation after it ``ADD(that,
    CONST)``); 0 for any other node. The sum is taken not to wrap: a
    destination below its base needs a term within ``base`` of 2**256,
    which no length that solc computes reaches under the gas limit."""
    T = sf.tape_op.shape[1]

    def row(arr, i):
        return jnp.take_along_axis(
            arr, jnp.clip(i, 0, T - 1)[:, None], axis=1)[:, 0]

    def small_const(i):
        imm = jnp.take_along_axis(
            sf.tape_imm, jnp.clip(i, 0, T - 1)[:, None, None], axis=1)[:, 0]
        ok = ((row(sf.tape_op, i) == int(SymOp.CONST))
              & jnp.all(imm[:, 1:] == 0, axis=1) & (imm[:, 0] < 2**31))
        return ok, imm[:, 0].astype(I64)

    base = jnp.zeros(node.shape, dtype=I64)
    cur, live = node, node != 0
    for _ in range(_BASE_DEPTH):
        is_add = live & (row(sf.tape_op, cur) == int(SymOp.ADD))
        a, b = row(sf.tape_a, cur), row(sf.tape_b, cur)
        (a_ok, a_val), (b_ok, b_val) = small_const(a), small_const(b)
        take_a = is_add & a_ok
        take_b = is_add & ~a_ok & b_ok
        base = base + jnp.where(take_a, a_val, 0) + jnp.where(take_b, b_val, 0)
        cur = jnp.where(take_a, b, jnp.where(take_b, a, cur))
        live = take_a | take_b
    return base


def _dest_floor_word(sf: SymFrontier, off64, off_sym):
    """The word a write at ``off`` lowers ``mem_floor`` to: the offset's
    own where it is concrete, else its concrete base's (word 0 without
    one)."""
    return _floor_word(jnp.where(off_sym == 0, off64,
                                 _concrete_base(sf, off_sym)))


def _event_slot(counter, mask, length: int):
    """Bounded per-lane event-log allocation: onehot[P, L] of the next
    free slot where `mask`; saturated logs silently drop (counter still
    counts attempts so overflow is observable)."""
    idx = jnp.minimum(counter, length - 1)
    rec = mask & (counter < length)
    return (jnp.arange(length)[None, :] == idx[:, None]) & rec[:, None]


def _lookup_constraint(sf: SymFrontier, node):
    """Is `node` already asserted on the path? -> (known, sign)."""
    C = sf.con_node.shape[1]
    live = jnp.arange(C)[None, :] < sf.con_len[:, None]
    m = live & (sf.con_node == node[:, None]) & (node[:, None] != 0)
    known = jnp.any(m, axis=1)
    idx = jnp.argmax(m, axis=1)
    sign = jnp.take_along_axis(sf.con_sign, idx[:, None], axis=1)[:, 0]
    return known, known & sign


def _append_constraint(sf: SymFrontier, mask, node, sign, pc):
    C = sf.con_node.shape[1]
    overflow = mask & (sf.con_len >= C)
    write = mask & ~overflow
    widx = jnp.where(write, jnp.minimum(sf.con_len, C), C)
    sign = jnp.broadcast_to(jnp.asarray(sign, bool), mask.shape)
    return sf.replace(
        con_node=ci._write_slot(sf.con_node, widx, node),
        con_sign=ci._write_slot(sf.con_sign, widx, sign),
        con_pc=ci._write_slot(sf.con_pc, widx, pc),
        con_len=sf.con_len + write.astype(I32),
        base=sf.base.trap(overflow, Trap.CONSTRAINT_LIMIT),
    )


# ---------------------------------------------------------------------------
# Claimed handlers: sym-aware replacements run after the concrete dispatch
# (their lanes were skipped there, so stack/sp are still pre-instruction)
# ---------------------------------------------------------------------------


def _h_sym_storage(sf: SymFrontier, spec: SymSpec, op, m) -> SymFrontier:
    """SLOAD/SSTORE with (possibly symbolic) keys and values.

    Key matching: concrete keys match by limb equality, symbolic keys by
    tape node id (hash-consing makes structurally equal keccak keys share
    an id — the analog of the reference's KeccakFunctionManager
    hash-linking ⚠unv), PLUS a numeric alias probe:
    a symbolic key whose known-bits domain (propagate.py, persistent
    ``kb_m``/``kb_v``) is fully determined has a definite numeric value
    and is DEMOTED to that value — it matches concrete keys and other
    fully-determined keys numerically, its SSTORE entry is stored
    concrete, and its SLOAD-miss leaf hash-conses on the value. A write
    through ``f(x)`` and a read through a structurally different but
    provably-equal ``g(y)`` therefore connect. Keys the domain cannot
    fully determine keep node-id matching (assumed-distinct: the same
    syntactic under-approximation the reference's independent BitVec
    keys give Z3 before hash-linking resolves them ⚠unv). Nodes not yet
    reached by a propagation sweep (``>= prop_len``) never demote — their
    kb rows may hold a recycled lane's stale domains.
    """
    f = sf.base
    key = ci._peek(f, 0)
    key_sym = _peek_sym(sf, 0)
    val = ci._peek(f, 1)
    val_sym = _peek_sym(sf, 1)
    is_store = op == 0x55
    static_viol = m & is_store & f.static
    m = m & ~static_viol
    sf = sf.replace(base=f.trap(static_viol, Trap.STATIC_WRITE))
    f = sf.base

    in_acct = f.st_acct == f.cur_acct[:, None]
    # numeric alias probe: definite values for fully-known-bits keys.
    # spec.alias_probe is a trace-time bool — False compiles the kb
    # gathers out entirely and the match reduces to the syntactic form.
    if spec.alias_probe:
        T = sf.tape_op.shape[1]
        kidx = jnp.clip(key_sym, 0, T - 1)
        key_kbm = jnp.take_along_axis(sf.kb_m, kidx[:, None, None],
                                      axis=1)[:, 0]
        key_kbv = jnp.take_along_axis(sf.kb_v, kidx[:, None, None],
                                      axis=1)[:, 0]
        key_known = ((key_sym != 0) & (key_sym < sf.prop_len)
                     & jnp.all(key_kbm == U32(0xFFFFFFFF), axis=-1))
        key_num = jnp.where(key_known[:, None], key_kbv, key).astype(U32)
        ent_sym = sf.st_key_sym
        eidx = jnp.clip(ent_sym, 0, T - 1)
        ent_kbm = jnp.take_along_axis(sf.kb_m, eidx[:, :, None], axis=1)
        ent_known = ((ent_sym != 0) & (ent_sym < sf.prop_len[:, None])
                     & jnp.all(ent_kbm == U32(0xFFFFFFFF), axis=-1))
        ent_kbv = jnp.take_along_axis(sf.kb_v, eidx[:, :, None], axis=1)
        ent_num = jnp.where(ent_known[:, :, None], ent_kbv,
                            f.st_keys).astype(U32)
    else:
        key_known = jnp.zeros_like(key_sym, dtype=bool)
        key_num = key
        ent_known = jnp.zeros_like(sf.st_key_sym, dtype=bool)
        ent_num = f.st_keys
    key_def = (key_sym == 0) | key_known
    eff_key_sym = jnp.where(key_known, 0, key_sym)  # demoted-to-concrete
    ent_def = (sf.st_key_sym == 0) | ent_known

    conc = (key_def[:, None] & ent_def
            & jnp.all(ent_num == key_num[:, None, :], axis=-1))
    symm = (key_sym[:, None] != 0) & (sf.st_key_sym == key_sym[:, None])
    match = f.st_used & in_acct & (conc | symm)
    # a VALUE hit requires a value-bearing entry (st_seq > 0): berlin
    # warm-tracking (_berlin_gas_post) allocates (key, 0, unwritten)
    # entries for concrete SLOAD misses, and matching those as hits
    # would read concrete 0 where the first load of the same slot
    # produced a symbolic STORAGE leaf — the same slot must keep reading
    # as that leaf. Seq-0 entries still count for SSTORE slot reuse
    # below, so a later store overwrites the warm entry in place.
    match_val = match & (sf.st_seq > 0)
    hit = jnp.any(match_val, axis=1)
    # dependency tracking: a hit on an entry NOT written this tx is a read
    # of a prior transaction's write (entries persist across the boundary
    # with st_written cleared)
    prior_hit = jnp.any(match_val & ~f.st_written, axis=1)
    sf = sf.replace(dep_read=sf.dep_read | (m & ~is_store & prior_hit))
    # LATEST-write matching slot, not a masked sum: the alias probe can
    # connect an entry written before its key's bits were proven WITH a
    # concrete entry of the same value — and slot INDEX order does not
    # track write order once a lower slot is re-written in place, so the
    # group's max-``st_seq`` entry is the live one (reads and the SSTORE
    # reuse slot below agree on this policy; stale members stay shadowed)
    sel = jnp.argmax(jnp.where(match, sf.st_seq, -1), axis=1).astype(I32)
    cur = jnp.take_along_axis(f.st_vals, sel[:, None, None], axis=1)[:, 0]
    cur = jnp.where(hit[:, None], cur, 0).astype(U32)
    cur_sym = jnp.take_along_axis(sf.st_val_sym, sel[:, None], axis=1)[:, 0]
    cur_sym = jnp.where(hit, cur_sym, 0).astype(I32)

    # SLOAD miss -> fresh STORAGE leaf (hash-consed on (account, key), so
    # repeated loads of the same key agree while distinct accounts'
    # identical keys stay independent); concrete-zero when storage isn't
    # symbolic. b encodes key_sym * A + account slot.
    miss_load = m & ~is_store & ~hit
    A = f.acct_used.shape[1]
    if spec.storage:
        # eff_key_sym/key_num: a demoted (fully-known) key hash-conses on
        # its VALUE, sharing the leaf a concrete key of that value gets
        sf, leaf = append_node(
            sf, miss_load, int(SymOp.FREE), int(FreeKind.STORAGE),
            eff_key_sym * A + f.cur_acct,
            jnp.where((eff_key_sym == 0)[:, None], key_num, 0).astype(U32),
        )
    else:
        leaf = jnp.zeros_like(key_sym)
    f = sf.base
    loaded = jnp.where(hit[:, None], cur, 0).astype(U32)
    loaded_sym = jnp.where(hit, cur_sym, leaf)

    # SSTORE into matching-or-free slot (shared alloc policy with the
    # concrete handler); same max-seq slot the read path selects, and
    # ANY match (incl. a seq-0 warm entry) is reused rather than
    # duplicated — only the VALUE-hit predicate above is seq-gated
    slot_id = sel
    widx, overflow = ci.storage_alloc(f, jnp.any(match, axis=1), slot_id,
                                      m & is_store)
    # SWC event records: first SSTORE after a RE-ENTERABLE external call
    # (STATICCALL/CREATE can't re-enter mutably), and first SSTORE through
    # a symbolic NON-keccak key (a direct-keccak key is a mapping access;
    # recording it would mask a later genuine arbitrary write, since only
    # the first event is kept)
    store_m = m & is_store
    first_after_call = store_m & (sf.n_mut_calls > 0) & (sf.sstore_after_call_pc < 0)
    T = sf.tape_op.shape[1]
    key_op = jnp.take_along_axis(
        sf.tape_op, jnp.clip(key_sym, 0, T - 1)[:, None], axis=1
    )[:, 0]
    key_is_hash = key_op == int(SymOp.KECCAK)
    # a demoted key has ONE reachable value on this path — not an
    # attacker-controlled arbitrary write target (eff, not key_sym)
    first_arb = store_m & (eff_key_sym != 0) & ~key_is_hash & (sf.arb_key_pc < 0)
    # SLOAD results ride the aux channel to sym_superstep's shared
    # writeback — base.stack/base.sp/stack_sym stay OUT of this claimed
    # handler's cond outputs (same traffic argument as dispatch's
    # WRITE_FIELDS: an untaken/taken cond otherwise materializes the
    # whole [P,S,8] stack at the boundary every storage superstep)
    return sf.replace(
        base=f.replace(
            st_keys=ci._write_slot(f.st_keys, widx, key_num),
            st_vals=ci._write_slot(f.st_vals, widx, val),
            st_used=ci._write_slot(f.st_used, widx, True),
            st_written=ci._write_slot(f.st_written, widx, True),
            st_acct=ci._write_slot(f.st_acct, widx, f.cur_acct),
        ).trap(overflow, Trap.STORAGE_SLOTS),
        st_key_sym=ci._write_slot(sf.st_key_sym, widx, eff_key_sym),
        st_val_sym=ci._write_slot(sf.st_val_sym, widx, val_sym),
        st_seq=ci._write_slot(sf.st_seq, widx, sf.st_seq_ctr + 1),
        st_seq_ctr=sf.st_seq_ctr + store_m.astype(I32),
        sstore_after_call_pc=jnp.where(first_after_call, f.pc, sf.sstore_after_call_pc),
        sstore_ac_cid=jnp.where(first_after_call, f.contract_id, sf.sstore_ac_cid),
        arb_key_node=jnp.where(first_arb, key_sym, sf.arb_key_node),
        arb_key_pc=jnp.where(first_arb, f.pc, sf.arb_key_pc),
        arb_key_cid=jnp.where(first_arb, f.contract_id, sf.arb_key_cid),
    ), {"r": loaded, "r_sym": loaded_sym, "w": m & ~is_store}


def _h_sym_jump(sf: SymFrontier, corpus: Corpus, op, m, old_pc, known, ksign) -> SymFrontier:
    """JUMP/JUMPI with symbolic dest and/or condition.

    - symbolic unknown condition + concrete valid dest: record a fork
      request (taken branch materialized by expand_forks) and continue on
      the fallthrough with ¬cond appended to the path condition
      (reference: ``jumpi_`` returning two states ⚠unv);
    - condition already asserted on this path: no fork, follow it;
    - symbolic dest on a (possibly) taken branch: record the node for the
      ArbitraryJump detector (SWC-127) and halt that branch.
    """
    f = sf.base
    dest_w = ci._peek(f, 0)
    dest_sym = _peek_sym(sf, 0)
    cond = ci._peek(f, 1)
    cond_sym = _peek_sym(sf, 1)
    is_jumpi = op == 0x57

    dest, valid_dest = ci.validate_jump_dest(f, corpus, dest_w)
    valid_dest = valid_dest & (dest_sym == 0)

    cond_is_sym = is_jumpi & (cond_sym != 0)
    resolved = ~is_jumpi | ~cond_is_sym | known
    taken_res = jnp.where(
        is_jumpi,
        jnp.where(cond_is_sym, ksign, ~u256.is_zero(cond)),
        True,
    )

    m_res = m & resolved
    m_fork = m & ~resolved
    # resolved, taken, symbolic dest -> SWC-127 record + halt
    sym_taken = m_res & taken_res & (dest_sym != 0)
    conc_taken = m_res & taken_res & (dest_sym == 0)
    bad = conc_taken & ~valid_dest
    # unresolved, symbolic dest: fallthrough survives; record the finding
    sym_unres = m_fork & (dest_sym != 0)
    # A concrete-but-invalid dest means the taken branch is an exceptional
    # halt (the concrete engine traps it); it is intentionally not forked —
    # matching the reference, which kills invalid-jump successors. The
    # fork also requires the ¬cond constraint write to succeed: a copy
    # whose sign-flip would hit an unrelated constraint slot would carry a
    # corrupted path condition.
    con_ok = sf.con_len < sf.con_node.shape[1]
    fork_ok = m_fork & valid_dest & con_ok
    sf = _append_constraint(sf, m_fork, cond_sym, False, old_pc)

    f = sf.base
    new_pc = jnp.where(m_res & conc_taken, dest.astype(I32), old_pc + 1)
    move = (m_res & ~bad & ~sym_taken) | m_fork
    d_sp = jnp.where(is_jumpi, 2, 1)
    return sf.replace(
        base=f.replace(
            pc=jnp.where(move, new_pc, f.pc),
            sp=jnp.where(m, f.sp - d_sp, f.sp),
            halted=f.halted | sym_taken,
        ).trap(bad, Trap.BAD_JUMP),
        sym_jump_dest=jnp.where(sym_taken | sym_unres, dest_sym, sf.sym_jump_dest),
        sym_jump_pc=jnp.where(sym_taken | sym_unres, old_pc, sf.sym_jump_pc),
        sym_jump_cid=jnp.where(sym_taken | sym_unres, f.contract_id, sf.sym_jump_cid),
        fork_req=sf.fork_req | fork_ok,
        fork_dest=jnp.where(fork_ok, dest.astype(I32), sf.fork_dest),
    )


def _note_backjump(sf: SymFrontier, mask, src, dest, loop_bound: int) -> SymFrontier:
    """Count taken BACKWARD jumps per (lane, contract, source pc, target);
    retire lanes whose revisit count exceeds ``loop_bound``.

    The frontier analog of the reference's ``BoundedLoopsStrategy``
    (``strategy/extensions/bounded_loops.py`` ⚠unv, SURVEY.md §1 row 7):
    instead of CFG-cycle counting over a work list, each lane tracks its
    hottest back-jump targets in a small table; a lane spinning past the
    bound traps with ``Trap.LOOP_BOUND`` — freeing its slot and its step
    budget for other paths instead of burning ``max_steps`` for the whole
    frontier. A miss on a full table reuses the coldest slot (heuristic:
    the hot loop is by definition the one being revisited).

    The key includes the JUMP's own pc: a shared subroutine placed before
    its call sites is entered via *distinct* backward jumps, which must
    not pool into one counter — only a repeated (src, dest) edge is a
    loop iteration."""
    if loop_bound <= 0:
        return sf
    P, LBS = sf.lb_key.shape
    key = ((sf.base.contract_id.astype(jnp.int64) * 32768 + dest) * 32768
           + src)
    live = jnp.arange(LBS)[None, :] < sf.lb_len[:, None]
    match = live & (sf.lb_key == key[:, None])
    hit = jnp.any(match, axis=1)
    hit_slot = jnp.argmax(match, axis=1).astype(I32)
    has_free = sf.lb_len < LBS
    cold = jnp.argmin(sf.lb_cnt, axis=1).astype(I32)
    slot = jnp.where(hit, hit_slot,
                     jnp.where(has_free, jnp.minimum(sf.lb_len, LBS - 1), cold))
    cur = jnp.take_along_axis(sf.lb_cnt, slot[:, None], axis=1)[:, 0]
    cnt = jnp.where(hit, cur + 1, 1)
    idx = jnp.where(mask, slot, LBS)
    return sf.replace(
        lb_key=ci._write_slot(sf.lb_key, idx, key),
        lb_cnt=ci._write_slot(sf.lb_cnt, idx, cnt),
        lb_len=sf.lb_len + (mask & ~hit & has_free).astype(I32),
        base=sf.base.trap(mask & (cnt > loop_bound), Trap.LOOP_BOUND),
    )


def _hop_count(stats, *counts):
    """``hop_stats`` with ``(column, i32[P])`` pairs added: a dense add
    (a column update is a scatter to the TPU)."""
    cols = jnp.arange(stats.shape[1])[None, :]
    for col, n in counts:
        stats = stats + jnp.where(cols == col, n.astype(I32)[:, None], 0)
    return stats


def _fr_set(arr, d, val, mask):
    """arr[P, D, ...]; arr[lane, d[lane]] = val[lane] where mask.
    Backend-adaptive (interpreter._write_slot): scatter on CPU; on TPU a
    one-hot compare-select — D is small (call_depth), so even the
    [P, D, M] frame-memory snapshots only touch D x the slice size."""
    Dn = arr.shape[1]
    idx = jnp.where(mask & (d >= 0), d, Dn).astype(I32)
    return ci._write_slot(arr, idx, val)


def _fr_get(arr, d):
    """arr[P, D, ...] gathered at per-lane depth index d."""
    idx = jnp.clip(d, 0, arr.shape[1] - 1).astype(I32)
    idxe = idx.reshape((idx.shape[0],) + (1,) * (arr.ndim - 1))
    return jnp.take_along_axis(arr, idxe, axis=1)[:, 0]


def _record_call_event(sf: SymFrontier, m, op, old_pc, to, to_sym, value,
                       value_sym) -> SymFrontier:
    """Append to the bounded per-tx call log (detection-module feed)."""
    CL = sf.call_to.shape[1]
    onehot = _event_slot(sf.n_calls, m, CL)
    return sf.replace(
        n_calls=sf.n_calls + m.astype(I32),
        n_mut_calls=sf.n_mut_calls + (
            m & ((op == 0xF1) | (op == 0xF2) | (op == 0xF4))
        ).astype(I32),
        call_to=jnp.where(onehot[:, :, None], to[:, None, :], sf.call_to),
        call_to_sym=jnp.where(onehot, to_sym[:, None], sf.call_to_sym),
        call_value=jnp.where(onehot[:, :, None], value[:, None, :], sf.call_value),
        call_value_sym=jnp.where(onehot, value_sym[:, None], sf.call_value_sym),
        call_op=jnp.where(onehot, op[:, None], sf.call_op),
        call_pc=jnp.where(onehot, old_pc[:, None], sf.call_pc),
        call_cid=jnp.where(onehot, sf.base.contract_id[:, None], sf.call_cid),
    )


def _h_sym_call(sf: SymFrontier, corpus: Corpus, op, m, old_pc,
                spec: SymSpec, limits: LimitsConfig) -> SymFrontier:
    """CALL / CALLCODE / DELEGATECALL / STATICCALL with real sub-frames.

    Reference: ``call_`` raising TransactionStartSignal + ``call.py``'s
    callee resolution (``mythril/laser/ethereum/{instructions,call}.py``
    ⚠unv, SURVEY.md §3.2). Three outcomes per lane:

    - **internal**: concrete callee resolving to a corpus account with
      code, concrete arg/ret windows, concrete (or absent) value, depth
      headroom → push a frame and start executing the callee at pc 0;
    - **eoa**: concrete callee that is a known codeless account → value
      transfer + success=1 (no code to run);
    - **external** (everything else: symbolic callee, unknown address,
      symbolic value/windows, depth exhausted): havoc the return value
      and output memory — the round-1 over-approximation, now the
      fallback instead of the only path.
    """
    f = sf.base
    has_value = (op == 0xF1) | (op == 0xF2)  # CALL, CALLCODE
    is_call = op == 0xF1
    is_deleg = op == 0xF4
    is_static_op = op == 0xFA
    sin = ci._J_STACK_IN[op]
    D = f.fr_ret_pc.shape[1]
    CD = f.calldata.shape[1]
    CDW = sf.cd_sym.shape[1]
    M = f.memory.shape[1]

    # --- operand fetch (gas, to, [value], argsOff, argsLen, retOff, retLen)
    to = ci._peek(f, 1)
    to_sym = _peek_sym(sf, 1)
    value = jnp.where(has_value[:, None], ci._peek(f, 2), 0).astype(U32)
    value_sym = jnp.where(has_value, _peek_sym(sf, 2), 0)
    base_i = jnp.where(has_value, 3, 2)
    a_off_w, a_off_s = ci._peek(f, base_i), _peek_sym(sf, base_i)
    a_len_w, a_len_s = ci._peek(f, base_i + 1), _peek_sym(sf, base_i + 1)
    r_off_w, r_off_s = ci._peek(f, base_i + 2), _peek_sym(sf, base_i + 2)
    r_len_w, r_len_s = ci._peek(f, base_i + 3), _peek_sym(sf, base_i + 3)
    a_off = u256.to_u64_saturating(a_off_w).astype(I64)
    a_len = u256.to_u64_saturating(a_len_w).astype(I64)
    r_off = u256.to_u64_saturating(r_off_w).astype(I64)
    r_len = u256.to_u64_saturating(r_len_w).astype(I64)

    # CALL with nonzero (possibly) value inside STATICCALL: exceptional halt
    static_viol = m & is_call & f.static & (
        (value_sym != 0) | ~u256.is_zero(value)
    )
    sf = sf.replace(base=f.trap(static_viol, Trap.STATIC_WRITE))
    f = sf.base
    m = m & ~static_viol

    # --- classification
    conc_windows = (a_off_s == 0) & (a_len_s == 0) & (r_off_s == 0) & (r_len_s == 0)
    found, slot = f.acct_lookup(to)
    callee_code = f.acct_field(f.acct_code, slot)
    value_conc = value_sym == 0
    # precompiles 0x1-0x9 (reference: natives.py dispatch in call.py ⚠unv):
    # concrete low address, concrete windows; handled without a frame.
    # Value transfers to precompile addresses are not tracked (documented).
    hi_zero = jnp.all(to[:, 1:] == 0, axis=1)
    pid = jnp.where((to_sym == 0) & hi_zero, to[:, 0].astype(I32), 0)
    RD_cap = f.returndata.shape[1]
    pre = m & (pid >= 1) & (pid <= 9) & conc_windows & (
        a_len <= min(M, PRE_IN_CAP))
    # identity output = input: if it can't fit the returndata buffer the
    # concrete result would silently truncate — demote to external havoc
    pre = pre & ~((pid == 4) & (a_len > RD_cap))
    resolvable = (
        m & (to_sym == 0) & found & conc_windows & value_conc
        & (f.depth < D) & (a_len <= CD)
    )
    internal = resolvable & (callee_code >= 0)
    eoa = resolvable & (callee_code == -1)  # CODE_UNKNOWN (-2) -> external

    # --- symbolic-callee enumeration (reference:
    # ``call.py get_call_parameters`` resolving a symbolic callee via
    # constraints ⚠unv, SURVEY §3.2). A CALL whose target word is
    # symbolic — every proxy/registry pattern — forks ONE candidate
    # account per superstep instead of havocking: the fork copy
    # re-executes this CALL with the target stack slot concretized to
    # acct_addr[k] under the path constraint to == addr_k (expand_forks
    # flips the appended constraint sign for the copy and applies the
    # fork_cslot/fork_cval concretization); the staying lane accumulates
    # ¬(to == addr_k) and, once the table is exhausted, falls through to
    # the external-havoc path carrying "to != every known account".
    # Symbolic value / symbolic windows / exhausted depth still havoc.
    A_n = f.acct_used.shape[1]
    enumable = (
        m & (to_sym != 0) & conc_windows & value_conc
        & (f.depth < D) & (a_len <= CD)
    )
    k_cand = jnp.clip(sf.call_enum, 0, A_n - 1)
    cand_valid = sf.call_enum < A_n
    slot_used = jnp.take_along_axis(f.acct_used, k_cand[:, None], axis=1)[:, 0]
    enum_spawn = (enumable & cand_valid & slot_used
                  & (sf.con_len < sf.con_node.shape[1]))
    # a GAP in the table (e.g. a reverted create unregistered its slot)
    # advances the scan without spawning; exhausted counter (or a full
    # constraint store) resolves to the external fallback
    enum_skip = enumable & cand_valid & ~slot_used
    enum_done = enumable & ~enum_spawn & ~enum_skip
    enum_hold = enum_spawn | enum_skip
    cand_addr = f.acct_field(f.acct_addr, k_cand)
    sf, caddr_id = append_node(sf, enum_spawn, int(SymOp.CONST), 0, 0,
                               cand_addr)
    sf, eq_id = append_node(sf, enum_spawn, int(SymOp.EQ), to_sym, caddr_id)
    sf = _append_constraint(sf, enum_spawn, eq_id, False, old_pc)
    sf = sf.replace(
        call_enum=jnp.where(enum_hold, sf.call_enum + 1,
                            jnp.where(enum_done, 0, sf.call_enum)),
        fork_req=sf.fork_req | enum_spawn,
        fork_dest=jnp.where(enum_spawn, old_pc, sf.fork_dest),
        fork_cslot=jnp.where(enum_spawn, f.sp - 2, sf.fork_cslot),
        fork_cval=jnp.where(enum_spawn[:, None], cand_addr, sf.fork_cval),
    )
    f = sf.base

    # a parked lane re-executes this CALL next superstep — the prologue's
    # base charge must not accumulate once per retry
    berlin = limits.gas_schedule == "berlin"
    gmin_t = ci._J_GAS_MIN_BERLIN if berlin else ci._J_GAS_MIN
    gmax_t = ci._J_GAS_MAX_BERLIN if berlin else ci._J_GAS_MAX
    # the static table charges the worst case (value transfer + new
    # account); refine for concretely-known cases so a fully concrete
    # call has exact gas (min == max): zero value never pays the 9000
    # transfer or 25000 new-account surcharge; a nonzero transfer to an
    # EXISTING account drops the 25000
    nonzero_val = has_value & value_conc & ~u256.is_zero(value)
    zero_val = has_value & value_conc & ~nonzero_val
    refund = jnp.where(is_call & zero_val, 9000 + 25000, 0)
    # the existing-account refund needs a CONCRETE target: a symbolic
    # call's true target can be outside the table (a fresh account that
    # does pay the 25000) even when its concrete shadow matches a row
    refund = jnp.where(is_call & nonzero_val & found & (to_sym == 0),
                       25000, refund)
    refund = jnp.where((op == 0xF2) & zero_val, 9000, refund)
    # berlin: a symbolic target that exhausted enumeration resolves here
    # (external havoc) without ever paying its cold-account surcharge —
    # its true target is provably outside the (warm-trackable) table
    ext_cold = 0
    if berlin:
        from ..disassembler.opcodes import G_COLD_ACCOUNT, G_WARM_ACCESS
        ext_sym = m & ~internal & ~eoa & ~pre & ~enum_hold & (to_sym != 0)
        ext_cold = jnp.where(ext_sym, G_COLD_ACCOUNT - G_WARM_ACCESS, 0)
    f = f.replace(
        gas_min=f.gas_min - jnp.where(enum_hold, gmin_t[op], 0),
        gas_max=f.gas_max + ext_cold
        - jnp.where(enum_hold, gmax_t[op], jnp.where(m, refund, 0)),
    )
    if f.op_hist is not None:
        # iprof mirrors the gas un-charge: a parked enumeration superstep
        # is bookkeeping, not an executed instance — net out epilogue's +1
        # so only the resolving superstep counts the CALL once
        f = f.replace(op_hist=ci._hist_add(
            f.op_hist, op, -enum_hold.astype(I32)))
    sf = sf.replace(base=f)

    external = m & ~internal & ~eoa & ~pre & ~enum_hold

    # memory expansion for the arg/ret windows (charged at call time)
    f = sf.base
    f, oob_a = ci._expand_memory(f, (internal | eoa | pre) & (a_len > 0), a_off + a_len)
    f, oob_r = ci._expand_memory(f, (internal | eoa | pre) & (r_len > 0), r_off + r_len)
    sf = sf.replace(base=f)
    oob = oob_a | oob_r
    internal = internal & ~oob
    eoa = eoa & ~oob
    pre = pre & ~oob

    # --- value transfer feasibility (concrete value; payer = executing acct)
    payer_bal = f.self_balance
    wants_value = has_value & ~u256.is_zero(value)
    insufficient = (internal | eoa) & wants_value & u256.lt(payer_bal, value)
    fail0 = insufficient  # push success=0, no frame, no transfer
    internal_go = internal & ~insufficient
    eoa_ok = eoa & ~insufficient
    # CALLCODE sends value to self (net zero); only plain CALL moves funds
    transfer = (internal_go | eoa_ok) & is_call & wants_value & (slot != f.cur_acct)
    # rollback snapshot must be PRE-transfer: a reverting value call undoes
    # the transfer (reference: world-state checkpoint restore ⚠unv)
    pre_transfer_bal = f.acct_bal
    payee_bal = f.acct_field(f.acct_bal, slot)
    payer_new = u256.sub(payer_bal, value)
    payee_new = u256.add(payee_bal, value)
    A = f.acct_used.shape[1]
    payer_oh = (jnp.arange(A)[None, :] == f.cur_acct[:, None]) & transfer[:, None]
    payee_oh = (jnp.arange(A)[None, :] == slot[:, None]) & transfer[:, None]
    acct_bal = jnp.where(payer_oh[:, :, None], payer_new[:, None, :], f.acct_bal)
    acct_bal = jnp.where(payee_oh[:, :, None], payee_new[:, None, :], acct_bal)
    f = f.replace(acct_bal=acct_bal)
    # the balance table changed: BALANCE reads after this point must not
    # share leaves with reads before it
    sf = sf.replace(base=f, bal_epoch=sf.bal_epoch + transfer.astype(I32))

    # --- event record for every path (modules consume this); a lane still
    # enumerating candidate callees records nothing yet — it records when
    # it finally resolves (each fork copy re-executes and records its own)
    sf = _record_call_event(sf, m & ~enum_hold, op, old_pc, to.astype(U32),
                            to_sym, value, value_sym)
    f = sf.base

    # --- external fallback: havoc retval + output region
    havoc_mem = external & ((r_len_s != 0) | ~u256.is_zero(r_len_w))
    sf, rv = append_node(sf, external, int(SymOp.FREE), int(FreeKind.RETVAL),
                         jnp.maximum(sf.n_calls - 1, 0))
    f = sf.base

    # DELEGATECALL msg.sender symbol for a top-frame push: the CURRENT
    # transaction's CALLER leaf — keyed by tx_id like the overlay's top
    # frame reads, so the delegated code constrains the same symbol the
    # witness renders (hash-consing dedups onto the seeded tx-0 leaf)
    deleg_caller = jnp.zeros_like(to_sym)
    if spec.caller:
        need_dc = internal_go & is_deleg & (f.depth == 0)
        sf, deleg_caller = append_node(sf, need_dc, int(SymOp.FREE),
                                       int(FreeKind.CALLER), sf.tx_id)
        f = sf.base

    # --- push the result word for the non-frame paths
    dest_slot = f.sp - sin
    m_push = external | eoa_ok | fail0 | pre
    one_w = jnp.zeros_like(to).at[:, 0].set(1)
    zero_w = jnp.zeros_like(to)
    res_w = jnp.where((eoa_ok | pre)[:, None], one_w, zero_w).astype(U32)
    stack = ci._set_slot(f.stack, dest_slot, res_w, m_push)
    res_sym = jnp.where(external, rv, 0)
    stack_sym = _set_sym_slot(sf.stack_sym, dest_slot, res_sym, m_push)

    # --- frame push for internal calls
    d = f.depth
    mi = internal_go
    # a partly covered tail word of the callee's calldata with symbolic
    # content is unknown on its own (the end of the last ABI argument
    # lies in it): one fresh leaf, not the whole frame's calldata
    aligned_a = (a_off % 32) == 0
    w0 = (a_off // 32).astype(I32)
    tail_w = (a_len // 32).astype(I32)
    tail_sym = aligned_a & ((a_len % 32) != 0) & (
        _take_word_sym(sf.mem_sym, w0 + tail_w) != 0)
    sf, tail_hv = _havoc(sf, mi & tail_sym)
    f = sf.base
    # EIP-150 gas forwarding: the callee runs under
    # used + min(gas operand, 63/64 * remaining); a symbolic gas operand
    # forwards the cap (all-but-one-64th). pop_frames restores the
    # caller's ceiling and, on exceptional failure, burns the whole
    # forwarded amount (a REVERT keeps only what the callee spent).
    gas_op = u256.to_u64_saturating(ci._peek(f, 0)).astype(I64)
    gas_op_sym = _peek_sym(sf, 0)
    remaining = jnp.maximum(f.gas_limit - f.gas_max, 0)
    fwd_cap = remaining - remaining // 64
    fwd = jnp.where(gas_op_sym == 0, jnp.minimum(gas_op, fwd_cap), fwd_cap)
    f2 = f.replace(
        fr_gas_limit=_fr_set(f.fr_gas_limit, d, f.gas_limit, mi),
        gas_limit=jnp.where(mi, f.gas_max + fwd, f.gas_limit),
        fr_warm_acct=_fr_set(f.fr_warm_acct, d, f.warm_acct, mi),
        fr_st_warm=_fr_set(f.fr_st_warm, d, f.st_warm, mi),
        fr_ret_pc=_fr_set(f.fr_ret_pc, d, old_pc, mi),
        fr_sp=_fr_set(f.fr_sp, d, f.sp - sin, mi),
        fr_sp_base=_fr_set(f.fr_sp_base, d, f.sp_base, mi),
        fr_static=_fr_set(f.fr_static, d, f.static, mi),
        fr_cur_acct=_fr_set(f.fr_cur_acct, d, f.cur_acct, mi),
        fr_contract_id=_fr_set(f.fr_contract_id, d, f.contract_id, mi),
        fr_caller_addr=_fr_set(f.fr_caller_addr, d, f.caller_addr, mi),
        fr_callvalue=_fr_set(f.fr_callvalue, d, f.callvalue, mi),
        fr_memory=_fr_set(f.fr_memory, d, f.memory, mi),
        fr_mem_words=_fr_set(f.fr_mem_words, d, f.mem_words, mi),
        fr_calldata=_fr_set(f.fr_calldata, d, f.calldata, mi),
        fr_calldata_len=_fr_set(f.fr_calldata_len, d, f.calldata_len, mi),
        fr_ret_off=_fr_set(f.fr_ret_off, d, r_off, mi),
        fr_ret_len=_fr_set(f.fr_ret_len, d, r_len, mi),
        fr_gas_min=_fr_set(f.fr_gas_min, d, f.gas_min, mi),
        fr_gas_max=_fr_set(f.fr_gas_max, d, f.gas_max, mi),
        fr_st_keys=_fr_set(f.fr_st_keys, d, f.st_keys, mi),
        fr_st_vals=_fr_set(f.fr_st_vals, d, f.st_vals, mi),
        fr_st_used=_fr_set(f.fr_st_used, d, f.st_used, mi),
        fr_st_written=_fr_set(f.fr_st_written, d, f.st_written, mi),
        fr_st_acct=_fr_set(f.fr_st_acct, d, f.st_acct, mi),
        fr_acct_bal=_fr_set(f.fr_acct_bal, d, pre_transfer_bal, mi),
        # ordinary call frame — not constructing an account (a stale slot
        # from a popped CREATE frame at this depth must not leak in)
        fr_create_slot=_fr_set(f.fr_create_slot, d,
                               jnp.full((f.n_lanes,), -1, dtype=I32), mi),
    )

    # callee calldata: bytes from the caller's memory window
    callee_cd = ci._gather_bytes(f.memory, a_off, CD, jnp.full_like(a_off, M))
    callee_cd = jnp.where(jnp.arange(CD)[None, :] < a_len[:, None], callee_cd, 0)
    # per-word syms: aligned windows map caller mem_sym; a partially
    # covered tail word or unaligned offset with symbolic content havocs
    # the whole frame calldata (coarse, sound)
    W = sf.mem_sym.shape[1]
    wids = jnp.arange(W)[None, :]
    win_lo = (a_off // 32)[:, None]
    win_hi = ((a_off + a_len + 31) // 32)[:, None]
    any_sym_window = jnp.any(
        (wids >= win_lo) & (wids < win_hi) & (sf.mem_sym != 0), axis=1
    )
    cd_havoc_new = (_reaches_floor(sf, a_off, a_len)
                    | (~aligned_a & any_sym_window))
    cd_sym_new = jnp.zeros_like(sf.cd_sym)
    cd_usym_new = jnp.zeros_like(sf.cd_usym)
    ush = sf.mem_ushift.astype(I64)
    for w in range(CDW):
        full_cover = aligned_a & ((32 * (w + 1)) <= a_len)
        src = _take_word_sym(sf.mem_sym, w0 + w)
        cd_sym_new = cd_sym_new.at[:, w].set(
            jnp.where(mi & ~cd_havoc_new,
                      jnp.where(full_cover, src,
                                jnp.where(tail_sym & (tail_w == w),
                                          tail_hv, 0)), 0)
        )
        # the words stored whole at the lane's shift, inside the window
        whole_in = aligned_a & (ush > 0) & ((32 * (w + 1)) + ush <= a_len)
        cd_usym_new = cd_usym_new.at[:, w].set(
            jnp.where(mi & whole_in & ~cd_havoc_new,
                      _take_word_sym(sf.mem_usym, w0 + w), 0))
    cd_uhead_new = (mi & aligned_a & ~cd_havoc_new & (sf.mem_uhead == w0)
                    & (a_len >= ush))
    # calls to a member of the lane's world with code, and those of them
    # a limit sent down the external path
    member = m & ~enum_hold & (to_sym == 0) & found & (callee_code >= 0)
    hop_stats = _hop_count(
        sf.hop_stats, (HOP_INTERNAL, internal_go), (HOP_EOA, eoa_ok),
        (HOP_PRECOMPILE, pre), (HOP_EXTERNAL, external),
        (HOP_MEMBER, member), (HOP_TRAPPED, member & external))
    # the deepest frame of the path: a maximum, not a count
    hop_stats = jnp.where(
        jnp.arange(N_HOP)[None, :] == HOP_DEPTH,
        jnp.maximum(hop_stats, jnp.where(mi, f.depth + 1, 0)[:, None]),
        hop_stats)

    new_caller = jnp.where(is_deleg[:, None], f.caller_addr, f.self_address).astype(U32)
    new_value = jnp.where(
        is_deleg[:, None], f.callvalue,
        jnp.where(has_value[:, None], value, 0),
    ).astype(U32)
    new_value_sym = jnp.where(is_deleg, sf.callvalue_sym, 0)
    # a DELEGATECALL frame inherits the caller frame's msg.sender symbol:
    # at the top frame that is the current tx's CALLER leaf (when
    # symbolic), deeper it is whatever the frame carried — sender checks
    # inside delegated code must see the same symbol the top-frame model
    # exposes
    eff_caller_sym = sf.caller_sym
    if spec.caller:
        eff_caller_sym = jnp.where(f.depth == 0, deleg_caller, eff_caller_sym)
    new_caller_sym = jnp.where(is_deleg, eff_caller_sym, 0)
    keep_acct = is_deleg | (op == 0xF2)  # DELEGATECALL/CALLCODE keep storage ctx

    f2 = f2.replace(
        pc=jnp.where(mi, 0, f2.pc),
        # enum lanes stay parked on this CALL (one candidate per superstep)
        pc_hold=f2.pc_hold | mi | enum_hold,
        sp=jnp.where(mi | m_push, f.sp - sin + m_push.astype(I32), f2.sp),
        sp_base=jnp.where(mi, f.sp - sin, f2.sp_base),
        depth=jnp.where(mi, f.depth + 1, f2.depth),
        contract_id=jnp.where(mi, callee_code, f2.contract_id),
        cur_acct=jnp.where(mi, jnp.where(keep_acct, f.cur_acct, slot), f2.cur_acct),
        caller_addr=jnp.where(mi[:, None], new_caller, f2.caller_addr),
        callvalue=jnp.where(mi[:, None], new_value, f2.callvalue),
        static=f2.static | (mi & is_static_op),
        memory=jnp.where(mi[:, None], 0, f2.memory),
        mem_words=jnp.where(mi, 0, f2.mem_words),
        calldata=jnp.where(mi[:, None], callee_cd, f2.calldata),
        calldata_len=jnp.where(mi, jnp.clip(a_len, 0, CD).astype(I32), f2.calldata_len),
        returndata_len=jnp.where(mi | m_push, 0, f2.returndata_len),
        stack=stack,
    )
    sf = sf.replace(
        base=f2,
        stack_sym=stack_sym,
        mem_sym=jnp.where(mi[:, None], 0, sf.mem_sym),
        # the callee starts on fresh memory; a precompile writes its
        # output into the caller's (the caller's own whole words end when
        # the frame pops: ``pop_frames``)
        mem_usym=jnp.where((mi | pre)[:, None], 0, sf.mem_usym),
        mem_ushift=jnp.where(mi, 0, sf.mem_ushift),
        mem_uhead=jnp.where(mi | pre, -1, sf.mem_uhead),
        hop_stats=hop_stats,
        mem_floor=jnp.where(mi, MEM_EXACT, _lower_floor(
            sf.mem_floor, havoc_mem, _dest_floor_word(sf, r_off, r_off_s))),
        retdata_sym=jnp.where(mi | eoa_ok | fail0, False,
                              sf.retdata_sym | external),
        cd_from_mem=sf.cd_from_mem | mi,
        cd_havoc=jnp.where(mi, cd_havoc_new, sf.cd_havoc),
        cd_sym=jnp.where(mi[:, None], cd_sym_new, sf.cd_sym),
        cd_usym=jnp.where(mi[:, None], cd_usym_new, sf.cd_usym),
        cd_ushift=jnp.where(mi, sf.mem_ushift, sf.cd_ushift),
        cd_uhead=jnp.where(mi, cd_uhead_new, sf.cd_uhead),
        fr_cd_usym=_fr_set(sf.fr_cd_usym, d, sf.cd_usym, mi),
        fr_cd_ushift=_fr_set(sf.fr_cd_ushift, d, sf.cd_ushift, mi),
        fr_cd_uhead=_fr_set(sf.fr_cd_uhead, d, sf.cd_uhead, mi),
        callvalue_sym=jnp.where(mi, new_value_sym, sf.callvalue_sym),
        caller_sym=jnp.where(mi, new_caller_sym, sf.caller_sym),
        fr_caller_sym=_fr_set(sf.fr_caller_sym, d, sf.caller_sym, mi),
        fr_mem_sym=_fr_set(sf.fr_mem_sym, d, sf.mem_sym, mi),
        fr_mem_floor=_fr_set(sf.fr_mem_floor, d, sf.mem_floor, mi),
        fr_cd_from_mem=_fr_set(sf.fr_cd_from_mem, d, sf.cd_from_mem, mi),
        fr_cd_havoc=_fr_set(sf.fr_cd_havoc, d, sf.cd_havoc, mi),
        fr_cd_sym=_fr_set(sf.fr_cd_sym, d, sf.cd_sym, mi),
        fr_callvalue_sym=_fr_set(sf.fr_callvalue_sym, d, sf.callvalue_sym, mi),
        fr_st_val_sym=_fr_set(sf.fr_st_val_sym, d, sf.st_val_sym, mi),
        fr_st_key_sym=_fr_set(sf.fr_st_key_sym, d, sf.st_key_sym, mi),
        fr_st_seq=_fr_set(sf.fr_st_seq, d, sf.st_seq, mi),
    )
    # precompile outputs land after the common bookkeeping so they can
    # override the pushed-result defaults for their lanes
    return lax.cond(
        jnp.any(pre),
        lambda s: _apply_precompiles(s, pre, pid, a_off, a_len, r_off, r_len,
                                     spec),
        lambda s: s,
        sf,
    )


CREATE_ADDR_BASE = 0xC0DE00000000  # fresh pseudo-addresses for CREATE results


PRE_IN_CAP = 448  # precompile input window cap (modexp header + 3x32-byte
# operands = 192; a 2-pair ECPAIRING check — the common signature-verify
# shape — is 384; sha256/identity accept up to this; longer inputs fall
# to the external-havoc path, counted like any unresolved call)


def _be_window_word(buf, start, width, INW: int):
    """u256 word from `width[P]` big-endian bytes at `start[P]` of buf[P,INW]
    (right-aligned: value = int.from_bytes(buf[start:start+width]))."""
    I = jnp.int64
    s = start.astype(I) + width.astype(I) - 32
    raw = ci._gather_bytes(buf, s, 32, jnp.full_like(s, INW))
    k = jnp.arange(32)[None, :]
    valid = (s[:, None] + k) >= start[:, None].astype(I)
    return ci._be_bytes_to_word(jnp.where(valid, raw, 0))


def _apply_precompiles(sf: SymFrontier, pre, pid, a_off, a_len, r_off,
                       r_len, spec: SymSpec = SymSpec()) -> SymFrontier:
    """Execute precompile calls 0x1-0x9 for the `pre` lanes.

    Reference: ``mythril/laser/ethereum/natives.py`` (⚠unv) — all nine
    computed concretely there; same here:

    - 0x2 sha256: device kernel on concrete input;
    - 0x4 identity: byte copy;
    - 0x5 modexp: device square-and-multiply for <= 32-byte operands;
    - 0x1 ecrecover: host callback (ops/secp256k1) on concrete input,
      uninterpreted ECRECOVER leaf per call site otherwise;
    - 0x3 ripemd160, 0x6/0x7/0x8 alt_bn128 add/mul/pairing, 0x9 blake2f:
      one batched host callback (ops/natives_host) on concrete input.
      A malformed input (off-curve point, bad blake2f length/flag) FAILS
      the call: success word rewritten to 0, empty returndata — the one
      precompile-failure channel the EVM has. A blake2f rounds word past
      ``BLAKE2F_MAX_ROUNDS`` falls to the sound havoc leaf instead of
      stalling the host (DoS fence, documented there).

    Symbolic input bytes demote every concrete case to the leaf path.
    Gas: per-native schedules charged below (modexp the EIP-2565 floor
    only — its input-dependent formula is not modeled, documented).
    """
    f = sf.base
    P, M = f.memory.shape
    RD = f.returndata.shape[1]
    INW = min(M, PRE_IN_CAP)  # static input gather width (pre <= this)
    W = sf.mem_sym.shape[1]

    wids = jnp.arange(W)[None, :]
    win_lo = (a_off // 32)[:, None]
    win_hi = ((a_off + a_len + 31) // 32)[:, None]
    sym_in = (_reaches_floor(sf, a_off, a_len) | jnp.any(
        (wids >= win_lo) & (wids < win_hi) & (sf.mem_sym != 0), axis=1
    )) & (a_len > 0)

    inp = ci._gather_bytes(f.memory, a_off, INW, jnp.full_like(a_off, M))
    inp = jnp.where(jnp.arange(INW)[None, :] < a_len[:, None], inp, 0)

    conc = pre & ~sym_in
    # trace-time capability gate (ops/callbacks.py): on a runtime
    # without host callbacks, concrete ecrecover and the
    # ripemd/bn128/blake2f natives degrade to the sound leaf path
    from ..ops.callbacks import host_callbacks_supported
    cb_ok = host_callbacks_supported()
    m_sha = conc & (pid == 2)
    m_id = conc & (pid == 4)
    m_ecr = conc & (pid == 1) & cb_ok

    # modexp header: three 32-byte big-endian lengths
    blen = u256.to_u64_saturating(ci._be_bytes_to_word(inp[:, 0:32])).astype(I64)
    elen = u256.to_u64_saturating(ci._be_bytes_to_word(inp[:, 32:64])).astype(I64)
    mlen = u256.to_u64_saturating(ci._be_bytes_to_word(inp[:, 64:96])).astype(I64)
    # the u64->i64 cast can wrap huge headers negative — a negative length
    # must NOT pass the <=32 window check (it would read garbage offsets)
    fits = ((blen >= 0) & (blen <= 32) & (elen >= 0) & (elen <= 32)
            & (mlen >= 0) & (mlen <= 32)
            & (96 + blen + elen + mlen <= a_len))
    m_mod = conc & (pid == 5) & fits
    # blake2f rounds word (first 4 input bytes, big-endian) read on device
    # so an attacker-size rounds count routes to the leaf, not the host
    rounds = ((inp[:, 0].astype(I64) << 24) | (inp[:, 1].astype(I64) << 16)
              | (inp[:, 2].astype(I64) << 8) | inp[:, 3].astype(I64))
    from ..ops.natives_host import BLAKE2F_MAX_ROUNDS
    m_host = conc & cb_ok & (
        (pid == 3) | (pid == 6) | (pid == 7) | (pid == 8)
        | ((pid == 9) & (rounds <= BLAKE2F_MAX_ROUNDS))
    )
    if RD < 64:  # tiny test shapes: no room for the 64-byte outputs
        m_host = m_host & (pid == 3)
    m_leaf = pre & ~m_sha & ~m_id & ~m_mod & ~m_ecr & ~m_host

    # concrete ecrecover via host callback (reference
    # uses libsecp256k1 ⚠unv — here ops/secp256k1, pure Python, memoized).
    # Invalid signatures return EMPTY output, exactly like the precompile.
    def _host_ecr(inp_np, mask_np):
        import numpy as np

        from ..ops.secp256k1 import ecrecover_batch

        res = np.zeros((inp_np.shape[0], 32), dtype=np.uint8)
        ok = np.zeros(inp_np.shape[0], dtype=bool)
        idx = np.where(mask_np)[0]
        for i, addr in zip(idx, ecrecover_batch(inp_np[idx, :128])):
            if addr is not None:
                res[i] = np.frombuffer(addr.to_bytes(32, "big"), np.uint8)
                ok[i] = True
        return res, ok

    # ripemd160 / bn128 / blake2f: one batched host callback (rare path,
    # gated like ecrecover). ok=False = the precompile call itself fails.
    def _host_nat(inp_np, pid_np, alen_np, mask_np):
        from ..ops.natives_host import natives_batch

        return natives_batch(inp_np, pid_np, alen_np, mask_np)

    def _cb_local(inp_l, m_ecr_l, pid_l, a_len_l, m_host_l):
        """Both precompile callbacks over a (shard-)local lane block.

        Under shard_map each device round-trips only its own lanes (and
        the per-shard ``any`` gate skips the host hop entirely on shards
        with no precompile lane); without a mesh this is the whole
        frontier, identical to the pre-round-5 single-device behavior.
        """
        Pl = inp_l.shape[0]

        @jax.named_scope("host_ecrecover")
        def _run_ecr(_):
            return jax.pure_callback(
                _host_ecr,
                (jax.ShapeDtypeStruct((Pl, 32), jnp.uint8),
                 jax.ShapeDtypeStruct((Pl,), jnp.bool_)),
                inp_l, m_ecr_l,
            )

        ecr_b, ecr_k = lax.cond(
            jnp.any(m_ecr_l), _run_ecr,
            lambda _: (jnp.zeros((Pl, 32), dtype=jnp.uint8),
                       jnp.zeros((Pl,), dtype=jnp.bool_)),
            0,
        )

        @jax.named_scope("host_natives")
        def _run_nat(_):
            return jax.pure_callback(
                _host_nat,
                (jax.ShapeDtypeStruct((Pl, 64), jnp.uint8),
                 jax.ShapeDtypeStruct((Pl,), jnp.int32),
                 jax.ShapeDtypeStruct((Pl,), jnp.bool_)),
                inp_l, pid_l, a_len_l, m_host_l,
            )

        nat_b, nat_n, nat_k = lax.cond(
            jnp.any(m_host_l), _run_nat,
            lambda _: (jnp.zeros((Pl, 64), dtype=jnp.uint8),
                       jnp.zeros((Pl,), dtype=jnp.int32),
                       jnp.zeros((Pl,), dtype=jnp.bool_)),
            0,
        )
        return ecr_b, ecr_k, nat_b, nat_n, nat_k

    # `if cb_ok` (a trace-time Python bool) keeps the callback custom-call
    # OUT of the traced program entirely on runtimes that reject it —
    # even an un-taken cond branch containing it would fail to compile
    if cb_ok:
        if spec.mesh is not None:
            from jax.sharding import PartitionSpec as _PS
            lane = _PS(spec.lane_axis)
            lane2 = _PS(spec.lane_axis, None)
            ecr_bytes, ecr_ok, nat_bytes, nat_len, nat_ok = jax.shard_map(
                _cb_local, mesh=spec.mesh,
                in_specs=(lane2, lane, lane, lane, lane),
                out_specs=(lane2, lane, lane2, lane, lane),
                check_vma=False,
            )(inp, m_ecr, pid, a_len, m_host)
        else:
            ecr_bytes, ecr_ok, nat_bytes, nat_len, nat_ok = _cb_local(
                inp, m_ecr, pid, a_len, m_host)
    else:
        ecr_bytes = jnp.zeros((P, 32), dtype=jnp.uint8)
        ecr_ok = jnp.zeros((P,), dtype=jnp.bool_)
        nat_bytes = jnp.zeros((P, 64), dtype=jnp.uint8)
        nat_len = jnp.zeros((P,), dtype=jnp.int32)
        nat_ok = jnp.zeros((P,), dtype=jnp.bool_)
    m_hok = m_host & nat_ok
    m_hfail = m_host & ~nat_ok

    from ..ops.sha256 import sha256_device
    sha_w = lax.cond(
        jnp.any(m_sha),
        lambda: sha256_device(inp, jnp.clip(a_len, 0, INW).astype(I32)),
        lambda: jnp.zeros((P, 8), dtype=U32),
    )
    mod_w = lax.cond(
        jnp.any(m_mod),
        lambda: u256.modexp(
            _be_window_word(inp, jnp.full_like(blen, 96), blen, INW),
            _be_window_word(inp, 96 + blen, elen, INW),
            _be_window_word(inp, 96 + blen + elen, mlen, INW),
        ),
        lambda: jnp.zeros((P, 8), dtype=U32),
    )

    # precompile gas (reference: natives.py per-native schedules ⚠unv);
    # modexp charges the EIP-2565 floor — its full input-dependent
    # formula is not modeled (documented); pairing is the EIP-1108
    # per-pair schedule; blake2f charges its concrete rounds word
    words = (a_len + 31) // 32
    pcost = jnp.select(
        [pid == 1, pid == 2, pid == 3, pid == 4, pid == 5,
         pid == 6, pid == 7, pid == 8, pid == 9],
        [3000, 60 + 12 * words, 600 + 120 * words, 15 + 3 * words,
         jnp.full_like(words, 200), jnp.full_like(words, 150),
         jnp.full_like(words, 6000), 45000 + 34000 * (a_len // 192),
         rounds],
        default=jnp.zeros_like(words),
    )
    f = ci._charge(f, pre, pcost)
    sf = sf.replace(base=f)

    # leaf result node (hash-consed per call site via the call index)
    kind = jnp.where(pid == 1, int(FreeKind.ECRECOVER), int(FreeKind.PRECOMPILE))
    sf, leaf = append_node(sf, m_leaf, int(SymOp.FREE), kind,
                           jnp.maximum(sf.n_calls - 1, 0))
    f = sf.base

    # output byte image (concrete cases) + logical output length
    out_len = jnp.where(pid == 4, jnp.minimum(a_len, RD),
                        jnp.where(pid == 5, mlen,
                                  jnp.where((pid == 6) | (pid == 7) | (pid == 9),
                                            64, 32))).astype(I64)
    out_len = jnp.where(m_ecr, jnp.where(ecr_ok, 32, 0), out_len)
    out_len = jnp.where(m_host, jnp.where(nat_ok, nat_len, 0).astype(I64),
                        out_len)
    out = jnp.where(m_id[:, None], inp[:, :RD] if INW >= RD else
                    jnp.pad(inp, ((0, 0), (0, RD - INW))), 0).astype(jnp.uint8)
    sha_bytes = ci._word_to_be_bytes(sha_w)  # u8[P,32]
    mod_be = ci._word_to_be_bytes(mod_w)
    # modexp output is the result right-aligned in mlen bytes
    kk = jnp.arange(RD, dtype=I64)[None, :]
    mod_src = jnp.clip(32 - mlen[:, None] + kk, 0, 31).astype(I32)
    mod_bytes = jnp.take_along_axis(
        jnp.pad(mod_be, ((0, 0), (0, max(0, RD - 32)))),
        jnp.minimum(mod_src, 31), axis=1)
    head = kk < 32
    out = jnp.where((m_sha[:, None] & head),
                    jnp.pad(sha_bytes, ((0, 0), (0, max(0, RD - 32)))), out)
    out = jnp.where(m_mod[:, None] & (kk < mlen[:, None]), mod_bytes, out)
    out = jnp.where((m_ecr & ecr_ok)[:, None] & head,
                    jnp.pad(ecr_bytes, ((0, 0), (0, max(0, RD - 32)))), out)
    nat_pad = (jnp.pad(nat_bytes, ((0, 0), (0, RD - 64))) if RD >= 64
               else nat_bytes[:, :RD])
    out = jnp.where(m_hok[:, None] & (kk < nat_len[:, None].astype(I64)),
                    nat_pad, out)

    # returndata buffer + memory window write
    conc_res = m_sha | m_id | m_mod | m_ecr | m_hok
    n_out = jnp.clip(out_len, 0, RD).astype(I32)
    returndata = jnp.where(pre[:, None], out, f.returndata)
    returndata = jnp.where(
        pre[:, None] & (jnp.arange(RD)[None, :] >= n_out[:, None]), 0, returndata
    ).astype(jnp.uint8)
    n_mem = jnp.minimum(out_len, r_len)
    jpos = jnp.arange(M, dtype=I64)[None, :]
    in_win = (jpos >= r_off[:, None]) & (jpos < (r_off + n_mem)[:, None])
    src = ci._gather_bytes(out, -r_off, M, n_mem)
    memory = jnp.where(in_win & conc_res[:, None], src, f.memory).astype(jnp.uint8)

    # sym overlay of the output window: concrete results clear covered
    # words (edge words with stale syms -> havoc); leaf results plant the
    # leaf on a single aligned word, anything wider/unaligned havocs
    full_lo = ((r_off + 31) // 32)[:, None]
    full_hi = ((r_off + n_mem) // 32)[:, None]
    covered = (wids >= full_lo) & (wids < full_hi) & conc_res[:, None]
    mem_sym = jnp.where(covered, 0, sf.mem_sym)
    edge = (((wids == (r_off // 32)[:, None]) | (wids == full_hi))
            & ~covered & conc_res[:, None] & (n_mem[:, None] > 0))
    edge_dirty = jnp.any(edge & (sf.mem_sym != 0), axis=1)
    leaf_word_ok = m_leaf & ((r_off % 32) == 0) & (r_len >= 32) & (out_len == 32)
    mem_sym = _set_word_sym(mem_sym, (r_off // 32).astype(I32), leaf, leaf_word_ok)
    mem_floor = _lower_floor(
        sf.mem_floor,
        (conc_res & edge_dirty) | (m_leaf & (r_len > 0) & ~leaf_word_ok),
        _floor_word(r_off))

    # a malformed input FAILS the call: the success word the caller
    # pushed (top of stack after the sp update) is rewritten to 0
    stack = ci._set_slot(f.stack, f.sp - 1,
                         jnp.zeros((P, 8), dtype=U32), m_hfail)

    return sf.replace(
        base=f.replace(memory=memory, returndata=returndata, stack=stack,
                       returndata_len=jnp.where(pre, n_out, f.returndata_len)),
        mem_sym=mem_sym,
        mem_floor=mem_floor,
        retdata_sym=jnp.where(pre, m_leaf, sf.retdata_sym),
    )


def _init_jumpdest_scan(code, length):
    """Jumpdest map of a per-lane code buffer u8[P, IC]: a byte is a valid
    JUMPDEST iff it is 0x5B and not inside a PUSH immediate. Sequential
    push-width skip via fori_loop (runs only under the CREATE cond)."""
    P, IC = code.shape

    def body(i, carry):
        skip, jd = carry
        b = code[:, i].astype(I32)
        live = i < length
        is_jd = (skip == 0) & (b == 0x5B) & live
        jd = jd.at[:, i].set(is_jd)
        push_w = jnp.where((skip == 0) & (b >= 0x60) & (b <= 0x7F),
                           b - 0x5F, 0)
        skip = jnp.maximum(skip - 1, 0) + push_w
        return skip, jd

    _, jd = lax.fori_loop(
        0, IC, body, (jnp.zeros(P, dtype=I32), jnp.zeros((P, IC), dtype=bool))
    )
    return jd


def _h_sym_create(sf: SymFrontier, op, m, old_pc) -> SymFrontier:
    """CREATE/CREATE2: run the init code in a real sub-frame.

    Reference: ``create_`` spawning a ContractCreationTransaction
    (``mythril/laser/ethereum/instructions.py`` + ``transaction/`` ⚠unv,
    SURVEY.md §2 "Transaction models"). A lane whose init window is
    concrete (bytes, offset, length, value — and salt for CREATE2) pushes
    a frame that EXECUTES the init code from a per-lane buffer: storage
    writes land on the fresh account (``cur_acct`` = the new slot),
    RETURN's payload is the deployed runtime image (matched against the
    corpus at pop — see ``pop_frames``), REVERT rolls back storage,
    balance and the account registration. CREATE2 addresses use the real
    keccak identity (0xff ++ deployer ++ salt ++ keccak(init)); plain
    CREATE addresses are deterministic fresh values (RLP-nonce addressing
    not modeled). Fallback (symbolic window/value/salt, init too long,
    nested constructor, no table/frame headroom): the round-3 behavior —
    register a fresh CODE_UNKNOWN account, push its address, skip the
    constructor (documented over-approximation).
    """
    f = sf.base
    P = f.n_lanes
    static_viol = m & f.static
    sf = sf.replace(base=f.trap(static_viol, Trap.STATIC_WRITE))
    f = sf.base
    m = m & ~static_viol
    sin = ci._J_STACK_IN[op]
    is_c2 = op == 0xF5
    value = ci._peek(f, 0)
    value_sym = _peek_sym(sf, 0)
    off = u256.to_u64_saturating(ci._peek(f, 1)).astype(I64)
    ln = u256.to_u64_saturating(ci._peek(f, 2)).astype(I64)
    off_s, ln_s = _peek_sym(sf, 1), _peek_sym(sf, 2)
    salt = jnp.where(is_c2[:, None], ci._peek(f, 3), 0).astype(U32)
    salt_sym = jnp.where(is_c2, _peek_sym(sf, 3), 0)
    f, _ = ci._expand_memory(f, m & (ln > 0), off + ln)
    sf = sf.replace(base=f)
    sf = _record_call_event(sf, m, op, old_pc, jnp.zeros_like(value).astype(U32),
                            jnp.zeros_like(value_sym), value.astype(U32), value_sym)
    f = sf.base

    # concrete-value feasibility (symbolic value: no transfer modeled, the
    # fresh address is still pushed — the RETVAL of a create is its address)
    value_conc = value_sym == 0
    wants = m & value_conc & ~u256.is_zero(value)
    payer_bal = f.self_balance
    insufficient = wants & u256.lt(payer_bal, value)
    ok = m & ~insufficient

    # register the new account in a free slot; a full table just skips
    # registration (the pushed address then resolves nowhere -> external)
    A = f.acct_used.shape[1]
    free = ~f.acct_used
    has_free = jnp.any(free, axis=1)
    slot = jnp.argmax(free, axis=1).astype(I32)
    reg = ok & has_free
    addr_w = u256.from_u64_scalar(
        jnp.uint64(CREATE_ADDR_BASE) + sf.create_cnt.astype(jnp.uint64))
    sidx = jnp.where(reg, slot, A)
    acct_addr = ci._write_slot(f.acct_addr, sidx, addr_w)
    init_bal = jnp.where((wants & ~insufficient)[:, None], value, 0).astype(U32)
    acct_bal = ci._write_slot(f.acct_bal, sidx, init_bal)
    # CODE_UNKNOWN, not EOA: the created contract HAS code (the init
    # code's dynamic result) — calls must havoc, never succeed concretely
    acct_code = ci._write_slot(f.acct_code, sidx, CODE_UNKNOWN)
    acct_used = ci._write_slot(f.acct_used, sidx, True)
    # deduct the payer (only when the endowment actually moved)
    pay_idx = jnp.where(reg & wants, f.cur_acct, A)
    acct_bal = ci._write_slot(acct_bal, pay_idx, u256.sub(payer_bal, value))

    # --- frame-execution eligibility: registered,
    # concrete window whose bytes carry no symbolic overlay, init fits the
    # buffer, frame + no nested constructor, concrete salt
    IC = f.init_code.shape[1]
    D = f.fr_ret_pc.shape[1]
    W = sf.mem_sym.shape[1]
    wids = jnp.arange(W)[None, :]
    win_sym = (_reaches_floor(sf, off, ln) | jnp.any(
        (wids >= (off // 32)[:, None])
        & (wids < ((off + ln + 31) // 32)[:, None])
        & (sf.mem_sym != 0), axis=1
    )) & (ln > 0)
    want_frame = (
        reg & (off_s == 0) & (ln_s == 0) & (salt_sym == 0) & ~win_sym
        & (ln > 0) & (ln <= IC) & (f.depth < D) & (f.init_depth == 0)
    )

    dest_slot = f.sp - sin
    m_push = m & ~want_frame  # frame lanes get their result at pop_frames
    res_w = jnp.where(ok[:, None], addr_w, 0).astype(U32)
    stack = ci._set_slot(f.stack, dest_slot, res_w, m_push)
    sf = sf.replace(
        base=f.replace(
            stack=stack,
            sp=jnp.where(m_push, f.sp - sin + 1, f.sp),
            returndata_len=jnp.where(m_push, 0, f.returndata_len),
            acct_addr=acct_addr, acct_bal=acct_bal,
            acct_code=acct_code, acct_used=acct_used,
        ),
        stack_sym=_set_sym_slot(sf.stack_sym, dest_slot,
                                jnp.zeros((P,), I32), m_push),
        retdata_sym=jnp.where(m_push, False, sf.retdata_sym),
        create_cnt=sf.create_cnt + m.astype(I32),
        bal_epoch=sf.bal_epoch + (reg & wants).astype(I32),
    )
    return lax.cond(
        jnp.any(want_frame),
        lambda s: _push_create_frame(s, want_frame, is_c2, slot, sin, off, ln,
                                     salt, value, old_pc,
                                     pre_transfer_bal=f.acct_bal),
        lambda s: s,
        sf,
    )


def _push_create_frame(sf: SymFrontier, mi, is_c2, slot, sin, off, ln, salt,
                       value, old_pc, pre_transfer_bal) -> SymFrontier:
    """Push the constructor frame for ``mi`` lanes (under the CREATE cond).

    The child executes the init bytes copied from the caller's memory
    (``exec_init`` fetch override), with ``cur_acct`` = the new account
    slot so SSTOREs persist on the child, empty calldata, and the
    endowment as callvalue. CREATE2 lanes overwrite the registered fresh
    address with the real keccak identity."""
    f = sf.base
    P, M = f.memory.shape
    IC = f.init_code.shape[1]
    d = f.depth

    init_code = ci._gather_bytes(f.memory, off, IC, jnp.full_like(off, M))
    init_code = jnp.where(jnp.arange(IC)[None, :] < ln[:, None], init_code, 0)
    init_code = jnp.where(mi[:, None], init_code, f.init_code).astype(jnp.uint8)
    init_jd = jnp.where(mi[:, None], _init_jumpdest_scan(init_code, ln.astype(I32)),
                        f.init_jd)

    # CREATE2: addr = keccak(0xff ++ deployer[20] ++ salt[32] ++ keccak(init))[12:]
    from ..ops.keccak import keccak256_device
    inner = keccak256_device(init_code, jnp.clip(ln, 0, IC).astype(I32))
    self_be = ci._word_to_be_bytes(f.self_address)      # u8[P,32]
    salt_be = ci._word_to_be_bytes(salt)
    inner_be = ci._word_to_be_bytes(inner)
    buf = jnp.concatenate(
        [jnp.full((P, 1), 0xFF, dtype=jnp.uint8), self_be[:, 12:32],
         salt_be, inner_be], axis=1)                     # u8[P,85]
    c2_addr = keccak256_device(buf, jnp.full(P, 85, dtype=I32))
    c2_addr = c2_addr.at[:, 5:].set(0)                   # low 160 bits
    do_c2 = mi & is_c2
    aidx = jnp.where(do_c2, slot, f.acct_used.shape[1])
    acct_addr = ci._write_slot(f.acct_addr, aidx, c2_addr)

    # CREATE forwards all-but-one-64th (EIP-150; no gas operand)
    remaining = jnp.maximum(f.gas_limit - f.gas_max, 0)
    fwd = remaining - remaining // 64
    f2 = f.replace(
        acct_addr=acct_addr,
        fr_gas_limit=_fr_set(f.fr_gas_limit, d, f.gas_limit, mi),
        gas_limit=jnp.where(mi, f.gas_max + fwd, f.gas_limit),
        fr_warm_acct=_fr_set(f.fr_warm_acct, d, f.warm_acct, mi),
        fr_st_warm=_fr_set(f.fr_st_warm, d, f.st_warm, mi),
        fr_ret_pc=_fr_set(f.fr_ret_pc, d, old_pc, mi),
        fr_sp=_fr_set(f.fr_sp, d, f.sp - sin, mi),
        fr_sp_base=_fr_set(f.fr_sp_base, d, f.sp_base, mi),
        fr_static=_fr_set(f.fr_static, d, f.static, mi),
        fr_cur_acct=_fr_set(f.fr_cur_acct, d, f.cur_acct, mi),
        fr_contract_id=_fr_set(f.fr_contract_id, d, f.contract_id, mi),
        fr_caller_addr=_fr_set(f.fr_caller_addr, d, f.caller_addr, mi),
        fr_callvalue=_fr_set(f.fr_callvalue, d, f.callvalue, mi),
        fr_memory=_fr_set(f.fr_memory, d, f.memory, mi),
        fr_mem_words=_fr_set(f.fr_mem_words, d, f.mem_words, mi),
        fr_calldata=_fr_set(f.fr_calldata, d, f.calldata, mi),
        fr_calldata_len=_fr_set(f.fr_calldata_len, d, f.calldata_len, mi),
        fr_ret_off=_fr_set(f.fr_ret_off, d, jnp.zeros_like(off), mi),
        fr_ret_len=_fr_set(f.fr_ret_len, d, jnp.zeros_like(ln), mi),
        fr_gas_min=_fr_set(f.fr_gas_min, d, f.gas_min, mi),
        fr_gas_max=_fr_set(f.fr_gas_max, d, f.gas_max, mi),
        fr_st_keys=_fr_set(f.fr_st_keys, d, f.st_keys, mi),
        fr_st_vals=_fr_set(f.fr_st_vals, d, f.st_vals, mi),
        fr_st_used=_fr_set(f.fr_st_used, d, f.st_used, mi),
        fr_st_written=_fr_set(f.fr_st_written, d, f.st_written, mi),
        fr_st_acct=_fr_set(f.fr_st_acct, d, f.st_acct, mi),
        fr_acct_bal=_fr_set(f.fr_acct_bal, d, pre_transfer_bal, mi),
        fr_create_slot=_fr_set(f.fr_create_slot, d, slot, mi),
    )
    f2 = f2.replace(
        pc=jnp.where(mi, 0, f2.pc),
        pc_hold=f2.pc_hold | mi,
        sp=jnp.where(mi, f.sp - sin, f2.sp),
        sp_base=jnp.where(mi, f.sp - sin, f2.sp_base),
        depth=jnp.where(mi, f.depth + 1, f2.depth),
        cur_acct=jnp.where(mi, slot, f2.cur_acct),
        caller_addr=jnp.where(mi[:, None], f.self_address, f2.caller_addr),
        callvalue=jnp.where(mi[:, None], value, f2.callvalue).astype(U32),
        memory=jnp.where(mi[:, None], 0, f2.memory),
        mem_words=jnp.where(mi, 0, f2.mem_words),
        calldata=jnp.where(mi[:, None], 0, f2.calldata),
        calldata_len=jnp.where(mi, 0, f2.calldata_len),
        returndata_len=jnp.where(mi, 0, f2.returndata_len),
        init_code=init_code,
        init_len=jnp.where(mi, ln.astype(I32), f.init_len),
        init_jd=init_jd,
        init_depth=jnp.where(mi, f.depth + 1, f.init_depth),
    )
    return sf.replace(
        base=f2,
        mem_sym=jnp.where(mi[:, None], 0, sf.mem_sym),
        mem_floor=jnp.where(mi, MEM_EXACT, sf.mem_floor),
        cd_from_mem=sf.cd_from_mem | mi,
        cd_havoc=jnp.where(mi, False, sf.cd_havoc),
        cd_sym=jnp.where(mi[:, None], 0, sf.cd_sym),
        mem_usym=jnp.where(mi[:, None], 0, sf.mem_usym),
        mem_ushift=jnp.where(mi, 0, sf.mem_ushift),
        mem_uhead=jnp.where(mi, -1, sf.mem_uhead),
        cd_usym=jnp.where(mi[:, None], 0, sf.cd_usym),
        cd_ushift=jnp.where(mi, 0, sf.cd_ushift),
        cd_uhead=jnp.where(mi, False, sf.cd_uhead),
        fr_cd_usym=_fr_set(sf.fr_cd_usym, d, sf.cd_usym, mi),
        fr_cd_ushift=_fr_set(sf.fr_cd_ushift, d, sf.cd_ushift, mi),
        fr_cd_uhead=_fr_set(sf.fr_cd_uhead, d, sf.cd_uhead, mi),
        callvalue_sym=jnp.where(mi, 0, sf.callvalue_sym),
        caller_sym=jnp.where(mi, 0, sf.caller_sym),
        fr_caller_sym=_fr_set(sf.fr_caller_sym, d, sf.caller_sym, mi),
        fr_mem_sym=_fr_set(sf.fr_mem_sym, d, sf.mem_sym, mi),
        fr_mem_floor=_fr_set(sf.fr_mem_floor, d, sf.mem_floor, mi),
        fr_cd_from_mem=_fr_set(sf.fr_cd_from_mem, d, sf.cd_from_mem, mi),
        fr_cd_havoc=_fr_set(sf.fr_cd_havoc, d, sf.cd_havoc, mi),
        fr_cd_sym=_fr_set(sf.fr_cd_sym, d, sf.cd_sym, mi),
        fr_callvalue_sym=_fr_set(sf.fr_callvalue_sym, d, sf.callvalue_sym, mi),
        fr_st_val_sym=_fr_set(sf.fr_st_val_sym, d, sf.st_val_sym, mi),
        fr_st_key_sym=_fr_set(sf.fr_st_key_sym, d, sf.st_key_sym, mi),
        fr_st_seq=_fr_set(sf.fr_st_seq, d, sf.st_seq, mi),
    )


@jax.named_scope("pop_frames")
def pop_frames(sf: SymFrontier, corpus: Corpus) -> SymFrontier:
    """Return control to the caller for every lane whose sub-frame ended.

    Reference: ``TransactionEndSignal`` handling in ``LaserEVM.exec`` —
    ``end_message_call`` restores the caller state and pushes the call's
    success flag (⚠unv, SURVEY.md §3.2). Genuine EVM halts inside the
    callee (revert, invalid, bad jump, OOG, stack) become success=0 with
    storage/balance rollback; engine-capacity traps kill the whole lane
    (the cap is an artifact, not an EVM outcome — counted in coverage).
    """
    f = sf.base
    ended = f.active & (f.depth > 0) & (f.halted | f.error)
    is_kill = jnp.zeros_like(f.error)
    for c in KILL_TRAPS:
        is_kill = is_kill | (f.err_code == c)
    mp = ended & ~(f.error & is_kill)
    success = mp & f.halted & ~f.reverted & ~f.error
    fail = mp & (f.error | f.reverted)
    d = jnp.maximum(f.depth - 1, 0)
    # constructor frames: fr_create_slot >= 0 marks the account being built
    cslot = _fr_get(f.fr_create_slot, d)
    is_initp = mp & (cslot >= 0)

    ret_pc = _fr_get(f.fr_ret_pc, d)
    csp = _fr_get(f.fr_sp, d)
    r_off = _fr_get(f.fr_ret_off, d)
    r_len = _fr_get(f.fr_ret_len, d)

    # caller memory restore + returndata write (REVERT carries data too;
    # an exceptional halt returns nothing)
    has_rd = mp & ~f.error
    memory = jnp.where(mp[:, None], _fr_get(f.fr_memory, d), f.memory)
    n_rd = jnp.minimum(r_len, f.retval_len.astype(I64))
    P, M = f.memory.shape
    jpos = jnp.arange(M, dtype=I64)[None, :]
    in_win = (jpos >= r_off[:, None]) & (jpos < (r_off + n_rd)[:, None])
    src = ci._gather_bytes(f.retval, -r_off, M, n_rd)
    memory = jnp.where(in_win & has_rd[:, None], src, memory).astype(jnp.uint8)

    # sym overlay: restore caller's, then map the returndata words
    mem_sym = jnp.where(mp[:, None], _fr_get(sf.fr_mem_sym, d), sf.mem_sym)
    mem_floor = jnp.where(mp, _fr_get(sf.fr_mem_floor, d), sf.mem_floor)
    roff_al = (r_off % 32) == 0
    RDW = sf.rv_sym.shape[1]
    rv_words_sym = jnp.any(
        (jnp.arange(RDW)[None, :] * 32 < n_rd[:, None]) & (sf.rv_sym != 0), axis=1
    )
    rv_unknown = sf.rv_havoc | rv_words_sym
    # aligned full words map exactly; anything messier havocs coarse
    clean_map = has_rd & roff_al & ~sf.rv_havoc
    for k in range(RDW):
        full = (32 * (k + 1)) <= n_rd
        mem_sym = _set_word_sym(
            mem_sym, (r_off // 32).astype(I32) + k,
            sf.rv_sym[:, k], clean_map & full,
        )
    tail_sym_rd = ((n_rd % 32) != 0) & jnp.any(
        (jnp.arange(RDW)[None, :] == (n_rd // 32)[:, None]) & (sf.rv_sym != 0),
        axis=1,
    )
    rd_havoc = has_rd & (
        (sf.rv_havoc & (r_len > 0)) | (~roff_al & rv_words_sym)
        | (roff_al & tail_sym_rd)
    )
    mem_floor = _lower_floor(mem_floor, rd_havoc, _floor_word(r_off))
    # symbolic return words the caller can read back, by what it reads
    n_rv = jnp.sum(((jnp.arange(RDW)[None, :] * 32 < n_rd[:, None])
                    & (sf.rv_sym != 0)).astype(I32), axis=1, dtype=I32)
    n_rv = jnp.where(has_rd, n_rv, 0)
    hop_stats = _hop_count(
        sf.hop_stats, (HOP_RET_EXACT, jnp.where(rd_havoc, 0, n_rv)),
        (HOP_RET_HAVOC, jnp.where(rd_havoc, jnp.maximum(n_rv, 1), 0)))

    # storage + balance rollback on failure
    def roll(cur, snap):
        sel = fail.reshape((P,) + (1,) * (cur.ndim - 1))
        return jnp.where(sel, snap, cur)

    st_keys = roll(f.st_keys, _fr_get(f.fr_st_keys, d))
    st_vals = roll(f.st_vals, _fr_get(f.fr_st_vals, d))
    st_used = roll(f.st_used, _fr_get(f.fr_st_used, d))
    st_written = roll(f.st_written, _fr_get(f.fr_st_written, d))
    st_acct = roll(f.st_acct, _fr_get(f.fr_st_acct, d))
    acct_bal = roll(f.acct_bal, _fr_get(f.fr_acct_bal, d))
    st_val_sym = roll(sf.st_val_sym, _fr_get(sf.fr_st_val_sym, d))
    st_key_sym = roll(sf.st_key_sym, _fr_get(sf.fr_st_key_sym, d))
    # seq rolls back WITH the entries (the counter itself stays monotonic
    # — gaps are harmless, only relative order matters)
    st_seq = roll(sf.st_seq, _fr_get(sf.fr_st_seq, d))
    # warm sets roll back with the frame (EIP-2929: a reverted call's
    # access-list growth is undone)
    warm_acct = roll(f.warm_acct, _fr_get(f.fr_warm_acct, d))
    st_warm = roll(f.st_warm, _fr_get(f.fr_st_warm, d))
    # gas: an EXCEPTIONAL halt burns the entire forwarded allowance
    # (child_limit - caller gas at push); a REVERT returns the unused
    # remainder, so the child's accumulated totals stand
    fwd = f.gas_limit - _fr_get(f.fr_gas_max, d)
    fail_exc = mp & f.error
    gas_min = jnp.where(fail_exc, _fr_get(f.fr_gas_min, d) + fwd, f.gas_min)
    gas_max = jnp.where(fail_exc, _fr_get(f.fr_gas_max, d) + fwd, f.gas_max)
    gas_limit = jnp.where(mp, _fr_get(f.fr_gas_limit, d), f.gas_limit)

    # success flag pushed at the caller's post-args sp; a constructor frame
    # pushes the CHILD ADDRESS instead (0 on failure) — the EVM result of
    # CREATE/CREATE2 is an address, not a boolean
    one_w = jnp.zeros((P, 8), dtype=U32).at[:, 0].set(1)
    child_addr = f.acct_field(f.acct_addr, jnp.maximum(cslot, 0))
    res_w = jnp.where(
        success[:, None],
        jnp.where(is_initp[:, None], child_addr, one_w),
        0,
    ).astype(U32)
    stack = ci._set_slot(f.stack, csp, res_w, mp)
    stack_sym = _set_sym_slot(sf.stack_sym, csp, jnp.zeros((P,), I32), mp)

    # constructor epilogue: the RETURN payload is the deployed runtime
    # image. Concretely match it against the corpus (factories deploying
    # known children become callable); empty code -> EOA-like; unmatched
    # -> CODE_UNKNOWN stays. A failed constructor unregisters the account
    # (its storage/balance rolled back with the frame snapshots; accounts
    # a NESTED create registered are not rolled back — documented).
    acct_used_p = ci._write_slot(
        f.acct_used,
        jnp.where(is_initp & fail, jnp.maximum(cslot, 0),
                  f.acct_used.shape[1]),
        False)

    def _resolve_child_code(acct_code_in):
        # the deployed image is concrete bytes in `retval`: byte-compare it
        # against every corpus image (both are zero-padded past their
        # lengths, so whole-window equality + length equality suffices).
        # A match makes the child CALLABLE (factory-deploys-known-child);
        # empty code -> EOA-like; no match / image beyond the retval cap
        # -> CODE_UNKNOWN (calls to it havoc, never wrong)
        rl = f.retval_len
        RD = f.retval.shape[1]
        MC = corpus.code.shape[1]
        Wn = min(RD, MC)
        eq = (
            jnp.all(f.retval[:, None, :Wn] == corpus.code[None, :, :Wn],
                    axis=2)
            & (rl[:, None] == corpus.code_len[None, :])
            & (corpus.code_len[None, :] <= RD)
            & (corpus.code_len[None, :] > 0)
        )
        # a symbolic byte anywhere in the returned image makes the concrete
        # compare meaningless — such a deploy stays CODE_UNKNOWN
        hit = jnp.any(eq, axis=1) & ~rv_unknown
        resolved = jnp.where(
            hit, jnp.argmax(eq, axis=1).astype(I32),
            jnp.where((rl == 0) & ~rv_unknown, -1, CODE_UNKNOWN),
        )
        cidx = jnp.where(is_initp & success, jnp.maximum(cslot, 0),
                         f.acct_used.shape[1])
        return ci._write_slot(acct_code_in, cidx, resolved)

    acct_code_p = lax.cond(jnp.any(is_initp & success), _resolve_child_code,
                           lambda ac: ac, f.acct_code)

    # a successful CREATE leaves EMPTY returndata in the caller (EVM rule:
    # only a reverting create exposes its revert payload)
    has_rd = has_rd & ~(is_initp & success)

    base = f.replace(
        pc=jnp.where(mp, ret_pc + 1, f.pc),
        sp=jnp.where(mp, csp + 1, f.sp),
        sp_base=jnp.where(mp, _fr_get(f.fr_sp_base, d), f.sp_base),
        depth=jnp.where(mp, d, f.depth),
        init_depth=jnp.where(is_initp, 0, f.init_depth),
        acct_used=acct_used_p,
        acct_code=acct_code_p,
        fr_create_slot=ci._write_slot(
            f.fr_create_slot,
            jnp.where(is_initp, d, f.fr_create_slot.shape[1]), -1),
        static=jnp.where(mp, _fr_get(f.fr_static, d), f.static),
        cur_acct=jnp.where(mp, _fr_get(f.fr_cur_acct, d), f.cur_acct),
        contract_id=jnp.where(mp, _fr_get(f.fr_contract_id, d), f.contract_id),
        caller_addr=jnp.where(mp[:, None], _fr_get(f.fr_caller_addr, d), f.caller_addr),
        callvalue=jnp.where(mp[:, None], _fr_get(f.fr_callvalue, d), f.callvalue),
        memory=memory,
        mem_words=jnp.where(mp, _fr_get(f.fr_mem_words, d), f.mem_words),
        calldata=jnp.where(mp[:, None], _fr_get(f.fr_calldata, d), f.calldata),
        calldata_len=jnp.where(mp, _fr_get(f.fr_calldata_len, d), f.calldata_len),
        returndata=jnp.where((mp & has_rd)[:, None], f.retval, f.returndata),
        returndata_len=jnp.where(mp, jnp.where(has_rd, f.retval_len, 0),
                                 f.returndata_len),
        retval_len=jnp.where(mp, 0, f.retval_len),
        stack=stack,
        st_keys=st_keys, st_vals=st_vals, st_used=st_used,
        st_written=st_written, st_acct=st_acct, acct_bal=acct_bal,
        warm_acct=warm_acct, st_warm=st_warm,
        gas_min=gas_min, gas_max=gas_max, gas_limit=gas_limit,
        halted=f.halted & ~mp,
        reverted=f.reverted & ~mp,
        error=f.error & ~mp,
        err_code=jnp.where(mp, 0, f.err_code),
    )
    return sf.replace(
        base=base,
        stack_sym=stack_sym,
        mem_sym=mem_sym,
        mem_floor=mem_floor,
        retdata_sym=jnp.where(mp, has_rd & rv_unknown, sf.retdata_sym),
        rv_sym=jnp.where(mp[:, None], 0, sf.rv_sym),
        rv_havoc=jnp.where(mp, False, sf.rv_havoc),
        cd_from_mem=jnp.where(mp, _fr_get(sf.fr_cd_from_mem, d), sf.cd_from_mem),
        cd_havoc=jnp.where(mp, _fr_get(sf.fr_cd_havoc, d), sf.cd_havoc),
        cd_sym=jnp.where(mp[:, None], _fr_get(sf.fr_cd_sym, d), sf.cd_sym),
        cd_usym=jnp.where(mp[:, None], _fr_get(sf.fr_cd_usym, d), sf.cd_usym),
        cd_ushift=jnp.where(mp, _fr_get(sf.fr_cd_ushift, d), sf.cd_ushift),
        cd_uhead=jnp.where(mp, _fr_get(sf.fr_cd_uhead, d), sf.cd_uhead),
        # the caller's own whole words are not kept across the call (its
        # aligned words hold leaves where they lay: sound)
        mem_usym=jnp.where(mp[:, None], 0, sf.mem_usym),
        mem_ushift=jnp.where(mp, 0, sf.mem_ushift),
        mem_uhead=jnp.where(mp, -1, sf.mem_uhead),
        hop_stats=hop_stats,
        callvalue_sym=jnp.where(mp, _fr_get(sf.fr_callvalue_sym, d), sf.callvalue_sym),
        caller_sym=jnp.where(mp, _fr_get(sf.fr_caller_sym, d), sf.caller_sym),
        # a failed value call rolled the balance table back — another change
        bal_epoch=sf.bal_epoch + fail.astype(I32),
        st_val_sym=st_val_sym,
        st_key_sym=st_key_sym,
        st_seq=st_seq,
        # only a genuine REVERT (require()-style) feeds SWC-123; callee
        # INVALID/OOG/bad-jump are assert-style failures (SWC-110 territory)
        sub_revert_pc=jnp.where(fail & f.reverted & ~f.error
                                & (sf.sub_revert_pc < 0), ret_pc,
                                sf.sub_revert_pc),
        sub_revert_cid=jnp.where(fail & f.reverted & ~f.error
                                 & (sf.sub_revert_pc < 0),
                                 _fr_get(f.fr_contract_id, d),
                                 sf.sub_revert_cid),
        # where the callee itself gave up, in its own code: the innermost
        # frame's, since it pops first
        sub_fail_pc=jnp.where(fail & f.reverted & ~f.error
                              & (sf.sub_fail_pc < 0), f.pc, sf.sub_fail_pc),
        sub_fail_cid=jnp.where(fail & f.reverted & ~f.error
                               & (sf.sub_fail_pc < 0), f.contract_id,
                               sf.sub_fail_cid),
    )


def _h_sym_claimed_misc(sf: SymFrontier, op, m_memoff, m_sha3off, m_copyoff,
                        m_haltoff, m_logoff) -> SymFrontier:
    """Symbolic-offset memory/copy/sha3/halt/log ops: stack bookkeeping +
    havoc over-approximation (no byte-accurate modeling at symbolic
    addresses under static shapes). A store or a copy invalidates memory
    from its destination's word up (``mem_floor``), not below it."""
    f = sf.base
    is_load = op == 0x51
    # the destination: operand 0, EXTCODECOPY's operand 1
    is_ext = op == 0x3C
    dst64 = u256.to_u64_saturating(jnp.where(
        is_ext[:, None], ci._peek(f, 1), ci._peek(f, 0))).astype(I64)
    dst_word = _dest_floor_word(
        sf, dst64, jnp.where(is_ext, _peek_sym(sf, 1), _peek_sym(sf, 0)))
    # LOG is a state modification: a symbolic-offset LOG inside a
    # STATICCALL frame must trap exactly like the concrete handler's
    static_viol = m_logoff & f.static
    m_logoff = m_logoff & ~static_viol
    sf = sf.replace(base=f.trap(static_viol, Trap.STATIC_WRITE))
    f = sf.base
    any_m = m_memoff | m_sha3off | m_copyoff | m_haltoff | m_logoff

    # MLOAD(sym off) / SHA3(sym args) -> fresh havoc result
    need_hv = (m_memoff & is_load) | m_sha3off
    sf, hv = _havoc(sf, need_hv)
    f = sf.base

    # result slots: MLOAD replaces top (sp-1); SHA3 pops 2 pushes 1 (sp-2)
    stack_sym = _set_sym_slot(sf.stack_sym, f.sp - 1, hv, m_memoff & is_load)
    stack_sym = _set_sym_slot(stack_sym, f.sp - 2, hv, m_sha3off)

    sin = ci._J_STACK_IN[op]
    sout = ci._J_STACK_OUT[op]
    d_sp = sin - sout
    is_revert = op == 0xFD
    has_data_halt = (op == 0xF3) | is_revert
    # symbolic-offset LOG: still record pc/cid/topic0 (topics may be
    # concrete even when the data window is not); payload word unknown (-1)
    LS = f.log_pc.shape[1]
    wl = jnp.where(m_logoff & (f.n_logs < LS),
                   jnp.minimum(f.n_logs, LS - 1), LS)
    n_topics = op.astype(I32) - 0xA0
    topic0 = ci._peek(f, 2)
    return sf.replace(
        base=f.replace(
            sp=jnp.where(any_m, f.sp - d_sp, f.sp),
            halted=f.halted | (m_haltoff & has_data_halt),
            reverted=f.reverted | (m_haltoff & is_revert),
            retval_len=jnp.where(m_haltoff, 0, f.retval_len),
            n_logs=f.n_logs + m_logoff.astype(I32),
            log_pc=ci._write_slot(f.log_pc, wl, f.pc),
            log_cid=ci._write_slot(f.log_cid, wl, f.contract_id),
            log_ntopics=ci._write_slot(f.log_ntopics, wl, n_topics),
            log_topic0=ci._write_slot(
                f.log_topic0, wl,
                jnp.where((n_topics >= 1)[:, None], topic0, 0).astype(
                    jnp.uint32)),
        ),
        log_topic0_sym=ci._write_slot(
            sf.log_topic0_sym, wl,
            jnp.where(n_topics >= 1, _peek_sym(sf, 2), 0)),
        log_data0_sym=ci._write_slot(sf.log_data0_sym, wl, -1),
        stack_sym=stack_sym,
        mem_floor=_lower_floor(
            sf.mem_floor, (m_memoff & ~is_load) | m_copyoff, dst_word),
        # a symbolic-window RETURN/REVERT leaves the payload unknown — the
        # caller's returndata havocs when this frame pops
        rv_havoc=sf.rv_havoc | m_haltoff,
    )


# ---------------------------------------------------------------------------
# Overlay: sym-id bookkeeping for concretely-dispatched lanes
# ---------------------------------------------------------------------------


def _take_word_sym(mem_sym, w):
    W = mem_sym.shape[1]
    return jnp.take_along_axis(mem_sym, jnp.clip(w, 0, W - 1)[:, None].astype(I32), axis=1)[:, 0]


def _set_word_sym(mem_sym, w, val, mask):
    W = mem_sym.shape[1]
    idx = jnp.where(mask & (w >= 0) & (w < W), w, W).astype(I32)
    return ci._write_slot(mem_sym, idx, val)


def _overlay(sf: SymFrontier, env: Env, spec: SymSpec, op, m, cls, pre_sp,
             pre_stack_sym, a, s, limits: LimitsConfig) -> SymFrontier:
    """Mirror the concrete handlers' stack movements on the sym-id plane
    and append tape nodes where symbolic operands flowed in. Uses the
    PRE-dispatch stack/syms (`a` = operand limbs, `s` = operand sym ids).
    """
    f = sf.base
    stack_sym = sf.stack_sym
    sin = ci._J_STACK_IN[op]

    # ---- CLS_STACK: push/dup/swap/pc/msize/gas ----
    m_stk = m & (cls == ci.CLS_STACK)
    is_push = (op >= 0x5F) & (op <= 0x7F)
    is_dup = (op >= 0x80) & (op <= 0x8F)
    is_swap = (op >= 0x90) & (op <= 0x9F)
    pushes0 = is_push | (op == 0x58) | (op == 0x59) | (op == 0x5A)
    dup_n = jnp.where(is_dup, op - 0x7F, 1)
    S = stack_sym.shape[1]
    dup_sym = jnp.take_along_axis(
        pre_stack_sym, jnp.clip(pre_sp - dup_n, 0, S - 1)[:, None].astype(I32), axis=1
    )[:, 0]
    stack_sym = _set_sym_slot(stack_sym, pre_sp, jnp.zeros_like(dup_sym), m_stk & pushes0)
    stack_sym = _set_sym_slot(stack_sym, pre_sp, dup_sym, m_stk & is_dup)
    swap_n = jnp.where(is_swap, op - 0x8F, 1)
    deep_sym = jnp.take_along_axis(
        pre_stack_sym, jnp.clip(pre_sp - 1 - swap_n, 0, S - 1)[:, None].astype(I32), axis=1
    )[:, 0]
    stack_sym = _set_sym_slot(stack_sym, pre_sp - 1, deep_sym, m_stk & is_swap)
    stack_sym = _set_sym_slot(stack_sym, pre_sp - 1 - swap_n, s[0], m_stk & is_swap)
    sf = sf.replace(stack_sym=stack_sym)

    # ---- value binops/unaries (ALU/MUL/DIVMOD/EXP classes) ----
    m_bin = m & (
        (cls == ci.CLS_ALU) | (cls == ci.CLS_MUL) | (cls == ci.CLS_DIVMOD) | (cls == ci.CLS_EXP)
    )
    node_op = _J_BINOP[op]
    is_unary = (op == 0x15) | (op == 0x19)  # ISZERO NOT
    any_sym = (s[0] != 0) | (~is_unary & (s[1] != 0))
    # SHR of a word whose head is exact in its concrete shadow (a
    # sub-frame's selector word) by at least the rest: concrete
    keeps_head = ((op == 0x1C) & (s[0] == 0) & (s[1] != 0)
                  & (s[1] == sf.head_node)
                  & (u256.to_u64_saturating(a[0]).astype(I64)
                     >= 256 - 8 * sf.head_len.astype(I64)))
    m_node = m_bin & any_sym & (node_op != 0) & ~keeps_head
    sf, aid = _sym_or_const(sf, m_node, s[0], a[0])
    sf, bid = _sym_or_const(sf, m_node & ~is_unary, s[1], a[1])
    bid = jnp.where(is_unary, 0, bid)  # unary nodes must not carry stale b
    sf, r_bin = append_node(sf, m_node, node_op, aid, bid)

    # record symbolic ADD/SUB/MUL/EXP events for the IntegerArithmetics
    # module (reference: overflow predicates built inline in the module's
    # pre-hook on these opcodes ⚠unv SURVEY.md §3.3; here the predicate is
    # assembled host-side from the recorded operand node ids)
    is_arith = (op == 0x01) | (op == 0x02) | (op == 0x03) | (op == 0x0A)
    m_ar = m_node & is_arith
    ar_onehot = _event_slot(sf.n_arith, m_ar, sf.arith_op.shape[1])
    old_pc_arr = sf.base.pc  # prologue left pc at the instruction
    sf = sf.replace(
        n_arith=sf.n_arith + m_ar.astype(I32),
        arith_op=jnp.where(ar_onehot, op[:, None], sf.arith_op),
        arith_a=jnp.where(ar_onehot, aid[:, None], sf.arith_a),
        arith_b=jnp.where(ar_onehot, bid[:, None], sf.arith_b),
        arith_r=jnp.where(ar_onehot, r_bin[:, None], sf.arith_r),
        arith_pc=jnp.where(ar_onehot, old_pc_arr[:, None], sf.arith_pc),
        arith_cid=jnp.where(ar_onehot, sf.base.contract_id[:, None], sf.arith_cid),
    )

    # ---- CLS_MODARITH: symbolic addmod/mulmod -> havoc (documented) ----
    m_mod = m & (cls == ci.CLS_MODARITH)
    m_mod_sym = m_mod & ((s[0] != 0) | (s[1] != 0) | (s[2] != 0))

    # ---- CLS_ENV: leaves (tx-scoped identity; dedup hits the tx-0 seeds) ----
    m_env = m & (cls == ci.CLS_ENV)
    is_cdload = op == 0x35
    off64 = u256.to_u64_saturating(a[0]).astype(I64)
    CD = limits.calldata_bytes
    beyond = off64 >= CD
    txb = sf.tx_id
    # free actor/input leaves exist only at the TOP frame: a sub-frame's
    # caller/callvalue/calldata are determined by the calling contract
    at_top = sf.base.depth == 0

    kind = jnp.full_like(op, -1)
    bsel = jnp.zeros_like(op)

    def leaf(enabled: bool, sel, k: int, bval):
        nonlocal kind, bsel
        if not enabled:
            return
        kind = jnp.where(sel, k, kind)
        bsel = jnp.where(sel, bval, bsel)

    # tx-scoped actor/input leaves
    leaf(spec.caller, (op == 0x33) & at_top, int(FreeKind.CALLER), txb)
    leaf(spec.callvalue, (op == 0x34) & at_top, int(FreeKind.CALLVALUE), txb)
    leaf(spec.calldata, (op == 0x36) & at_top, int(FreeKind.CALLDATASIZE), txb)
    leaf(spec.calldata, is_cdload & (s[0] == 0) & ~beyond & at_top,
         int(FreeKind.CALLDATA_WORD),
         (txb.astype(I64) * TX_STRIDE + off64).astype(I32))
    # globals across the tx sequence: ORIGIN always symbolic (the
    # reference models tx.origin as a free symbol; SWC-115 scans for it)
    leaf(True, op == 0x32, int(FreeKind.ORIGIN), 0)
    leaf(spec.block_env, op == 0x42, int(FreeKind.TIMESTAMP), 0)
    leaf(spec.block_env, op == 0x43, int(FreeKind.NUMBER), 0)
    leaf(spec.block_env, op == 0x44, int(FreeKind.PREVRANDAO), 0)
    leaf(spec.block_env, op == 0x3A, int(FreeKind.GASPRICE), 0)
    # balances: a symbolic leaf per (epoch, ACCOUNT SLOT) — balances change
    # under symbolic value transfers, so a concrete table read could be
    # wrong; known accounts share one leaf per slot WITHIN an epoch, and
    # the epoch bumps whenever the concrete table changes (transfer /
    # rollback / tx boundary) so pre/post reads are not forced equal
    is_balance = op == 0x31
    known_acct, acct_slot = sf.base.acct_lookup(a[0])
    known_bal = is_balance & known_acct & (s[0] == 0)
    epoch_b = sf.bal_epoch * BAL_STRIDE
    leaf(spec.block_env, op == 0x47, int(FreeKind.BALANCE),
         epoch_b + sf.base.cur_acct)
    leaf(spec.block_env, known_bal, int(FreeKind.BALANCE),
         epoch_b + acct_slot)
    # RETURNDATASIZE after a symbolic call
    leaf(True, (op == 0x3D) & sf.retdata_sym, int(FreeKind.RETDATASIZE),
         jnp.maximum(sf.n_calls - 1, 0))

    # a CALLDATALOAD at a symbolic offset, top frame: a select over the
    # transaction's bytes (solc's decode of a dynamic argument reads its
    # length so). It rides the leaves' append: one hash scan for both
    cd_symoff = m_env & is_cdload & (s[0] != 0)
    cd_select = cd_symoff & at_top & bool(spec.calldata)
    need_leaf = (m_env & (kind >= 0)) | cd_select
    sel_imm = jnp.zeros((f.pc.shape[0], 8), dtype=U32).at[:, 0].set(
        jnp.where(cd_select, txb, 0).astype(U32))
    sf, env_leaf = append_node(
        sf, need_leaf,
        jnp.where(cd_select, int(SymOp.CD_SELECT), int(SymOp.FREE)),
        jnp.where(cd_select, s[0], kind), jnp.where(cd_select, 0, bsel),
        sel_imm)
    sf = sf.replace(cd_reads=sf.cd_reads + jnp.stack(
        [cd_select, cd_symoff & ~cd_select], axis=1).astype(I32))

    # havoc cases: unknowable values must never collapse to a wrong
    # concrete 0 (EXTCODESIZE/EXTCODEHASH of unknown addresses, BALANCE of
    # unknown addresses, BLOCKHASH, a symbolic-offset CALLDATALOAD inside
    # a sub-frame).
    # EXTCODESIZE/EXTCODEHASH of a table account are answered concretely
    # by the concrete handler (corpus image hashes precomputed).
    unknown_addr = (s[0] != 0) | ~known_acct
    # a table account whose CODE is unknown (CREATE result): size/bytes
    # must havoc, never read as the concrete 0/zeros the table yields
    code_unknown = known_acct & (
        sf.base.acct_field(sf.base.acct_code, acct_slot) == CODE_UNKNOWN
    )
    # a concrete-offset CALLDATALOAD past the modeled window would read a
    # silent concrete 0 even though CALLDATASIZE is symbolic beyond it —
    # havoc instead (the engine's own policy: never a wrong value)
    cd_beyond_window = bool(spec.calldata) & is_cdload & (s[0] == 0) & beyond & at_top
    env_hv_need = m_env & (
        (cd_symoff & ~cd_select)
        | cd_beyond_window
        | (is_balance & unknown_addr)
        | (op == 0x40)  # BLOCKHASH
        | (((op == 0x3B) | (op == 0x3F)) & (unknown_addr | code_unknown))
    )
    # sub-frame CALLVALUE / CALLDATALOAD: values flow from the caller's
    # frame (tracked sym ids), not free leaves
    sub = ~at_top
    cv_sub = m_env & (op == 0x34) & sub
    CDW = sf.cd_sym.shape[1]
    cw = (off64 // 32).astype(I32)
    cd_al = (off64 % 32) == 0

    def _cd_sym_at(w):
        v = jnp.take_along_axis(
            sf.cd_sym, jnp.clip(w, 0, CDW - 1)[:, None], axis=1
        )[:, 0]
        return jnp.where((w >= 0) & (w < CDW), v, 0)

    cda = _cd_sym_at(cw)
    cdb = _cd_sym_at(cw + 1)
    cd_sub = m_env & is_cdload & sub & (s[0] == 0)
    # the word the caller stored whole at this offset of the window
    cdu = jnp.where(
        ~cd_al & ((off64 % 32).astype(I32) == sf.cd_ushift) & (cw < CDW)
        & ~sf.cd_havoc,
        jnp.take_along_axis(sf.cd_usym, jnp.clip(cw, 0, CDW - 1)[:, None],
                            axis=1)[:, 0], 0)
    cd_whole = cd_sub & (cdu != 0)
    hv_cd_need = cd_sub & ~cd_whole & (
        sf.cd_havoc | (~cd_al & ((cda != 0) | (cdb != 0))))
    # word 0 under a concrete selector: its node stands for the whole
    # word, its concrete shadow is exact in the first ``cd_ushift`` bytes
    cd_head = (cd_sub & cd_al & (cw == 0) & sf.cd_uhead & ~sf.cd_havoc
               & (cda != 0))
    cd_exact = cd_whole | (cd_sub & cd_al & ~sf.cd_havoc & (cda != 0)
                           & ~cd_head)
    sf = sf.replace(
        head_node=jnp.where(cd_head, cda, sf.head_node),
        head_len=jnp.where(cd_head, sf.cd_ushift, sf.head_len),
        hop_stats=_hop_count(
            sf.hop_stats, (HOP_CD_EXACT, cd_exact & (cdu >= 0)),
            (HOP_CD_HAVOC, hv_cd_need)))

    env_hv_need = env_hv_need | hv_cd_need
    sf, env_hv = _havoc(sf, env_hv_need)
    # sub-frame CALLER: a DELEGATECALL frame carries the caller frame's
    # msg.sender symbol (advisor r2: sender checks inside delegated code
    # must not be decided concretely while the top-frame model is symbolic)
    cl_sub = m_env & (op == 0x33) & sub & (sf.caller_sym != 0)
    r_env = jnp.where(need_leaf, env_leaf, 0)
    r_env = jnp.where(cv_sub, sf.callvalue_sym, r_env)
    r_env = jnp.where(cl_sub, sf.caller_sym, r_env)
    r_env = jnp.where(cd_sub & cd_al & ~sf.cd_havoc, cda, r_env)
    r_env = jnp.where(cd_whole, jnp.maximum(cdu, 0), r_env)
    r_env = jnp.where(env_hv_need, env_hv, r_env)
    # "executed ORIGIN" flag (DeprecatedOperations SWC-111): the leaf node
    # may pre-exist via seeding, so presence on the tape is not evidence
    sf = sf.replace(origin_read=sf.origin_read | (m_env & (op == 0x32)))

    # ---- CLS_SHA3 (concrete args): keccak chain over the hashed window ----
    m_sha = m & (cls == ci.CLS_SHA3)
    ln64 = u256.to_u64_saturating(a[1]).astype(I64)
    w0 = (off64 // 32).astype(I32)
    # chain span derived from the concrete handler's hash cap so they can't
    # drift: any ln the concrete handler accepts (<= MAX_HASH_BYTES, else
    # the lane errors there) fits in NCW words from w0
    NCW = (ci.MAX_HASH_BYTES + 31 + 31) // 32
    nw = jnp.clip((off64 % 32 + ln64 + 31) // 32, 0, NCW).astype(I32)
    wsyms = [
        _take_word_sym(sf.mem_sym, w0 + k) for k in range(NCW)
    ]
    in_win = [(jnp.int32(k) < nw) for k in range(NCW)]
    any_w_sym = jnp.zeros_like(m_sha)
    for k in range(NCW):
        any_w_sym = any_w_sym | (in_win[k] & (wsyms[k] != 0))
    # a window that does not fully fit the chain span would truncate the
    # hashed data and yield a WRONG digest downstream — havoc instead
    # (over-approximation policy: never a wrong value)
    fits_chain = (off64 % 32 + ln64) <= 32 * NCW
    sha_unknown = _reaches_floor(sf, off64, ln64)
    m_hvsha = m_sha & (ln64 > 0) & (sha_unknown | (any_w_sym & ~fits_chain))
    m_chain = m_sha & any_w_sym & ~sha_unknown & fits_chain
    sf, sha_hv = _havoc(sf, m_hvsha)
    seed_imm = jnp.zeros((f.pc.shape[0], 8), dtype=U32)
    seed_imm = seed_imm.at[:, 0].set(jnp.clip(ln64, 0, 2**31).astype(U32))
    seed_imm = seed_imm.at[:, 1].set((off64 % 32).astype(U32))
    sf, chain = append_node(sf, m_chain, int(SymOp.KECCAK_SEED), 0, 0, seed_imm)
    M = f.memory.shape[1]
    for k in range(NCW):
        mk = m_chain & in_win[k]
        w_conc = ci._be_bytes_to_word(
            ci._gather_bytes(sf.base.memory, (w0 + k).astype(I64) * 32, 32,
                             jnp.full_like(off64, M))
        )
        imm_k = jnp.where((wsyms[k] == 0)[:, None], w_conc, 0).astype(U32)
        sf, chain2 = append_node(sf, mk, int(SymOp.KECCAK_ABS), chain, wsyms[k], imm_k)
        chain = jnp.where(mk, chain2, chain)
    sf, dig = append_node(sf, m_chain, int(SymOp.KECCAK), chain, 0)
    r_sha = jnp.where(m_hvsha, sha_hv, jnp.where(m_chain, dig, 0))

    # ---- CLS_MEM (concrete offsets) ----
    m_mem = m & (cls == ci.CLS_MEM)
    is_load = op == 0x51
    is_store8 = op == 0x53
    aligned = (off64 % 32) == 0
    wm = (off64 // 32).astype(I32)
    wsym_a = _take_word_sym(sf.mem_sym, wm)
    wsym_b = _take_word_sym(sf.mem_sym, wm + 1)
    # words at or above the lane's floor are unknown whatever they hold
    unk_a = wm >= sf.mem_floor
    unk_ab = wm + jnp.where(aligned, 0, 1) >= sf.mem_floor
    # the word stored whole at this very offset (``mem_usym``): the
    # unaligned counterpart of ``wsym_a``
    sh = (off64 % 32).astype(I32)
    same_shift = ~aligned & (sh == sf.mem_ushift)
    uent = jnp.where(same_shift & ~unk_ab & (off64 + 32 <= M),
                     _take_word_sym(sf.mem_usym, wm), 0)
    whole = uent != 0
    # MLOAD
    load_sym_needed = m_mem & is_load & ~whole & (
        (aligned & (wsym_a != 0))
        | (~aligned & ((wsym_a != 0) | (wsym_b != 0))) | unk_ab
    )
    hv_load_need = load_sym_needed & (~aligned | unk_ab)
    # unaligned MSTORE: havoc both covered words if anything symbolic
    st_mask = m_mem & ~is_load
    un_st = st_mask & ~is_store8 & ~aligned
    un_any = un_st & (
        (s[1] != 0) | (wsym_a != 0) | (wsym_b != 0) | unk_ab
    )
    sf, hv_a = _havoc(sf, hv_load_need | un_any)
    r_mload = jnp.where(
        load_sym_needed, jnp.where(aligned & ~unk_ab, wsym_a, hv_a),
        jnp.where(m_mem & is_load & whole, jnp.maximum(uent, 0), 0)
    )
    mstore_aligned = st_mask & ~is_store8 & aligned
    mem_sym = _set_word_sym(sf.mem_sym, wm, s[1], mstore_aligned)
    sf, hv_b = _havoc(sf, un_any)
    mem_sym = _set_word_sym(mem_sym, wm, hv_a, un_any)
    mem_sym = _set_word_sym(mem_sym, wm + 1, hv_b, un_any)
    # MSTORE8: havoc the word if value or word symbolic
    m8_any = st_mask & is_store8 & ((s[1] != 0) | (wsym_a != 0) | unk_a)
    sf, hv_c = _havoc(sf, m8_any)
    mem_sym = _set_word_sym(mem_sym, wm, hv_c, m8_any)
    # the unaligned words: a store at the lane's shift (or the lane's
    # first) records its word whole; one at another shift starts anew. An
    # aligned store or an MSTORE8 ends the two whole words it writes into
    restart = un_st & (sh != sf.mem_ushift)
    usym = jnp.where(restart[:, None], 0, sf.mem_usym)
    usym = _set_word_sym(
        usym, wm, jnp.where(s[1] != 0, s[1], USYM_CONCRETE), un_st)
    cut = st_mask & ~un_st
    usym = _set_word_sym(usym, wm, jnp.zeros_like(wm), cut)
    usym = _set_word_sym(usym, wm - 1, jnp.zeros_like(wm), cut)
    # the word whose head stays concrete under the first argument
    head_kept = un_st & (wsym_a == 0) & ~unk_a
    uhead = jnp.where(restart, -1, sf.mem_uhead)
    uhead = jnp.where((cut & (uhead == wm)) | (un_st & (uhead == wm + 1)),
                      -1, uhead)
    uhead = jnp.where(head_kept, wm, uhead)
    sf = sf.replace(mem_sym=mem_sym, mem_usym=usym, mem_uhead=uhead,
                    mem_ushift=jnp.where(un_st, sh, sf.mem_ushift))

    # ---- CLS_COPY (concrete args) ----
    m_cp = m & (cls == ci.CLS_COPY)
    is_ext = op == 0x3C
    dst64 = jnp.where(is_ext, u256.to_u64_saturating(a[1]), off64).astype(I64)
    cln64 = u256.to_u64_saturating(jnp.where(is_ext[:, None], a[3], a[2])).astype(I64)
    is_cdcopy = op == 0x37
    is_rdcopy = op == 0x3E
    # calldatacopy of symbolic calldata / returndatacopy after a symbolic
    # call: memory is unknown from the destination's word up (the words
    # filled stay havoc leaves). Sub-frame calldata is only symbolic
    # where the caller's memory window was.
    cd_symbolic = jnp.where(
        at_top,
        jnp.full_like(sf.cd_havoc, spec.calldata),
        sf.cd_havoc | jnp.any(sf.cd_sym != 0, axis=1),
    )
    cd_havoc = m_cp & (cln64 > 0) & (
        (is_cdcopy & cd_symbolic) | (is_rdcopy & sf.retdata_sym)
    )
    # concrete-source copies (code/extcode/concrete returndata): fully
    # covered words become concrete; partial edge words with stale syms ->
    # havoc flag. EXTCODECOPY of an unknown-code account (CREATE result)
    # is NOT a concrete source — the zeros the concrete handler wrote are
    # wrong, so the window havocs instead.
    ext_unknown = is_ext & code_unknown
    conc_src = (m_cp & ~is_cdcopy & ~(is_rdcopy & sf.retdata_sym)
                & (cln64 > 0) & ~ext_unknown)
    W = sf.mem_sym.shape[1]
    wids = jnp.arange(W)[None, :]
    full_lo = ((dst64 + 31) // 32)[:, None]
    full_hi = ((dst64 + cln64) // 32)[:, None]
    full_cover = (wids >= full_lo) & (wids < full_hi) & conc_src[:, None]
    mem_sym2 = jnp.where(full_cover, 0, sf.mem_sym)
    edge_lo = (dst64 // 32)[:, None]
    edge_hi = ((dst64 + cln64) // 32)[:, None]
    edge = ((wids == edge_lo) | (wids == edge_hi)) & ~full_cover & conc_src[:, None]
    edge_dirty = jnp.any(edge & (sf.mem_sym != 0), axis=1)
    # a copy ends the whole words it writes into
    ubyte = wids.astype(I64) * 32 + sf.mem_ushift[:, None]
    copied_into = ((m_cp & (cln64 > 0))[:, None]
                   & (ubyte < (dst64 + cln64)[:, None])
                   & (ubyte + 32 > dst64[:, None]))
    sf = sf.replace(
        mem_sym=mem_sym2,
        mem_usym=jnp.where(copied_into, 0, sf.mem_usym),
        mem_uhead=jnp.where(m_cp & (cln64 > 0), -1, sf.mem_uhead),
        mem_floor=_lower_floor(
            sf.mem_floor,
            cd_havoc | (conc_src & edge_dirty)
            | (m_cp & ext_unknown & (cln64 > 0)), _floor_word(dst64)),
    )

    # ---- CLS_HALT: capture return-payload syms; SELFDESTRUCT beneficiary ----
    m_halt = m & (cls == ci.CLS_HALT)
    has_data = (op == 0xF3) | (op == 0xFD)
    rv_words = sf.rv_sym.shape[1]
    cap_ok = m_halt & has_data & aligned & ~_reaches_floor(sf, off64, ln64)
    rv_sym = sf.rv_sym
    for k in range(rv_words):
        in_rv = (jnp.int32(k) * 32) < ln64
        rv_sym = rv_sym.at[:, k].set(
            jnp.where(cap_ok & in_rv, _take_word_sym(sf.mem_sym, wm + k), rv_sym[:, k])
        )
    is_sd = op == 0xFF
    is_inv = op == 0xFE
    first_inv = m_halt & is_inv & (sf.inv_pc < 0)
    first_sd = m_halt & is_sd & (sf.sd_pc < 0)

    # SELFDESTRUCT balance sweep (reference: selfdestruct_ transfer
    # ⚠unv): a CONCRETE beneficiary in the account table is credited and
    # the executing account zeroed; a symbolic/unknown beneficiary only
    # zeroes self (funds leave the modeled world) — the epoch bump makes
    # later BALANCE reads fresh leaves either way, never a stale value.
    fb = sf.base
    m_sd = m_halt & is_sd
    ben_found, ben_slot = fb.acct_lookup(a[0])
    sweep = m_sd & (s[0] == 0) & ben_found & (ben_slot != fb.cur_acct)
    lanes_sd = jnp.arange(fb.pc.shape[0])
    A_sd = fb.acct_used.shape[1]
    ben_bal = fb.acct_field(fb.acct_bal, ben_slot)
    self_bal = fb.self_balance
    acct_bal_sd = fb.acct_bal.at[
        lanes_sd, jnp.where(sweep, ben_slot, A_sd)].set(
        u256.add(ben_bal, self_bal), mode="drop")
    acct_bal_sd = acct_bal_sd.at[
        lanes_sd, jnp.where(m_sd, fb.cur_acct, A_sd)].set(
        jnp.zeros_like(self_bal), mode="drop")
    sf = sf.replace(base=fb.replace(acct_bal=acct_bal_sd),
                    bal_epoch=sf.bal_epoch + m_sd.astype(I32))

    sf = sf.replace(
        rv_sym=rv_sym,
        sd_to_sym=jnp.where(m_halt & is_sd, s[0], sf.sd_to_sym),
        sd_to=jnp.where((m_halt & is_sd)[:, None], a[0], sf.sd_to).astype(U32),
        sd_pc=jnp.where(first_sd, sf.base.pc, sf.sd_pc),
        sd_cid=jnp.where(first_sd, sf.base.contract_id, sf.sd_cid),
        inv_pc=jnp.where(first_inv, sf.base.pc, sf.inv_pc),
        inv_cid=jnp.where(first_inv, sf.base.contract_id, sf.inv_cid),
    )

    # ---- CLS_LOG: sym overlay of the record the concrete handler wrote ----
    m_log = m & (cls == ci.CLS_LOG)
    LS = sf.base.log_pc.shape[1]
    log_idx = sf.base.n_logs - 1  # concrete handler already bumped it
    wl = jnp.where(m_log & (log_idx >= 0) & (log_idx < LS), log_idx, LS)
    lanes_all = jnp.arange(f.pc.shape[0])
    d0_sym = jnp.where(aligned & ~unk_a, wsym_a, -1)
    d0_sym = jnp.where(u256.to_u64_saturating(a[1]) == 0, 0, d0_sym)
    log_nt = op - 0xA0  # LOG0 has no topic: s[2] is an unrelated slot
    sf = sf.replace(
        log_topic0_sym=ci._write_slot(
            sf.log_topic0_sym, wl, jnp.where(log_nt >= 1, s[2], 0)),
        log_data0_sym=ci._write_slot(sf.log_data0_sym, wl, d0_sym),
    )

    # ---- write result syms into the result slot (clears stale ids) ----
    r = jnp.zeros_like(op)
    r = jnp.where(m_node, r_bin, r)
    r = jnp.where(m_env, r_env, r)
    r = jnp.where(m_sha, r_sha, r)
    r = jnp.where(m_mem & is_load, r_mload, r)
    m_modhv = m_mod_sym
    sf2, hv_mod = _havoc(sf, m_modhv)
    sf = sf2
    r = jnp.where(m_modhv, hv_mod, r)
    writes_result = (
        m_bin | m_mod | m_env | m_sha | (m_mem & is_load)
    )
    res_slot = pre_sp - sin
    sf = sf.replace(
        stack_sym=_set_sym_slot(sf.stack_sym, res_slot, r, writes_result)
    )
    return sf


# ---------------------------------------------------------------------------
# Superstep / forking / run loop
# ---------------------------------------------------------------------------


def _berlin_gas_pre(sf: SymFrontier, op, run, a, s) -> SymFrontier:
    """EIP-2929 cold surcharges, charged to the EXECUTING frame before
    dispatch (so a sub-call's rollback snapshot includes its caller's
    access cost — access-list growth is never refunded... except by frame
    revert, which the fr_warm_* snapshots handle).

    Warm/cold resolution: storage keys against the associative cache's
    per-tx ``st_warm`` bits; addresses against the account table's
    ``warm_acct``. A SYMBOLIC key/address — and any address outside the
    table — cannot be tracked: the surcharge lands in ``gas_max`` only
    (``gas_min`` keeps the all-warm floor), preserving min <= actual <=
    max. Account-op targets are marked warm here; storage marking happens
    post-dispatch (``_berlin_gas_post``) once SSTORE has allocated."""
    from ..disassembler.opcodes import (G_COLD_ACCOUNT, G_COLD_SLOAD,
                                        G_WARM_ACCESS)

    f = sf.base
    P = f.n_lanes
    # the static berlin table already charged the WARM base; the cold
    # surcharge is the DIFFERENCE (EVM: cold replaces, not augments)
    SUR_SLOAD = G_COLD_SLOAD - G_WARM_ACCESS
    SUR_ACCT = G_COLD_ACCOUNT - G_WARM_ACCESS

    # --- storage: SLOAD/SSTORE (key = operand 0)
    m_st = run & ((op == 0x54) | (op == 0x55))
    key_conc = s[0] == 0
    hit, _, slot = ci._storage_lookup(f, a[0])
    K = f.st_warm.shape[1]
    warm_bit = jnp.take_along_axis(
        f.st_warm, jnp.clip(slot, 0, K - 1)[:, None], axis=1)[:, 0]
    st_cold = ~(hit & warm_bit)
    st_sur_max = jnp.where(m_st, SUR_SLOAD, 0).astype(I64)
    st_sur_min = jnp.where(m_st & key_conc & st_cold, SUR_SLOAD, 0).astype(I64)
    st_sur_max = jnp.where(m_st & key_conc & ~st_cold, 0, st_sur_max)

    # --- account access: BALANCE/EXTCODESIZE/EXTCODECOPY/EXTCODEHASH
    # (addr = operand 0), CALL family (operand 1), SELFDESTRUCT (operand 0)
    m_acct0 = run & ((op == 0x31) | (op == 0x3B) | (op == 0x3C)
                     | (op == 0x3F) | (op == 0xFF))
    m_call = run & (ci._J_CLASS[op] == ci.CLS_CALL)
    addr_w = jnp.where(m_call[:, None], a[1], a[0])
    addr_sym = jnp.where(m_call, s[1], s[0])
    m_addr = (m_acct0 | m_call)
    found, aslot = f.acct_lookup(addr_w)
    A = f.warm_acct.shape[1]
    awarm = found & jnp.take_along_axis(
        f.warm_acct, jnp.clip(aslot, 0, A - 1)[:, None], axis=1)[:, 0]
    addr_conc = addr_sym == 0
    tracked = addr_conc & found
    # a SYMBOLIC CALL target is not charged here: the callee-enumeration
    # fork that resolves it re-executes with a concrete target and pays
    # then (charging the parked lane once per retry would compound); the
    # never-resolving external fallback pays in _h_sym_call. The other
    # address ops (BALANCE/EXTCODE*/SELFDESTRUCT) execute exactly once,
    # so a symbolic address charges cold into gas_max right now.
    ac_sur_min = jnp.where(m_addr & tracked & ~awarm, SUR_ACCT, 0).astype(I64)
    ac_sur_max = jnp.where(
        (m_addr & addr_conc & (~found | ~awarm))
        | (m_acct0 & ~addr_conc), SUR_ACCT, 0).astype(I64)

    # mark touched table accounts warm (symbolic addresses can't resolve)
    aidx = jnp.where(m_addr & tracked, aslot, A)
    warm_acct = ci._write_slot(f.warm_acct, aidx, True)

    return sf.replace(base=f.replace(
        gas_min=f.gas_min + st_sur_min + ac_sur_min,
        gas_max=f.gas_max + st_sur_max + ac_sur_max,
        warm_acct=warm_acct,
    ))


def _berlin_gas_post(sf: SymFrontier, op, run, key_w, key_s) -> SymFrontier:
    """Post-dispatch storage warm marking: the touched key's cache entry
    (allocated by SSTORE, the symbolic SLOAD memo, or here for a concrete
    SLOAD miss) gets its per-tx warm bit."""
    f = sf.base
    P = f.n_lanes
    m_st = run & ((op == 0x54) | (op == 0x55)) & (key_s == 0) & ~f.error
    hit, _, slot = ci._storage_lookup(f, key_w)
    # concrete SLOAD miss: allocate a (key, 0, unwritten) entry so the
    # NEXT access is provably warm (the concrete handler doesn't insert)
    need_alloc = m_st & ~hit & (op == 0x54)
    widx, overflow = ci.storage_alloc(f, hit, slot, need_alloc)
    st_keys = ci._write_slot(f.st_keys, widx, key_w)
    st_used = ci._write_slot(f.st_used, widx, True)
    st_acct = ci._write_slot(f.st_acct, widx, f.cur_acct)
    # a full cache simply loses warm tracking (overcharges later, sound)
    K = f.st_warm.shape[1]
    midx = jnp.where(m_st & hit, slot,
                     jnp.where(need_alloc & ~overflow, widx, K))
    st_warm = ci._write_slot(f.st_warm, jnp.clip(midx, 0, K), True)
    return sf.replace(base=f.replace(
        st_keys=st_keys, st_used=st_used, st_acct=st_acct, st_warm=st_warm,
    ))


# declared write sets for the narrow claimed-handler conds (dotted paths
# into the SymFrontier pytree; enforced at trace time by ci.narrow_cond)
_TAPE_WRITES = ("tape_op", "tape_a", "tape_b", "tape_imm", "tape_hash",
                "tape_len")
_STORAGE_WRITES = (
    "base.st_keys", "base.st_vals", "base.st_used",
    "base.st_written", "base.st_acct", "base.error", "base.err_code",
    "st_key_sym", "st_val_sym", "st_seq", "st_seq_ctr", "dep_read",
    "sstore_after_call_pc", "sstore_ac_cid", "arb_key_node", "arb_key_pc",
    "arb_key_cid",
) + _TAPE_WRITES
_JUMP_WRITES = (
    "base.pc", "base.sp", "base.halted", "base.error", "base.err_code",
    "con_node", "con_sign", "con_pc", "con_len",
    "sym_jump_dest", "sym_jump_pc", "sym_jump_cid", "fork_req", "fork_dest",
)
_MISC_WRITES = (
    "base.sp", "base.halted", "base.reverted", "base.retval_len",
    "base.n_logs", "base.log_pc", "base.log_cid", "base.log_ntopics",
    "base.log_topic0", "base.error", "base.err_code",
    "havoc_cnt", "log_topic0_sym", "log_data0_sym", "stack_sym",
    "mem_floor", "rv_havoc",
) + _TAPE_WRITES

# pop_frames' declared write set: everything the caller-restore touches —
# but NOT the fr_* frame stacks ([P, D, ...] snapshots, read-only here),
# the tape, kb_m/kb_v, or the con_* constraint arrays. Keeping those out
# of the cond boundary matters: the old full-state ``lax.cond`` carried
# every leaf of the frontier (frame-stack snapshots alone are D× the base
# state) through the boundary on EVERY superstep — one of the cond-copy
# buckets tools/scaling_report.py attributes.
_POP_FRAME_WRITES = (
    "base.pc", "base.sp", "base.sp_base", "base.depth", "base.init_depth",
    "base.acct_used", "base.acct_code", "base.fr_create_slot",
    "base.static", "base.cur_acct", "base.contract_id",
    "base.caller_addr", "base.callvalue", "base.memory", "base.mem_words",
    "base.calldata", "base.calldata_len", "base.returndata",
    "base.returndata_len", "base.retval_len", "base.stack",
    "base.st_keys", "base.st_vals", "base.st_used", "base.st_written",
    "base.st_acct", "base.acct_bal", "base.warm_acct", "base.st_warm",
    "base.gas_min", "base.gas_max", "base.gas_limit",
    "base.halted", "base.reverted", "base.error", "base.err_code",
    "stack_sym", "mem_sym", "mem_floor", "retdata_sym", "rv_sym",
    "rv_havoc", "cd_from_mem", "cd_havoc", "cd_sym", "callvalue_sym",
    "caller_sym", "bal_epoch", "st_val_sym", "st_key_sym", "st_seq",
    "sub_revert_pc", "sub_revert_cid",
    "cd_usym", "cd_ushift", "cd_uhead", "mem_usym", "mem_ushift",
    "mem_uhead", "hop_stats", "sub_fail_pc", "sub_fail_cid",
)


@jax.named_scope("sym_superstep")
def sym_superstep(sf: SymFrontier, env: Env, corpus: Corpus,
                  spec: SymSpec = SymSpec(),
                  limits: LimitsConfig = DEFAULT_LIMITS) -> SymFrontier:
    """Advance every running lane by one instruction, symbolically."""
    berlin = limits.gas_schedule == "berlin"
    f, op, run, old_pc = ci.prologue(sf.base, corpus, berlin=berlin)
    sf = sf.replace(base=f)
    cls = ci._J_CLASS[op]
    pre_sp = f.sp
    pre_stack_sym = sf.stack_sym
    a = [ci._peek(f, i) for i in range(4)]
    s = [_peek_sym(sf, i) for i in range(7)]
    if berlin:
        sf = _berlin_gas_pre(sf, op, run, a, s)
        f = sf.base

    is_jumpi = op == 0x57
    known, ksign = _lookup_constraint(sf, s[1])
    claim_jump = run & (cls == ci.CLS_JUMP) & ((s[0] != 0) | (is_jumpi & (s[1] != 0)))
    claim_storage = run & (cls == ci.CLS_STORAGE)
    claim_call = run & (cls == ci.CLS_CALL)
    claim_create = run & (cls == ci.CLS_CREATE)
    claim_callish = claim_call | claim_create
    claim_memoff = run & (cls == ci.CLS_MEM) & (s[0] != 0)
    claim_sha3off = run & (cls == ci.CLS_SHA3) & ((s[0] != 0) | (s[1] != 0))
    is_ext = op == 0x3C
    claim_copyoff = run & (cls == ci.CLS_COPY) & (
        (s[0] != 0) | (s[1] != 0) | (s[2] != 0) | (is_ext & (s[3] != 0))
    )
    has_data_halt = (op == 0xF3) | (op == 0xFD)
    claim_haltoff = run & (cls == ci.CLS_HALT) & has_data_halt & ((s[0] != 0) | (s[1] != 0))
    claim_logoff = run & (cls == ci.CLS_LOG) & ((s[0] != 0) | (s[1] != 0))
    claimed = (
        claim_jump | claim_storage | claim_callish | claim_memoff
        | claim_sha3off | claim_copyoff | claim_haltoff | claim_logoff
    )

    f = ci.dispatch(sf.base, env, corpus, op, run, old_pc, skip=claimed)
    # the supersteps in which ``dispatch`` took ``_h_copy``'s cond
    copied = jnp.any(run & ~claimed & (cls == ci.CLS_COPY))
    sf = sf.replace(base=f, copy_steps=sf.copy_steps + copied.astype(I32))

    sf = _overlay(sf, env, spec, op, run & ~claimed, cls, pre_sp,
                  pre_stack_sym, a, s, limits)

    def _cond_apply(sf, mask, fn):
        return lax.cond(jnp.any(mask), fn, lambda x: x, sf)

    # the hot claimed handlers run behind NARROW conds (ci.narrow_cond):
    # only their declared write sets become cond outputs, keeping the
    # rest of the SymFrontier (frame stacks, memory, calldata overlays)
    # out of the boundary. CALL/CREATE write half the frontier and fire
    # rarely — they keep the plain full-state cond.
    P = sf.base.pc.shape[0]
    sf, st_aux = ci.narrow_cond(
        jnp.any(claim_storage),
        lambda x: _h_sym_storage(x, spec, op, claim_storage),
        sf, _STORAGE_WRITES,
        aux_defaults={
            "r": jnp.zeros((P, 8), dtype=jnp.uint32),
            "r_sym": jnp.zeros(P, dtype=I32),
            "w": jnp.zeros(P, dtype=bool),
        })
    # shared claimed writeback: the SLOAD result lands here, and sp for
    # ALL storage-claimed lanes advances by the arity table (SLOAD 0,
    # SSTORE -2) — one stack pass instead of a stack-carrying cond
    fb = sf.base
    sf = sf.replace(
        base=fb.replace(
            stack=ci._set_slot(fb.stack, fb.sp - 1, st_aux["r"], st_aux["w"]),
            sp=jnp.where(claim_storage, fb.sp + ci._J_D_SP[op], fb.sp),
        ),
        stack_sym=_set_sym_slot(sf.stack_sym, fb.sp - 1, st_aux["r_sym"],
                                st_aux["w"]),
    )
    sf = ci.narrow_cond(
        jnp.any(claim_jump),
        lambda x: _h_sym_jump(x, corpus, op, claim_jump, old_pc, known,
                              ksign),
        sf, _JUMP_WRITES)
    sf = _cond_apply(sf, claim_call,
                     lambda x: _h_sym_call(x, corpus, op, claim_call, old_pc,
                                           spec, limits))
    sf = _cond_apply(sf, claim_create,
                     lambda x: _h_sym_create(x, op, claim_create, old_pc))
    misc = claim_memoff | claim_sha3off | claim_copyoff | claim_haltoff | claim_logoff
    sf = ci.narrow_cond(
        jnp.any(misc),
        lambda x: _h_sym_claimed_misc(x, op, claim_memoff, claim_sha3off,
                                      claim_copyoff, claim_haltoff,
                                      claim_logoff),
        sf, _MISC_WRITES)

    if berlin:
        sf = _berlin_gas_post(sf, op, run, a[0], s[0])

    # bounded loops: any jump that landed at-or-before its own pc (the
    # fork-taken copies are counted in expand_forks)
    fb = sf.base
    back = (run & (cls == ci.CLS_JUMP) & ~fb.halted & ~fb.error
            & (fb.pc <= old_pc))
    sf = _note_backjump(sf, back, old_pc, fb.pc, limits.loop_bound)

    f = ci.epilogue(sf.base, op, run, old_pc)
    sf = sf.replace(base=f)
    # sub-frames that halted (or failed) this step return to their caller.
    # Narrow cond: only pop_frames' declared writes cross the boundary —
    # the fr_* snapshot stacks, tape, and constraint arrays bypass it, so
    # the (rare) pop never forces a full-frontier carry copy.
    any_ended = jnp.any(sf.base.active & (sf.base.depth > 0)
                        & (sf.base.halted | sf.base.error))
    return ci.narrow_cond(any_ended, lambda x: pop_frames(x, corpus),
                          sf, _POP_FRAME_WRITES)


def between_txs(sf: SymFrontier, require_mutation: bool = True,
                runtime_offset: int = 0,
                dependency_prune: bool = True,
                first_message_tx: int = 0) -> SymFrontier:
    """Advance surviving lanes to the next symbolic transaction.

    Counterpart of the reference's ``open_states`` handoff
    (``transaction/symbolic.py:execute_message_call`` iterating world
    states that survived the previous tx ⚠unv, SURVEY.md §3.2): a lane
    proceeds iff it halted normally AND mutated storage — dropping
    non-mutating paths is exactly the reference's MutationPruner
    (``laser/plugin/plugins/mutation_pruner.py`` ⚠unv). A selfdestructed
    contract has no code left, so those lanes retire too. Per-tx machine
    state resets; storage, the tape, and path constraints carry over;
    the one-shot event records (calls, arith, INVALID/SSTORE pcs) are
    per-transaction and reset — the per-tx context snapshots taken by
    ``SymExecWrapper`` already preserved them for detection.
    tx-scoped leaves re-key via tx_id (TX_STRIDE encoding).

    ``require_mutation=False`` + ``runtime_offset`` serve the
    creation→runtime handoff (reference: ``execute_contract_creation``
    then message calls ⚠unv): a constructor needn't write storage for its
    deploy to count, and the surviving lanes switch from the creation
    image to the runtime image (the corpus holds it ``runtime_offset``
    images further on) while keeping their storage. The switch follows
    the lane's own home contract, not the lane's position: a constructor
    that forks (solc's non-payable check does) puts the copy in any free
    lane of the frontier, another contract's block included.
    """
    b = sf.base
    P = sf.n_lanes
    go = b.active & b.halted & ~b.error & ~b.reverted & ~b.selfdestructed
    if require_mutation:
        go = go & jnp.any(b.st_written, axis=1)
    if dependency_prune:
        # DependencyPruner (reference: ``plugins/dependency_pruner.py``
        # ⚠unv, SURVEY §5.7 "the single biggest algorithmic speedup"): a
        # later message-call path that read nothing any prior tx wrote
        # behaved exactly like an earlier message call from the same state
        # — its issues were already collected in this tx's snapshot, so it
        # retires instead of spawning redundant deeper exploration. The
        # FIRST message call is exempt (``first_message_tx`` shifts by one
        # when a creation tx ran: the constructor is different code, not
        # an equivalent ancestor).
        go = go & ((sf.tx_id <= first_message_tx) | sf.dep_read)
    new_home = (b.home_contract + runtime_offset if runtime_offset
                else b.home_contract)
    attacker = jnp.broadcast_to(
        jnp.asarray(u256.from_int(ATTACKER_ADDRESS)), (P, 8)
    ).astype(jnp.uint32)
    return sf.replace(
        base=b.replace(
            active=go,
            halted=jnp.zeros_like(b.halted),
            err_code=jnp.zeros_like(b.err_code),
            reverted=jnp.zeros_like(b.reverted),
            pc=jnp.where(go, 0, b.pc),
            stack=jnp.where(go[:, None, None], 0, b.stack),
            sp=jnp.where(go, 0, b.sp),
            depth=jnp.where(go, 0, b.depth),
            sp_base=jnp.where(go, 0, b.sp_base),
            static=jnp.where(go, False, b.static),
            cur_acct=jnp.where(go, b.home_acct, b.cur_acct),
            home_contract=jnp.where(go, new_home, b.home_contract),
            contract_id=jnp.where(go, new_home, b.contract_id),
            caller_addr=jnp.where(go[:, None], attacker, b.caller_addr),
            callvalue=jnp.where(go[:, None], 0, b.callvalue).astype(jnp.uint32),
            memory=jnp.where(go[:, None], 0, b.memory),
            mem_words=jnp.where(go, 0, b.mem_words),
            gas_min=jnp.where(go, 0, b.gas_min),
            gas_max=jnp.where(go, 0, b.gas_max),
            calldata_len=jnp.where(go, b.calldata.shape[1], b.calldata_len),
            returndata_len=jnp.where(go, 0, b.returndata_len),
            retval_len=jnp.where(go, 0, b.retval_len),
            n_logs=jnp.where(go, 0, b.n_logs),
            log_pc=jnp.where(go[:, None], 0, b.log_pc),
            log_cid=jnp.where(go[:, None], 0, b.log_cid),
            log_ntopics=jnp.where(go[:, None], 0, b.log_ntopics),
            log_topic0=jnp.where(go[:, None, None], 0, b.log_topic0),
            log_data0=jnp.where(go[:, None, None], 0, b.log_data0),
            st_written=jnp.where(go[:, None], False, b.st_written),
            init_depth=jnp.where(go, 0, b.init_depth),
            init_len=jnp.where(go, 0, b.init_len),
            # EIP-2929 access lists are per-transaction: reset to the
            # tx-start warm set (origin/caller + the target account)
            warm_acct=jnp.where(
                go[:, None],
                (jnp.arange(b.warm_acct.shape[1])[None, :] == ACCT_ATTACKER)
                | (jnp.arange(b.warm_acct.shape[1])[None, :]
                   == b.home_acct[:, None]),
                b.warm_acct),
            st_warm=jnp.where(go[:, None], False, b.st_warm),
        ),
        stack_sym=jnp.where(go[:, None], 0, sf.stack_sym),
        mem_sym=jnp.where(go[:, None], 0, sf.mem_sym),
        mem_floor=jnp.where(go, MEM_EXACT, sf.mem_floor),
        retdata_sym=jnp.where(go, False, sf.retdata_sym),
        rv_sym=jnp.where(go[:, None], 0, sf.rv_sym),
        rv_havoc=jnp.where(go, False, sf.rv_havoc),
        mem_usym=jnp.where(go[:, None], 0, sf.mem_usym),
        mem_ushift=jnp.where(go, 0, sf.mem_ushift),
        mem_uhead=jnp.where(go, -1, sf.mem_uhead),
        cd_reads=jnp.zeros_like(sf.cd_reads),
        hop_stats=jnp.zeros_like(sf.hop_stats),
        cd_from_mem=jnp.where(go, False, sf.cd_from_mem),
        cd_havoc=jnp.where(go, False, sf.cd_havoc),
        cd_sym=jnp.where(go[:, None], 0, sf.cd_sym),
        cd_usym=jnp.where(go[:, None], 0, sf.cd_usym),
        cd_ushift=jnp.where(go, 0, sf.cd_ushift),
        cd_uhead=jnp.where(go, False, sf.cd_uhead),
        callvalue_sym=jnp.where(go, 0, sf.callvalue_sym),
        caller_sym=jnp.where(go, 0, sf.caller_sym),
        # new tx: the (symbolic) incoming callvalue changes balances again
        bal_epoch=sf.bal_epoch + go.astype(I32),
        sub_revert_pc=jnp.where(go, -1, sf.sub_revert_pc),
        sub_revert_cid=jnp.where(go, 0, sf.sub_revert_cid),
        sub_fail_pc=jnp.where(go, -1, sf.sub_fail_pc),
        sub_fail_cid=jnp.where(go, 0, sf.sub_fail_cid),
        tx_id=jnp.where(go, sf.tx_id + 1, sf.tx_id),
        # per-tx one-shot event records reset so tx N+1 can't inherit
        # tx N's calls/arith/SSTORE-after-call evidence (the per-tx
        # snapshot consumed them already)
        sym_jump_dest=jnp.where(go, 0, sf.sym_jump_dest),
        sym_jump_pc=jnp.where(go, -1, sf.sym_jump_pc),
        sym_jump_cid=jnp.where(go, 0, sf.sym_jump_cid),
        # the saturation counters reset for EVERY lane (not just survivors):
        # coverage_summary sums them across tx snapshots, and a retired
        # lane's stale count would be recounted each remaining tx
        n_calls=jnp.zeros_like(sf.n_calls),
        n_mut_calls=jnp.zeros_like(sf.n_mut_calls),
        call_op=jnp.where(go[:, None], 0, sf.call_op),
        call_to=jnp.where(go[:, None, None], 0, sf.call_to),
        call_to_sym=jnp.where(go[:, None], 0, sf.call_to_sym),
        call_value=jnp.where(go[:, None, None], 0, sf.call_value),
        call_value_sym=jnp.where(go[:, None], 0, sf.call_value_sym),
        call_pc=jnp.where(go[:, None], 0, sf.call_pc),
        call_cid=jnp.where(go[:, None], 0, sf.call_cid),
        log_topic0_sym=jnp.where(go[:, None], 0, sf.log_topic0_sym),
        log_data0_sym=jnp.where(go[:, None], 0, sf.log_data0_sym),
        origin_read=jnp.where(go, False, sf.origin_read),
        inv_pc=jnp.where(go, -1, sf.inv_pc),
        inv_cid=jnp.where(go, 0, sf.inv_cid),
        sstore_after_call_pc=jnp.where(go, -1, sf.sstore_after_call_pc),
        sstore_ac_cid=jnp.where(go, 0, sf.sstore_ac_cid),
        arb_key_node=jnp.where(go, 0, sf.arb_key_node),
        arb_key_pc=jnp.where(go, -1, sf.arb_key_pc),
        arb_key_cid=jnp.where(go, 0, sf.arb_key_cid),
        dropped_forks=jnp.zeros_like(sf.dropped_forks),
        call_enum=jnp.zeros_like(sf.call_enum),
        fork_cslot=jnp.full_like(sf.fork_cslot, -1),
        n_arith=jnp.zeros_like(sf.n_arith),
        arith_op=jnp.where(go[:, None], 0, sf.arith_op),
        arith_a=jnp.where(go[:, None], 0, sf.arith_a),
        arith_b=jnp.where(go[:, None], 0, sf.arith_b),
        arith_r=jnp.where(go[:, None], 0, sf.arith_r),
        arith_pc=jnp.where(go[:, None], 0, sf.arith_pc),
        arith_cid=jnp.where(go[:, None], 0, sf.arith_cid),
        # retired lanes (reverted / error / non-mutating) free their slots
        # for forks of the surviving ones; their results were consumed by
        # the per-tx detection pass before this call. Loss accounting
        # (err_code / killed_infeasible) resets so the host-side per-tx
        # tally in SymExecWrapper counts each lost lane exactly once even
        # after its slot is recycled by expand_forks.
        killed_infeasible=jnp.zeros_like(sf.killed_infeasible),
        # per-tx loop budget + dependency evidence reset
        lb_key=jnp.where(go[:, None], -1, sf.lb_key),
        lb_cnt=jnp.where(go[:, None], 0, sf.lb_cnt),
        lb_len=jnp.where(go, 0, sf.lb_len),
        dep_read=jnp.where(go, False, sf.dep_read),
    )


@jax.named_scope("plan_fork_map")
def plan_fork_map(req2, free2, key, fork_policy: str = "fifo"):
    """The fork source→destination mapping machinery, factored out of
    :func:`expand_forks` so tools/scaling_report.py can trace and cost
    it in isolation (the whole-frontier copy around it is linear in P
    and drowns this term inside the full ``expand_forks`` jaxpr).

    Inputs are block-shaped ``[G, B]``: the live request mask, the free
    mask, and the policy key (ignored for fifo). Returns
    ``(src2 [G, B], is_copy [P], slot [P])`` — per-destination source
    index, the copy mask, and the per-source admission sentinel
    (``slot == P`` ⇔ starved; any other value means admitted).

    Scatter-free on every backend: one sort gives the admission order,
    and the map is built destination-major (a cumsum over the free mask
    plus one gather), so the cost is linear in P; tests/fork_map_ref.py
    holds the source-major reference it is compared with.
    """
    G, B = req2.shape
    P = G * B
    loc = jnp.arange(B, dtype=I32)[None, :]
    n_free = jnp.sum(free2.astype(I32), axis=1, keepdims=True)
    if fork_policy == "fifo":
        rank = jnp.cumsum(req2.astype(I32), axis=1) - req2.astype(I32)
    else:
        # pack (key, lane) into ONE int32 composite: composites are
        # unique (the lane index breaks key ties exactly like a stable
        # argsort), so a single sort gives the admission order and a
        # searchsorted over the sorted composites gives each lane's
        # rank — no second argsort.
        # The key budget shrinks when B is huge so the composite
        # stays inside int32; policy keys are ≤ 16 bits by
        # construction (weighted caps at 65535, random at 0x7FFF,
        # depth at the constraint capacity), so KSENT only bites on
        # absurd B — where a key collision merely falls back to
        # lane-order tie-breaking, still a valid admission order.
        KSENT = min(1 << 16, (2 ** 31 - 1 - (B - 1)) // B)
        kcap = jnp.minimum(key, KSENT - 1)
        ukey = jnp.where(req2, kcap, KSENT) * B + loc
        skey = jnp.sort(ukey, axis=1)
        order = (skey % B).astype(I32)
        rank = jax.vmap(jnp.searchsorted)(skey, ukey).astype(I32)
    # beam: admit at most B//4 forks per block per superstep (shallowest
    # first via the key above) — the frontier analog of a beam width
    # (reference: beam.py ⚠unv); the rest defer/drop by mode
    n_adm = (jnp.minimum(n_free, max(1, B // 4))
             if fork_policy == "beam" else n_free)
    # destination-major mapping (scatter-free, compare-free): the free
    # slot with free-rank t receives the t-th admitted request, so a
    # cumsum over the free mask plus one gather of `order` builds the
    # map — no [G, B] scatter and no [G, B, B] one-hot compare
    if fork_policy == "fifo":
        # requesters in lane order; B pads the tail (never gathered:
        # free_rank < n_admit <= n_req keeps the index in-range)
        order = jnp.sort(jnp.where(req2, loc, B), axis=1).astype(I32)
    n_req = jnp.sum(req2.astype(I32), axis=1, keepdims=True)
    n_admit = jnp.minimum(n_adm, n_req)
    free_rank = jnp.cumsum(free2.astype(I32), axis=1) - free2.astype(I32)
    is_copy2 = free2 & (free_rank < n_admit)
    src_i = jnp.take_along_axis(
        order, jnp.clip(free_rank, 0, B - 1), axis=1)
    src2 = jnp.where(is_copy2, src_i, jnp.broadcast_to(loc, (G, B)))
    is_copy = is_copy2.reshape(P)
    # per-source admission bit (drop/defer accounting): admitted
    # requests are exactly those ranked inside the admission window
    slot = jnp.where(req2 & (rank < n_adm), 0, P).reshape(P)
    return src2, is_copy, slot


@jax.named_scope("expand_forks")
def expand_forks(sf: SymFrontier, loop_bound: int = 0,
                 fork_block: int = 0,
                 fork_policy: str = "fifo",
                 defer_starved: bool = False,
                 visited=None) -> SymFrontier:
    """Materialize fork requests: copy each forking lane into a free lane
    (prefix-sum compaction), point the copy at the jump target, and flip
    its final path-condition sign to "taken". Forks beyond capacity are
    counted in ``dropped_forks`` (the frontier equivalent of the
    reference's unbounded ``work_list.append`` ⚠unv). A copy whose taken
    target is a BACKWARD jump feeds the bounded-loops policy.

    ``defer_starved=True`` (SURVEY §5.7 spill machinery) turns the drop channel into a RETRY: a request with no free lane
    un-executes its branch decision — pc back on the JUMPI (or still
    parked on the CALL), operand pops and the appended constraint undone
    — and the lane re-raises the identical request next superstep, when
    retiring lanes may have freed slots. ``fork_req`` stays set on parked
    lanes so the host seam can see persistent starvation and rebalance
    them into other blocks' free lanes (``rebalance_parked``); nothing is
    lost inside a chunk.

    ``fork_block`` makes the compaction SHARD-LOCAL:
    with the lane axis sharded over devices, a global cumsum/sort would
    gather the whole frontier every superstep. Blocked, every reduction /
    sort / gather runs along the intra-block axis — lanes fork only into
    free lanes of their own block, so a block-aligned sharding never
    communicates here. ``0`` means one global block (single-chip default);
    results are identical for equal blocking regardless of the mesh.

    ``fork_policy`` is the search-strategy lever (reference: BFS/DFS
    ``BasicSearchStrategy`` orderings ⚠unv, SURVEY §1 row 7 — here the
    frontier steps together, so ordering only matters when fork slots run
    short): "fifo" admits by lane order, "shallow" prefers forks with the
    SHORTEST path condition (breadth-flavored), "deep" the longest
    (depth-flavored).

    The source→slot map itself is :func:`plan_fork_map`'s.
    """
    P = sf.n_lanes
    if fork_block > 0 and P % fork_block != 0:
        # silent fallback would reintroduce the cross-shard gather the
        # blocking exists to avoid — surface the misconfiguration
        raise ValueError(f"fork_block {fork_block} must divide P={P}")
    if fork_block <= 0:
        fork_block = P
    B = fork_block
    G = P // B
    # a lane the feasibility sweep killed between its request and this
    # expansion must NOT be copied back to life (its con_len was already
    # unwound, so the sign-flip would land on an unrelated constraint)
    req_live = sf.fork_req & sf.base.active
    req2 = req_live.reshape(G, B)
    free2 = (~sf.base.active).reshape(G, B)
    if fork_policy == "fifo":
        key = None
    else:
        depth = sf.con_len.reshape(G, B)
        C = sf.con_node.shape[1]
        if fork_policy in ("shallow", "beam"):
            key = depth
        elif fork_policy == "deep":
            key = C - depth
        elif fork_policy in ("weighted", "random"):
            # shared per-(lane, target, depth) hash — deterministic
            # (counter-free) so runs replay exactly. "weighted" scales it
            # by path depth (reference: the weighted-random strategy's
            # 2^-depth bias ⚠unv, SURVEY §1 row 7 — shallow paths
            # usually win but a lucky deep fork can jump the queue);
            # "random" uses it raw (reference: ``strategy/basic.py``
            # naive-random ordering ⚠unv, no depth bias).
            h = (jnp.arange(P, dtype=jnp.uint32) * jnp.uint32(2654435761)
                 + sf.fork_dest.astype(jnp.uint32) * jnp.uint32(40503)
                 + sf.con_len.astype(jnp.uint32) * jnp.uint32(131))
            h = (h >> 16) ^ h
            if fork_policy == "weighted":
                key = ((h.astype(I32) & 1023).reshape(G, B)
                       * (depth + 1)) % 65536
            else:
                key = (h & jnp.uint32(0x7FFF)).astype(I32).reshape(G, B)
        elif fork_policy == "coverage":
            # coverage-guided: forks whose taken target has NOT been
            # visited admit first (reference: coverage_strategy wrapper
            # ⚠unv); ties resolve by lane order (stable sort)
            if visited is None:
                key = jnp.zeros((G, B), dtype=I32)
            else:
                MC = visited.shape[1]
                seen = visited[
                    jnp.clip(sf.base.contract_id, 0, visited.shape[0] - 1),
                    jnp.clip(sf.fork_dest, 0, MC - 1)]
                key = seen.astype(I32).reshape(G, B)
        else:
            raise ValueError(f"unknown fork_policy: {fork_policy}")
    src2, is_copy, slot = plan_fork_map(req2, free2, key, fork_policy)
    req = req_live

    # the iprof residual sidecar is lane-independent: detach it so the
    # lane-axis gather below never touches it (and cannot mistake the
    # [256] row for a [P]-shaped leaf when P happens to equal 256)
    resid = sf.base.op_resid
    if resid is not None:
        sf = sf.replace(base=sf.base.replace(op_resid=None))

    # scalar run-total counters pass through untouched (ndim == 0); they
    # must not be gathered over the lane axis. The gather itself runs
    # along the intra-block axis only.
    def _gather(x):
        if x.ndim == 0:
            return x
        xb = x.reshape((G, B) + x.shape[1:])
        idx = src2.reshape((G, B) + (1,) * (x.ndim - 1))
        return jnp.take_along_axis(xb, idx, axis=1).reshape(x.shape)

    new = jax.tree.map(_gather, sf)
    b = new.base
    C = new.con_sign.shape[1]
    last = (jnp.arange(C)[None, :] == (new.con_len - 1)[:, None]) & is_copy[:, None]
    # fork copies must not inherit the source lane's loss counter — that
    # would double-count every prior drop once per fork
    starved = req & (slot == P)
    n_dropped = jnp.zeros(P, I32) if defer_starved else starved.astype(I32)
    dropped = jnp.where(is_copy, 0, new.dropped_forks) + n_dropped
    # the source lane sits at (JUMPI pc)+1 after the superstep, so a taken
    # target strictly below the copied pc is a backward jump
    back_copy = is_copy & (new.fork_dest < b.pc)
    # symbolic-callee forks: the copy re-executes the CALL with the target
    # stack slot concretized to the candidate address (its flipped EQ
    # constraint asserts to == addr, so the concrete write is faithful)
    cs = new.fork_cslot
    S = b.stack.shape[1]
    cidx = jnp.where(is_copy & (cs >= 0) & (cs < S), cs, S).astype(I32)
    stack_c = ci._write_slot(b.stack, cidx, new.fork_cval)
    stack_sym_c = ci._write_slot(new.stack_sym, cidx, 0)

    is_cf = cs >= 0  # call-enumeration fork (source parked on the CALL)
    if defer_starved:
        # un-execute the branch decision so the lane retries next superstep:
        # JUMPI sources step back onto the branch and re-push its operands;
        # CALL sources (already parked) rewind the candidate counter; both
        # pop the constraint the handler appended this superstep
        pc_new = jnp.where(is_copy, new.fork_dest,
                           jnp.where(starved & ~is_cf, b.pc - 1, b.pc))
        sp_new = jnp.where(starved & ~is_cf, b.sp + 2, b.sp)
        con_len_new = new.con_len - starved.astype(I32)
        # the retried JUMPI re-pays its static charge next superstep
        # (10 = G_HIGH; schedule-independent); CALL retries refund inside
        # the call handler itself
        g_undo = jnp.where(starved & ~is_cf, 10, 0).astype(b.gas_min.dtype)
        b = b.replace(gas_min=b.gas_min - g_undo, gas_max=b.gas_max - g_undo)
        if b.op_hist is not None:
            # iprof: the un-executed JUMPI re-runs next superstep — take
            # back epilogue's +1 so the retry loop nets to one count
            # (0x57 = JUMPI; non-call forks only come from JUMPI)
            b = b.replace(op_hist=b.op_hist.at[:, 0x57].add(
                -(starved & ~is_cf).astype(I32)))
        call_enum_new = jnp.where(
            is_copy, 0, new.call_enum - (starved & is_cf).astype(I32))
        fork_req_new = starved
    else:
        pc_new = jnp.where(is_copy, new.fork_dest, b.pc)
        sp_new = b.sp
        con_len_new = new.con_len
        call_enum_new = jnp.where(is_copy, 0, new.call_enum)
        fork_req_new = jnp.zeros_like(new.fork_req)
    if b.op_hist is not None:
        # iprof: a fork copy starts with an empty executed-op histogram —
        # its pre-fork instructions were already counted on the source
        # lane. But the RECYCLED slot may hold a retired lane's not-yet-
        # harvested counts (harvest only runs at tx boundaries): those
        # rows accumulate into the residual sidecar before the zeroing —
        # harvest sums every row plus the sidecar, so totals are
        # conserved while every live lane's row stays its own (ADVICE
        # r5). Legacy frontiers without the sidecar fold into a live
        # lane's row as before.
        dead_rows = jnp.sum(
            jnp.where(is_copy[:, None], sf.base.op_hist, 0), axis=0,
            dtype=I32)
        if resid is not None:
            resid = resid + dead_rows
            b = b.replace(op_hist=jnp.where(is_copy[:, None], 0, b.op_hist))
        else:
            tgt = jnp.argmax(b.active & ~is_copy).astype(I32)
            b = b.replace(
                op_hist=jnp.where(is_copy[:, None], 0, b.op_hist)
                .at[tgt].add(dead_rows))
    new = new.replace(
        base=b.replace(
            pc=pc_new,
            sp=sp_new,
            active=b.active | is_copy,
            stack=stack_c,
            op_resid=resid,
        ),
        stack_sym=stack_sym_c,
        con_sign=jnp.where(last, True, new.con_sign),
        con_len=con_len_new,
        fork_req=fork_req_new,
        fork_cslot=jnp.full_like(new.fork_cslot, -1),
        fork_cval=jnp.zeros_like(new.fork_cval),
        # a concretized copy is no longer enumerating; its next symbolic
        # call site (if any) must scan the table from slot 0
        call_enum=call_enum_new,
        dropped_forks=dropped,
        dropped_total=new.dropped_total + jnp.sum(n_dropped, dtype=I32),
    )
    return _note_backjump(new, back_copy, b.pc - 1, new.fork_dest, loop_bound)


def rebalance_parked(sf: SymFrontier, fork_block: int = 0,
                     active=None, fork_req=None):
    """Move persistently starved fork-requesting lanes into other blocks'
    free slots. Host-planned at the chunk seam, device-applied as one
    gather/scatter per leaf — the jitted superstep loop stays shard-local
    (SURVEY §5.7 spill-to-host overflow + §5.8 cross-device rebalancing:
    only the scheduler boundary communicates).

    A lane parked on a starved fork (``fork_req`` still set after
    ``expand_forks`` with ``defer_starved``) whose own block has no free
    slot is RELOCATED to the block with the most free slots (needs >= 2:
    one for the lane, one for the fork it will re-raise); its old slot
    frees up for its neighbors. Returns ``(sf, n_moved)``.

    ``active``/``fork_req`` accept host copies of those leaves a caller
    already transferred this chunk boundary (SymExecWrapper shares ONE
    fetch between this planner, the drain check, and the telemetry
    gauges) — each is a device→host sync, and paying it twice per chunk
    was measurable on the device path."""
    import numpy as np

    if active is None:
        active = np.asarray(sf.base.active)
    if fork_req is None:
        fork_req = np.asarray(sf.fork_req)
    parked = np.asarray(fork_req) & np.asarray(active)
    if not parked.any():
        return sf, 0
    P = parked.shape[0]
    B = fork_block if fork_block > 0 else P
    G = P // B
    free = ~np.asarray(active)
    free_cnt = free.reshape(G, B).sum(axis=1)
    free_lists = [list(np.where(free.reshape(G, B)[g])[0] + g * B)
                  for g in range(G)]
    src_idx, dst_idx = [], []
    for lane in np.where(parked)[0]:
        g = lane // B
        if free_cnt[g] > 0:
            continue  # the local retry will succeed on its own
        g2 = int(np.argmax(free_cnt))
        if free_cnt[g2] < 2:
            continue  # no global headroom for (lane + its fork)
        dst = free_lists[g2].pop()
        free_cnt[g2] -= 1
        src_idx.append(int(lane))
        dst_idx.append(int(dst))
        # the vacated slot serves the source block's remaining requests
        free_cnt[g] += 1
        free_lists[g].append(int(lane))
    if not src_idx:
        return sf, 0
    src = jnp.asarray(src_idx, dtype=I32)
    dst = jnp.asarray(dst_idx, dtype=I32)

    # lane-independent residual sidecar: keep it out of the lane move
    resid = sf.base.op_resid
    if resid is not None:
        sf = sf.replace(base=sf.base.replace(op_resid=None))

    def move(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        return x.at[dst].set(x[src])

    new = jax.tree.map(move, sf)
    b = new.base.replace(active=new.base.active.at[src].set(False))
    if b.op_hist is not None:
        # iprof: the lane's counts moved with it; the vacated slot must
        # not keep a stale copy (the harvest sums every row), and the
        # DESTINATION slots' pre-move rows (a retired lane's unharvested
        # counts) must not vanish — they land in the residual sidecar
        # (legacy frontiers without one: fold into the first moved row)
        dead_rows = jnp.sum(sf.base.op_hist[dst], axis=0, dtype=I32)
        if resid is not None:
            resid = resid + dead_rows
            b = b.replace(op_hist=b.op_hist.at[src].set(0))
        else:
            b = b.replace(
                op_hist=b.op_hist.at[src].set(0).at[dst[0]].add(dead_rows))
    return new.replace(
        base=b.replace(op_resid=resid),
        fork_req=new.fork_req.at[src].set(False),
    ), len(src_idx)


def _pool_stuck(xp, active, fork_req, running):
    """The predicate of a stuck pool over the array module ``xp``: NumPy
    on the host's copies at a seam, ``jnp`` inside the compiled loop."""
    parked = fork_req & active
    return (xp.any(parked) & xp.all(active)
            & ~xp.any(running & active & ~parked))


def pool_stuck(active, fork_req, running) -> bool:
    """Is the lane pool at a full frontier's fixpoint? From host copies of
    ``base.active``, ``fork_req`` and ``base.running``: some lane is
    parked on a fork it could not place, every lane is taken, and no
    running lane is anything but parked. No superstep moves such a
    frontier; only a feasibility sweep that kills a lane can."""
    import numpy as np

    return bool(_pool_stuck(np, np.asarray(active), np.asarray(fork_req),
                            np.asarray(running)))


def empty_observation(n_lanes: int) -> tuple:
    """What ``pool_fixpoint`` has seen before a call's first sweep:
    nothing, and not stuck."""
    mask = jnp.zeros(n_lanes, dtype=bool)
    return (jnp.zeros((), dtype=bool), jnp.zeros((), dtype=I32),
            jnp.zeros((), dtype=I32), mask, mask, mask)


def pool_fixpoint(seen: tuple, swept, active, fork_req, running,
                  killed_total, dropped_total) -> tuple:
    """Has the lane pool reached its fixpoint? Called once a superstep
    with the frontier as the trip leaves it and ``swept``, whether the
    trip held a feasibility sweep; ``seen`` is the observation taken at
    the sweep before. Returns ``(seen, fixpoint)``.

    An observation is ``pool_stuck``'s predicate, the run totals of
    kills and drops and the three masks, and only a trip with a sweep
    takes one. No superstep moves a stuck frontier (a parked lane
    un-executes its branch and raises the same request again); only a
    sweep that kills a lane frees a slot, and a sweep walks every node
    a lane has added since the last one. So when two observations in a
    row are stuck and equal in every part, a whole sweep ran from a
    stuck frontier, killed nothing and handed the frontier back: every
    later superstep would too, whatever budget is left. An empty
    ``seen`` (``empty_observation``: this is the call's first sweep)
    proves nothing."""
    stuck = _pool_stuck(jnp, active, fork_req, running)
    now = (stuck, killed_total, dropped_total, active, fork_req, running)
    fixpoint = swept & stuck & seen[0]
    for before, after in zip(seen[1:], now[1:]):
        fixpoint &= jnp.all(before == after)
    return (jax.tree.map(lambda a, b: jnp.where(swept, a, b), now, seen),
            fixpoint)


def starved_lanes(n_contracts: int, active, fork_req, running, home):
    """The lanes ``relieve_starved`` gives up, as a host mask; None where
    it gives up none."""
    import numpy as np

    if not pool_stuck(active, fork_req, running):
        return None     # a lane is free, or a lane still moves
    active = np.asarray(active)
    parked = np.asarray(fork_req) & active
    P = active.shape[0]
    share = P // n_contracts
    contract = np.asarray(home) % n_contracts   # creation | runtime image
    held = np.bincount(contract, minlength=n_contracts)
    waits = np.bincount(contract[parked], minlength=n_contracts) > 0
    if not (waits & (held < share // 4)).any():
        return None
    out = parked & (held > share)[contract]
    return out if out.any() else None


def relieve_starved(sf: SymFrontier, n_contracts: int,
                    active, fork_req, running, home):
    """Break the fixpoint of a full frontier for the contracts it starves.

    With ``defer_starved`` a fork that finds no free lane parks its lane,
    and nothing retires a lane inside a transaction. So once every lane
    is taken and every running lane is parked, no superstep changes the
    frontier again: the rest of the budget and the drain spin, and every
    parked lane ends as a dropped fork. Who holds the lanes at that
    point is decided by who forked first. A contract of five functions
    beside neighbours of sixty is stopped a handful of lanes short of
    all it needs, in a frontier of a thousand.

    Each contract is therefore guaranteed a FLOOR of a quarter of its
    share of the frontier (``P // n_contracts`` lanes). At such a
    fixpoint, if a contract that holds less than its floor waits for a
    lane, the contracts that hold more than their share give up their
    parked lanes (lost at a fixpoint whatever happens next; they are
    counted as dropped forks). The freed lanes go to whoever still
    asks, the contracts under their share; where those fill the frontier
    again the next seam repeats the step, so the contracts of least
    demand finish first.

    Host-planned at the chunk seam from the leaves the seam has fetched
    anyway (``home``: ``base.home_contract``; ``starved_lanes`` is the
    plan), device-applied as one mask over ``active`` and ``fork_req``;
    the compiled superstep loop is untouched. Returns
    ``(sf, n_evicted)``."""
    out = starved_lanes(n_contracts, active, fork_req, running, home)
    if out is None:
        return sf, 0
    # a mask, not an index list: one program whatever the count (a
    # scatter would compile anew for every new number of lanes)
    keep = jnp.asarray(~out)
    return sf.replace(
        base=sf.base.replace(active=sf.base.active & keep),
        fork_req=sf.fork_req & keep,
    ), int(out.sum())


#: what ``plan_seam_admission`` reads of the frontier a transaction ended
#: with, fetched only where a contract's carried states exceed its share
SEAM_STORAGE = ("base.st_used", "base.st_written", "base.st_keys",
                "base.st_vals", "st_key_sym", "st_val_sym", "st_seq",
                "base.contract_id", "base.pc", "sub_fail_cid",
                "sub_fail_pc")


def plan_seam_admission(n_contracts: int, carried, home, ended, failed,
                        started, storage, guard_slots,
                        concrete_storage: bool = False):
    """Which of the end states ``between_txs`` carried start the next
    call, per contract. Host-planned from the seam's reads; returns None
    where every carried state starts (the step is inert), else a dict:
    ``merged`` and ``dropped`` (masks of lanes to retire), ``queue``
    (the lanes that wait, most novel first within their contract),
    ``contract`` (of every lane) and ``fanout`` (lanes one state of each
    contract takes).

    Every carried state re-enters at ``pc=0`` and forks through the
    dispatcher again, and nothing retires a lane inside a transaction.
    What one state's call takes was just observed: ``fanout``, the lanes
    a contract held when the transaction ended (``ended``) over the
    states it ``started`` with. A contract whose carried states times
    that exceed its share of the pool (``P // n_contracts``) cannot run
    them all, and which of them gets anywhere is decided by who forks
    first. The step chooses instead, where there is something to choose
    by: **a carried state that overwrote the slot a failed guard of the
    same contract tested** can take the next call past that guard; the
    states that did not can only repeat what this transaction explored,
    from other balances. A failed guard is a path that ended reverted
    or in error (``failed``) on a branch whose block loads a fixed slot
    (``guard_slots(image, pc)``: read off the code at the path's last
    ``pc``) that its storage cache holds under a concrete key with a
    concrete value left by an earlier transaction (the constructor's
    ``owner`` and ``initialized``): only a write can change what that
    guard sees. The number of such paths over the concrete slots a
    state wrote in this transaction is its novelty. Where no earlier
    transaction left a value (the first call from unconstrained
    storage: a guard on a symbolic leaf forks both ways in one call,
    and no write unlocks anything) nothing is novel, and the step is
    inert wherever no contract over its share has a novel state. From
    ``concrete_storage`` a slot that no transaction wrote reads as zero,
    a concrete value like the constructor's: a guard on it
    (``require(members[msg.sender])`` before anyone joined) counts too.
    A path that failed because a CALLEE's guard did (a member of the
    lane's system, frames deep: its ``require`` reverts, and every
    caller's ``require(success)`` after it) tested that guard's slots:
    the innermost reverted frame's last ``pc`` and image
    (``sub_fail_pc`` / ``sub_fail_cid``) are read like the path's own.

    Where it acts, it holds EVERY contract over its share to it (the
    pool is one, and a neighbour's carried states are what starved the
    novel one): in order of novelty, then of lane, a state whose whole
    written storage is concrete and equal to an earlier one's is
    ``merged`` into it (the same storage: the next call cannot tell them
    apart); the first ``share // fanout`` (at least one) start; as many
    of the next as the share has lanes beside those states' fan-out go
    into ``queue`` and wait in their lanes (``hold_carried``) until a
    later seam starts them or gives them up (``plan_waiting``); the rest are
    ``dropped``, the budget's cut, counted with the dropped forks.

    ``storage`` is called only where some contract is over its share,
    and returns host copies of ``SEAM_STORAGE`` of the frontier the
    transaction ended with (``between_txs`` clears ``st_written``)."""
    import numpy as np

    carried = np.asarray(carried)
    P = carried.shape[0]
    share = P // n_contracts
    contract = np.asarray(home) % n_contracts   # creation | runtime image
    n_carried = np.bincount(contract[carried], minlength=n_contracts)
    held = np.bincount(contract[np.asarray(ended)], minlength=n_contracts)
    fanout = np.maximum(1, -(-held // np.maximum(1, started)))
    over = (n_carried > 1) & (n_carried * fanout > share)
    if not over.any():
        return None
    (used, written, keys, vals, key_sym, val_sym, seq, image, pc,
     *sub) = storage()
    sub_image, sub_pc = sub if sub else (image, np.full_like(pc, -1))
    named = used & (key_sym == 0)       # entries under a concrete key
    # 32 bytes an entry, compared whole
    kb = np.ascontiguousarray(keys).view("V32")[..., 0]
    vb = np.ascontiguousarray(vals).view("V32")[..., 0]
    readers: dict = {}
    guards: dict = {}       # (image, pc) -> the slots tested there
    left = named & (val_sym == 0) & (seq > 0) & ~written
    for lane in np.nonzero(np.asarray(failed) & over[contract])[0]:
        sites = [(int(image[lane]), int(pc[lane]))]
        if sub_pc[lane] >= 0:
            sites.append((int(sub_image[lane]), int(sub_pc[lane])))
        had = {k.tobytes() for k in kb[lane, left[lane]]}
        held = {k.tobytes() for k in kb[lane, named[lane] & (seq[lane] > 0)]}
        for site in sites:
            if site not in guards:
                guards[site] = [int(k).to_bytes(32, "little")
                                for k in guard_slots(*site)]
            for k in guards[site]:
                if k in had or (concrete_storage and k not in held):
                    readers[contract[lane], k] = readers.get(
                        (contract[lane], k), 0) + 1
    novelty = np.zeros(P, dtype=np.int64)
    lanes, slots = np.nonzero(
        named & written & (carried & over[contract])[:, None])
    for lane, k in zip(lanes, kb[lanes, slots]):
        novelty[lane] += readers.get((contract[lane], k.tobytes()), 0)
    if not novelty.any():
        return None
    concrete = ~(used & (seq > 0) & ((key_sym != 0) | (val_sym != 0))
                 ).any(axis=1)
    merged, dropped = np.zeros(P, dtype=bool), np.zeros(P, dtype=bool)
    queue = []
    for c in np.nonzero(over)[0]:
        mine = np.nonzero(carried & (contract == c))[0]
        seen, room, rank = set(), max(1, share // fanout[c]), 0
        hold = max(0, share - room * fanout[c])
        for lane in mine[np.argsort(-novelty[mine], kind="stable")]:
            if concrete[lane]:
                w = used[lane] & (seq[lane] > 0)
                sig = tuple(sorted(zip(kb[lane, w].tolist(),
                                       vb[lane, w].tolist())))
                if sig in seen:
                    merged[lane] = True
                    continue
                seen.add(sig)
            if room:
                room -= 1
            elif rank < hold:
                # the contracts take turns: every one's next state
                # before any one's second, the novel ones before both
                queue.append((novelty[lane] == 0, rank, int(lane)))
                rank += 1
            else:
                dropped[lane] = True
    return {"merged": merged, "dropped": dropped,
            "queue": [q[2] for q in sorted(queue)],
            "contract": contract, "fanout": fanout}


def hold_carried(sf: SymFrontier, retire, wait=None, start=None):
    """Apply a seam's plan for the carried states as one mask, as
    ``relieve_starved`` does: the lanes of ``retire`` leave the frontier,
    those of ``wait`` stay in their lanes, halted, so that no superstep
    moves them and no fork takes their place, and those of ``start``
    waited and now run."""
    b = sf.base
    halted = b.halted
    if wait is not None:
        halted = halted | jnp.asarray(wait)
    if start is not None:
        halted = halted & jnp.asarray(~start)
    return sf.replace(base=b.replace(
        active=b.active & jnp.asarray(~retire), halted=halted))


def plan_waiting(n_contracts: int, plan: dict, active, fork_req, running):
    """What a seam does for the lanes a plan of ``plan_seam_admission``
    left waiting, from host copies of the seam's leaves: ``(queue,
    start, drop)``, the lanes that go on waiting, those that start and
    those given up. At a full frontier's fixpoint all are given up
    (nothing retires a lane inside a transaction, so the room one of
    them needs will not come, and they have explored nothing yet; the
    lanes go to the forks that are parked); until then, where a
    contract has kept under its share by a whole ``fanout``, its next
    ones start. Waiting lanes that a feasibility sweep killed leave the
    queue."""
    import numpy as np

    active = np.asarray(active)
    queue = [lane for lane in plan["queue"] if active[lane]]
    if queue and pool_stuck(active, fork_req, running):
        return [], [], queue
    contract, fanout = plan["contract"], plan["fanout"]
    share, free = active.shape[0] // n_contracts, int((~active).sum())
    used = (np.bincount(contract[active], minlength=n_contracts)
            - np.bincount(contract[queue], minlength=n_contracts))
    start, wait = [], []
    for lane in queue:
        c = contract[lane]
        if min(free, share - used[c]) >= fanout[c]:
            used[c] += fanout[c]
            free -= fanout[c]
            start.append(lane)
        else:
            wait.append(lane)
    return wait, start, []


@jax.named_scope("migrate_parked_device")
def migrate_parked_device(sf: SymFrontier, fork_block: int,
                          mig_cap: int = 8) -> SymFrontier:
    """In-jit cross-block migration of starved fork-requesting lanes.

    The TPU-native tier of SURVEY §5.8's "cross-device rebalancing":
    where ``rebalance_parked`` plans on the host at the CHUNK seam (a
    device→host→device round trip — DCN on a pod), this runs INSIDE the
    jitted superstep loop. The only cross-block data flow is a compact
    ``[G, MIG]`` lane-payload buffer: every reduction/cumsum runs along
    the intra-block axis (shard-local under a block-aligned lane
    sharding), the assignment plan is [G]-shaped metadata, and GSPMD
    lowers the buffer exchange to a small all-gather that rides ICI.
    The reference has no analog (single process, unbounded worklist —
    ``mythril/laser/ethereum/svm.py`` ⚠unv); the pattern is the
    scaling-playbook "communicate at the scheduler boundary, and only
    compact state".

    Semantics (mirrors the host planner): a lane parked on a starved
    fork (``defer_starved`` retry machinery) whose block has ZERO free
    slots is moved to a block with >= 2 free slots (one for the lane,
    one headroom for the fork it re-raises next superstep); freer blocks
    fill first; at most ``mig_cap`` lanes leave or enter any block per
    call (bounded buffer — the rest stay parked and retry). The moved
    lane keeps ``fork_req`` set; its old slot deactivates. iprof rows
    travel with the lane; a replaced slot's unharvested row folds into
    the migrant's row so harvest totals are conserved.
    """
    P = sf.n_lanes
    B = fork_block if fork_block > 0 else P
    G = P // B
    if G <= 1:
        return sf  # single block: nothing to migrate into
    MIG = max(1, min(mig_cap, B // 2))
    NF = G * MIG  # flat buffer size

    # lane-independent residual sidecar: keep it out of the lane-axis
    # reshape/gather below (reattached, with any newly orphaned rows,
    # at the end — structure in == structure out, as lax.cond requires)
    resid = sf.base.op_resid
    if resid is not None:
        sf = sf.replace(base=sf.base.replace(op_resid=None))

    ab = sf.base.active.reshape(G, B)
    stb = (sf.fork_req & sf.base.active).reshape(G, B)
    freeb = ~ab
    fc = jnp.sum(freeb, axis=1, dtype=I32)            # free slots per block
    expb = stb & (fc == 0)[:, None]                    # exportable lanes
    r_exp = jnp.cumsum(expb.astype(I32), axis=1) - 1   # intra-block rank
    sel = expb & (r_exp < MIG)
    n_exp = jnp.minimum(jnp.sum(expb, axis=1, dtype=I32), MIG)

    # export buffer slot j <- intra-block lane with rank j (B = empty pad)
    hit = sel[:, :, None] & (r_exp[:, :, None] == jnp.arange(MIG)[None, None, :])
    exp_idx = jnp.where(jnp.any(hit, axis=1),
                        jnp.argmax(hit, axis=1), B).astype(I32)  # [G, MIG]

    # import capacity: fc-1 keeps one slot of fork headroom; freer blocks
    # get lower global import ranks so they fill first
    cap = jnp.clip(fc - 1, 0, MIG)
    order = jnp.argsort(-fc, stable=True)
    cap_sorted = cap[order]
    ioff_sorted = jnp.cumsum(cap_sorted) - cap_sorted  # exclusive prefix
    ioff = jnp.zeros(G, I32).at[order].set(ioff_sorted.astype(I32))
    total_cap = jnp.sum(cap, dtype=I32)

    eoff = (jnp.cumsum(n_exp) - n_exp).astype(I32)     # global export ranks
    total_exp = jnp.sum(n_exp, dtype=I32)
    M = jnp.minimum(total_exp, total_cap)              # matched moves

    # flat buffer id per global export rank (NF = unmatched sentinel)
    grank = eoff[:, None] + jnp.arange(MIG, dtype=I32)[None, :]
    valid_e = jnp.arange(MIG)[None, :] < n_exp[:, None]
    flat_ids = jnp.arange(NF, dtype=I32).reshape(G, MIG)
    src_of_rank = jnp.full(NF, NF, I32).at[
        jnp.where(valid_e, grank, NF)].set(flat_ids, mode="drop")

    # t-th free slot of block g receives global import rank ioff[g] + t
    r_free = jnp.cumsum(freeb.astype(I32), axis=1) - 1
    imp_take = jnp.clip(M - ioff, 0, cap)              # imports per block
    is_imp = freeb & (r_free < imp_take[:, None])      # [G, B]
    q = ioff[:, None] + r_free
    srcflat = src_of_rank[jnp.clip(q, 0, NF - 1)]      # [G, B]
    srcflat = jnp.where(is_imp, srcflat, 0)            # harden pads

    exported = sel & ((eoff[:, None] + r_exp) < M)     # claimed -> vacate
    imp_flat = is_imp.reshape(P)

    def mv(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        rest = x.shape[1:]
        xb = x.reshape((G, B) + rest)
        idx = jnp.clip(exp_idx, 0, B - 1).reshape(
            (G, MIG) + (1,) * len(rest))
        buf = jnp.take_along_axis(
            xb, jnp.broadcast_to(idx, (G, MIG) + rest), axis=1)
        flat = buf.reshape((NF,) + rest)
        vals = flat[srcflat]                           # [G, B, ...] from NF
        sel_imp = is_imp.reshape((G, B) + (1,) * len(rest))
        return jnp.where(sel_imp, vals, xb).reshape(x.shape)

    new = jax.tree.map(mv, sf)
    vac = exported.reshape(P)
    b = new.base.replace(active=new.base.active & ~vac)
    if b.op_hist is not None:
        # migrant rows travelled via mv(); vacated rows zero (they
        # moved); replaced slots' pre-import rows (retired-lane counts
        # harvest has not seen) accumulate into the residual sidecar —
        # totals are conserved because harvest sums every row plus the
        # sidecar, and no live lane's row absorbs another lane's counts
        # (ADVICE r5). Legacy frontiers without a sidecar keep the old
        # fold into the first imported slot's row.
        dead_rows = jnp.sum(
            jnp.where(imp_flat[:, None], sf.base.op_hist, 0),
            axis=0).astype(I32)
        if resid is not None:
            resid = resid + dead_rows
            b = b.replace(op_hist=jnp.where(vac[:, None], 0, b.op_hist))
        else:
            tgt = jnp.argmax(imp_flat).astype(I32)
            b = b.replace(op_hist=jnp.where(vac[:, None], 0, b.op_hist)
                          .at[tgt].add(jnp.where(jnp.any(imp_flat),
                                                 dead_rows, 0)))
    return new.replace(base=b.replace(op_resid=resid),
                       fork_req=new.fork_req & ~vac)


def _sym_run_impl(sf: SymFrontier, env: Env, corpus: Corpus,
                  spec: SymSpec = SymSpec(),
                  limits: LimitsConfig = DEFAULT_LIMITS,
                  max_steps: int = 256,
                  propagate_every=None,
                  fork_block: int = 0,
                  track_coverage: bool = False,
                  fork_policy: str = "fifo",
                  defer_starved: bool = False,
                  migrate_every: int = 0):
    """Run the symbolic engine until quiescence or max_steps supersteps:
    one while-loop, one superstep a trip, the quiescence check before
    every trip.
    ``propagate_every`` > 0 interleaves feasibility sweeps that kill
    provably-unsat lanes (reference: lazy ``Solver.check()`` pruning);
    0 disables them; None uses ``limits.propagate_every``.
    ``fork_block`` confines fork compaction to lane blocks (pass the
    per-device lane count when sharding the lane axis).
    ``track_coverage=True`` additionally returns a ``bool[C, MAX_CODE]``
    visited-pc bitmap (reference: InstructionCoveragePlugin ⚠unv) —
    return type becomes ``(sf, visited)``.
    ``migrate_every`` > 0 (with ``defer_starved`` and a multi-block
    ``fork_block``) runs the in-jit cross-block lane migration
    (``migrate_parked_device``) every that many supersteps — the ICI
    tier of SURVEY §5.8's rebalancing; the host-seam
    ``rebalance_parked`` remains the chunk-boundary tier.
    Both cadences are anchored to the call's own step index."""
    from .propagate import kill_infeasible

    if propagate_every is None:
        propagate_every = limits.propagate_every

    P_run = sf.n_lanes
    C, MC = corpus.code.shape
    visited0 = jnp.zeros((C, MC), dtype=bool)
    # the pool's fixpoint ends the loop (``pool_fixpoint``): only where
    # lanes can park, and only a sweep can prove it. Elsewhere the carry
    # holds no observation and the program is the one without the rule
    watch_pool = bool(defer_starved and propagate_every)

    def cond(state):
        i, s, _, pool = state
        go = (i < max_steps) & jnp.any(s.base.running)
        return go & ~pool[1] if watch_pool else go

    def body(state):
        i, s, visited, pool = state
        if track_coverage:
            # init-frame pcs index the per-lane init buffer, not the
            # contract image — they must not pollute its bitmap
            run = s.base.running & ~s.base.exec_init
            cid = jnp.where(run, s.base.contract_id, C)
            pc = jnp.clip(s.base.pc, 0, MC - 1)
            visited = visited.at[cid, pc].set(True, mode="drop")
        s = sym_superstep(s, env, corpus, spec, limits)
        # expand_forks tree-gathers EVERY leaf of the frontier; gate it so
        # supersteps with no pending fork request (the common case) skip
        # that full-frontier pass. Identity-valued when no live request.
        s = lax.cond(
            jnp.any(s.fork_req & s.base.active),
            lambda x: expand_forks(x, limits.loop_bound, fork_block,
                                   fork_policy, defer_starved,
                                   visited if track_coverage else None),
            lambda x: x,
            s,
        )
        if propagate_every:
            swept = (i % propagate_every) == propagate_every - 1
            s = ci.narrow_cond(
                swept,
                kill_infeasible, s,
                ("iv_lo", "iv_hi", "kb_m", "kb_v", "prop_len",
                 "base.active", "fork_req", "killed_infeasible",
                 "killed_total"),
            )
        if migrate_every > 0 and defer_starved and 0 < fork_block < P_run:
            # fire only when some block is BOTH exhausted and starving —
            # the [G] predicate is metadata-cheap; the payload pass is
            # inside the cond
            Bm = fork_block
            abm = s.base.active.reshape(P_run // Bm, Bm)
            stm = (s.fork_req & s.base.active).reshape(P_run // Bm, Bm)
            occ = jnp.sum(abm, axis=1)
            # a starving exhausted block AND a destination with >= 2 free
            # slots — without the capacity side a saturated frontier would
            # pay the full-leaf no-op migration pass every firing
            need = (jnp.any(jnp.any(stm, axis=1) & (occ == Bm))
                    & jnp.any(occ <= Bm - 2))
            s = lax.cond(
                ((i % migrate_every) == migrate_every - 1) & need,
                lambda x: migrate_parked_device(x, fork_block),
                lambda x: x,
                s,
            )
        if watch_pool:
            # judged as the trip leaves the frontier, after everything it
            # does. A migration needs a free lane, so it never fires
            # between two stuck observations; one that fired before them
            # moved a lane, which the masks show: the compare covers it
            pool = pool_fixpoint(pool[0], swept, s.base.active, s.fork_req,
                                 s.base.running, s.killed_total,
                                 s.dropped_total)
        return i + 1, s, visited, pool

    # the observation starts empty in every call: what the host's seam
    # did to the frontier since the last one cannot be taken for
    # standing still
    pool0 = ((empty_observation(P_run), jnp.zeros((), dtype=bool))
             if watch_pool else ())
    steps, sf, visited, pool = lax.while_loop(
        cond, body, (jnp.int32(0), sf, visited0, pool0))
    # the loop counter as the loop left it: the only record of how many
    # supersteps a call that ended on quiescence really ran
    sf = sf.replace(steps_total=sf.steps_total + steps)
    if defer_starved:
        # whether THIS call left on the rule (a call without sweeps
        # never does)
        sf = sf.replace(fixpoint=pool[1] if watch_pool
                        else jnp.zeros((), dtype=bool))
    return (sf, visited) if track_coverage else sf


_SYM_RUN_STATIC = ("spec", "limits", "max_steps", "propagate_every",
                   "fork_block", "track_coverage", "fork_policy",
                   "defer_starved", "migrate_every")

sym_run = jax.jit(_sym_run_impl, static_argnames=_SYM_RUN_STATIC)
