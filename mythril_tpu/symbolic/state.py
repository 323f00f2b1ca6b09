"""SymFrontier: the concrete frontier plus the symbolic overlay.

Design: concrete limb arrays stay authoritative for concrete values; a
parallel "sym id" overlay marks which slots hold symbolic expressions
(id != 0 → value is tape node, limbs are garbage). This replaces the
reference's per-object Z3 expressions on stack/memory/storage
(``mythril/laser/ethereum/state/*.py`` ⚠unv) with two flat arrays per
storage class.

Granularity choices (documented over-approximations; each introduces
fresh unconstrained variables rather than wrong values):
- memory symbolics are tracked per 32-byte word (``mem_sym``);
- a word stored whole by one MSTORE at an unaligned offset is kept
  beside them (``mem_usym``, one byte shift a lane: solc's ABI encoder
  puts every argument at ``ptr + 4 + 32k``) and read back whole at the
  same offset as the node it was; the aligned words it covers hold
  HAVOC leaves, as does every other unaligned symbolic store or load;
- a copy of symbolic calldata, or a store or copy at a symbolic offset,
  lowers ``mem_floor``, the lowest memory word that may be unknown: an
  MLOAD at or above it returns a fresh HAVOC leaf, one below it is exact.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import jax.numpy as jnp
from flax import struct

from ..config import LimitsConfig, DEFAULT_LIMITS
from ..core.frontier import Frontier, make_frontier
from .ops import SymOp, WELL_KNOWN, N_WELL_KNOWN
from .spec import SymSpec  # noqa: F401  (re-exported: its historical home)

I32 = jnp.int32
U32 = jnp.uint32
#: ``SymFrontier.mem_floor`` of a lane whose memory is exact throughout
MEM_EXACT = 2**31 - 1


#: ``mem_usym`` / ``cd_usym`` entry of a word stored whole with a
#: concrete value (its bytes in the concrete memory are exact)
USYM_CONCRETE = -1

#: columns of ``SymFrontier.hop_stats``: CALL-family instructions by
#: fate, calls whose target was a member of the lane's world with code
#: (``MEMBER``) and those of them that fell to the external path for a
#: limit (``TRAPPED``), symbolic words read across a frame boundary by
#: side and fate, and the deepest frame the path ran in
(HOP_INTERNAL, HOP_EOA, HOP_PRECOMPILE, HOP_EXTERNAL, HOP_MEMBER,
 HOP_TRAPPED, HOP_CD_EXACT, HOP_CD_HAVOC, HOP_RET_EXACT, HOP_RET_HAVOC,
 HOP_DEPTH) = range(11)
N_HOP = 11


def tape_row_hash(op, a, b, imm):
    """u32 fingerprint of one tape row (op, a, b, imm[..., 8]).

    The hash-cons scan in ``append_node`` compares this ONE word per
    entry instead of the full 12-word row (3 x i32 + 8 x u32 imm) — the
    full row is verified only for the single candidate the hash matched,
    so a collision degrades to a missed dedup (sound: a duplicate node,
    never a wrong id). Any writer of tape rows must store the matching
    hash (``append_node`` and the seed rows in ``make_sym_frontier``).
    """
    op = jnp.asarray(op).astype(U32)
    a = jnp.asarray(a).astype(U32)
    b = jnp.asarray(b).astype(U32)
    h = (op * U32(0x9E3779B1)) ^ (a * U32(0x85EBCA6B)) ^ (b * U32(0xC2B2AE35))
    # positional odd multipliers: permuted limbs must hash differently
    mult = jnp.asarray([0x27D4EB2F, 0x165667B1, 0xD6E8FEB9, 0xA3D8A6E3,
                        0x83B58237, 0xCC9E2D51, 0x1B873593, 0xE6546B65],
                       dtype=U32)
    h = h ^ jnp.sum(imm.astype(U32) * mult, axis=-1, dtype=U32)
    h = h ^ (h >> 16)
    h = h * U32(0x7FEB352D)
    return h ^ (h >> 15)


@struct.dataclass
class SymFrontier:
    base: Frontier
    # --- overlay: sym ids (0 = concrete) ---
    stack_sym: jnp.ndarray   # i32[P, S]
    mem_sym: jnp.ndarray     # i32[P, M/32]
    mem_floor: jnp.ndarray   # i32[P] lowest memory word that may be unknown
    # (MEM_EXACT: none). A write or a copy at a symbolic offset lowers it
    # to the word of its concrete base (0 without one); reads, hashes and
    # call windows below it stay exact, those reaching it are havoc
    mem_usym: jnp.ndarray    # i32[P, M/32] the word stored whole at byte
    # ``32 * w + mem_ushift``: its node id, ``USYM_CONCRETE`` for a
    # concrete value, 0 for no such word (or one written into since)
    mem_ushift: jnp.ndarray  # i32[P] the lane's byte shift (0: none yet)
    mem_uhead: jnp.ndarray   # i32[P] the aligned word whose first
    # ``mem_ushift`` bytes were concrete when an unaligned store took the
    # rest (the selector before the first argument); -1: none
    retdata_sym: jnp.ndarray  # bool[P] returndata of last call is symbolic
    st_val_sym: jnp.ndarray  # i32[P, K]
    st_key_sym: jnp.ndarray  # i32[P, K] sym id of the key stored in the slot
    st_seq: jnp.ndarray      # i32[P, K] write sequence number of the entry
    # (0 = never written). The numeric alias probe can put MULTIPLE
    # entries in one alias group (a slot written before its key's bits
    # were proven + a concrete slot of the same value); slot INDEX order
    # does not track write order once a lower slot is re-written in
    # place, so reads/writes select the group's max-seq entry instead.
    st_seq_ctr: jnp.ndarray  # i32[P] per-lane monotonic SSTORE counter
    rv_sym: jnp.ndarray      # i32[P, RD/32] sym ids of the RETURN/REVERT payload
    rv_havoc: jnp.ndarray    # bool[P] RETURN/REVERT payload unknown (claimed
    # symbolic-offset halt) — the caller's returndata havocs on pop
    # --- sub-call frame overlay ---
    cd_from_mem: jnp.ndarray  # bool[P] calldata is caller memory (depth > 0),
    # not free symbolic leaves
    cd_havoc: jnp.ndarray    # bool[P] this frame's calldata bytes unknown
    cd_sym: jnp.ndarray      # i32[P, CD/32] per-word sym ids of frame calldata
    cd_usym: jnp.ndarray     # i32[P, CD/32] ``mem_usym`` of the call's window:
    # the calldata word at byte ``32 * k + cd_ushift``
    cd_ushift: jnp.ndarray   # i32[P]
    cd_uhead: jnp.ndarray    # bool[P] the first ``cd_ushift`` bytes of the
    # frame's calldata are concrete (``mem_uhead`` was the window's word 0)
    head_node: jnp.ndarray   # i32[P] node a sub-frame's CALLDATALOAD(0) gave
    # for a word whose first ``head_len`` bytes are exact in its concrete
    # shadow: a SHR that keeps only those bytes is concrete (the selector)
    head_len: jnp.ndarray    # i32[P]
    callvalue_sym: jnp.ndarray  # i32[P] sym id of this frame's callvalue
    caller_sym: jnp.ndarray  # i32[P] sym id of this frame's msg.sender (0 =
    # concrete; a DELEGATECALL frame inherits the caller frame's CALLER leaf)
    bal_epoch: jnp.ndarray   # i32[P] balance-leaf version: bumped whenever the
    # concrete balance table changes (transfer / rollback / tx boundary) so
    # BALANCE reads across the change get fresh leaves instead of being
    # forced equal (advisor r2 low)
    fr_mem_sym: jnp.ndarray  # i32[P, D, M/32] saved caller memory overlay
    fr_mem_floor: jnp.ndarray  # i32[P, D]
    fr_cd_from_mem: jnp.ndarray  # bool[P, D]
    fr_cd_havoc: jnp.ndarray  # bool[P, D]
    fr_cd_sym: jnp.ndarray   # i32[P, D, CD/32]
    fr_cd_usym: jnp.ndarray  # i32[P, D, CD/32]
    fr_cd_ushift: jnp.ndarray  # i32[P, D]
    fr_cd_uhead: jnp.ndarray  # bool[P, D]
    fr_callvalue_sym: jnp.ndarray  # i32[P, D]
    fr_caller_sym: jnp.ndarray  # i32[P, D]
    fr_st_val_sym: jnp.ndarray  # i32[P, D, K] storage-overlay snapshots
    fr_st_key_sym: jnp.ndarray  # i32[P, D, K]  (revert rollback)
    fr_st_seq: jnp.ndarray      # i32[P, D, K]
    sub_revert_pc: jnp.ndarray  # i32[P] pc of the CALL whose callee
    # reverted/failed (-1 = none; SWC-123 RequirementsViolation feed)
    sub_revert_cid: jnp.ndarray  # i32[P] contract owning that CALL site
    sub_fail_pc: jnp.ndarray  # i32[P] where the first callee frame of this
    # transaction that reverted ended, in ITS code (-1 = none): the guard
    # a path failed at three contracts deep (the seam's admission step)
    sub_fail_cid: jnp.ndarray  # i32[P] the image that frame ran
    # --- SSA tape ---
    tape_op: jnp.ndarray     # i32[P, T]
    tape_a: jnp.ndarray      # i32[P, T]
    tape_b: jnp.ndarray      # i32[P, T]
    tape_imm: jnp.ndarray    # u32[P, T, 8]
    tape_hash: jnp.ndarray   # u32[P, T] row fingerprint (tape_row_hash) —
    # the hash-cons scan's fast path; must stay in sync with every write
    tape_len: jnp.ndarray    # i32[P]
    havoc_cnt: jnp.ndarray   # i32[P] fresh-variable counter (HAVOC uniqueness)
    cd_reads: jnp.ndarray    # i32[P, 2] this transaction's CALLDATALOADs at a
    # symbolic offset on the lane's path: [answered by a CD_SELECT node,
    # answered by a HAVOC leaf] (the harvest's ``engine_calldata_symreads_total``)
    hop_stats: jnp.ndarray   # i32[P, N_HOP] this transaction's calls and frame
    # crossings on the lane's path, by the ``HOP_*`` columns below (the
    # harvest's ``engine_calls_total`` / ``engine_hop_words_total``)
    create_cnt: jnp.ndarray  # i32[P] CREATE/CREATE2 counter (fresh addresses)
    # --- persistent abstract domains (incremental propagation) ---
    # the tape is SSA append-only, so a node's interval/known-bits never
    # change once computed: sweeps only propagate nodes in
    # [prop_len, tape_len) instead of re-walking the whole tape (the
    # full re-walk was ~96% of symbolic runtime at P=4096).
    # Measured tradeoff of keeping them resident (P=4096, T=512, v5e):
    # +1 GiB frontier memory and ~1.5 ms/superstep of extra expand_forks
    # gather traffic, against ~6.9 s PER SWEEP saved (57 s -> 3.6 s for a
    # 64-step run). Dropping them from the fork gather would force fresh
    # copies to re-propagate their whole tape, reverting the win.
    iv_lo: jnp.ndarray       # u32[P, T, 8] per-node interval lower bound
    iv_hi: jnp.ndarray       # u32[P, T, 8]
    kb_m: jnp.ndarray        # u32[P, T, 8] known-bits mask
    kb_v: jnp.ndarray        # u32[P, T, 8] known-bits value
    prop_len: jnp.ndarray    # i32[P] nodes already propagated
    # --- path condition ---
    tx_id: jnp.ndarray       # i32[P] current transaction index (0-based)
    con_node: jnp.ndarray    # i32[P, C]
    con_sign: jnp.ndarray    # bool[P, C]
    con_pc: jnp.ndarray      # i32[P, C] pc of the branch that asserted it
    con_len: jnp.ndarray     # i32[P]
    killed_infeasible: jnp.ndarray  # bool[P] pruned by constraint propagation
    killed_total: jnp.ndarray  # i32[] run total of propagation kills (survives
    # lane recycling — per-lane flags are lost when expand_forks reuses a slot)
    # --- bounded-loops policy (reference: BoundedLoopsStrategy ⚠unv) ---
    lb_key: jnp.ndarray      # i64[P, LBS] back-jump keys ((cid, src, dest) packed)
    lb_cnt: jnp.ndarray      # i32[P, LBS] taken-count per target
    lb_len: jnp.ndarray      # i32[P]
    # --- dependency pruner (reference: DependencyPruner ⚠unv) ---
    dep_read: jnp.ndarray    # bool[P] this tx read a key a PRIOR tx wrote
    # --- fork plumbing (filled by the JUMPI handler, drained by expand_forks) ---
    fork_req: jnp.ndarray    # bool[P]
    fork_dest: jnp.ndarray   # i32[P] jump target of the taken branch
    dropped_forks: jnp.ndarray  # i32[P] forks lost to capacity (reported)
    dropped_total: jnp.ndarray  # i32[] run total of dropped forks
    steps_total: jnp.ndarray  # i32[] run total of supersteps sym_run's loop ran
    # (each call adds its final loop counter; quiescence ends a call early)
    copy_steps: jnp.ndarray  # i32[] run total of the supersteps in which
    # ``dispatch`` took the copy class's cond (some running lane at an
    # unclaimed CALLDATACOPY / CODECOPY / EXTCODECOPY / RETURNDATACOPY)
    # symbolic-callee enumeration (CALL with symbolic target forks one
    # candidate account per superstep; the fork copy re-executes the CALL
    # with the target stack slot concretized — see _h_sym_call)
    call_enum: jnp.ndarray   # i32[P] next candidate account slot to try
    fork_cslot: jnp.ndarray  # i32[P] stack slot the fork copy concretizes (-1 = none)
    fork_cval: jnp.ndarray   # u32[P, 8] concrete value for that slot
    # --- detection-facing event records ---
    # every pc-bearing event also records the EXECUTING contract id at
    # record time (``*_cid``): a pc recorded inside a callee frame must not
    # be attributed to the lane's home contract (advisor r2 medium)
    sym_jump_dest: jnp.ndarray  # i32[P] node id of a symbolic JUMP dest (SWC-127)
    sym_jump_pc: jnp.ndarray    # i32[P] pc of that jump (-1 = none)
    sym_jump_cid: jnp.ndarray   # i32[P] contract executing that jump
    n_calls: jnp.ndarray     # i32[P]
    n_mut_calls: jnp.ndarray  # i32[P] CALL/CALLCODE/DELEGATECALL only (re-enterable)
    call_to: jnp.ndarray     # u32[P, CL, 8] concrete callee (if concrete)
    call_to_sym: jnp.ndarray  # i32[P, CL]
    call_value: jnp.ndarray  # u32[P, CL, 8]
    call_value_sym: jnp.ndarray  # i32[P, CL]
    call_op: jnp.ndarray     # i32[P, CL] raw opcode (CALL/DELEGATECALL/...)
    call_pc: jnp.ndarray     # i32[P, CL]
    call_cid: jnp.ndarray    # i32[P, CL] contract executing the call site
    # LOG record overlay: sym id of topic0 / first data word per record
    # (0 = concrete, -1 = unknown at symbolic offset / havoc'd memory)
    log_topic0_sym: jnp.ndarray  # i32[P, LS]
    log_data0_sym: jnp.ndarray   # i32[P, LS]
    sd_to_sym: jnp.ndarray   # i32[P] SELFDESTRUCT beneficiary sym id
    sd_to: jnp.ndarray       # u32[P, 8] concrete beneficiary
    sd_pc: jnp.ndarray       # i32[P] pc of the first SELFDESTRUCT (-1 = none)
    sd_cid: jnp.ndarray      # i32[P] contract whose code executed it
    # one-shot event records for the remaining SWC modules
    origin_read: jnp.ndarray  # bool[P] lane executed ORIGIN (SWC-111/115)
    inv_pc: jnp.ndarray      # i32[P] pc of an executed INVALID (-1 = none; SWC-110)
    inv_cid: jnp.ndarray     # i32[P]
    sstore_after_call_pc: jnp.ndarray  # i32[P] first SSTORE after an ext call (SWC-107)
    sstore_ac_cid: jnp.ndarray  # i32[P]
    arb_key_node: jnp.ndarray  # i32[P] key node of first symbolic-key SSTORE (SWC-124)
    arb_key_pc: jnp.ndarray    # i32[P]
    arb_key_cid: jnp.ndarray   # i32[P]
    # symbolic-arithmetic events (IntegerArithmetics SWC-101 feed)
    n_arith: jnp.ndarray     # i32[P]
    arith_op: jnp.ndarray    # i32[P, AL] EVM opcode (ADD/SUB/MUL/EXP)
    arith_a: jnp.ndarray     # i32[P, AL] operand node ids (post sym_or_const)
    arith_b: jnp.ndarray     # i32[P, AL]
    arith_r: jnp.ndarray     # i32[P, AL] result node id
    arith_pc: jnp.ndarray    # i32[P, AL]
    arith_cid: jnp.ndarray   # i32[P, AL]
    # bool[]: the last ``sym_run`` call left its loop at the lane pool's
    # fixpoint (``pool_fixpoint``), with budget to spare. Written by every
    # ``defer_starved`` call and read at the host's seam beside
    # ``steps_total``. A frontier that no such call has seen carries no
    # leaf, so a bare run's program has no such argument; a caller that
    # chains ``defer_starved`` calls starts from ``False`` (one program)
    fixpoint: Optional[jnp.ndarray] = None

    @property
    def n_lanes(self) -> int:
        return self.base.pc.shape[0]

    @property
    def tape_cap(self) -> int:
        return self.tape_op.shape[1]


def make_sym_frontier(
    n_lanes: int,
    limits: LimitsConfig = DEFAULT_LIMITS,
    contract_id=None,
    gas_limit: int = 10_000_000,
    active=None,
    calldata=None,
    calldata_len=None,
    **world_kw,
) -> SymFrontier:
    """Fresh frontier with the well-known leaves pre-seeded on every tape.
    Concrete ``calldata`` may be supplied for concolic/concrete replay; the
    default leaves the buffer zeroed (symbolic reads resolve to leaves).
    ``world_kw`` forwards world-state setup (n_contracts, contract_addrs,
    caller, balances) to :func:`make_frontier`."""
    P = n_lanes
    L = limits
    if calldata_len is None:
        calldata_len = np.full(P, L.calldata_bytes, dtype=np.int32)
    base = make_frontier(
        P, L, contract_id=contract_id, gas_limit=gas_limit, active=active,
        calldata=calldata, calldata_len=calldata_len, **world_kw,
    )
    T, C, K, S = L.tape_len, L.max_constraints, L.storage_slots, L.max_stack
    CL = L.call_log

    rows = WELL_KNOWN(L.calldata_bytes)
    n_wk = N_WELL_KNOWN(L.calldata_bytes)
    assert n_wk <= T, "tape too small for well-known leaves"
    t_op = np.zeros((P, T), dtype=np.int32)
    t_a = np.zeros((P, T), dtype=np.int32)
    t_b = np.zeros((P, T), dtype=np.int32)
    for i, (op, kind, idx) in enumerate(rows, start=1):
        t_op[:, i] = op
        t_a[:, i] = kind
        t_b[:, i] = idx

    z = lambda *s: jnp.zeros(s, dtype=I32)
    D = L.call_depth
    CDW = L.calldata_bytes // 32
    return SymFrontier(
        base=base,
        stack_sym=z(P, S),
        mem_sym=z(P, L.mem_bytes // 32),
        mem_floor=jnp.full(P, MEM_EXACT, dtype=I32),
        mem_usym=z(P, L.mem_bytes // 32),
        mem_ushift=z(P),
        mem_uhead=jnp.full(P, -1, dtype=I32),
        retdata_sym=jnp.zeros(P, dtype=bool),
        st_val_sym=z(P, K),
        st_key_sym=z(P, K),
        st_seq=z(P, K),
        st_seq_ctr=z(P),
        rv_sym=z(P, L.returndata_bytes // 32),
        rv_havoc=jnp.zeros(P, dtype=bool),
        cd_from_mem=jnp.zeros(P, dtype=bool),
        cd_havoc=jnp.zeros(P, dtype=bool),
        cd_sym=z(P, CDW),
        cd_usym=z(P, CDW),
        cd_ushift=z(P),
        cd_uhead=jnp.zeros(P, dtype=bool),
        head_node=z(P),
        head_len=z(P),
        callvalue_sym=z(P),
        caller_sym=z(P),
        bal_epoch=z(P),
        fr_mem_sym=z(P, D, L.mem_bytes // 32),
        fr_mem_floor=jnp.full((P, D), MEM_EXACT, dtype=I32),
        fr_cd_from_mem=jnp.zeros((P, D), dtype=bool),
        fr_cd_havoc=jnp.zeros((P, D), dtype=bool),
        fr_cd_sym=z(P, D, CDW),
        fr_cd_usym=z(P, D, CDW),
        fr_cd_ushift=z(P, D),
        fr_cd_uhead=jnp.zeros((P, D), dtype=bool),
        fr_callvalue_sym=z(P, D),
        fr_caller_sym=z(P, D),
        fr_st_val_sym=z(P, D, K),
        fr_st_key_sym=z(P, D, K),
        fr_st_seq=z(P, D, K),
        sub_revert_pc=jnp.full(P, -1, dtype=I32),
        sub_revert_cid=z(P),
        sub_fail_pc=jnp.full(P, -1, dtype=I32),
        sub_fail_cid=z(P),
        tape_op=jnp.asarray(t_op),
        tape_a=jnp.asarray(t_a),
        tape_b=jnp.asarray(t_b),
        tape_imm=jnp.zeros((P, T, 8), dtype=U32),
        tape_hash=tape_row_hash(jnp.asarray(t_op), jnp.asarray(t_a),
                                jnp.asarray(t_b),
                                jnp.zeros((P, T, 8), dtype=U32)),
        tape_len=jnp.full(P, n_wk, dtype=I32),
        havoc_cnt=z(P),
        cd_reads=z(P, 2),
        hop_stats=z(P, N_HOP),
        create_cnt=z(P),
        iv_lo=jnp.zeros((P, T, 8), dtype=U32),
        iv_hi=jnp.zeros((P, T, 8), dtype=U32),
        kb_m=jnp.zeros((P, T, 8), dtype=U32).at[:, 0].set(0xFFFFFFFF),
        kb_v=jnp.zeros((P, T, 8), dtype=U32),
        prop_len=jnp.ones(P, dtype=I32),  # node 0 pre-seeded ([0,0], known)
        tx_id=z(P),
        con_node=z(P, C),
        con_sign=jnp.zeros((P, C), dtype=bool),
        con_pc=z(P, C),
        con_len=z(P),
        killed_infeasible=jnp.zeros(P, dtype=bool),
        killed_total=jnp.zeros((), dtype=I32),
        lb_key=jnp.full((P, L.loop_slots), -1, dtype=jnp.int64),
        lb_cnt=z(P, L.loop_slots),
        lb_len=z(P),
        dep_read=jnp.zeros(P, dtype=bool),
        fork_req=jnp.zeros(P, dtype=bool),
        fork_dest=z(P),
        call_enum=z(P),
        fork_cslot=jnp.full(P, -1, dtype=I32),
        fork_cval=jnp.zeros((P, 8), dtype=U32),
        dropped_forks=z(P),
        dropped_total=jnp.zeros((), dtype=I32),
        steps_total=jnp.zeros((), dtype=I32),
        copy_steps=jnp.zeros((), dtype=I32),
        sym_jump_dest=z(P),
        sym_jump_pc=jnp.full(P, -1, dtype=I32),
        sym_jump_cid=z(P),
        n_calls=z(P),
        n_mut_calls=z(P),
        call_to=jnp.zeros((P, CL, 8), dtype=U32),
        call_to_sym=z(P, CL),
        call_value=jnp.zeros((P, CL, 8), dtype=U32),
        call_value_sym=z(P, CL),
        call_op=z(P, CL),
        call_pc=z(P, CL),
        call_cid=z(P, CL),
        log_topic0_sym=z(P, L.log_slots),
        log_data0_sym=z(P, L.log_slots),
        sd_to_sym=z(P),
        sd_to=jnp.zeros((P, 8), dtype=U32),
        sd_pc=jnp.full(P, -1, dtype=I32),
        sd_cid=z(P),
        origin_read=jnp.zeros(P, dtype=bool),
        inv_pc=jnp.full(P, -1, dtype=I32),
        inv_cid=z(P),
        sstore_after_call_pc=jnp.full(P, -1, dtype=I32),
        sstore_ac_cid=z(P),
        arb_key_node=z(P),
        arb_key_pc=jnp.full(P, -1, dtype=I32),
        arb_key_cid=z(P),
        n_arith=z(P),
        arith_op=z(P, L.arith_log),
        arith_a=z(P, L.arith_log),
        arith_b=z(P, L.arith_log),
        arith_r=z(P, L.arith_log),
        arith_pc=z(P, L.arith_log),
        arith_cid=z(P, L.arith_log),
    )
