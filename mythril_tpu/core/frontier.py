"""The SoA frontier: all machine state for P lanes as fixed-shape arrays.

Replaces the reference's per-path object graph — ``GlobalState`` /
``MachineState`` / ``Account.storage`` / calldata objects
(``mythril/laser/ethereum/state/*.py`` ⚠unv, SURVEY.md §2 "State model") —
with one pytree of arrays whose leading dim is the lane index. A lane is
one (contract, path) pair; masks (``active``/``halted``/``error``) play the
role of the reference's work-list membership.

Storage is a bounded per-lane associative cache (key/value/used arrays)
rather than a Z3 ``Array``: SLOAD is a vectorized compare-select across
slots, SSTORE a masked scatter into the matching-or-free slot. Cache
overflow raises ``error`` (masked trap), host spill arrives with the
multi-tx layer.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax.numpy as jnp
from flax import struct

from ..config import LimitsConfig, DEFAULT_LIMITS
from ..ops import u256


class Trap:
    """Error causes (first one wins, recorded in ``Frontier.err_code``).

    The reference raises typed VmExceptions and silently discards the
    state (⚠unv); here every masked trap is attributed so the report can
    say exactly what coverage was lost to which static cap."""

    NONE = 0
    STACK = 1            # stack under/overflow vs max_stack cap
    INVALID_OP = 2       # undefined opcode (incl. INVALID 0xFE)
    BAD_JUMP = 3         # jump target not a JUMPDEST
    OOB_MEM = 4          # memory access past mem_bytes cap
    STORAGE_SLOTS = 5    # storage associative cache full
    HASH_LIMIT = 6       # SHA3 input longer than max_hash_bytes
    OOG = 7              # out of gas
    TAPE_LIMIT = 8       # symbolic tape full
    CONSTRAINT_LIMIT = 9  # path-condition slots full
    STATIC_WRITE = 10    # state modification inside a STATICCALL frame
    ACCOUNTS_FULL = 11   # world-state account table full
    LOOP_BOUND = 12      # retired by the bounded-loops policy (intentional
    # pruning, reference: BoundedLoopsStrategy ⚠unv — not a capacity loss)


TRAP_NAMES = {
    Trap.STACK: "stack_cap",
    Trap.INVALID_OP: "invalid_opcode",
    Trap.BAD_JUMP: "bad_jump",
    Trap.OOB_MEM: "memory_cap",
    Trap.STORAGE_SLOTS: "storage_cap",
    Trap.HASH_LIMIT: "hash_size_cap",
    Trap.OOG: "out_of_gas",
    Trap.TAPE_LIMIT: "tape_cap",
    Trap.CONSTRAINT_LIMIT: "constraint_cap",
    Trap.STATIC_WRITE: "static_write",
    Trap.ACCOUNTS_FULL: "accounts_cap",
    Trap.LOOP_BOUND: "loop_bound",
}

# trap codes that are capacity artifacts of this engine (coverage loss)
# rather than genuine EVM exceptional halts
CAP_TRAPS = (Trap.STACK, Trap.OOB_MEM, Trap.STORAGE_SLOTS, Trap.HASH_LIMIT,
             Trap.TAPE_LIMIT, Trap.CONSTRAINT_LIMIT, Trap.ACCOUNTS_FULL)

# traps that KILL a lane outright even inside a sub-frame (pop_frames must
# not convert them into a callee failure the caller observes): capacity
# artifacts plus intentional loop-bound retirement
KILL_TRAPS = CAP_TRAPS + (Trap.LOOP_BOUND,)


# Reference's well-known actors (mythril/laser/ethereum/transaction ⚠unv).
ATTACKER_ADDRESS = 0xDEADBEEFDEADBEEFDEADBEEFDEADBEEFDEADBEEF
CREATOR_ADDRESS = 0xAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFEAFFE

# account-table slot convention (uniform across lanes so host fixtures can
# address slots without per-lane maps): 0 = attacker EOA, 1 = creator EOA,
# 2+i = corpus contract i (when the corpus fits max_accounts; otherwise
# slot 2 holds the lane's own contract only)
ACCT_ATTACKER = 0
ACCT_CREATOR = 1
ACCT_CONTRACT0 = 2

# acct_code sentinel: the account has code, but not in the corpus
CODE_UNKNOWN = -2


def contract_address(i: int) -> int:
    """Deterministic default address of corpus contract i."""
    return 0xAFFE + 0x10000 * i


@struct.dataclass
class Frontier:
    # --- control ---
    active: jnp.ndarray  # bool[P] lane holds a live path
    halted: jnp.ndarray  # bool[P] executed STOP/RETURN/REVERT/SELFDESTRUCT
    error: jnp.ndarray  # bool[P] abnormal halt (invalid op, stack, bad jump, oob)
    err_code: jnp.ndarray  # i32[P] first Trap cause (0 = none)
    reverted: jnp.ndarray  # bool[P] halted via REVERT
    pc: jnp.ndarray  # i32[P]
    contract_id: jnp.ndarray  # i32[P] index into Corpus arrays (code to run)
    # --- call-frame context (reference: GlobalState.environment + tx_stack
    # depth ⚠unv; sub-frames share the stack array via sp_base) ---
    depth: jnp.ndarray  # i32[P] current call depth (0 = top frame)
    sp_base: jnp.ndarray  # i32[P] first stack slot owned by this frame
    static: jnp.ndarray  # bool[P] STATICCALL context (writes trap)
    cur_acct: jnp.ndarray  # i32[P] account slot whose storage/balance we use
    home_acct: jnp.ndarray  # i32[P] the lane's own contract account (tx reset)
    home_contract: jnp.ndarray  # i32[P] the lane's own corpus index (tx reset)
    caller_addr: jnp.ndarray  # u32[P, 8] msg.sender of this frame
    callvalue: jnp.ndarray  # u32[P, 8] msg.value of this frame
    pc_hold: jnp.ndarray  # bool[P] transient: handler set pc; epilogue must
    # not advance it this step (cleared by epilogue)
    # --- saved caller frames (reference: the Python call stack through
    # Instruction.call_ + tx_stack ⚠unv; here explicit save/restore arrays
    # indexed by depth; the stack array itself is shared via sp_base) ---
    fr_ret_pc: jnp.ndarray  # i32[P, D] pc of the CALL instruction
    fr_sp: jnp.ndarray  # i32[P, D] caller sp after popping the call args
    fr_sp_base: jnp.ndarray  # i32[P, D]
    fr_static: jnp.ndarray  # bool[P, D]
    fr_cur_acct: jnp.ndarray  # i32[P, D]
    fr_contract_id: jnp.ndarray  # i32[P, D]
    fr_caller_addr: jnp.ndarray  # u32[P, D, 8]
    fr_callvalue: jnp.ndarray  # u32[P, D, 8]
    fr_memory: jnp.ndarray  # u8[P, D, M]
    fr_mem_words: jnp.ndarray  # i32[P, D]
    fr_calldata: jnp.ndarray  # u8[P, D, CD]
    fr_calldata_len: jnp.ndarray  # i32[P, D]
    fr_ret_off: jnp.ndarray  # i64[P, D] caller's returndata destination
    fr_ret_len: jnp.ndarray  # i64[P, D]
    fr_gas_min: jnp.ndarray  # i64[P, D] gas snapshot (restored on failure:
    fr_gas_max: jnp.ndarray  # i64[P, D]  no 63/64 forwarding model)
    # storage + balance snapshots for sub-frame revert rollback
    fr_st_keys: jnp.ndarray  # u32[P, D, K, 8]
    fr_st_vals: jnp.ndarray  # u32[P, D, K, 8]
    fr_st_used: jnp.ndarray  # bool[P, D, K]
    fr_st_written: jnp.ndarray  # bool[P, D, K]
    fr_st_acct: jnp.ndarray  # i32[P, D, K]
    fr_acct_bal: jnp.ndarray  # u32[P, D, A, 8]
    fr_create_slot: jnp.ndarray  # i32[P, D] account slot a CREATE frame is
    # constructing (-1 = ordinary call frame)
    fr_gas_limit: jnp.ndarray  # i64[P, D] caller's gas ceiling (EIP-150:
    # the callee runs under used + min(gas operand, 63/64 remaining))
    # --- EIP-2929 warm sets (berlin schedule; rolled back with frames) ---
    warm_acct: jnp.ndarray  # bool[P, A] account touched this tx
    st_warm: jnp.ndarray  # bool[P, K] storage-cache slot touched this tx
    fr_warm_acct: jnp.ndarray  # bool[P, D, A]
    fr_st_warm: jnp.ndarray  # bool[P, D, K]
    # --- in-tx CREATE init-code execution (one live init frame per lane;
    # a constructor's own nested CREATE falls back to the codeless path) ---
    init_code: jnp.ndarray  # u8[P, IC] init code being executed
    init_len: jnp.ndarray  # i32[P]
    init_jd: jnp.ndarray  # bool[P, IC] jumpdest map of the init buffer
    init_depth: jnp.ndarray  # i32[P] frame depth running init code (0 = none)
    # --- per-lane world state (reference: WorldState/Account ⚠unv) ---
    acct_addr: jnp.ndarray  # u32[P, A, 8]
    acct_code: jnp.ndarray  # i32[P, A] corpus index (-1 = EOA / no code;
    # CODE_UNKNOWN=-2 = account HAS code the corpus doesn't hold, e.g. a
    # CREATE result — calls to it must take the external-havoc path)
    acct_bal: jnp.ndarray  # u32[P, A, 8]
    acct_used: jnp.ndarray  # bool[P, A]
    # --- stack ---
    stack: jnp.ndarray  # u32[P, S, 8]
    sp: jnp.ndarray  # i32[P] number of occupied slots
    # --- memory ---
    memory: jnp.ndarray  # u8[P, M]
    mem_words: jnp.ndarray  # i32[P] highest touched 32-byte word count (MSIZE/gas)
    # --- gas used (min/max accounting, reference: MachineState min_gas_used/max_gas_used) ---
    gas_min: jnp.ndarray  # i64[P]
    gas_max: jnp.ndarray  # i64[P]
    gas_limit: jnp.ndarray  # i64[P]
    # --- storage associative cache ---
    st_keys: jnp.ndarray  # u32[P, K, 8]
    st_vals: jnp.ndarray  # u32[P, K, 8]
    st_used: jnp.ndarray  # bool[P, K]
    st_written: jnp.ndarray  # bool[P, K] written (vs merely loaded) this tx
    st_acct: jnp.ndarray  # i32[P, K] account slot owning the entry
    # --- calldata / returndata ---
    calldata: jnp.ndarray  # u8[P, CD]
    calldata_len: jnp.ndarray  # i32[P]
    returndata: jnp.ndarray  # u8[P, RD] (from most recent sub-call)
    returndata_len: jnp.ndarray  # i32[P]
    retval: jnp.ndarray  # u8[P, RD] RETURN/REVERT payload of this frame
    retval_len: jnp.ndarray  # i32[P]
    # --- events ---
    n_logs: jnp.ndarray  # i32[P] LOG attempts (records cap at log_slots)
    log_pc: jnp.ndarray  # i32[P, LS] pc of each recorded LOG
    log_cid: jnp.ndarray  # i32[P, LS] contract executing it
    log_ntopics: jnp.ndarray  # i32[P, LS] 0..4
    log_topic0: jnp.ndarray  # u32[P, LS, 8] first topic (event signature)
    log_data0: jnp.ndarray  # u32[P, LS, 8] first 32 bytes of the payload
    selfdestructed: jnp.ndarray  # bool[P] executed SELFDESTRUCT
    # --- metrics (reference: BenchmarkPlugin states/sec ⚠unv, SURVEY §5.1) ---
    n_steps: jnp.ndarray  # i32[P] instructions this lane actually executed
    # per-opcode execution histogram (reference: --enable-iprof's
    # InstructionProfiler table ⚠unv, SURVEY §5.1). None = disabled (the
    # leaf vanishes from the pytree, so the hot path pays nothing); enable
    # with `attach_iprof`. i32[P, 256], one row per lane so it shards with
    # the lane axis; epilogue scatter-adds the executed opcode each
    # superstep, expand_forks zeroes copies' rows (a fork child inherits
    # its parent's PATH, not its parent's executed instructions).
    op_hist: Optional[jnp.ndarray] = None
    # residual sidecar for op_hist (ADVICE r5): when slot recycling
    # (expand_forks) or lane movement (rebalance/migrate) would orphan a
    # retired lane's not-yet-harvested rows, they accumulate HERE — a
    # lane-independent i32[256] — instead of being folded into an
    # arbitrary live lane's row, so per-lane consumers of op_hist stay
    # attributable. Harvest = sum(op_hist rows) + op_resid; both zero
    # together at tx boundaries. None whenever op_hist is None (legacy
    # hand-built frontiers with op_hist but no sidecar keep the old
    # fold-into-a-live-lane behavior).
    op_resid: Optional[jnp.ndarray] = None

    @property
    def n_lanes(self) -> int:
        return self.pc.shape[0]

    @property
    def max_stack(self) -> int:
        return self.stack.shape[1]

    @property
    def running(self) -> jnp.ndarray:
        """Lanes that still execute: active and not halted/errored."""
        return self.active & ~self.halted & ~self.error

    @property
    def exec_init(self) -> jnp.ndarray:
        """Lanes whose CURRENT frame executes CREATE init code (opcode
        fetch, PUSH immediates, CODESIZE/CODECOPY and JUMPDEST validation
        read the per-lane ``init_code`` buffer instead of the corpus)."""
        return (self.init_depth > 0) & (self.depth == self.init_depth)

    def attach_iprof(self) -> "Frontier":
        """Enable the per-opcode instruction profiler (zeroed per-lane
        histogram + zeroed residual sidecar row)."""
        return self.replace(
            op_hist=jnp.zeros((self.n_lanes, 256), dtype=jnp.int32),
            op_resid=jnp.zeros(256, dtype=jnp.int32))

    def trap(self, mask, code: int) -> "Frontier":
        """Set the error flag under ``mask``, attributing the FIRST cause."""
        return self.replace(
            error=self.error | mask,
            err_code=jnp.where(mask & (self.err_code == 0), code, self.err_code),
        )

    # --- world-state helpers ---

    def acct_field(self, arr, slot) -> jnp.ndarray:
        """Per-lane gather arr[P, A, ...] at account slot[P]."""
        idx = jnp.clip(slot, 0, arr.shape[1] - 1).astype(jnp.int32)
        if arr.ndim == 3:
            return jnp.take_along_axis(arr, idx[:, None, None], axis=1)[:, 0]
        return jnp.take_along_axis(arr, idx[:, None], axis=1)[:, 0]

    @property
    def self_address(self) -> jnp.ndarray:
        return self.acct_field(self.acct_addr, self.cur_acct)

    @property
    def self_balance(self) -> jnp.ndarray:
        return self.acct_field(self.acct_bal, self.cur_acct)

    def acct_lookup(self, addr) -> tuple:
        """(found bool[P], slot i32[P]) of the account holding ``addr``."""
        match = self.acct_used & jnp.all(
            self.acct_addr == addr[:, None, :], axis=-1
        )
        return jnp.any(match, axis=1), jnp.argmax(match, axis=1).astype(jnp.int32)


@struct.dataclass
class Env:
    """Tx-global execution environment (reference: block info on
    ``GlobalState`` ⚠unv). Frame-scoped values (address, caller,
    callvalue, balances) live on the :class:`Frontier` so sub-call frames
    can swap them; only what is constant across a transaction stays here.
    u256 limb arrays [P, 8]."""

    origin: jnp.ndarray
    gasprice: jnp.ndarray
    coinbase: jnp.ndarray
    timestamp: jnp.ndarray
    number: jnp.ndarray
    prevrandao: jnp.ndarray
    blk_gaslimit: jnp.ndarray
    chainid: jnp.ndarray
    basefee: jnp.ndarray


@struct.dataclass
class Corpus:
    """Shared contract images (one per contract, lanes index via contract_id)."""

    code: jnp.ndarray  # u8[C, MAX_CODE]
    code_len: jnp.ndarray  # i32[C]
    is_jumpdest: jnp.ndarray  # bool[C, MAX_CODE]
    code_hash: jnp.ndarray  # u32[C, 8] keccak256 of each image, host-
    # precomputed once so EXTCODEHASH answers concretely for corpus code
    deploys: Optional[jnp.ndarray] = None  # bool[C]: the image is the
    # creation code of a top-level deploy. Its epilogue copies the
    # runtime code it embeds to memory and RETURNs it; the caller
    # supplies that image, so the payload is never read and its copy is
    # cut at ``mem_bytes`` instead of trapping (a runtime of 5-24 KB
    # does not fit the memory model). None for a corpus without
    # creation images: no leaf, the programs compiled for it unchanged

    @staticmethod
    def from_images(images, n_creation: int = 0) -> "Corpus":
        """``n_creation``: the first so many images are creation code."""
        from ..ops.keccak import keccak256_host_int

        hashes = np.stack([
            u256.from_int(keccak256_host_int(
                bytes(np.asarray(im.code[:im.code_len], dtype=np.uint8))))
            for im in images])
        return Corpus(
            code=jnp.asarray(np.stack([im.code for im in images])),
            code_len=jnp.asarray(np.array([im.code_len for im in images], dtype=np.int32)),
            is_jumpdest=jnp.asarray(np.stack([im.is_jumpdest for im in images])),
            code_hash=jnp.asarray(hashes),
            deploys=(jnp.arange(len(images)) < n_creation
                     if n_creation else None),
        )


def make_frontier(
    n_lanes: int,
    limits: LimitsConfig = DEFAULT_LIMITS,
    contract_id=None,
    calldata: Optional[np.ndarray] = None,
    calldata_len=None,
    gas_limit: int = 10_000_000,
    active=None,
    n_contracts: int = 1,
    contract_addrs: Optional[Sequence[int]] = None,
    caller: int = ATTACKER_ADDRESS,
    callvalue: int = 0,
    balance: int = 10**18,
    attacker_balance: int = 10**20,
    systems: Optional[Sequence[Optional[Sequence[int]]]] = None,
) -> Frontier:
    """Fresh frontier with a seeded per-lane world state.

    Account layout (see slot-convention constants above): attacker and
    creator EOAs, then the corpus contracts — every lane gets the same
    table when ``2 + n_contracts <= max_accounts``; otherwise each lane
    registers only its own contract at slot 2. The executing account
    (``cur_acct``) is the lane's own contract.

    ``systems`` (a campaign batch that holds linked systems): for each
    contract the indices of the members of ITS system, in the manifest's
    order and itself among them, or None for a contract that is alone.
    A member's lanes then hold attacker, creator and the members of that
    one system from slot 2 on, at ``contract_addrs``' addresses, and
    nothing of the batch's other contracts; a contract that is alone
    registers only itself. Without ``systems`` the all-or-own rule above
    stands as it was.
    """
    P = n_lanes
    L = limits
    A = L.max_accounts
    z8 = lambda *s: jnp.zeros(s + (8,), dtype=jnp.uint32)
    if contract_id is None:
        contract_id = jnp.zeros(P, dtype=jnp.int32)
    contract_id = jnp.asarray(contract_id, dtype=jnp.int32)
    if calldata is None:
        calldata = jnp.zeros((P, L.calldata_bytes), dtype=jnp.uint8)
    else:
        calldata = jnp.asarray(calldata, dtype=jnp.uint8)
        assert calldata.shape == (P, L.calldata_bytes)
    if calldata_len is None:
        calldata_len = jnp.zeros(P, dtype=jnp.int32)
    if active is None:
        active = jnp.ones(P, dtype=bool)

    if contract_addrs is None:
        contract_addrs = [contract_address(i) for i in range(n_contracts)]
    C = len(contract_addrs)

    # account table (numpy host build, then broadcast / scatter)
    addr = np.zeros((P, A, 8), dtype=np.uint32)
    code = np.full((P, A), -1, dtype=np.int32)
    bal = np.zeros((P, A, 8), dtype=np.uint32)
    used = np.zeros((P, A), dtype=bool)
    addr[:, ACCT_ATTACKER] = u256.from_int(ATTACKER_ADDRESS)
    bal[:, ACCT_ATTACKER] = u256.from_int(attacker_balance)
    used[:, ACCT_ATTACKER] = True
    addr[:, ACCT_CREATOR] = u256.from_int(CREATOR_ADDRESS)
    bal[:, ACCT_CREATOR] = u256.from_int(attacker_balance)
    used[:, ACCT_CREATOR] = True
    cid_np = np.asarray(contract_id)
    if systems is not None:
        cur_acct = np.full(P, ACCT_CONTRACT0, dtype=np.int32)
        for i in range(C):
            members = list(systems[i]) if systems[i] is not None else [i]
            if ACCT_CONTRACT0 + len(members) > A:
                raise ValueError(
                    f"a system of {len(members)} members does not fit "
                    f"max_accounts={A} (attacker, creator, then at most "
                    f"{A - ACCT_CONTRACT0} members)")
            lanes = np.flatnonzero(cid_np == i)
            for k, j in enumerate(members):
                addr[lanes, ACCT_CONTRACT0 + k] = u256.from_int(
                    contract_addrs[j])
                code[lanes, ACCT_CONTRACT0 + k] = j
                bal[lanes, ACCT_CONTRACT0 + k] = u256.from_int(balance)
                used[lanes, ACCT_CONTRACT0 + k] = True
            cur_acct[lanes] = ACCT_CONTRACT0 + members.index(i)
    elif ACCT_CONTRACT0 + C <= A:
        for i, a in enumerate(contract_addrs):
            addr[:, ACCT_CONTRACT0 + i] = u256.from_int(a)
            code[:, ACCT_CONTRACT0 + i] = i
            bal[:, ACCT_CONTRACT0 + i] = u256.from_int(balance)
            used[:, ACCT_CONTRACT0 + i] = True
        cur_acct = ACCT_CONTRACT0 + cid_np
    else:
        for lane in range(P):
            i = int(cid_np[lane]) if cid_np.ndim else int(cid_np)
            addr[lane, ACCT_CONTRACT0] = u256.from_int(contract_addrs[i])
            code[lane, ACCT_CONTRACT0] = i
            bal[lane, ACCT_CONTRACT0] = u256.from_int(balance)
            used[lane, ACCT_CONTRACT0] = True
        cur_acct = np.full(P, ACCT_CONTRACT0, dtype=np.int32)

    def w(v: int):
        return jnp.broadcast_to(jnp.asarray(u256.from_int(v)), (P, 8))

    D = L.call_depth
    return Frontier(
        active=active,
        halted=jnp.zeros(P, dtype=bool),
        error=jnp.zeros(P, dtype=bool),
        err_code=jnp.zeros(P, dtype=jnp.int32),
        reverted=jnp.zeros(P, dtype=bool),
        pc=jnp.zeros(P, dtype=jnp.int32),
        contract_id=contract_id,
        depth=jnp.zeros(P, dtype=jnp.int32),
        sp_base=jnp.zeros(P, dtype=jnp.int32),
        static=jnp.zeros(P, dtype=bool),
        cur_acct=jnp.asarray(cur_acct, dtype=jnp.int32),
        home_acct=jnp.asarray(cur_acct, dtype=jnp.int32),
        home_contract=contract_id,
        caller_addr=w(caller),
        callvalue=w(callvalue),
        pc_hold=jnp.zeros(P, dtype=bool),
        fr_ret_pc=jnp.zeros((P, D), dtype=jnp.int32),
        fr_sp=jnp.zeros((P, D), dtype=jnp.int32),
        fr_sp_base=jnp.zeros((P, D), dtype=jnp.int32),
        fr_static=jnp.zeros((P, D), dtype=bool),
        fr_cur_acct=jnp.zeros((P, D), dtype=jnp.int32),
        fr_contract_id=jnp.zeros((P, D), dtype=jnp.int32),
        fr_caller_addr=z8(P, D),
        fr_callvalue=z8(P, D),
        fr_memory=jnp.zeros((P, D, L.mem_bytes), dtype=jnp.uint8),
        fr_mem_words=jnp.zeros((P, D), dtype=jnp.int32),
        fr_calldata=jnp.zeros((P, D, L.calldata_bytes), dtype=jnp.uint8),
        fr_calldata_len=jnp.zeros((P, D), dtype=jnp.int32),
        fr_ret_off=jnp.zeros((P, D), dtype=jnp.int64),
        fr_ret_len=jnp.zeros((P, D), dtype=jnp.int64),
        fr_gas_min=jnp.zeros((P, D), dtype=jnp.int64),
        fr_gas_max=jnp.zeros((P, D), dtype=jnp.int64),
        fr_st_keys=z8(P, D, L.storage_slots),
        fr_st_vals=z8(P, D, L.storage_slots),
        fr_st_used=jnp.zeros((P, D, L.storage_slots), dtype=bool),
        fr_st_written=jnp.zeros((P, D, L.storage_slots), dtype=bool),
        fr_st_acct=jnp.zeros((P, D, L.storage_slots), dtype=jnp.int32),
        fr_acct_bal=z8(P, D, A),
        fr_create_slot=jnp.full((P, D), -1, dtype=jnp.int32),
        fr_gas_limit=jnp.zeros((P, D), dtype=jnp.int64),
        # tx-start warm set: origin/caller + the executing account
        # (EIP-2929 pre-warms tx.origin and tx.to)
        warm_acct=jnp.zeros((P, A), dtype=bool)
        .at[jnp.arange(P), ACCT_ATTACKER].set(True)
        .at[jnp.arange(P), jnp.asarray(cur_acct, dtype=jnp.int32)].set(True),
        st_warm=jnp.zeros((P, L.storage_slots), dtype=bool),
        fr_warm_acct=jnp.zeros((P, D, A), dtype=bool),
        fr_st_warm=jnp.zeros((P, D, L.storage_slots), dtype=bool),
        init_code=jnp.zeros((P, L.init_code_bytes), dtype=jnp.uint8),
        init_len=jnp.zeros(P, dtype=jnp.int32),
        init_jd=jnp.zeros((P, L.init_code_bytes), dtype=bool),
        init_depth=jnp.zeros(P, dtype=jnp.int32),
        acct_addr=jnp.asarray(addr),
        acct_code=jnp.asarray(code),
        acct_bal=jnp.asarray(bal),
        acct_used=jnp.asarray(used),
        stack=z8(P, L.max_stack),
        sp=jnp.zeros(P, dtype=jnp.int32),
        memory=jnp.zeros((P, L.mem_bytes), dtype=jnp.uint8),
        mem_words=jnp.zeros(P, dtype=jnp.int32),
        gas_min=jnp.zeros(P, dtype=jnp.int64),
        gas_max=jnp.zeros(P, dtype=jnp.int64),
        gas_limit=jnp.full(P, gas_limit, dtype=jnp.int64),
        st_keys=z8(P, L.storage_slots),
        st_vals=z8(P, L.storage_slots),
        st_used=jnp.zeros((P, L.storage_slots), dtype=bool),
        st_written=jnp.zeros((P, L.storage_slots), dtype=bool),
        st_acct=jnp.zeros((P, L.storage_slots), dtype=jnp.int32),
        calldata=calldata,
        calldata_len=jnp.asarray(calldata_len, dtype=jnp.int32),
        returndata=jnp.zeros((P, L.returndata_bytes), dtype=jnp.uint8),
        returndata_len=jnp.zeros(P, dtype=jnp.int32),
        retval=jnp.zeros((P, L.returndata_bytes), dtype=jnp.uint8),
        retval_len=jnp.zeros(P, dtype=jnp.int32),
        n_logs=jnp.zeros(P, dtype=jnp.int32),
        log_pc=jnp.zeros((P, L.log_slots), dtype=jnp.int32),
        log_cid=jnp.zeros((P, L.log_slots), dtype=jnp.int32),
        log_ntopics=jnp.zeros((P, L.log_slots), dtype=jnp.int32),
        log_topic0=z8(P, L.log_slots),
        log_data0=z8(P, L.log_slots),
        selfdestructed=jnp.zeros(P, dtype=bool),
        n_steps=jnp.zeros(P, dtype=jnp.int32),
    )


def make_env(
    n_lanes: int,
    origin: int = ATTACKER_ADDRESS,
    timestamp: int = 1_700_000_000,
    number: int = 17_000_000,
    chainid: int = 1,
) -> Env:
    P = n_lanes

    def w(v: int):
        return jnp.broadcast_to(jnp.asarray(u256.from_int(v)), (P, 8))

    return Env(
        origin=w(origin),
        gasprice=w(10**9),
        coinbase=w(0xC01BA5E),
        timestamp=w(timestamp),
        number=w(number),
        prevrandao=w(0x123456789ABCDEF),
        blk_gaslimit=w(30_000_000),
        chainid=w(chainid),
        basefee=w(10**9),
    )
