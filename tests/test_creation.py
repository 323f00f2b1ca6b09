"""Contract-creation transactions + in-tx CREATE semantics.

Constructor-established invariants (owner set in the
constructor) must be visible to the message-call transactions, removing
the storage-havoc over-approximation FP on owner-guarded code.
Reference: ``execute_contract_creation`` + ``ContractCreationTransaction``
(``mythril/laser/ethereum/transaction/symbolic.py`` ⚠unv).
"""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.core.frontier import (ACCT_CONTRACT0, ATTACKER_ADDRESS,
                                       CREATOR_ADDRESS)
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.ops import u256
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run
from mythril_tpu.symbolic.engine import CREATE_ADDR_BASE
from mythril_tpu.analysis import SymExecWrapper, fire_lasers

L = TEST_LIMITS

# constructor: owner = msg.sender; return empty payload (the wrapper is
# handed the runtime image explicitly, as solc artifacts provide it)
CTOR_SETS_OWNER = assemble("CALLER", 0, "SSTORE", 0, 0, "RETURN")

# runtime: owner-guarded drain — if (caller == owner) caller.call{value,to
# from calldata}; the classic EtherThief FP shape under storage havoc
GUARDED_DRAIN = assemble(
    "CALLER", 0, "SLOAD", "EQ", ("ref", "ok"), "JUMPI", "STOP",
    ("label", "ok"),
    0, 0, 0, 0,
    36, "CALLDATALOAD",
    4, "CALLDATALOAD",
    ("push2", 0xFFFF), "CALL",
    "POP", "STOP",
)


def swcs(report):
    return {i.swc_id for i in report.issues}


def test_creation_storage_persists_into_message_tx():
    # runtime copies the constructor-written slot 0 into slot 1
    runtime = assemble(0, "SLOAD", 1, "SSTORE", "STOP")
    sym = SymExecWrapper(
        [runtime], creation_bytecodes=[CTOR_SETS_OWNER],
        limits=L, spec=SymSpec(storage=False),
        lanes_per_contract=8, max_steps=128, transaction_count=1,
    )
    assert len(sym.tx_contexts) == 2, "creation ctx + one message ctx"
    sf = sym.sf
    used = np.asarray(sf.base.st_used)
    keys = np.asarray(sf.base.st_keys)
    vals = np.asarray(sf.base.st_vals)
    lanes = np.where(np.asarray(sf.base.active))[0]
    assert lanes.size >= 1
    lane = lanes[0]
    by_key = {u256.to_int(keys[lane, k]): u256.to_int(vals[lane, k])
              for k in range(used.shape[1]) if used[lane, k]}
    assert by_key[0] == CREATOR_ADDRESS, "constructor write persisted"
    assert by_key[1] == CREATOR_ADDRESS, "runtime read observed it"


def test_no_etherthief_fp_when_constructor_sets_owner():
    # with the creation tx modeled and no storage
    # havoc, the owner guard is concrete (owner == CREATOR != ATTACKER) and
    # the drain is unreachable
    sym = SymExecWrapper(
        [GUARDED_DRAIN], creation_bytecodes=[CTOR_SETS_OWNER],
        limits=L, spec=SymSpec(storage=False),
        lanes_per_contract=8, max_steps=128, transaction_count=1,
    )
    report = fire_lasers(sym)
    assert "105" not in swcs(report), "owner-guarded drain must not FP"


def test_etherthief_fires_without_creation_info():
    # positive control: same runtime analyzed without the constructor and
    # with havoc'd storage keeps the (sound) over-approximated finding
    sym = SymExecWrapper(
        [GUARDED_DRAIN], limits=L, spec=SymSpec(storage=True),
        lanes_per_contract=8, max_steps=128, transaction_count=1,
    )
    report = fire_lasers(sym)
    assert "105" in swcs(report)


def test_constructor_issue_attributed_to_constructor():
    # an unguarded SELFDESTRUCT in the constructor itself is a finding
    # ON THE CREATION CODE (reference reports constructor issues too)
    ctor = assemble(0, "SELFDESTRUCT")
    runtime = assemble("STOP")
    sym = SymExecWrapper(
        [runtime], creation_bytecodes=[ctor], contract_names=["Victim"],
        limits=L, lanes_per_contract=8, max_steps=64, transaction_count=1,
    )
    report = fire_lasers(sym, white_list=["AccidentallyKillable"])
    issues = [i for i in report.issues if i.swc_id == "106"]
    assert issues and issues[0].contract == "Victim (constructor)"


def run_single(code, max_steps=64, n_lanes=4, balance=10**18):
    img = ContractImage.from_bytecode(code, L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(n_lanes, L, active=active, balance=balance)
    env = make_env(n_lanes)
    return sym_run(sf, env, corpus, SymSpec(), L, max_steps=max_steps)


def test_create_pushes_fresh_concrete_address():
    # CREATE(value=0, off=0, len=0) -> deterministic fresh address, stored
    code = assemble(0, 0, 0, "CREATE", 0, "SSTORE", "STOP")
    out = run_single(code)
    used = np.asarray(out.base.st_used)
    vals = np.asarray(out.base.st_vals)
    lane_vals = [u256.to_int(vals[0, k]) for k in range(used.shape[1])
                 if used[0, k]]
    assert lane_vals == [CREATE_ADDR_BASE]
    # the new account is registered (codeless) in the lane's world state
    acct_used = np.asarray(out.base.acct_used)
    acct_addr = np.asarray(out.base.acct_addr)
    addrs = {u256.to_int(acct_addr[0, s]) for s in range(acct_used.shape[1])
             if acct_used[0, s]}
    assert CREATE_ADDR_BASE in addrs


def test_create_endowment_moves_balance():
    code = assemble(0, 0, 1000, "CREATE", "POP", "STOP")
    out = run_single(code)
    bal = np.asarray(out.base.acct_bal)
    assert u256.to_int(bal[0, ACCT_CONTRACT0]) == 10**18 - 1000
    acct_used = np.asarray(out.base.acct_used)
    acct_addr = np.asarray(out.base.acct_addr)
    for s in range(acct_used.shape[1]):
        if acct_used[0, s] and u256.to_int(acct_addr[0, s]) == CREATE_ADDR_BASE:
            assert u256.to_int(bal[0, s]) == 1000
            break
    else:
        raise AssertionError("created account not registered")


def test_call_to_created_account_stays_symbolic():
    # code-review r3: the created account HAS code (unknown to the
    # corpus) — a CALL to it must take the external-havoc path, not
    # succeed concretely as an EOA transfer
    code = assemble(
        0, 0, 0, "CREATE",
        0, 0, 0, 0, 0, "DUP6", ("push2", 0xFFFF), "CALL",
        ("ref", "y"), "JUMPI", 1, 0, "SSTORE", "STOP",
        ("label", "y"), 2, 0, "SSTORE", "STOP",
    )
    out = run_single(code, n_lanes=8, max_steps=128)
    act = np.asarray(out.base.active)
    used = np.asarray(out.base.st_used)
    keys = np.asarray(out.base.st_keys)
    vals = np.asarray(out.base.st_vals)
    got = set()
    for lane in np.where(act)[0]:
        for k in range(used.shape[1]):
            if used[lane, k] and not keys[lane, k].any():
                got.add(u256.to_int(vals[lane, k]))
    assert got == {1, 2}, "both success outcomes must be explored"


# --- in-tx CREATE/CREATE2 init-code execution ---

# child init code: storage[0] = 1 on the CHILD account, deploy empty code
CHILD_INIT_EMPTY = assemble(1, 0, "SSTORE", 0, 0, "RETURN")

# child runtime: storage[5] = 0x42 (6 bytes: 6042600555 00)
CHILD_RUNTIME = assemble(0x42, 5, "SSTORE", "STOP")
# init code that deploys CHILD_RUNTIME (PUSH6 runtime; MSTORE; RETURN 6@26)
CHILD_INIT_DEPLOY = assemble(
    ("push6", int.from_bytes(CHILD_RUNTIME, "big")), 0, "MSTORE",
    6, 26, "RETURN",
)


def _run_factory(factory_code, extra_images=(), n_lanes=8, max_steps=128):
    # extra_images ride in the CORPUS only (deploy-matching needs the
    # bytes, not an account): the account table keeps slot 3 free for the
    # created child (TEST_LIMITS.max_accounts == 4)
    imgs = [ContractImage.from_bytecode(c, L.max_code)
            for c in (factory_code, *extra_images)]
    corpus = Corpus.from_images(imgs)
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(
        n_lanes, L, contract_id=np.zeros(n_lanes, np.int32), active=active,
        n_contracts=1, balance=10**18,
    )
    env = make_env(n_lanes)
    return sym_run(sf, env, corpus, SymSpec(), L, max_steps=max_steps)


def test_create_runs_init_code_and_persists_child_storage():
    """CREATE pushes a real constructor frame: the init code executes, its
    SSTORE lands on the CHILD account, and the pushed result is the child
    address (reference: execute_contract_creation ⚠unv)."""
    init_int = int.from_bytes(CHILD_INIT_EMPTY, "big")
    n = len(CHILD_INIT_EMPTY)
    factory = assemble(
        ("push" + str(n), init_int), 0, "MSTORE",   # init at offset 32-n
        n, 32 - n, 0, "CREATE",                     # len, off, value
        1, "SSTORE", "STOP",                        # storage[1] = child addr
    )
    out = _run_factory(factory)
    b = out.base
    assert bool(np.asarray(b.active)[0]) and not bool(np.asarray(b.error)[0])
    # child registered at the first free slot (3) as an empty-code deploy
    assert bool(np.asarray(b.acct_used)[0, 3])
    assert int(np.asarray(b.acct_code)[0, 3]) == -1, "empty deploy -> EOA-like"
    child_addr = u256.to_int(np.asarray(b.acct_addr)[0, 3])
    assert child_addr >= CREATE_ADDR_BASE
    # child's constructor write persisted on the child's storage
    used = np.asarray(b.st_used)[0]
    keys = np.asarray(b.st_keys)[0]
    vals = np.asarray(b.st_vals)[0]
    acct = np.asarray(b.st_acct)[0]
    entries = {(int(acct[k]), u256.to_int(keys[k])): u256.to_int(vals[k])
               for k in range(used.shape[0]) if used[k]}
    assert entries.get((3, 0)) == 1, f"child ctor write missing: {entries}"
    # factory stored the child address
    assert entries.get((2, 1)) == child_addr


def test_create_deploys_corpus_matched_child_then_calls_it():
    """The deployed runtime image is byte-matched against the corpus: a
    factory deploying a known child can then CALL it and the child's code
    actually executes (SWC evidence inside the child becomes reachable)."""
    init_int = int.from_bytes(CHILD_INIT_DEPLOY, "big")
    n = len(CHILD_INIT_DEPLOY)
    factory = assemble(
        0, 0, 0, 0, 0,                              # call tail: rl ro al ao val
        ("push" + str(n), init_int), 0, "MSTORE",
        n, 32 - n, 0, "CREATE",                     # -> child addr on stack
        ("push2", 60000), "CALL",
        "POP", "STOP",
    )
    out = _run_factory(factory, extra_images=(CHILD_RUNTIME,))
    b = out.base
    assert bool(np.asarray(b.active)[0]) and not bool(np.asarray(b.error)[0])
    assert int(np.asarray(b.acct_code)[0, 3]) == 1, "deployed image matched"
    used = np.asarray(b.st_used)[0]
    keys = np.asarray(b.st_keys)[0]
    vals = np.asarray(b.st_vals)[0]
    acct = np.asarray(b.st_acct)[0]
    entries = {(int(acct[k]), u256.to_int(keys[k])): u256.to_int(vals[k])
               for k in range(used.shape[0]) if used[k]}
    assert entries.get((3, 5)) == 0x42, \
        f"child runtime did not execute after deploy: {entries}"


def test_create_revert_rolls_back_child_registration():
    """A reverting constructor unregisters the child account and pushes 0."""
    init_revert = assemble(0, 0, "REVERT")
    init_int = int.from_bytes(init_revert, "big")
    n = len(init_revert)
    factory = assemble(
        ("push" + str(n), init_int), 0, "MSTORE",
        n, 32 - n, 0, "CREATE",
        1, "SSTORE", "STOP",
    )
    out = _run_factory(factory)
    b = out.base
    assert bool(np.asarray(b.active)[0]) and not bool(np.asarray(b.error)[0])
    assert not bool(np.asarray(b.acct_used)[0, 3]), "ghost account leaked"
    used = np.asarray(b.st_used)[0]
    keys = np.asarray(b.st_keys)[0]
    vals = np.asarray(b.st_vals)[0]
    acct = np.asarray(b.st_acct)[0]
    entries = {(int(acct[k]), u256.to_int(keys[k])): u256.to_int(vals[k])
               for k in range(used.shape[0]) if used[k]}
    assert entries.get((2, 1)) == 0, "CREATE must push 0 on revert"


def test_create2_keccak_address():
    """CREATE2 addresses follow the EIP-1014 identity (0xff ++ deployer ++
    salt ++ keccak(init)), computed with the device keccak kernel and
    checked against the host reference implementation."""
    from mythril_tpu.ops.keccak import keccak256_host
    from mythril_tpu.core.frontier import contract_address

    salt = 0x1234
    init_int = int.from_bytes(CHILD_INIT_EMPTY, "big")
    n = len(CHILD_INIT_EMPTY)
    factory = assemble(
        ("push" + str(n), init_int), 0, "MSTORE",
        ("push2", salt), n, 32 - n, 0, "CREATE2",   # salt, len, off, value
        1, "SSTORE", "STOP",
    )
    out = _run_factory(factory)
    b = out.base
    assert bool(np.asarray(b.active)[0]) and not bool(np.asarray(b.error)[0])
    assert bool(np.asarray(b.acct_used)[0, 3])
    got = u256.to_int(np.asarray(b.acct_addr)[0, 3])
    deployer = contract_address(0)
    buf = (b"\xff" + deployer.to_bytes(20, "big") + salt.to_bytes(32, "big")
           + keccak256_host(bytes(CHILD_INIT_EMPTY)))
    want = int.from_bytes(keccak256_host(buf)[12:], "big")
    assert got == want, f"CREATE2 address {got:#x} != EIP-1014 {want:#x}"
