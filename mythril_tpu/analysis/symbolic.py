"""SymExecWrapper + AnalysisContext: wire the engine to the modules.

Reference: ``mythril/analysis/symbolic.py`` (⚠unv) — ``SymExecWrapper``
builds the LASER VM with strategy/plugins/modules and runs it. Here it
builds the corpus + frontier, runs ``sym_run`` (one jitted call — the
whole exploration), and exposes an :class:`AnalysisContext` that modules
consume batched.
"""

from __future__ import annotations

import hashlib
import logging
from operator import attrgetter
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import DEFAULT_LIMITS, LimitsConfig
from ..core import Corpus, make_env
from ..core.frontier import ATTACKER_ADDRESS, CAP_TRAPS, TRAP_NAMES
from ..disassembler import ContractImage
from ..disassembler.opcodes import STACK_IN, STACK_OUT
from ..obs import metrics as obs_metrics
from ..obs.device import HostLeaves, fetch, phase_timer, tally
from ..obs import trace as obs_trace
from ..smt.eval import Assignment
from ..smt.solver import solve_tape
from ..smt.tape import HostNode, HostTape, extract_tape, intern_node
from ..symbolic import SymSpec, between_txs, make_sym_frontier, sym_run
from ..ops.keccak import keccak256_host_int
from ..symbolic.state import MEM_EXACT
from ..symbolic.engine import (SEAM_STORAGE, hold_carried,
                               plan_seam_admission, plan_waiting,
                               pool_stuck, rebalance_parked,
                               relieve_starved, starved_lanes)

log = logging.getLogger(__name__)

#: what ends a straight-line block: JUMPDEST, JUMP, JUMPI and the halts
_BLOCK_ENDS = (0x5B, 0x56, 0x57, 0x00, 0xF3, 0xFD, 0xFE, 0xFF)


def guard_slots(image: ContractImage, pc: int,
                caller: Optional[int] = None) -> frozenset:
    """The storage slots of the guard that a path failed at, as far as
    the code names them. ``pc`` is where the path ended (a ``REVERT`` or
    ``INVALID``, or just past it); the guard is the ``JUMPI`` it fell
    through, as solc lays a ``require`` out (the branch, then the revert
    with its reason), and its slots are the ``PUSH k; SLOAD`` pairs of
    the block that ends in that ``JUMPI``, and, where the sender's
    address is concrete (``caller``), the ``mapping[msg.sender]`` reads
    there: ``CALLER .. MSTORE; PUSH k .. MSTORE; .. SHA3; SLOAD`` is the
    slot ``keccak(caller . k)`` (:func:`_hashed_slots`). Empty where the
    code has another shape: such a path tested no slot that the seam's
    admission step can name."""
    code = image.code[:image.code_len].tobytes()
    starts = np.flatnonzero(image.is_code[:image.code_len])
    at = int(np.searchsorted(starts, pc, side="right")) - 1
    # the path's last instruction, then back over the revert's own
    # block (a reason string's stores) to the JUMPI it fell through
    if at > 0 and code[starts[at]] not in (0xFD, 0xFE):
        at -= 1
    if at < 0 or code[starts[at]] not in (0xFD, 0xFE):
        return frozenset()      # it ended elsewhere: a trap, not a guard
    while at >= 0 and code[starts[at]] != 0x57:
        if code[starts[at]] in (0x5B, 0x56, 0x00, 0xF3, 0xFF):
            return frozenset()
        at -= 1
    slots = set()
    for j in range(at - 1, 0, -1):
        op, before = code[starts[j]], code[starts[j - 1]]
        if op in _BLOCK_ENDS:
            break
        if op == 0x54 and 0x5F <= before <= 0x7F:
            slots.add(int.from_bytes(
                code[starts[j - 1] + 1:starts[j]], "big"))
    if caller is not None:
        first = at
        while first > 0 and code[starts[first - 1]] not in _BLOCK_ENDS:
            first -= 1
        slots |= _hashed_slots(code, starts[first:at + 1], caller)
    return frozenset(slots)


def _hashed_slots(code: bytes, starts, caller: int) -> set:
    """The ``SLOAD`` keys of one straight-line block that are a hash of
    constants and the sender: the block run over a stack and a memory of
    known words (``PUSH``, ``CALLER``, ``DUP``, ``SWAP``, ``AND``,
    ``MSTORE``, ``SHA3``; what any other instruction leaves is
    unknown). ``starts``: its instructions, and the one that ends it."""
    stack: list = []
    mem: dict = {}
    hashed, slots = set(), set()

    def pop():
        return stack.pop() if stack else None

    for pos, end in zip(starts[:-1], starts[1:]):
        op = code[pos]
        if 0x5F <= op <= 0x7F:
            stack.append(int.from_bytes(code[pos + 1:end], "big"))
        elif op == 0x33:
            stack.append(caller)
        elif 0x80 <= op <= 0x9F:
            n = op - 0x7F if op < 0x90 else op - 0x8E
            stack[:0] = [None] * (n - len(stack))
            if op < 0x90:
                stack.append(stack[-n])
            else:
                stack[-1], stack[-n] = stack[-n], stack[-1]
        elif op == 0x52:
            off, word = pop(), pop()
            if off is None:
                mem.clear()
            else:
                mem[off] = word
        elif op == 0x16:
            a, b = pop(), pop()
            stack.append(None if a is None or b is None else a & b)
        elif op == 0x20:
            off, size = pop(), pop()
            words = ([mem.get(off + k) for k in range(0, size, 32)]
                     if off is not None and size in (32, 64) else [None])
            if None in words:
                stack.append(None)
            else:
                stack.append(keccak256_host_int(b"".join(
                    w.to_bytes(32, "big") for w in words)))
                hashed.add(stack[-1])
        elif op == 0x54:
            key = pop()
            if key in hashed:
                slots.add(key)
            stack.append(None)
        else:
            del stack[len(stack) - min(len(stack), int(STACK_IN[op])):]
            stack.extend([None] * int(STACK_OUT[op]))
    return slots


@dataclass
class AnalysisContext:
    """Batched view of one finished exploration, handed to modules."""

    sf: object               # final SymFrontier
    corpus: Corpus
    limits: LimitsConfig
    contract_names: List[str]
    solver_iters: int = 400
    solver_timeout: Optional[float] = None  # seconds per query (None = off)
    # lanes newly errored during THIS transaction, per trap name (filled by
    # SymExecWrapper; None for standalone contexts, where coverage falls
    # back to reading the snapshot directly)
    trap_counts: Optional[Dict[str, int]] = None
    # exploration of this tx stopped on the wall-clock deadline, not
    # quiescence (reference: --execution-timeout degrade, SURVEY §5.3)
    timed_out: bool = False
    _tapes: Dict[int, HostTape] = field(default_factory=dict)
    _tape_idx: Dict[int, dict] = field(default_factory=dict, repr=False)
    _leaves: HostLeaves = field(init=False, repr=False)

    def __post_init__(self):
        self._leaves = HostLeaves(self.sf)

    def host(self, name: str) -> np.ndarray:
        """Leaf ``name`` of the frontier (``"n_arith"``,
        ``"base.active"``) as a NumPy array: copied whole from the
        device on the first request, the same read-only array on every
        later one. Every per-lane or per-event index belongs on this
        copy: an index of the device leaf dispatches a program, which
        queues behind the ``sym_run`` call of the next batch's device
        phase (PERF.md §5 item 2)."""
        return self._leaves(name)

    def lanes(self, include_errors: bool = False,
              include_reverted: bool = False) -> np.ndarray:
        """Lane indices that hold surviving paths. Exceptional halts are
        discarded like the reference's VmException states; reverted paths
        are excluded by default — a reverting transaction has no effect,
        so predicates witnessed only on a revert path (e.g. the guard
        branch of a SafeMath add) are not findings. The Exceptions module
        opts into error lanes explicitly."""
        keep = self.host("base.active").copy()
        if not include_errors:
            keep &= ~self.host("base.error")
        if not include_reverted:
            keep &= ~self.host("base.reverted")
        return np.where(keep)[0]

    def tape(self, lane: int) -> HostTape:
        if lane not in self._tapes:
            self._tapes[lane] = extract_tape(self.sf, lane,
                                             cache=self._leaves)
        return self._tapes[lane]

    def tape_index(self, lane: int) -> dict:
        """Cached ``node_index`` of the lane's base tape. Callers that
        intern extra nodes must COPY it (``dict(...)``) first — the cached
        index must keep describing the unmutated base tape."""
        if lane not in self._tape_idx:
            from ..smt.tape import node_index

            self._tape_idx[lane] = node_index(self.tape(lane).nodes)
        return self._tape_idx[lane]

    def solve(self, lane: int, extra_constraints=(),
              extra_nodes=()) -> Optional[Assignment]:
        """Witness for the lane's path condition + extra (node, sign)
        constraints. ``extra_nodes`` are INTERNED onto the tape (callers
        still address them as if appended at ``len(tape.nodes)+k`` —
        constraint ids in that range are remapped): a predicate node the
        path already carries shares its id, so an already-asserted
        opposite sign becomes a provable polarity conflict (unsat)
        instead of an exhausted witness search (unknown)."""
        from ..symbolic.ops import SymOp

        base = self.tape(lane)
        nodes = list(base.nodes)
        idx = dict(self.tape_index(lane))
        n0 = len(nodes)
        remap = []
        for n in extra_nodes:
            # an extra node may reference an earlier extra node by its
            # pre-intern (positional) id — but ONLY ops whose operands
            # ARE node ids get remapped: FREE carries (kind, index) and
            # CONST carries payload, either of which can numerically
            # exceed n0 without being a reference
            a, b = n.a, n.b
            if n.op not in (int(SymOp.FREE), int(SymOp.CONST)):
                a = remap[a - n0] if a >= n0 else a
                b = remap[b - n0] if b >= n0 else b
            remap.append(intern_node(nodes, HostNode(n.op, a, b, n.imm), idx))
        cons = list(base.constraints) + [
            (remap[i - n0] if i >= n0 else i, s)
            for i, s in extra_constraints
        ]
        t = HostTape(nodes=nodes, constraints=cons)
        return solve_tape(t, max_iters=self.solver_iters,
                          max_time=self.solver_timeout)

    def contract_of(self, lane: int) -> int:
        return int(self.host("base.contract_id")[lane])

    def cid_name(self, cid: int) -> str:
        """Display name for a recorded contract id (modules should prefer a
        per-event ``*_cid`` over ``contract_of``: an event recorded inside a
        callee frame belongs to the callee's code, not the lane's home
        contract)."""
        if 0 <= cid < len(self.contract_names):
            return self.contract_names[cid]
        return f"contract_{cid}"

    def contract_name(self, lane: int) -> str:
        return self.cid_name(self.contract_of(lane))

    def tx_sequence(self, asn: Assignment) -> List[dict]:
        """Render a witness as the reference-style concrete tx list (one
        entry per symbolic transaction). All `calldatasize` bytes are
        emitted — trimming zeros would change CALLDATASIZE on replay and
        can flip size-check branches."""
        from ..core.frontier import CREATOR_ADDRESS
        from ..symbolic.ops import FreeKind

        origin = asn.scalars.get((int(FreeKind.ORIGIN), 0), asn.caller)
        out = []
        for t in asn.txs:
            size = t.calldatasize if t.calldatasize is not None else len(t.calldata)
            size = max(0, min(size, len(t.calldata)))
            out.append({
                "input": "0x" + bytes(t.calldata[:size]).hex(),
                "value": hex(t.callvalue),
                "origin": hex(origin),
                "caller": hex(t.caller),
            })
        if out and getattr(self.corpus, "deploys", None) is not None:
            # the run deployed: step 0 is the creation transaction, sent
            # by the creator (its input: the constructor's arguments)
            out[0].update(origin=hex(CREATOR_ADDRESS),
                          caller=hex(CREATOR_ADDRESS))
        return out


def _count_traps(err_code: np.ndarray) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for code, name in TRAP_NAMES.items():
        n = int((err_code == code).sum())
        if n:
            out[name] = n
    return out


def coverage_summary(tx_contexts) -> dict:
    """Lost-coverage accounting over a run's per-tx context snapshots.

    The reference silently discards VmException states; here every loss
    channel is counted so parity claims are auditable: lanes errored per trap cause, forks dropped to capacity,
    saturated event logs, and propagation kills.
    """
    final = tx_contexts[-1]
    limits = final.limits
    errored: dict = {}
    if all(c.trap_counts is not None for c in tx_contexts):
        # per-tx tallies (exact even when expand_forks recycled an errored
        # lane's slot in a later transaction)
        for c in tx_contexts:
            for name, n in c.trap_counts.items():
                errored[name] = errored.get(name, 0) + n
    else:
        errored = _count_traps(final.host("base.err_code"))
    cap_names = {TRAP_NAMES[c] for c in CAP_TRAPS}
    cap_lost = sum(n for name, n in errored.items() if name in cap_names)
    # event logs reset per tx, so saturation counts sum across snapshots
    sat_calls = sum(
        int((c.host("n_calls") > limits.call_log).sum())
        for c in tx_contexts
    )
    sat_arith = sum(
        int((c.host("n_arith") > limits.arith_log).sum())
        for c in tx_contexts
    )
    active = final.host("base.active")
    error = final.host("base.error")
    out = {
        "lanes": int(active.shape[0]),
        "surviving_paths": int((active & ~error).sum()),
        "lanes_errored": errored,
        "lanes_lost_to_caps": cap_lost,
        "dropped_forks": int(final.host("dropped_total")),
        "killed_infeasible": int(final.host("killed_total")),
        "saturated_call_logs": sat_calls,
        "saturated_arith_logs": sat_arith,
    }
    if any(getattr(c, "timed_out", False) for c in tx_contexts):
        still_running = int(
            (active & ~final.host("base.halted") & ~error).sum())
        out["deadline_expired_running"] = still_running
    return out


@dataclass
class BatchBuild:
    """What :func:`build_batch` hands a :class:`SymExecWrapper`: the
    batch packed for the device, before anything has run. A wrapper
    takes it over whole (it grows ``images`` and ``names`` when it loads
    a callee mid-run), so a bundle starts one exploration."""

    images: list            # creation images [0, n_creation), then runtime
    names: List[str]        # one an image
    n_creation: int
    corpus: Corpus
    visited: np.ndarray     # bool[len(images), max_code], all False
    known_addrs: set
    systems: Dict[str, List[int]]   # member indices by linked system
    sf: object              # the seeded SymFrontier
    env: object
    # where the build ran, for whoever lays its spans beside the phase
    # that took it: the thread and the clock around the three stages
    tid: int = 0
    mono: float = 0.0
    dur: float = 0.0


def build_batch(
    bytecodes: Sequence[bytes],
    contract_names: Optional[Sequence[str]] = None,
    contract_addrs: Optional[Sequence[int]] = None,
    limits: LimitsConfig = DEFAULT_LIMITS,
    lanes_per_contract: int = 64,
    creation_bytecodes: Optional[Sequence[bytes]] = None,
    spill: bool = True,
    enable_iprof: bool = False,
    links: Optional[Sequence[Optional[dict]]] = None,
) -> BatchBuild:
    """Pack a batch for the device: the images, the corpus (a host
    Keccak-256 an image), the seeded frontier and the environment, as
    three ``batch_build`` spans (``stage`` = ``images`` / ``corpus`` /
    ``frontier``) on the calling thread. The arguments are
    :class:`SymExecWrapper`'s of the same names. Nothing here depends on
    another batch or reads the device, so a caller that knows its next
    batch may build it on another thread while this one explores (the
    pipelined campaign does) and hand the bundle to the wrapper as
    ``build``; a wrapper without one calls this inline."""
    import threading
    import time as _time

    import jax
    import jax.numpy as jnp

    from ..core.frontier import CREATOR_ADDRESS, contract_address

    t0 = _time.monotonic()
    with phase_timer("batch_build", stage="images") as span:
        runtime_imgs = [ContractImage.from_bytecode(c, limits.max_code)
                        for c in bytecodes]
        C = len(runtime_imgs)
        names = list(contract_names
                     or [f"contract_{i}" for i in range(C)])
        with_creation = creation_bytecodes is not None
        if with_creation:
            assert len(creation_bytecodes) == C
            creation_imgs = [
                ContractImage.from_bytecode(c, limits.max_code)
                for c in creation_bytecodes]
            # corpus layout: creation images [0, C), runtime
            # images [C, 2C)
            images = creation_imgs + runtime_imgs
            names = [f"{n} (constructor)" for n in names] + names
        else:
            images = runtime_imgs
        span.attrs.update(
            images=len(images),
            code_bytes=sum(map(len, bytecodes)) + sum(
                map(len, creation_bytecodes or ())))
    n_creation = C if with_creation else 0
    with phase_timer("batch_build", stage="corpus"):
        corpus = Corpus.from_images(images, n_creation)
    visited = np.zeros((len(images), limits.max_code), dtype=bool)
    known_addrs = set(
        contract_addrs if contract_addrs is not None
        else [contract_address(i) for i in range(C)])
    # linked systems: member indices by system, in the manifest's
    # order (the order the records came in), and each contract's own
    systems = None
    by_system: Dict[str, List[int]] = {}
    if links is not None and any(links):
        assert len(links) == C
        for i, ln in enumerate(links):
            if ln:
                by_system.setdefault(ln["system"], []).append(i)
        systems = [by_system[ln["system"]] if ln else None
                   for ln in links]
        contract_addrs = [
            ln["address"] if ln else contract_address(i)
            for i, ln in enumerate(links)]
        known_addrs = set(contract_addrs)
    with phase_timer("batch_build", stage="frontier") as span:
        P = C * lanes_per_contract
        cid0 = np.repeat(np.arange(C, dtype=np.int32),
                         lanes_per_contract)
        active = np.zeros(P, dtype=bool)
        active[::lanes_per_contract] = True  # one seed lane a contract
        sf = make_sym_frontier(
            P, limits, contract_id=cid0, active=active, n_contracts=C,
            contract_addrs=(list(contract_addrs)
                            if contract_addrs is not None else None),
            caller=(CREATOR_ADDRESS if with_creation
                    else ATTACKER_ADDRESS),
            **({} if systems is None else {"systems": systems}),
        )
        if with_creation:
            # account table resolves calls/extcode against RUNTIME
            # images
            b = sf.base
            sf = sf.replace(base=b.replace(
                acct_code=jnp.where(b.acct_code >= 0, b.acct_code + C,
                                    b.acct_code),
            ))
        if enable_iprof:
            # per-lane opcode histograms ride the frontier
            sf = sf.replace(base=sf.base.attach_iprof())
        if spill:
            # the scalar every ``defer_starved`` call writes, there
            # from the first call on: one frontier structure, one
            # program
            sf = sf.replace(fixpoint=jnp.zeros((), dtype=bool))
        env = make_env(P)
        # from shapes and dtypes alone: no device sync
        frontier_bytes = sum(x.nbytes for x in jax.tree.leaves(sf))
        obs_metrics.REGISTRY.gauge(
            "frontier_bytes",
            help="bytes of the symbolic frontier's device "
                 "leaves").set(frontier_bytes)
        span.attrs["frontier_bytes"] = int(frontier_bytes)
    return BatchBuild(
        images=images, names=names, n_creation=n_creation, corpus=corpus,
        visited=visited, known_addrs=known_addrs, systems=by_system,
        sf=sf, env=env, tid=threading.get_ident(), mono=t0,
        dur=_time.monotonic() - t0)


class SymExecWrapper:
    """Build + run the symbolic exploration for a batch of contracts.

    ``creation_bytecodes`` (reference: ``execute_contract_creation`` then
    message calls, ``mythril/laser/ethereum/transaction/symbolic.py``
    ⚠unv) enables the creation transaction: each lane first runs its
    contract's CREATION bytecode with the CREATOR as caller, persists the
    constructor's storage writes, then switches to the runtime image for
    the ``transaction_count`` attacker message calls. Constructor
    arguments (appended to init code in real deployments) read as zero
    bytes past the compiled length; the RETURN payload is not re-derived —
    the caller supplies the runtime image, as solc artifacts do (so the
    deploy epilogue's copy of it is cut at the memory model's end and
    does not trap: ``Corpus.deploys``).

    Telemetry: the ``superstep`` / ``drain`` / ``harvest`` spans carry
    ``tx_kind`` (``creation`` | ``message``), a ``tx_seam`` span times each
    handoff to the next transaction (``carried``: the lanes that go on),
    and ``engine_paths_total{tx}`` / ``engine_dropped_forks_total{tx}``
    count, per transaction index, the paths that survived it and the
    forks it lost to the budget (the ``harvest`` span carries the two
    as ``paths`` / ``dropped``). Every ``superstep`` span says whether
    its own seam found the lane pool ``stuck``, and a transaction's last
    one what ``ended`` it (``fixpoint`` | ``budget`` | ``quiescent`` |
    ``deadline``); a call that left its loop at the pool's fixpoint
    (``engine.pool_fixpoint``, the frontier's ``fixpoint`` scalar) says
    ``ended_in="fixpoint"`` and counts in
    ``engine_inloop_fixpoint_exits_total{tx}``;
    ``engine_fixpoint_ends_total{tx}`` counts the transactions ended
    there and ``engine_calls_skipped_total{tx}`` the ``sym_run`` calls
    their budget still allowed. All of it rides the reads the harvest
    and the seam make anyway.

    The seam between two calls also decides which of the end states
    that passed the pruners start the next one (``engine.
    plan_seam_admission``: inert unless a contract's carried states
    exceed its share of the lane pool and one of them overwrote what a
    failed path had read). Its ``tx_seam`` span says ``passed`` =
    ``admitted`` + ``merged`` + ``deferred`` + ``dropped``,
    ``engine_seam_states_total{tx,fate}`` counts the last fates, a
    ``superstep`` span after waiting lanes started carries ``round``,
    and the ``harvest`` span has ``paths`` / ``dropped`` per contract.

    ``links`` (a campaign batch that holds linked systems; one entry a
    contract: None, or ``{"system": name, "address": int}`` as the
    corpus directory's manifest gave it, members of one system in the
    manifest's order) puts every member of a system into the account
    table of that system's lanes at its manifest address
    (``make_frontier``'s ``systems``), and the creation transaction's
    seam joins the end states of a system's constructors into ONE world
    from which every member's message calls start
    (:meth:`_join_worlds`, a ``system_world`` span inside that
    ``tx_seam``). Without ``links`` nothing of this runs.

    ``build`` (a :class:`BatchBuild`: what :func:`build_batch` made of
    these same arguments, on whichever thread) is the batch already
    packed: the constructor goes straight to the exploration. Without it
    the constructor builds the batch itself, on its own thread, as the
    first three ``batch_build`` spans of its lead-in.
    """

    def __init__(
        self,
        bytecodes: Sequence[bytes],
        contract_names: Optional[Sequence[str]] = None,
        contract_addrs: Optional[Sequence[int]] = None,
        limits: LimitsConfig = DEFAULT_LIMITS,
        spec: SymSpec = SymSpec(),
        lanes_per_contract: int = 64,
        max_steps: int = 512,
        solver_iters: int = 400,
        solver_timeout: Optional[float] = None,
        transaction_count: int = 1,
        creation_bytecodes: Optional[Sequence[bytes]] = None,
        execution_timeout: Optional[float] = None,
        create_timeout: Optional[float] = None,
        checkpoint_dir: Optional[str] = None,
        deadline_chunk_steps: int = 64,
        plugins: Sequence = (),
        strategy: str = "bfs",
        spill: bool = True,
        fork_block: int = 0,
        migrate_every: int = 8,
        enable_iprof: bool = False,
        dyn_loader=None,
        dynld_limit: int = 4,
        warm_shapes: Optional[set] = None,
        on_first_call: Optional[Callable[[], None]] = None,
        links: Optional[Sequence[Optional[dict]]] = None,
        build: Optional[BatchBuild] = None,
    ):
        import time as _time

        from .. import compile_cache
        from ..plugin.loader import LaserPluginLoader

        # every process that drives the engine does so through here
        compile_cache.enable()

        # cross-wrapper warm-shape sharing: sym_run is one module-level
        # jit, so its XLA cache is PROCESS-wide — a second wrapper of
        # the same engine shape replays cached executables. A caller
        # running many same-shape batches (CorpusCampaign, the serve
        # scheduler) passes one set per shape class so the cold/compile
        # accounting (engine_compiles_total, cold= span attr, deadline
        # pacing's first-sample skip) stops re-counting warm shapes.
        # Mutated in place by explore(); None keeps per-instance sets.
        if warm_shapes is not None:
            self._warm_chunk_shapes = warm_shapes

        self.plugin_loader = LaserPluginLoader()
        for p in plugins:
            self.plugin_loader.load(p)
        self.limits = limits
        self.spec = spec
        self.max_steps = max_steps
        # reference strategy names -> fork-admission policies (the
        # frontier is breadth-first by construction; the policy decides
        # which forks to ADMIT when slots run short, SURVEY §1 row 7)
        self.fork_policy = {"bfs": "fifo", "dfs": "deep",
                            "shallow": "shallow", "deep": "deep",
                            "fifo": "fifo",
                            "naive-random": "random",
                            "random": "random",
                            "weighted-random": "weighted",
                            "weighted": "weighted",
                            "coverage": "coverage",
                            "beam": "beam"}[strategy]
        self.timed_out = False
        self.checkpoint_dir = checkpoint_dir
        # spill machinery (SURVEY §5.7): starved forks
        # DEFER instead of dropping (the lane parks on its branch and
        # retries), and the host re-seeds persistently parked lanes into
        # other blocks' free slots between chunks
        self.spill = spill
        self.fork_block = fork_block
        # in-jit cross-block migration (SURVEY §5.8 ICI tier): only
        # meaningful when fork compaction is blocked (fork_block > 0) and
        # spill parks starved lanes; a no-op otherwise (and inside
        # sym_run when G == 1). The host-seam rebalance stays as the
        # chunk-boundary tier for lanes migration could not place.
        self.migrate_every = migrate_every if spill else 0
        self._parked_end = 0
        self._rebalanced = 0
        self._chunk = max(1, deadline_chunk_steps)
        self._deadline_at = (
            None if execution_timeout is None
            else _time.monotonic() + execution_timeout
        )
        # the lead-in: what this thread does before its first
        # ``sym_run`` call, while the device waits. Without a finished
        # ``build`` the batch is built here, as three ``batch_build``
        # spans; the fourth runs from here to the first call
        # (docs/observability.md)
        if build is None:
            build = build_batch(
                bytecodes, contract_names=contract_names,
                contract_addrs=contract_addrs, limits=limits,
                lanes_per_contract=lanes_per_contract,
                creation_bytecodes=creation_bytecodes, spill=spill,
                enable_iprof=enable_iprof, links=links)
        C = len(bytecodes)
        assert len(build.images) - build.n_creation == C
        names = build.names
        with_creation = build.n_creation > 0
        runtime_base = build.n_creation
        self.images = build.images
        self._n_creation = build.n_creation
        self._member_names = names[-C:]
        self.corpus = build.corpus
        self._visited = build.visited
        # mid-execution dynamic loading (reference: DynLoader.dynld
        # resolving CALL targets as execution reaches them ⚠unv, SURVEY
        # §3.4): the corpus is a static jit shape, so loading happens at
        # the BETWEEN-TX host seam — tx N's concrete-but-unknown call
        # targets are fetched, appended to the corpus, and registered in
        # the account table, and tx N+1's calls to them resolve into
        # real code (load-on-first-touch, one tx later; the pre-pass in
        # utils/loader.py prefetch_callees covers the static-reference
        # case up front). None = offline, no attempt.
        self.dyn_loader = dyn_loader
        self.dynld_limit = dynld_limit
        self._known_addrs = build.known_addrs
        self._dynld_miss: set = set()
        self._dynld_fails: Dict[int, int] = {}  # transient-failure counts
        self.dynld_loaded: List[int] = []  # addresses loaded mid-run
        self._dynld_sha: List[str] = []    # sha256 of each loaded image
        # linked systems: member indices by system, in the manifest's
        # order (the order the records came in)
        self._systems = build.systems
        self.failed_deployments: List[dict] = []
        sf, env = build.sf, build.env
        # instruction profiler (reference: --enable-iprof ⚠unv,
        # SURVEY §5.1): per-lane opcode histograms ride the frontier;
        # the host harvests + zeroes them at each tx boundary so slot
        # recycling can't lose or double-count a retired lane's rows
        self.enable_iprof = enable_iprof
        self._iprof = np.zeros(256, dtype=np.int64)
        # host mirror of the frontier's run-total superstep counter
        # (a chunk's count is the difference across its sym_run call)
        self._steps_seen = 0
        self._copy_seen = 0     # the same for ``copy_steps``
        # from here to the first ``sym_run`` call: the plugins, the
        # read of ``base.active``, ``on_tx_start``
        self._lead_in = phase_timer("batch_build", stage="start").start()
        # fired once, when the first ``sym_run`` call is enqueued: from
        # there on this thread blocks in reads with the interpreter lock
        # released (the pipelined campaign starts the previous batch's
        # host phase here)
        self._on_first_call = on_first_call
        # (mono start, mono end) of every ``sym_run`` call, by the
        # ``superstep`` timer's own two clock reads: what the campaign
        # measures a host phase's hidden seconds against
        self.sym_run_calls: List[Tuple[float, float]] = []

        # multi-tx outer loop (reference: execute_transactions iterating
        # open_states ⚠unv SURVEY.md §3.2): snapshot a context after each
        # tx so detection sees lanes that between_txs retires
        self.tx_contexts: List[AnalysisContext] = []

        DRAIN_ROUNDS = 4

        def explore(sf):
            """One transaction's exploration, chunked when a wall-clock
            deadline is set (reference: --execution-timeout checked in the
            exec loop, SURVEY §5.3). Chunks re-enter the same compiled
            sym_run; between chunks the host checks the clock and may
            checkpoint. With spill the transaction also ends at the seam
            of a call that left its loop at the lane pool's fixpoint
            (``engine.pool_fixpoint``), if the seam's scheduling step
            leaves the frontier as that call handed it over."""
            try:
                sf, ended = walk(sf)
                if held:
                    held[-1].attrs["ended"] = ended
                return self._close_waiting(sf)
            finally:
                seal()

        # the last call's ``superstep`` span: timed as ever, emitted
        # once its seam has said what it found (``stuck``) and, for a
        # transaction's last call, what ended it
        held: list = []

        def seal():
            while held:
                held.pop().emit()

        def walk(sf):
            import time as _time

            warm_shapes: set = getattr(self, "_warm_chunk_shapes", set())
            self._warm_chunk_shapes = warm_shapes
            q = max(1, self._chunk // 4)

            def chunk_at(done):
                # max_steps is a static jit arg: every distinct n is a
                # full-engine XLA compile. Quantize tails to the small
                # chunk so at most THREE shapes exist per run (chunk,
                # chunk//4, and one sub-q remainder).
                n = min(self._chunk, max_steps - done)
                return q if q < n < self._chunk else n

            def chunks_left(done):
                """The chunk calls the step budget still allows."""
                k = 0
                while done < max_steps:
                    done += chunk_at(done)
                    k += 1
                return k

            def fixpoint_end(skipped):
                reg = obs_metrics.REGISTRY
                labels = {"tx": str(self._cur_tx)}
                reg.counter(
                    "engine_fixpoint_ends_total",
                    help="transactions ended at the seam of a call that "
                         "left its loop at the lane pool's fixpoint",
                    labels=labels).inc()
                reg.counter(
                    "engine_calls_skipped_total",
                    help="sym_run calls (chunks and drain rounds) the "
                         "budget of a transaction ended at its pool's "
                         "fixpoint still allowed", labels=labels
                ).inc(skipped)
                if held:
                    held[-1].attrs["skipped"] = skipped

            def superstep(sf, n, shape, also=(), run_kw=None, **attrs):
                """One ``sym_run`` call of at most ``n`` supersteps,
                inside a span that ends when the device does: the call
                only enqueues the program, and the read of its results
                (the coverage bitmap, the run-total step counters and
                the ``also`` leaves of the new frontier, in ONE
                transfer) is what waits for it. ``shape`` keys the
                compiled program in ``warm_shapes``. Returns the
                frontier, the supersteps that ran, the span's seconds,
                whether the call compiled, and the fetched ``also``
                leaves. A call that left its loop at the pool's fixpoint
                (the ``fixpoint`` leaf, where ``also`` reads it) says so
                on its span and is counted."""
                cold = shape not in warm_shapes
                w0 = tally()[1]
                seal()
                if self._round:
                    attrs["round"] = self._round
                self._end_lead_in()
                with obs_trace.timer("superstep", tx=self._cur_tx,
                                     tx_kind=self._tx_kind, steps=n,
                                     cold=cold, stuck=False, **attrs) as sp:
                    sf, vis = sym_run(
                        sf, env, self.corpus, spec, limits,
                        max_steps=n, track_coverage=True,
                        fork_policy=self.fork_policy,
                        fork_block=self.fork_block,
                        **(run_kw or {}))
                    enqueue_s = sp.elapsed
                    self._first_call_enqueued()
                    got = fetch(
                        (vis, sf.steps_total, sf.copy_steps)
                        + tuple(attrgetter(a)(sf) for a in also),
                        ",".join(("visited", "steps_total", "copy_steps",
                                  *also)))
                    steps_run = int(got[1]) - self._steps_seen
                    copy_steps = int(got[2]) - self._copy_seen
                    left_inside = bool(
                        dict(zip(also, got[3:])).get("fixpoint"))
                    if left_inside:
                        sp.attrs["ended_in"] = "fixpoint"
                    sp.attrs.update(
                        steps_run=steps_run,
                        copy_steps=copy_steps,
                        enqueue_s=round(enqueue_s, 6),
                        device_wait_s=round(tally()[1] - w0, 6))
                    # timed to here, emitted by ``seal`` once the
                    # call's seam has said what it found
                    held.append(sp.hold())
                self.sym_run_calls.append((sp.t_mono, sp.t_mono + sp.dur))
                self._steps_seen = int(got[1])
                self._copy_seen = int(got[2])
                self._visited |= got[0]
                reg = obs_metrics.REGISTRY
                if left_inside:
                    reg.counter(
                        "engine_inloop_fixpoint_exits_total",
                        help="sym_run calls that left their loop at the "
                             "lane pool's fixpoint, with budget to spare",
                        labels={"tx": str(self._cur_tx)}).inc()
                if cold:
                    warm_shapes.add(shape)
                    reg.counter(
                        "engine_compiles_total",
                        help="distinct chunk shapes compiled").inc()
                reg.counter(
                    "engine_supersteps_total",
                    help="supersteps sym_run's loop ran (quiescence or "
                         "the pool's fixpoint ends a call early)"
                ).inc(steps_run)
                reg.counter(
                    "engine_supersteps_budget_total",
                    help="supersteps the sym_run calls were allowed "
                         "(sum of max_steps)").inc(n)
                reg.counter(
                    "engine_copy_supersteps_total",
                    help="supersteps in which some lane ran a copy "
                         "opcode's handler (dispatch took CLS_COPY's cond)",
                    labels={"tx": str(self._cur_tx)}).inc(copy_steps)
                return sf, steps_run, sp.dur, cold, got[3:]

            # what a seam of the spill machinery reads of the frontier,
            # in the one transfer of its ``superstep`` call
            SEAM = ("base.active", "fork_req", "base.running",
                    "base.home_contract", "fixpoint")

            def rebalance(sf, act_h, freq_h, run_h, home_h, fix_h):
                """A seam's scheduling step: parked lanes move to blocks
                with free lanes; where the whole frontier is full and
                stuck, the contracts it starves are relieved (the
                lanes given up are lost forks, counted with those still
                parked at the end). Returns the frontier and whether the
                pool is stuck for good: the call left its loop at the
                fixpoint (``fix_h``) and the step did nothing, so the
                next call would start from the frontier that one handed
                over."""
                with obs_trace.span("rebalance", tx=self._cur_tx):
                    sf, moved = rebalance_parked(sf, self.fork_block,
                                                 active=act_h,
                                                 fork_req=freq_h)
                    evicted = served = 0
                    if not moved and self._seam_plan is not None:
                        # the lanes the last seam left waiting go first:
                        # they start where there is room and are given
                        # up before any fork of a state that runs
                        sf, served = self._serve_waiting(
                            sf, C, act_h, freq_h, run_h)
                    if not (moved or served):
                        sf, evicted = relieve_starved(
                            sf, C, act_h, freq_h, run_h, home_h)
                        if evicted:
                            self._count_lost(evicted, starved_lanes(
                                C, act_h, freq_h, run_h, home_h), home_h)
                self._rebalanced += moved
                reg = obs_metrics.REGISTRY
                reg.counter(
                    "rebalanced_lanes_total",
                    help="parked lanes re-seeded at host seams").inc(moved)
                reg.counter(
                    "evicted_lanes_total",
                    help="parked lanes given up at a full frontier's "
                         "fixpoint for a contract under its floor"
                ).inc(evicted)
                idle = not (moved or evicted or served)
                if held:
                    held[-1].attrs["stuck"] = idle and pool_stuck(
                        act_h, freq_h, run_h)
                return sf, idle and bool(fix_h)

            if (self._deadline_at is None and self.checkpoint_dir is None
                    and not self.spill):
                # execute + fork fuse inside the jitted superstep loop;
                # the host-visible unit (and the span) is the whole call
                sf, steps_run, *_ = superstep(
                    sf, max_steps, ("whole", max_steps), done=0)
                return sf, ("quiescent" if steps_run < max_steps
                            else "budget")
            steps_done = 0
            sec_per_step = 0.0
            ended, proven = "budget", False
            chunk_kw = dict(defer_starved=self.spill,
                            migrate_every=self.migrate_every)
            telemetry = (obs_metrics.REGISTRY.enabled
                         or obs_trace.active())
            while steps_done < max_steps:
                n = chunk_at(steps_done)
                # deadline granularity: when the
                # remaining budget would not cover a full chunk, fall to
                # the small chunk instead of overshooting by seconds.
                if (self._deadline_at is not None and sec_per_step
                        and n == self._chunk):
                    remaining = self._deadline_at - _time.monotonic()
                    if remaining < sec_per_step * n:
                        n = q
                # ONE device→host transfer per chunk, shared by EVERY
                # seam consumer: the span's end, the step counter, the
                # rebalance planner, the telemetry gauges AND the loop's
                # quiescence check ride the same fetch (each separate
                # read is a blocking sync). A bare run with telemetry
                # off and spill off reads only ``running`` beside the
                # bitmap. With spill the same fetch (the frontier's
                # ``fixpoint`` scalar beside the masks) also says whether
                # the pool is stuck for good, which ends the transaction.
                # (Reusing the pre-rebalance fetch for the quiescence
                # check is exact: rebalance RELOCATES lanes, or retires
                # parked ones for another that goes on waiting — never
                # changing whether any lane is running.)
                seam = (SEAM if self.spill
                        else SEAM[:3] if telemetry else SEAM[2:3])
                sf, steps_run, dur, cold, got = superstep(
                    sf, n, n, also=seam, run_kw=chunk_kw,
                    done=steps_done)
                act_h, freq_h = got[:2] if len(got) >= 3 else (None, None)
                run_h = got[2] if len(got) >= 3 else got[0]
                # a shape's first run pays XLA compilation — not a
                # sample; the rate is the device's (the span ends when
                # the device does) over the supersteps that ran
                if not cold and steps_run:
                    sec_per_step = max(sec_per_step, dur / steps_run)
                steps_done += n
                if self.spill:
                    sf, proven = rebalance(sf, *got)
                self._observe_frontier(sf, active=act_h, fork_req=freq_h)
                self.plugin_loader.fire("on_chunk", sf, steps_done)
                if self.checkpoint_dir is not None:
                    self._save_checkpoint(sf, steps_done)
                if proven:
                    # every further call of this transaction, chunk or
                    # drain round, would hand back this frontier
                    fixpoint_end(chunks_left(steps_done) + DRAIN_ROUNDS)
                    self._count_lost(int((freq_h & act_h).sum()),
                                     freq_h & act_h, got[3])
                    return sf, "fixpoint"
                if not bool(run_h.any()):
                    ended = "quiescent"
                    break
                if (self._deadline_at is not None
                        and _time.monotonic() >= self._deadline_at):
                    self.timed_out = True
                    ended = "deadline"
                    break
            if self.spill:
                # drain phase: lanes still parked at budget end re-raise
                # their forks into slots the rebalance freed — they were
                # admitted late through no fault of their path, so they
                # get bounded extra chunks (reference analog: the work
                # list drains until empty or timeout)
                with obs_trace.timer("drain", tx=self._cur_tx,
                                     tx_kind=self._tx_kind) as drain:
                    # one fetch per drain round, shared with the
                    # rebalance planner and the final parked count
                    # (the seam before it has judged the last call's
                    # ``fixpoint``: this frontier is no call's yet)
                    got = (*fetch(tuple(attrgetter(a)(sf) for a in SEAM[:4]),
                                  ",".join(SEAM[:4])), False)
                    parked = got[1] & got[0]
                    for left in range(DRAIN_ROUNDS, 0, -1):
                        # a round runs for a parked lane, or for a lane
                        # the last seam left waiting that now has room
                        if not (parked.any() or (
                                self._seam_plan is not None and plan_waiting(
                                    C, self._seam_plan, *got[:3])[1])):
                            break
                        if self.timed_out or (
                                self._deadline_at is not None
                                and _time.monotonic() >= self._deadline_at):
                            ended = "deadline"
                            break  # the drain respects the wall clock too
                        sf, proven = rebalance(sf, *got)
                        if proven:
                            fixpoint_end(left)
                            ended = "fixpoint"
                            break
                        # the chunk loop's program (same static args)
                        sf, _, _, _, got = superstep(
                            sf, self._chunk, self._chunk, also=SEAM,
                            run_kw=chunk_kw, drain=True)
                        parked = got[1] & got[0]
                        # no scheduling step follows the last round
                        held[-1].attrs["stuck"] = pool_stuck(*got[:3])
                    if self._round:
                        drain.attrs["round"] = self._round
                # forks still parked after draining are lost coverage —
                # count them in the drop channel for honesty (reusing
                # the drain loop's final fetch — no extra sync)
                self._count_lost(int(parked.sum()), parked, got[3])
            return sf, ended

        def run_one_tx(sf, is_last: bool, handoff_kw=None):
            self.plugin_loader.fire("on_tx_start", self._cur_tx, sf)
            sf = explore(sf)
            # harvest: pull per-tx results (traps, iprof rows) off the
            # device and snapshot the context modules will consume
            with obs_trace.timer("harvest", tx=self._cur_tx,
                                 tx_kind=self._tx_kind) as harvest:
                # err_code is zeroed by between_txs, so every nonzero
                # code here is a loss from THIS transaction. The
                # per-transaction path and dropped-fork counts ride
                # the same transfer
                # and per contract, with what the seam's admission
                # step needs of the frontier as the transaction left it
                (err_h, act_h, bad_h, dropped_h, rev_h, home_h,
                 forks_h, floor_h, cd_h, hop_h) = fetch(
                    (sf.base.err_code, sf.base.active, sf.base.error,
                     sf.dropped_total, sf.base.reverted,
                     sf.base.home_contract, sf.dropped_forks,
                     sf.mem_floor, sf.cd_reads, sf.hop_stats),
                    "base.err_code,base.active,base.error,dropped_total,"
                    "base.reverted,base.home_contract,dropped_forks,"
                    "mem_floor,cd_reads,hop_stats")
                trap_counts = _count_traps(err_h)
                paths, lost = self._count_tx(
                    int((act_h & ~bad_h).sum()), int(dropped_h))
                of = home_h % C     # creation | runtime image
                kept = act_h & ~bad_h & ~rev_h
                harvest.attrs.update(
                    **self._count_dynamic(floor_h[kept], cd_h[kept],
                                          trap_counts.get("loop_bound", 0)),
                    **self._count_hops(hop_h, act_h & ~bad_h, of, C),
                    paths=paths, dropped=lost,
                    paths_by_contract=np.bincount(
                        of[act_h & ~bad_h], minlength=C).tolist(),
                    dropped_by_contract=(self._lost_by + np.bincount(
                        of, weights=forks_h, minlength=C).astype(
                            np.int64)).tolist())
                self._lost_by[:] = 0
                ctx = AnalysisContext(
                    sf=sf, corpus=self.corpus, limits=limits,
                    contract_names=names, solver_iters=solver_iters,
                    solver_timeout=solver_timeout,
                    trap_counts=trap_counts, timed_out=self.timed_out,
                )
                self.tx_contexts.append(ctx)
                if self.enable_iprof:
                    import jax.numpy as jnp
                    self._iprof += fetch(sf.base.op_hist, "base.op_hist").sum(
                        axis=0, dtype=np.int64)
                    repl = {"op_hist": jnp.zeros_like(sf.base.op_hist)}
                    if sf.base.op_resid is not None:
                        # residual sidecar: retired lanes' counts
                        # orphaned by slot recycling / lane movement
                        # since the last harvest (per-lane rows stay
                        # attributable)
                        self._iprof += fetch(
                            sf.base.op_resid,
                            "base.op_resid").astype(np.int64)
                        repl["op_resid"] = jnp.zeros_like(sf.base.op_resid)
                    sf = sf.replace(base=sf.base.replace(**repl))
            self.plugin_loader.fire("on_tx_end", ctx)
            if not is_last:
                if self.dyn_loader is not None:
                    # must run BEFORE between_txs: it reads this tx's
                    # call log, which the handoff clears
                    sf = self._dynld_between_txs(sf, names)
                kw = dict(handoff_kw or {})
                # with a creation tx, the first MESSAGE call is tx_id 1 —
                # the dependency pruner must not retire its paths
                kw.setdefault("first_message_tx", 1 if with_creation else 0)
                with obs_trace.timer("tx_seam", tx=self._cur_tx,
                                     tx_kind=self._tx_kind) as seam:
                    ended = sf
                    sf = between_txs(sf, **kw)
                    # the read the next transaction starts with (is
                    # anything left to extend?), made here so that the
                    # span ends when the handoff has run on the device
                    passed = fetch(sf.base.active, "base.active")
                    if self._systems and self._tx_kind == "creation":
                        sf, passed = self._join_worlds(sf, C, passed)
                    sf, self._carried, fates = self._admit_carried(
                        ended, sf, C, passed, act_h,
                        act_h & (rev_h | bad_h), home_h)
                    seam.attrs.update(carried=int(self._carried.sum()),
                                      passed=int(passed.sum()), **fates)
            return sf

        self._cur_tx = 0
        self._tx_kind = "creation" if with_creation else "message"
        self._dropped_seen = 0
        self._carried = None    # ``active`` as the last seam left it
        # the seam's admission step (``engine.plan_seam_admission``):
        # the plan whose queue still waits, the states each contract's
        # call began with, the times waiting lanes were started since,
        # and this transaction's lost forks per contract
        self._seam_plan = None
        self._started = np.ones(C, dtype=np.int64)
        self._round = 0
        self._lost_by = np.zeros(C, dtype=np.int64)
        try:
            self.plugin_loader.fire("initialize", self)
            if with_creation:
                # --create-timeout (reference: a separate wall-clock
                # budget for the creation transaction ⚠unv): narrow the
                # deadline for the constructor run only, then restore —
                # hitting the CREATION budget must not cancel the
                # message-call phase
                outer_deadline = self._deadline_at
                if create_timeout is not None:
                    cd = _time.monotonic() + create_timeout
                    self._deadline_at = (cd if outer_deadline is None
                                         else min(outer_deadline, cd))
                # a constructor needn't mutate storage for the deploy to
                # count
                sf = run_one_tx(sf, is_last=False, handoff_kw=dict(
                    require_mutation=False, runtime_offset=runtime_base))
                self._cur_tx += 1
                self._tx_kind = "message"
                if create_timeout is not None:
                    self._deadline_at = outer_deadline
                    if self.timed_out and (
                            outer_deadline is None
                            or _time.monotonic() < outer_deadline):
                        self.timed_out = False
            for t in range(transaction_count):
                if self.timed_out:
                    break  # deadline: report what was explored so far
                alive, self._carried = self._carried, None
                if alive is None:
                    alive = fetch(sf.base.active, "base.active")
                if not bool(alive.any()):
                    break  # nothing survived: no state left to extend
                sf = run_one_tx(sf, is_last=(t == transaction_count - 1))
                self._cur_tx += 1
        finally:
            self._end_lead_in()
        self.sf = sf
        self.ctx = self.tx_contexts[-1]
        self.plugin_loader.fire("on_run_end", self)

    def _end_lead_in(self) -> None:
        """End the lead-in's last ``batch_build`` span: at the first
        ``sym_run`` call, or where the run ends without one."""
        sp, self._lead_in = self._lead_in, None
        if sp is not None:
            sp.stop()

    def _first_call_enqueued(self) -> None:
        """Fire ``on_first_call`` once; what it raises stays out of the
        exploration."""
        hook, self._on_first_call = self._on_first_call, None
        if hook is not None:
            try:
                hook()
            except Exception:  # noqa: BLE001 — a hook, not the run
                log.exception("on_first_call hook failed")

    def _count_lost(self, n: int, lanes=None, home=None) -> None:
        """``n`` forks given up at a host seam go into the drop channel
        the coverage and ``engine_dropped_forks_total{tx}`` read
        (``_parked_end``); ``lanes``, their mask, and ``home``, the
        ``home_contract`` of every lane, let the harvest say whose they
        were."""
        self._parked_end += n
        if lanes is not None:
            C = len(self._lost_by)
            self._lost_by += np.bincount(np.asarray(home)[lanes] % C,
                                         minlength=C)

    def _seam_fate(self, fate: str, n: int) -> None:
        obs_metrics.REGISTRY.counter(
            "engine_seam_states_total",
            help="end states that passed the pruners at a transaction's "
                 "seam, by what became of them: passed = admitted + "
                 "merged + dropped once the next call has ended; "
                 "deferred counts those made to wait first",
            labels={"tx": str(self._seam_tx), "fate": fate}).inc(n)

    def _admit_carried(self, ended, sf, C, passed, ended_active, failed,
                       home):
        """The seam's admission step: which of the states that
        ``passed`` the pruners start the next call (``engine.
        plan_seam_admission``), applied as one mask. ``ended`` is the
        frontier the transaction left, the other arrays host copies of
        its leaves. Returns the frontier, the mask of the lanes that
        go on and the seam's fates."""
        self._seam_tx, self._round = self._cur_tx, 0
        plan = plan_seam_admission(
            C, passed, home, ended_active, failed, self._started,
            lambda: fetch(tuple(attrgetter(a)(ended) for a in SEAM_STORAGE),
                          ",".join(SEAM_STORAGE)),
            lambda image, pc: guard_slots(
                self.images[image], pc,
                None if self.spec.caller else ATTACKER_ADDRESS),
            concrete_storage=not self.spec.storage)
        merged = dropped = wait = np.zeros_like(passed)
        if plan is not None:
            merged, dropped = plan["merged"], plan["dropped"]
            wait = np.zeros_like(passed)
            wait[plan["queue"]] = True
            sf = hold_carried(sf, merged | dropped, wait)
            self._count_lost(int(dropped.sum()), dropped, home)
        start = passed & ~(merged | dropped | wait)
        fates = dict(admitted=int(start.sum()), merged=int(merged.sum()),
                     deferred=int(wait.sum()), dropped=int(dropped.sum()))
        self._seam_plan = plan if wait.any() else None
        self._started = np.bincount((home % C)[start], minlength=C)
        self._seam_fate("passed", int(passed.sum()))
        for fate, n in fates.items():
            self._seam_fate(fate, n)
        return sf, passed & ~(merged | dropped), fates

    def _serve_waiting(self, sf, C, active, fork_req, running):
        """A chunk seam's turn for the lanes the last transaction seam
        left waiting (``engine.plan_waiting``), applied as one mask;
        returns the frontier and how many lanes it started or gave
        up."""
        plan = self._seam_plan
        plan["queue"], start, drop = plan_waiting(
            C, plan, active, fork_req, running)
        if start:
            self._round += 1
            self._started += np.bincount(plan["contract"][start],
                                         minlength=C)
            self._seam_fate("admitted", len(start))
        if start or drop:
            go = np.zeros_like(active)
            go[start] = True
            sf = hold_carried(sf, self._drop_waiting(plan, drop), start=go)
        if not plan["queue"]:
            self._seam_plan = None
        return sf, len(start) + len(drop)

    def _drop_waiting(self, plan, lanes):
        """Waiting lanes given up: lost forks of their contracts. Returns
        their mask."""
        mask = np.zeros(len(plan["contract"]), dtype=bool)
        mask[lanes] = True
        if lanes:
            self._count_lost(len(lanes), mask, plan["contract"])
            self._seam_fate("dropped", len(lanes))
        return mask

    def _close_waiting(self, sf):
        """A transaction's end: the lanes that still wait never started.
        They leave the frontier before the harvest reads it, and count
        as lost."""
        plan, self._seam_plan = self._seam_plan, None
        if plan is None:
            return sf
        return hold_carried(sf, self._drop_waiting(plan, plan["queue"]))

    def _count_tx(self, paths: int, dropped_total: int) -> tuple:
        """One transaction's ``engine_paths_total{tx}`` and
        ``engine_dropped_forks_total{tx}``: the lanes that ended it
        alive and without error, and the forks it lost: the engine's
        running total (what it dropped at the budget) less what the
        transactions before had, plus those still parked when its drain
        ended (``_parked_end``, which the coverage adds the same way)."""
        lost = dropped_total + self._parked_end - self._dropped_seen
        self._dropped_seen += lost
        reg = obs_metrics.REGISTRY
        labels = {"tx": str(self._cur_tx)}
        reg.counter("engine_paths_total",
                    help="paths alive and without error at the end of "
                         "a transaction", labels=labels).inc(paths)
        reg.counter("engine_dropped_forks_total",
                    help="forks a transaction lost to the lane budget",
                    labels=labels).inc(lost)
        return paths, lost

    def _count_dynamic(self, floor, cd_reads, loop_trapped: int) -> dict:
        """What decoding dynamic arguments did to the paths that ended
        one transaction without error or revert (``floor`` and
        ``cd_reads`` are theirs; where a reverted path's memory ended
        says nothing, and a reason string's stores lower its floor):
        the state their memory ended in (``engine_paths_memory_total{tx,state}``:
        ``exact``, nothing invalidated; ``floored``, from a word above
        the scratch words and the free pointer, words 0-2; ``havoc``,
        from one of those down), their calldata reads at a symbolic
        offset by what answered them
        (``engine_calldata_symreads_total{how}``), and the lanes the
        loop bound retired (``engine_loop_bound_traps_total{tx}``).
        Returns the ``harvest`` span's share of it."""
        reg = obs_metrics.REGISTRY
        tx = str(self._cur_tx)
        states = {"exact": int((floor == MEM_EXACT).sum()),
                  "havoc": int((floor <= 2).sum())}
        states["floored"] = len(floor) - states["exact"] - states["havoc"]
        for state, n in states.items():
            reg.counter("engine_paths_memory_total",
                        help="paths at the end of a transaction, by how "
                             "much of their memory was still exact",
                        labels={"tx": tx, "state": state}).inc(n)
        reads = cd_reads.sum(axis=0)
        for how, n in zip(("select", "havoc"), reads):
            reg.counter("engine_calldata_symreads_total",
                        help="CALLDATALOADs at a symbolic offset on the "
                             "paths that ended a transaction, by what "
                             "answered them",
                        labels={"how": how}).inc(int(n))
        reg.counter("engine_loop_bound_traps_total",
                    help="lanes the loop bound retired in a transaction",
                    labels={"tx": tx}).inc(loop_trapped)
        return dict(mem_floored_paths=states["floored"],
                    mem_havoc_paths=states["havoc"],
                    cd_selects=int(reads[0]), loop_trapped=loop_trapped)

    def _count_hops(self, hop, ended, of, C: int) -> dict:
        """What the paths that ended one transaction alive and without
        error did at their calls (``hop``: their ``hop_stats`` rows;
        ``of``: each lane's contract): CALL-family instructions by fate
        (``engine_calls_total{tx,fate}``), calls to members of the
        lane's world with code by whether a limit sent them down the
        external path (``engine_member_calls_total{tx,fate}``) and the
        symbolic words read across a frame boundary by side and fate
        (``engine_hop_words_total{side,fate}``). Returns the ``harvest``
        span's share: per contract the paths that ran three contracts
        deep."""
        from ..symbolic import state as st

        reg = obs_metrics.REGISTRY
        tx = str(self._cur_tx)
        tot = hop[ended].sum(axis=0, dtype=np.int64)
        for fate, col in (("internal", st.HOP_INTERNAL), ("eoa", st.HOP_EOA),
                          ("precompile", st.HOP_PRECOMPILE),
                          ("external", st.HOP_EXTERNAL)):
            reg.counter("engine_calls_total",
                        help="CALL-family instructions on the paths that "
                             "ended a transaction, by what the call did",
                        labels={"tx": tx, "fate": fate}).inc(int(tot[col]))
        trapped = int(tot[st.HOP_TRAPPED])
        for fate, n in (("framed", int(tot[st.HOP_MEMBER]) - trapped),
                        ("trapped", trapped)):
            reg.counter("engine_member_calls_total",
                        help="calls to a member of the lane's world with "
                             "code: run as a frame, or sent down the "
                             "external path by a limit (call depth, "
                             "calldata window, a symbolic window or value)",
                        labels={"tx": tx, "fate": fate}).inc(n)
        for side, fate, col in (
                ("calldata", "exact", st.HOP_CD_EXACT),
                ("calldata", "havoc", st.HOP_CD_HAVOC),
                ("return", "exact", st.HOP_RET_EXACT),
                ("return", "havoc", st.HOP_RET_HAVOC)):
            reg.counter("engine_hop_words_total",
                        help="symbolic words read across a frame boundary "
                             "(a callee's calldata, a caller's return "
                             "word): the other frame's node, or a havoc "
                             "leaf",
                        labels={"side": side, "fate": fate}).inc(
                            int(tot[col]))
        deep = ended & (hop[:, st.HOP_DEPTH] >= 2)
        out = dict(depth3_by_contract=np.bincount(
            of[deep], minlength=C).tolist())
        if self._systems:
            out["contract_names"] = list(self._member_names)
        return out

    def _join_worlds(self, sf, C: int, passed):
        """The creation transaction's seam, for linked systems: the end
        states of a system's constructors become ONE world (every
        member's storage rows under its account slot, its balance), and
        every member's lane starts its message calls from it. A member
        whose constructor left no end state, or more than one, or state
        tied to symbols of its own lane's tape, fails its system's
        deployment: the system's lanes retire and the failure is kept in
        ``failed_deployments``. A world of more rows than the lane's
        storage table holds is refused (ValueError), not truncated.
        Returns the frontier and the mask of the lanes that go on."""
        import jax.numpy as jnp

        from ..core.frontier import ACCT_CONTRACT0
        from ..symbolic.ops import N_WELL_KNOWN

        with obs_trace.timer("system_world",
                             systems=len(self._systems)) as span:
            leaves = ("base.home_contract", "base.st_used", "base.st_keys",
                      "base.st_vals", "base.st_acct", "st_val_sym",
                      "st_key_sym", "st_seq", "st_seq_ctr", "base.acct_bal")
            (home, used, keys, vals, acct, val_sym, key_sym, seq, ctr,
             bal) = (np.array(a) for a in fetch(
                 tuple(attrgetter(a)(sf) for a in leaves), ",".join(leaves)))
            K = used.shape[1]
            n_wk = N_WELL_KNOWN(self.limits.calldata_bytes)
            passed = passed.copy()
            retire = np.zeros_like(passed)
            rows_joined = 0
            for name, members in self._systems.items():
                lanes = [np.flatnonzero(passed & (home % C == i))
                         for i in members]
                why = None
                for i, ls in zip(members, lanes):
                    if len(ls) != 1:
                        why = (f"{self._member_names[i]}: constructor ended "
                               f"in {len(ls)} states")
                        break
                    mine = used[ls[0]]
                    if (val_sym[ls[0]][mine] > n_wk).any() or (
                            key_sym[ls[0]][mine] > n_wk).any():
                        why = (f"{self._member_names[i]}: constructor left "
                               f"symbolic storage")
                        break
                if why is not None:
                    self.failed_deployments.append(
                        {"system": name, "reason": why})
                    for i in members:
                        retire |= passed & (home % C == i)
                    continue
                picks = [(ls[0], np.flatnonzero(used[ls[0]]))
                         for ls in lanes]
                n = sum(len(r) for _, r in picks)
                if n > K:
                    raise ValueError(
                        f"system {name}: its deployed world has {n} "
                        f"storage rows, a lane's table holds "
                        f"storage_slots={K}")
                rows_joined += n

                # every member's rows, one after the other; the write
                # order (``st_seq``) restarts at 1, the members' balances
                # come from their own lanes, and every member's lane gets
                # the same world
                first = picks[0][0]
                for arr in (used, keys, vals, acct, val_sym, key_sym):
                    world = np.zeros_like(arr[first])
                    world[:n] = np.concatenate([arr[l][r] for l, r in picks])
                    for l, _ in picks:
                        arr[l] = world
                world_seq = np.zeros_like(seq[first])
                world_seq[:n] = np.arange(1, n + 1) * (
                    np.concatenate([seq[l][r] for l, r in picks]) > 0)
                world_bal = bal[first].copy()
                for k, (l, _) in enumerate(picks):
                    world_bal[ACCT_CONTRACT0 + k] = bal[l][ACCT_CONTRACT0 + k]
                for l, _ in picks:
                    seq[l], ctr[l], bal[l] = world_seq, n, world_bal
            passed &= ~retire
            b = sf.base
            sf = sf.replace(
                base=b.replace(
                    st_used=jnp.asarray(used), st_keys=jnp.asarray(keys),
                    st_vals=jnp.asarray(vals), st_acct=jnp.asarray(acct),
                    acct_bal=jnp.asarray(bal)),
                st_val_sym=jnp.asarray(val_sym),
                st_key_sym=jnp.asarray(key_sym),
                st_seq=jnp.asarray(seq), st_seq_ctr=jnp.asarray(ctr))
            if retire.any():
                sf = hold_carried(sf, retire)
            failed = len(self.failed_deployments)
            span.attrs.update(rows=rows_joined, failed=failed)
            reg = obs_metrics.REGISTRY
            reg.counter("engine_systems_deployed_total",
                        help="linked systems whose constructors' end "
                             "states were joined into one world").inc(
                                 len(self._systems) - failed)
            reg.counter("engine_system_deployments_failed_total",
                        help="linked systems a member of which left no "
                             "single concrete end state").inc(failed)
        return sf, passed

    def _dynld_between_txs(self, sf, names):
        """Fetch code for this tx's concrete-but-unknown call targets.

        Reference: ``DynLoader.dynld`` loads callee code the moment LASER
        executes a CALL to an unknown address (⚠unv, SURVEY §3.4). The
        frontier analog defers to the tx seam: harvest the call log's
        concrete targets, fetch the unknown ones over RPC, append their
        images to the corpus (a new static shape — the next chunk pays
        one recompile) and register them in a per-lane-free account-table
        column, so the NEXT transaction's calls resolve into real code.
        Paths of the current tx that already took the havoc leaf for such
        a call stay sound over-approximations, same as the pre-load state
        of the reference. Misses and successes are cached; the per-run
        load budget is ``dynld_limit``.
        """
        import jax.numpy as jnp

        from ..core.frontier import CREATOR_ADDRESS
        from ..ops import u256
        from ..symbolic.engine import CREATE_ADDR_BASE
        from ..utils.loader import DynLoaderError

        limits = self.limits
        budget = self.dynld_limit - len(self.dynld_loaded)
        if budget <= 0:
            return sf
        b = sf.base
        n = fetch(sf.n_calls, "n_calls")
        CL = sf.call_to.shape[1]
        # ADVICE r5: harvest only from non-error lanes — a trapped path's
        # call log can hold garbage targets computed past the failure
        # point, and on a live network junk-that-happens-to-hold-code
        # would burn dynld budget and account-table columns
        ok_lane = ~fetch(b.error, "base.error")
        conc = ((np.arange(CL)[None, :] < n[:, None])
                & (fetch(sf.call_to_sym, "call_to_sym") == 0)
                & ok_lane[:, None])
        to = fetch(sf.call_to, "call_to")
        cand = {int(u256.to_int(to[p, j])) for p, j in zip(*np.where(conc))}
        skip = self._known_addrs | self._dynld_miss
        fetched = []
        for a in sorted(cand):
            # 0x1..0x9 are precompiles (ADVICE r5): they execute natively,
            # never hold fetchable code — spending RPC round-trips and
            # budget slots on them starves real callees
            if (not 0x09 < a < 1 << 160 or a in skip
                    or a in (ATTACKER_ADDRESS, CREATOR_ADDRESS)
                    or CREATE_ADDR_BASE <= a < CREATE_ADDR_BASE + (1 << 32)):
                continue  # pseudo-addresses of CREATE results are local
            if len(fetched) >= budget:
                log.warning("dynld: per-run budget %d reached; remaining "
                            "unknown callees stay havoc", self.dynld_limit)
                break
            try:
                code = self.dyn_loader.dynld(a)
            except DynLoaderError as e:
                # a transport/format failure is NOT "no code": retry at
                # the next seam, and only cache the miss after repeated
                # failures (a transient 5xx must not havoc a live callee
                # for the rest of a long multi-tx run)
                fails = self._dynld_fails.get(a, 0) + 1
                self._dynld_fails[a] = fails
                if fails >= 2:
                    self._dynld_miss.add(a)
                log.warning("dynld 0x%040x failed (attempt %d): %s",
                            a, fails, e)
                continue
            if not code or len(code) > limits.max_code:
                self._dynld_miss.add(a)  # EOA / oversized: stays havoc
                continue
            fetched.append((a, code))
        if not fetched:
            return sf
        used = fetch(b.acct_used, "base.acct_used")
        free_cols = np.where(~used.any(axis=0))[0]
        if len(free_cols) < len(fetched):
            log.warning(
                "dynld: account table holds %d of %d loaded callees "
                "(max_accounts=%d); the rest stay havoc",
                len(free_cols), len(fetched), used.shape[1])
            for a, _ in fetched[len(free_cols):]:
                self._dynld_miss.add(a)  # retrying can never succeed
            fetched = fetched[:len(free_cols)]
            if not fetched:
                return sf
        addr_np = fetch(b.acct_addr, "base.acct_addr").copy()
        code_np = fetch(b.acct_code, "base.acct_code").copy()
        used_np = used.copy()
        for col, (a, code) in zip(free_cols, fetched):
            idx = len(self.images)
            self.images.append(
                ContractImage.from_bytecode(code, limits.max_code))
            names.append(f"onchain_0x{a:040x}")
            self._known_addrs.add(a)
            self.dynld_loaded.append(a)
            self._dynld_sha.append(hashlib.sha256(code).hexdigest())
            addr_np[:, col] = u256.from_int(a)
            code_np[:, col] = idx
            used_np[:, col] = True
            log.info("dynld: loaded 0x%040x (%d bytes) as corpus #%d",
                     a, len(code), idx)
        self.corpus = Corpus.from_images(self.images, self._n_creation)
        # ADVICE r5: the grown corpus is a NEW static shape — every chunk
        # size recompiles, so the warm-shape set must reset or the next
        # tx's first (compile-dominated) sample feeds sec_per_step and
        # permanently inflates the deadline pacing. sec_per_step itself
        # is per-explore()-local, so clearing the gate set suffices.
        self._warm_chunk_shapes = set()
        grow = len(self.images) - self._visited.shape[0]
        self._visited = np.vstack(
            [self._visited, np.zeros((grow, limits.max_code), dtype=bool)])
        return sf.replace(base=b.replace(
            acct_addr=jnp.asarray(addr_np),
            acct_code=jnp.asarray(code_np),
            acct_used=jnp.asarray(used_np),
        ))

    def _save_checkpoint(self, sf, steps_done: int) -> None:
        import os

        from ..utils.checkpoint import save_frontier

        os.makedirs(self.checkpoint_dir, exist_ok=True)
        save_frontier(
            os.path.join(self.checkpoint_dir, "frontier.npz"), sf,
            # dynld_loaded: a restorer's template corpus must append
            # these addresses' code IN ORDER, or the frontier's
            # acct_code indices past the original images dangle; the
            # sha256 lets the restore verify the node still serves the
            # bytes the checkpointed paths actually executed
            {"tx": self._cur_tx, "steps_done": steps_done,
             "dynld_loaded": [
                 {"address": f"0x{a:040x}", "sha256": h}
                 for a, h in zip(self.dynld_loaded, self._dynld_sha)]},
        )

    def _observe_frontier(self, sf, active=None, fork_req=None) -> None:
        """Frontier occupancy / park gauges after a chunk. ``active``/
        ``fork_req`` accept the chunk boundary's already-fetched host
        arrays (the spill/rebalance path pulls them anyway), so the
        gauges never force an EXTRA blocking device→host sync; absent
        them, the reads happen here and only when telemetry is actually
        on — a bare run must not pay them. (A rebalance between the
        shared fetch and this call is harmless: it relocates lanes
        without changing the active or parked COUNTS, which is all the
        gauges report.)"""
        reg = obs_metrics.REGISTRY
        if not (reg.enabled or obs_trace.active()):
            return
        act = (fetch(sf.base.active, "base.active") if active is None
               else active)
        freq = fetch(sf.fork_req, "fork_req") if fork_req is None else fork_req
        parked = int((freq & act).sum())
        reg.gauge("frontier_active_lanes",
                  help="live lanes after the last chunk").set(float(act.sum()))
        reg.gauge("frontier_occupancy",
                  help="live-lane fraction of the frontier").set(
            float(act.mean()) if act.size else 0.0)
        reg.gauge("frontier_parked_lanes",
                  help="lanes parked on a starved fork").set(float(parked))

    def instruction_coverage(self) -> Dict[str, float]:
        """Per-contract % of real instructions reached (reference:
        InstructionCoveragePlugin's end-of-run log ⚠unv, SURVEY §2)."""
        out = {}
        names = self.tx_contexts[-1].contract_names if self.tx_contexts else []
        for ci, img in enumerate(self.images):
            starts = img.is_code
            n = int(starts.sum())
            hit = int((self._visited[ci] & starts).sum())
            name = names[ci] if ci < len(names) else f"contract_{ci}"
            out[name] = round(100.0 * hit / n, 1) if n else 100.0
        return out

    @property
    def iprof(self) -> Dict[str, int]:
        """Executed-instruction counts by mnemonic (reference: the
        ``--enable-iprof`` InstructionProfiler table ⚠unv, SURVEY §5.1),
        most-executed first. Empty unless ``enable_iprof=True``."""
        from ..disassembler.opcodes import name_of

        out = {name_of(op): int(n) for op, n in enumerate(self._iprof) if n}
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def iprof_table(self) -> str:
        """The profile as reference-style text: one row per opcode with
        count and share, totals last."""
        prof = self.iprof
        total = sum(prof.values())
        lines = ["Instruction profile (executed instances):",
                 f"{'OPCODE':<14}{'COUNT':>12}{'SHARE':>9}"]
        for name, n in prof.items():
            lines.append(f"{name:<14}{n:>12}{100.0 * n / total:>8.2f}%")
        lines.append(f"{'TOTAL':<14}{total:>12}{100.0:>8.2f}%")
        return "\n".join(lines)

    @property
    def coverage(self) -> dict:
        cov = coverage_summary(self.tx_contexts)
        cov["instruction_coverage_pct"] = self.instruction_coverage()
        if self.spill:
            # deferred forks never counted as dropped in-engine; any still
            # parked when the budget ran out are honest losses
            cov["dropped_forks"] += self._parked_end
            cov["rebalanced_lanes"] = self._rebalanced
        return cov
