"""Concolic execution: concrete-seed trace + branch flipping.

Reference: ``mythril/concolic/{concolic,concrete_data,find_trace}.py``
(⚠unv, SURVEY.md §2 row "Concolic engine", BASELINE config 5): replay a
concrete transaction, then negate chosen branch conditions and solve for
inputs that drive the other side — the symbolic half of a hybrid fuzzer.

Frontier-first shape: the SYMBOLIC engine already explores all branches
at once, so "find the concrete trace" is a host-side selection — evaluate
each surviving lane's path condition under the seed input and pick the
lane the seed satisfies. Flipping branch k of that lane = solving its
constraint prefix with constraint k negated. One ``sym_run`` serves every
flip (no re-execution per branch, unlike the reference's replay loop).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from ..config import DEFAULT_LIMITS, LimitsConfig
from ..core import Corpus, make_env
from ..disassembler import ContractImage
from ..obs.device import HostLeaves
from ..smt.eval import Assignment, evaluate
from ..smt.solver import solve_tape
from ..smt.tape import HostTape, extract_tape
from ..symbolic import SymSpec, make_sym_frontier, sym_run


@dataclass
class FlippedBranch:
    pc: int                 # JUMPI whose condition was negated
    constraint_index: int   # index in the trace lane's path condition
    calldata: bytes         # new input driving the other side
    callvalue: int
    caller: int


def _seed_assignment(calldata: bytes, callvalue: int, caller: int) -> Assignment:
    asn = Assignment()
    t = asn.tx(0)
    t.calldata = bytearray(calldata)
    t.calldatasize = len(calldata)
    t.callvalue = callvalue
    t.caller = caller
    return asn


def _satisfied(tape: HostTape, asn: Assignment) -> bool:
    vals = evaluate(tape, asn)
    return all(bool(vals[n]) == sign for n, sign in tape.constraints)


def find_trace_lane(sf, seed: Assignment,
                    cache: Optional[HostLeaves] = None) -> Optional[int]:
    """Lane whose path condition the seed input satisfies (the concrete
    trace the reference's ``concrete_execution`` would record ⚠unv)."""
    cache = cache or HostLeaves(sf)
    act = cache("base.active")
    err = cache("base.error")
    for lane in np.where(act & ~err)[0]:
        if _satisfied(extract_tape(sf, int(lane), cache=cache), seed):
            return int(lane)
    return None


def concolic_execution(
    code: bytes,
    seed_calldata: bytes,
    jump_addresses: Optional[Sequence[int]] = None,
    callvalue: int = 0,
    caller: Optional[int] = None,
    limits: LimitsConfig = DEFAULT_LIMITS,
    n_lanes: int = 64,
    max_steps: int = 512,
    solver_iters: int = 400,
) -> List[FlippedBranch]:
    """Flip branches of the seed input's trace.

    ``jump_addresses`` restricts flipping to those JUMPI pcs (the
    reference's ``--jump-addresses``); None flips every branch on the
    trace. Returns one :class:`FlippedBranch` per solvable flip.
    """
    from ..core.frontier import ATTACKER_ADDRESS

    caller = ATTACKER_ADDRESS if caller is None else caller
    img = ContractImage.from_bytecode(code, limits.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(n_lanes, limits, active=active)
    env = make_env(n_lanes)
    sf = sym_run(sf, env, corpus, SymSpec(), limits, max_steps=max_steps)

    seed = _seed_assignment(seed_calldata, callvalue, caller)
    cache = HostLeaves(sf)
    lane = find_trace_lane(sf, seed, cache=cache)
    if lane is None:
        return []  # seed diverged (e.g. exploration capped before halt)

    tape = extract_tape(sf, lane, cache=cache)
    con_pc = cache("con_pc")[lane]
    out: List[FlippedBranch] = []
    for j, (node, sign) in enumerate(tape.constraints):
        pc = int(con_pc[j]) if j < len(con_pc) else -1
        if jump_addresses is not None and pc not in jump_addresses:
            continue
        flipped = HostTape(
            nodes=tape.nodes,
            constraints=list(tape.constraints[:j]) + [(node, not sign)],
        )
        asn = solve_tape(flipped, max_iters=solver_iters)
        if asn is None:
            continue
        t = asn.tx(0)
        size = t.calldatasize if t.calldatasize is not None else len(t.calldata)
        size = max(0, min(size, len(t.calldata)))
        out.append(FlippedBranch(
            pc=pc, constraint_index=j,
            calldata=bytes(t.calldata[:size]),
            callvalue=t.callvalue, caller=t.caller,
        ))
    return out


def load_concrete_data(path: str):
    """Parse a reference-shaped concolic trace file (``myth concolic
    input.json``; ``mythril/concolic/concrete_data.py`` ⚠unv): a JSON
    document with ``initialState.accounts`` (code/storage/balance per
    address) and ``steps`` (one recorded transaction each: address,
    input, value, origin/caller).

    Returns ``(code, calldata, callvalue, caller)`` for the LAST step —
    the transaction whose branches get flipped (the reference replays
    the whole sequence; the frontier engine's multi-tx exploration
    subsumes the earlier steps' state effects only when they mutate the
    target's storage, a documented divergence: single-step traces are
    exact, multi-step traces flip the final call against fresh state).
    """
    import json

    def _int(v, default=0):
        if v is None:
            return default
        if isinstance(v, int):
            return v
        return int(str(v), 16 if str(v).startswith("0x") else 10)

    def _bytes(v):
        return bytes.fromhex(str(v or "0x").removeprefix("0x"))

    with open(path) as fh:
        doc = json.load(fh)
    steps = doc.get("steps") or []
    if not steps:
        raise ValueError(f"{path}: trace has no steps")
    step = steps[-1]
    target = str(step.get("address", "")).lower()
    accounts = {k.lower(): v
                for k, v in (doc.get("initialState", {})
                             .get("accounts", {})).items()}
    acct = accounts.get(target)
    if acct is None or not acct.get("code"):
        raise ValueError(
            f"{path}: no account code for step target {target!r}")
    return (
        _bytes(acct["code"]),
        _bytes(step.get("input")),
        _int(step.get("value")),
        _int(step.get("caller") or step.get("origin"), default=0) or None,
    )
