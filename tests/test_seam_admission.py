"""The seam's admission step over hand-made host arrays
(``engine.plan_seam_admission``, ``engine.plan_waiting``): pure NumPy
planners, so no engine is compiled here. ``tests/benchmark/
test_bench_twocall.py`` runs the step in the engine against the plain
EVM."""

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.symbolic import engine

C, P, K = 2, 16, 4      # contracts, lanes (a share of 8), storage entries
OWNER, FLAG = 0, 43


def word(v: int):
    return [(v >> (32 * i)) & 0xFFFFFFFF for i in range(8)]


class Seam:
    """The frontier a transaction ended with, lane by lane: contract 0
    holds lanes 0-7, contract 1 lanes 8-15."""

    def __init__(self):
        self.home = np.repeat(np.arange(C, dtype=np.int32), P // C)
        self.ended = np.zeros(P, dtype=bool)
        self.failed = np.zeros(P, dtype=bool)
        self.carried = np.zeros(P, dtype=bool)
        self.used = np.zeros((P, K), dtype=bool)
        self.written = np.zeros((P, K), dtype=bool)
        self.keys = np.zeros((P, K, 8), dtype=np.uint32)
        self.vals = np.zeros((P, K, 8), dtype=np.uint32)
        self.key_sym = np.zeros((P, K), dtype=np.int32)
        self.val_sym = np.zeros((P, K), dtype=np.int32)
        self.seq = np.zeros((P, K), dtype=np.int32)
        self.image = self.home.copy()
        self.pc = np.zeros(P, dtype=np.int32)
        self.guards = {}        # (image, pc) -> slots its guard loads
        self.fetched = 0

    def lane(self, lane, fate, *entries):
        """``entries``: (key, value, "p" an earlier transaction's write
        that the guard this path failed at loads | "w" written in this
        transaction, symbolic value?)"""
        self.ended[lane] = True
        self.failed[lane] = fate == "reverted"
        self.carried[lane] = fate == "carried"
        for k, (key, val, how, *sym) in enumerate(entries):
            self.used[lane, k] = True
            self.keys[lane, k], self.vals[lane, k] = word(key), word(val)
            self.written[lane, k] = how == "w"
            if how == "p":
                self.pc[lane] = 100 + lane
                self.guards[int(self.image[lane]), 100 + lane] = [key]
            self.seq[lane, k] = k + 1
            self.val_sym[lane, k] = 7 if sym and sym[0] else 0
        return self

    def storage(self):
        self.fetched += 1
        return (self.used, self.written, self.keys, self.vals,
                self.key_sym, self.val_sym, self.seq, self.image, self.pc)

    def plan(self, started=(1, 1)):
        return engine.plan_seam_admission(
            C, self.carried, self.home, self.ended, self.failed,
            np.asarray(started), self.storage,
            lambda image, pc: self.guards.get((image, pc), ()))


def wallet(sym_guard=False, twins=False):
    """Contract 0 ended its call in 8 lanes from one state: a failed
    ``onlyOwner`` that read slot 0, four carried states that wrote
    balances (lanes 1-4), one that wrote the owner (lane 6). Contract 1
    carried one state."""
    s = Seam().lane(0, "reverted", (OWNER, 0xAFFE, "p", sym_guard))
    for lane in range(1, 5):
        s.lane(lane, "carried", (1 << 200 | lane, 5, "w", not twins))
    s.lane(5, "reverted").lane(7, "reverted")
    s.lane(6, "carried", (OWNER, 0xDEAD, "w", True))
    return s.lane(8, "carried", (9, 1, "w"))


def test_the_state_that_wrote_what_a_failed_guard_read_starts_first():
    s = wallet()
    plan = s.plan()
    # a fan-out of 8 lanes in a share of 8: one state starts, and it is
    # the one that wrote the owner; no lane is left for one to wait in
    assert plan["queue"] == [] and not plan["merged"].any()
    assert np.nonzero(plan["dropped"])[0].tolist() == [1, 2, 3, 4]
    assert plan["fanout"].tolist() == [8, 1]
    assert s.fetched == 1
    # at a fan-out of 6 two lanes of the share are left: two states
    # wait there, in lane order, and two go
    s.ended[[5, 7]] = False
    plan = s.plan()
    assert plan["fanout"].tolist() == [6, 1] and plan["queue"] == [1, 2]
    assert np.nonzero(plan["dropped"])[0].tolist() == [3, 4]


@pytest.mark.parametrize("case", [
    "under_its_share", "symbolic_storage", "nothing_novel", "one_state",
    "the_failed_guard_loads_no_slot", "the_failed_path_wrote_it_itself"])
def test_the_step_is_inert(case):
    s = wallet(sym_guard=case == "symbolic_storage")
    started = (1, 1)
    if case == "under_its_share":
        started = (8, 1)        # eight states made those eight lanes
    if case == "nothing_novel":
        s.carried[6] = False    # the owner's writer did not pass
    if case == "one_state":
        s.carried[1:5] = False
    if case == "the_failed_guard_loads_no_slot":
        s.guards.clear()        # the entry is its ancestor's, untested
    if case == "the_failed_path_wrote_it_itself":
        s.written[0, 0] = True
    assert s.plan(started) is None
    # the storage leaves are read only where a contract is over
    assert s.fetched == (0 if case in ("under_its_share", "one_state")
                         else 1)


def test_states_with_the_same_concrete_storage_merge_into_the_first():
    s = wallet(twins=True)
    s.keys[2, 0] = s.keys[1, 0]         # lane 2 wrote what lane 1 wrote
    plan = s.plan()
    assert np.nonzero(plan["merged"])[0].tolist() == [2]
    assert np.nonzero(plan["dropped"])[0].tolist() == [1, 3, 4]
    # a symbolic value keeps two states apart
    s.val_sym[2, 0] = 3
    assert not s.plan()["merged"].any()


def test_the_contracts_queues_take_turns_and_novel_states_lead():
    s = wallet()
    for lane in (9, 10, 11):
        s.lane(lane, "carried", (1 << 200 | lane, 5, "w", True))
    s.lane(12, "reverted", (FLAG, 1, "p"))
    s.lane(13, "carried", (FLAG, 0, "w"))
    s.lane(14, "carried", (FLAG, 2, "w"))
    s.ended[[5, 7]] = False
    plan = s.plan((1, 3))
    # contract 0: fan-out 6, lane 6 starts, lanes 1 and 2 wait.
    # Contract 1: 6 states of fan-out 3 in a share of 8: lanes 13 and 14
    # (novel) start, 8 and 9 wait in the two lanes left, 10 and 11 go;
    # the two queues alternate
    assert plan["fanout"].tolist() == [6, 3]
    assert plan["queue"] == [1, 8, 2, 9]
    assert np.nonzero(plan["dropped"])[0].tolist() == [3, 4, 10, 11]


def waiting(active, parked, queue, fanout=(4, 4), running=None):
    plan = {"queue": list(queue), "fanout": np.asarray(fanout),
            "contract": np.repeat(np.arange(C), len(active) // C)}
    wait = np.zeros(len(active), dtype=bool)
    wait[queue] = True
    if running is None:
        running = active & ~wait
    return engine.plan_waiting(C, plan, active, parked, running)


def test_waiting_lanes_are_given_up_at_a_full_frontiers_fixpoint():
    active = np.ones(P, dtype=bool)
    parked = np.zeros(P, dtype=bool)
    parked[[3, 12]] = True
    # every lane that still runs is parked on a fork
    assert waiting(active, parked, [1, 9, 2], running=parked) == (
        [], [], [1, 9, 2])


def test_a_waiting_lane_starts_where_its_contract_has_a_fan_out_of_room():
    active = np.zeros(P, dtype=bool)
    active[[0, 1, 2, 8, 9, 10, 11, 12, 13]] = True
    # contract 0 runs one lane beside two that wait: 4 of its 8 free;
    # contract 1 runs four beside two that wait: not 4 of room
    none = np.zeros(P, dtype=bool)
    assert waiting(active, none, [1, 12, 2, 13]) == ([12, 2, 13], [1], [])
    # a lane a sweep killed leaves the queue without a word
    active[12] = False
    assert waiting(active, none, [12, 13], (9, 9)) == ([13], [], [])


def test_hold_carried_is_one_mask_over_active_and_halted():
    import jax.numpy as jnp

    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.symbolic import make_sym_frontier

    sf = make_sym_frontier(4, TEST_LIMITS, active=np.ones(4, dtype=bool))
    sf = sf.replace(base=sf.base.replace(
        halted=jnp.asarray([False, False, True, False])))
    mask = lambda *lanes: np.isin(np.arange(4), lanes)  # noqa: E731
    out = engine.hold_carried(sf, mask(0), wait=mask(1), start=mask(2))
    assert np.asarray(out.base.active).tolist() == [False, True, True, True]
    assert np.asarray(out.base.halted).tolist() == [False, True, False,
                                                    False]
    assert np.asarray(out.base.running).tolist() == [False, False, True,
                                                     True]


# --- the slot a failed guard tested, read off the code ---------------------

def _image(*tokens):
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.disassembler.asm import assemble

    return ContractImage.from_bytecode(assemble(*tokens), 512)


REASON = [0x40, "MLOAD", ("push32", 0x08C379A0 << 224), "DUP2", "MSTORE",
          0x20, "DUP2", "MSTORE", 0, "DUP1", "REVERT"]


@pytest.mark.parametrize("case, body, want", [
    ("onlyOwner", ["CALLER", 0, "SLOAD", ("push20", (1 << 160) - 1), "AND",
                   "EQ", ("ref", "ok"), "JUMPI", *REASON], {0}),
    ("require_not_initialized", [43, "SLOAD", "ISZERO", ("ref", "ok"),
                                 "JUMPI", 0, "DUP1", "REVERT"], {43}),
    ("two_flags", [5, "SLOAD", 6, "SLOAD", "AND", ("ref", "ok"), "JUMPI",
                   0, "DUP1", "REVERT"], {5, 6}),
    # the load lies in the block before: SafeMath's check of a sum
    ("safe_add", [7, "SLOAD", ("ref", "sub"), "JUMP", ("label", "sub"),
                  "DUP1", 1, "ADD", "LT", "ISZERO", ("ref", "ok"), "JUMPI",
                  0, "DUP1", "REVERT"], set()),
    # a mapping's slot is no fixed slot
    ("mapping", ["CALLER", 0, "MSTORE", 64, 0, "SHA3", "SLOAD",
                 ("ref", "ok"), "JUMPI", 0, "DUP1", "REVERT"], set()),
    # a revert no branch guards
    ("bare_revert", [0, "SLOAD", "POP", ("label", "x"), 0, "DUP1",
                     "REVERT"], set()),
])
def test_guard_slots_reads_the_failed_require_off_the_code(case, body, want):
    from mythril_tpu.analysis.symbolic import guard_slots

    image = _image(("label", "f"), *body, ("label", "ok"), "STOP")
    at = max(i for i in range(image.code_len)
             if image.is_code[i] and image.code[i] == 0xFD)
    # a path ends on its REVERT, or with its pc just past it
    assert guard_slots(image, at) == guard_slots(image, at + 1) == want
    # a path that stopped on the passing side failed no guard there
    assert guard_slots(image, 0) == frozenset()


# --- the report's rows a (tx, fate) -------------------------------------------

def test_trace_report_prints_a_row_a_fate_and_the_contracts_shares(tmp_path):
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(root, "tools", "trace_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    def span(name, mono, tx, **attrs):
        return dict(schema=1, kind="span", name=name, t=0.0, mono=mono,
                    dur=1.0, tid=9, tx=tx, tx_kind="message", **attrs)

    recs = []
    for t0 in (0.0, 100.0):     # two batches
        recs += [
            span("harvest", t0 + 1, 1, paths=100, dropped=3,
                 paths_by_contract=[60, 40], dropped_by_contract=[3, 0]),
            span("tx_seam", t0 + 2, 1, carried=14, passed=50, admitted=4,
                 merged=1, deferred=10, dropped=35),
            span("superstep", t0 + 3, 2, steps=64, steps_run=64),
            span("superstep", t0 + 5, 2, steps=64, steps_run=64, round=1),
            span("harvest", t0 + 7, 2, paths=90, dropped=50,
                 paths_by_contract=[30, 60], dropped_by_contract=[45, 5])]
    path = tmp_path / "t.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    text = report.report(*report.load_trace(str(path))).splitlines()
    at = next(i for i, ln in enumerate(text)
              if ln.startswith("== seam admission (tx, fate)"))
    rows = [ln.split() for ln in text[at + 2:at + 7]]
    assert [r[:4] for r in rows] == [
        ["1", "passed", "100", "100.0%"], ["1", "admitted", "8", "8.0%"],
        ["1", "deferred", "20", "20.0%"], ["1", "dropped", "70", "70.0%"],
        ["1", "merged", "2", "2.0%"]]
    assert "1 later round(s) of tx 2" in text[at + 4]
    at = next(i for i, ln in enumerate(text)
              if ln.startswith("== paths and lost forks per contract"))
    assert [ln.split() for ln in text[at + 1:at + 5]] == [
        ["1", "dropped", "6", "0"], ["1", "paths", "120", "80"],
        ["2", "dropped", "90", "10"], ["2", "paths", "60", "120"]]
    # a trace from before the step has neither table
    old = [{k: v for k, v in r.items() if k not in (
        "passed", "admitted", "merged", "deferred", "dropped",
        "paths_by_contract", "dropped_by_contract") or r["name"] == "harvest"
        and k == "dropped"} for r in recs]
    path.write_text("".join(json.dumps(r) + "\n" for r in old))
    text = report.report(*report.load_trace(str(path)))
    assert "seam admission" not in text and "per contract" not in text
