"""Corpus ``dynargs-v1`` and cell ``dynargs.campaign``: the corpus is
what it says it is (the repo's plain EVM runs every constructor, every
witness and its prefixes, and pairs of calls on the safe siblings), the
engine's verdicts equal the labels at the test limits, what it reports
replays there (``member_execute`` in three steps; ``batch_overflow``'s
``input`` passes both ``require``s with ``cnt * _value`` at or above
2**256), ``correct`` comes out false under each control, and the three
readers read a hand-made run and nothing. On ``test_bench_twocall.py``'s
plan, with ``test_bench_deployed.py``'s helpers (the corpora share their
blocks and their accounts); the engine runs at that file's test-limits
shape.
"""

import copy
import itertools
import json
import os
import sys

import pytest

import test_bench_deployed as base
from bench_paths import BENCH, ROOT, load

from mythril_tpu.ops.keccak import keccak256_host_int

da = load("corpora/dynargs-v1.py", "bench_dynargs_v1")
run = load("run.py", "bench_run_dynargs")
verdicts = load("verdicts.py", "bench_verdicts_dynargs")

CELL = "dynargs.campaign"
KINDS = ("batch_overflow", "batch_checked", "member_execute",
         "member_execute_safe", "kill")
SAFE = ("batch_checked", "member_execute_safe")
deploy, call, SELECTOR = base.deploy, base.call, base.SELECTOR
BALANCE = keccak256_host_int(
    da.STRANGER.to_bytes(32, "big") + (1).to_bytes(32, "big"))


def both_sets(seed, max_code=24576):
    return da.batch(seed, 0, max_code) + da.batch(seed, 1, max_code)


def functions(c) -> list:
    return [int.from_bytes(m, "big") for m, _ in SELECTOR.findall(c["code"])]


class Stores(base.Probe):
    """The plain EVM, noting the key of every SSTORE it executes (a
    call that reverts later has still reached them) and whether a MUL
    had wrapped by then. A CALLDATACOPY of
    more than 64 KiB runs out of gas on any chain: here it ends the
    call with no effect (the plain EVM has no gas limit of its own, and
    an adversarial length would have it fill the machine's memory)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.stored, self.wrapped = [], False

    def step(self):
        op = self.code[self.pc] if self.pc < len(self.code) else 0
        if op == 0x02 and len(self.stack) >= 2:
            self.wrapped |= self.stack[-1] * self.stack[-2] >= 1 << 256
        if op == 0x55 and len(self.stack) >= 2:
            self.stored.append((self.stack[-1], self.wrapped))
        if op == 0x37 and len(self.stack) >= 3 and self.stack[-3] > 1 << 16:
            self.halted = self.reverted = True
            return
        super().step()


def probe(c, storage, data):
    """A stranger's call in the plain EVM: (result, the value each CALL
    sent, whether a product wrapped, as ``cnt * _value`` does, and the
    call got past the ``require``s behind it, to the SSTORE of the
    sender's balance)."""
    env = base.RefEnv()
    env.caller = env.origin = da.STRANGER
    evm = Stores(c["code"], data, env=env, storage=dict(storage))
    res = evm.run(max_steps=5000)
    assert not res.error, c["name"]
    if not res.halted:      # a loop the gas limit would have ended
        res.reverted = True
    return res, evm.sent, (BALANCE, True) in evm.stored


def batch_overflowed(c, storage, data) -> bool:
    return probe(c, storage, data)[2]


def effect(swc: str, c, steps) -> bool:
    """``base.effect`` for this corpus: SWC-101 is the wrapped
    ``batchTransfer`` that also runs to its end."""
    if swc != "101":
        return base.effect(swc, c, steps)
    storage, res = deploy(c), None
    for data in steps:
        before = storage
        res, _ = call(c, storage, data)
        if not res.reverted:
            storage = res.storage
    return (res is not None and not res.reverted
            and batch_overflowed(c, before, steps[-1]))


# --- the corpus ------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_same_seed_same_stream_and_every_seed_the_same_work(seed):
    a, b = da.batch(seed, 3), da.batch(seed, 3)
    assert a == b
    other = da.batch(seed + 1, 3)
    assert [c["creation"] for c in a] != [c["creation"] for c in other]
    shape = [sorted((c["kind"], len(functions(c))) for c in x)
             for x in (a, other)]
    assert shape[0] == shape[1]
    assert len({tuple(c["kind"] for c in da.batch(s, 3))
                for s in range(6)}) > 1
    sets = [sorted((c["kind"], len(functions(c)))
                   for c in da.batch(seed, bi)) for bi in range(4)]
    assert sets[0] == sets[2] != sets[1] == sets[3]
    for batch in sets[:2]:
        kinds = [k for k, _ in batch]
        assert [kinds.count(k) for k in KINDS] == [2, 2, 2, 1, 1]
        assert all(20 <= n <= 60 for _, n in batch)


@pytest.mark.parametrize("max_code", [24576, 512])
def test_creation_code_returns_the_runtime_code_and_writes_the_state(
        max_code):
    member = keccak256_host_int(
        da.CREATOR.to_bytes(32, "big") + da.MEMBERS_SLOT.to_bytes(32, "big"))
    for c in both_sets(13, max_code):
        storage = deploy(c)     # asserts the code returned is c["code"]
        assert storage[da.OWNER_SLOT] == da.CREATOR
        assert storage[da.INIT_SLOT] == 1
        assert storage[da.SUPPLY_SLOT] == da.SUPPLY
        assert (storage.get(member) == 1) == c["kind"].startswith("member")
        assert len(storage) == 4 + c["kind"].startswith("member")
        assert c["creation"].endswith(c["code"])
        assert len(c["creation"]) <= max_code
        # a decode is one CALLDATACOPY (multi_transfer has two), and the
        # corpus's own count of the functions that decode agrees
        copies = c["code"].count(b"\x01\x37")    # .. ADD CALLDATACOPY
        assert c["dynamic"] <= copies
        if max_code == 24576:
            n = len(functions(c))
            own = {"member_execute": 2, "member_execute_safe": 2}.get(
                c["kind"], 1)
            fillers = n - own
            in_fillers = c["dynamic"] - (c["kind"] != "kill")
            assert 5 * in_fillers >= fillers, (c["name"], in_fillers, n)
            assert 3000 <= len(c["code"]) <= 19500 + 43, len(c["code"])
        else:
            assert c["dynamic"] == (c["kind"] != "kill")
    sizes = sorted(len(c["code"]) for c in both_sets(13))
    assert sizes[0] < 4200 and sizes[-1] > 15000


@pytest.mark.parametrize("max_code", [24576, 512])
@pytest.mark.parametrize("kind", KINDS)
def test_each_witness_reaches_its_flaw_and_no_shorter_prefix_does(
        kind, max_code):
    seen = 0
    for c in both_sets(21, max_code):
        if c["kind"] != kind:
            continue
        assert sorted(c["witness"]) == c["must_report"]
        assert not set(c["must_report"]) & set(c["must_not_report"])
        assert bool(c["witness"]) == (kind not in SAFE)
        for swc, steps in c["witness"].items():
            assert len(steps) == (2 if kind in da.TWO_CALL else 1)
            assert {"batch_overflow": "101", "member_execute": "105",
                    "kill": "106"}[kind] == swc
            assert effect(swc, c, steps), (c["name"], swc)
            for k in range(len(steps)):
                assert not effect(swc, c, steps[:k]), (c["name"], swc, k)
            # a second call alone does not do it either
            assert len(steps) == 1 or not effect(swc, c, steps[1:])
            seen += 1
    assert seen == {"batch_overflow": 4, "member_execute": 4,
                    "kill": 2}.get(kind, 0)
    if kind == "batch_overflow":
        data = steps[0]     # 164 bytes: cnt = 2, _value = 2**255
        assert len(data) == 164
        assert int.from_bytes(data[68:100], "big") == 2


def attacks(f) -> list:
    """A stranger's calls of one function: static words, and the
    dynamic layouts of this corpus's attacks."""
    words = (da.STRANGER, da.CREATOR, 0, 1, 1 << 255)
    return ([da.calldata(f, (w, w, w)) for w in words]
            + [da.abi(f, ([da.R1, da.R2], 1 << 255)),
               da.abi(f, ([da.STRANGER] * 16, 1 << 252)),
               da.abi(f, (da.STRANGER, 1, b"")),
               da.abi(f, ([da.STRANGER], [1]))])


def broke(c, storage, data):
    """(destroyed, the stranger paid, or a wrapped ``batchTransfer``;
    the storage the call left, None where it reverted)"""
    res, sent, wrapped = probe(c, storage, data)
    ok = not res.reverted
    return (wrapped or (ok and (res.selfdestructed or any(
        to == da.STRANGER and v > 0 for to, v in sent))),
        res.storage if ok and not res.selfdestructed else None)


@pytest.mark.parametrize("max_code", [24576, 512])
@pytest.mark.parametrize("kind", SAFE)
def test_no_pair_of_calls_breaks_a_safe_sibling(kind, max_code):
    """Every function called by a stranger with adversarial words and
    layouts, then again from a storage each function can leave (at the
    real size, where the smallest contract of the kind has 21-35
    functions, the second call with the dynamic layouts only): nothing
    destroys the sibling, pays the stranger or wraps ``cnt * _value``
    past the ``require``s. Its flawed twin breaks under the same
    calls."""
    cs = sorted((c for c in both_sets(33, max_code) if c["kind"] == kind),
                key=lambda c: len(c["code"]))
    twin = {"batch_checked": "batch_overflow",
            "member_execute_safe": "member_execute"}[kind]
    flawed = min((c for c in both_sets(33, max_code) if c["kind"] == twin),
                 key=lambda c: len(c["code"]))
    for c, want in ((cs[0], False), (flawed, True)):
        second = [d for f in functions(c)
                  for d in attacks(f)[0 if max_code == 512 else -4:]]
        start, left, hit = deploy(c), {}, False
        for f in functions(c):
            for data in attacks(f):
                bad, storage = broke(c, start, data)
                hit |= bad
                if storage is not None:
                    left[f] = storage
        for storage, data in itertools.product(left.values(), second):
            if hit:
                break
            hit |= broke(c, storage, data)[0]
        assert hit is want, (c["name"], len(left))


# --- the engine against the plain EVM --------------------------------------

def seams(records) -> list:
    return [r for r in records if r.get("kind") == "span"
            and r["name"] == "tx_seam"]


def explore():
    """Both lean sets through the device phase at
    ``test_bench_deployed.py``'s shape (8 contracts x 16 lanes, creation
    transaction, two message calls, concrete storage, test limits):
    (contracts, report, spans) a batch."""
    import mythril_tpu  # noqa: F401
    from mythril_tpu.analysis import fire_lasers
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.mythril.campaign import CorpusCampaign
    from mythril_tpu.obs import trace as obs_trace
    from mythril_tpu.symbolic import SymSpec

    sys.path.insert(0, BENCH)
    import hostcb_cache

    hostcb_cache.install()
    camp = CorpusCampaign([], batch_size=8, lanes_per_contract=base.LANES,
                          limits=TEST_LIMITS, spec=SymSpec(storage=False),
                          max_steps=128, transaction_count=2)
    out = []
    for k in (0, 1):
        cs = da.batch(2 ** 31 + 40, k, 512)
        tracer = obs_trace.configure(buffer=True)
        try:
            sym = camp._explore_batch(
                k, [c["name"] for c in cs], [c["code"] for c in cs],
                creations=[c["creation"] for c in cs])
            spans = tracer.drain_buffer()
        finally:
            obs_trace.close()
        out.append((cs, fire_lasers(sym), spans))
    return out


def test_engine_against_the_plain_evm_at_the_test_limits():
    """One engine run for the file's test-limits checks. The verdicts
    equal the labels; ``member_execute``'s finding is a three-step
    sequence whose second call pays the stranger, and not without its
    first; ``batch_overflow``'s reported ``input`` carries an offset
    off the head, a ``cnt`` of 1-20 and a ``_value`` whose product
    wraps, and gets past both ``require``s in the plain EVM (until this
    PR it was selector, offset 0, zeros, and reverted at ``cnt > 0``);
    the harvest says what the decodes did to memory."""
    replayed = {}
    for cs, report, spans in explore():
        reported = {c["name"]: set() for c in cs}
        for i in report.issues:
            if i.contract in reported:
                reported[i.contract].add(str(i.swc_id))
        rows = verdicts.compare(cs, reported)
        assert not [r for r in rows if verdicts.wrong(r)], rows
        by_name = {c["name"]: c for c in cs}
        for i in report.issues:
            c = by_name.get(i.contract)
            if c is None or str(i.swc_id) not in c["must_report"]:
                continue
            seq = i.transaction_sequence
            assert int(seq[0]["caller"], 16) == da.CREATOR
            assert {int(t["caller"], 16) for t in seq[1:]} == {da.STRANGER}
            steps = [bytes.fromhex(t["input"][2:]) for t in seq[1:]]
            if c["kind"] == "batch_overflow":
                data = steps[-1]
                offset = int.from_bytes(data[4:36], "big")
                assert 64 <= offset and offset + 36 <= len(data)
                assert 1 <= int.from_bytes(
                    data[4 + offset:36 + offset], "big") <= da.MAX_RECEIVERS
                assert batch_overflowed(c, deploy(c), data), (c["name"], seq)
            else:
                assert base.effect(str(i.swc_id), c, steps), (c["name"], seq)
            if c["kind"] == "member_execute":
                assert len(seq) == 3, (c["name"], seq)
                assert not base.effect("105", c, steps[1:])
            replayed[c["kind"]] = replayed.get(c["kind"], 0) + 1
        for s in seams(spans):
            assert s["passed"] == (s["admitted"] + s["merged"]
                                   + s["deferred"] + s["dropped"]), s
        last = [r for r in spans if r.get("kind") == "span"
                and r["name"] == "harvest"][-1]
        # every path through a decode kept its scratch words, and every
        # length read was a select
        assert last["mem_floored_paths"] > 0 == last["mem_havoc_paths"]
        assert last["cd_selects"] > 0 and last["loop_trapped"] >= 0
    assert replayed == {"batch_overflow": 4, "member_execute": 4,
                        "kill": 2}, replayed


# --- the cell ------------------------------------------------------------------

def cell(control=None):
    loaded = copy.deepcopy(run.load_cell(ROOT, CELL))
    loaded.config["analyze_args"] += base.SMALL
    if control:
        loaded.config["analyze_args"] += loaded.config["controls"][
            control]["args"]
    return loaded


def drive(loaded, seed):
    lines = []
    out = run.run_cell(ROOT, CELL, seed, 2.0, False, require_tpu=False,
                       loaded=loaded, log=lines.append)
    return out, lines


def wrong(lines) -> set:
    return {(m.group(1), "missing" if "missing=['1" in ln else "extra")
            for ln in lines if ln.startswith("wrong verdict")
            for m in [base.re.search(r"a\d{6}_(\S+) ", ln)]}


@pytest.mark.parametrize("control, missing", [
    (None, set()),
    ("one_transaction", {"member_execute"}),
    ("module_withheld", {"member_execute", "batch_overflow"}),
])
def test_correct_is_true_for_the_cell_and_false_under_each_control(
        control, missing):
    out, lines = drive(cell(control), 2 ** 31 + 12)
    assert set(out["metrics"]) == {"contracts_per_min", "setup_s"}
    assert wrong(lines) == {(k, "missing") for k in missing}, lines
    assert out["correct"] is (not missing)
    assert out["attempted"] >= 8
    # 2 labels a set go missing for each kind, nothing else moves
    assert out["failed"] == len(missing) * out["attempted"] // 4
    assert any(ln.startswith("check programs compiled inside the "
                             "window: 0 (limit 0)") for ln in lines)


# --- the three readers, over snapshots made by hand ---------------------------

def memory(tx, exact, floored, havoc):
    return {f'engine_paths_memory_total{{state="{s}",tx="{tx}"}}': float(n)
            for s, n in (("exact", exact), ("floored", floored),
                         ("havoc", havoc))}


def reads(select, havoc):
    return {'engine_calldata_symreads_total{how="select"}': float(select),
            'engine_calldata_symreads_total{how="havoc"}': float(havoc)}


def loops(tx, trapped, paths):
    return {f'engine_loop_bound_traps_total{{tx="{tx}"}}': float(trapped),
            f'engine_paths_total{{tx="{tx}"}}': float(paths)}


OBS = {
    "kind": "campaign", "batches": 2,
    "registry_before": {"counters": {
        **memory(1, 100, 10, 5), **memory(2, 50, 20, 10), **reads(30, 4),
        **loops(1, 2, 200), **loops(2, 3, 100)}},
    "registry_after": {"counters": {
        **memory(1, 400, 50, 9), **memory(2, 250, 100, 50), **reads(130, 24),
        **loops(1, 8, 1000), **loops(2, 9, 500)}},
    "spans": [],
}


def reader(name: str):
    return load(f"layer_metrics/{name}.py", "bench_" + name)


@pytest.mark.parametrize("name, want", [
    ("mem_exact_share", 100.0 * (200 + 80) / (200 + 80 + 40)),
    ("calldata_select_share", 100.0 * 100 / 120),
    ("loop_bound_trap_share", 100.0 * 12 / (12 + 1200)),
])
def test_reader_over_a_hand_made_run(name, want):
    assert reader(name).read(OBS) == pytest.approx(want)


NAMES = ("mem_exact_share", "calldata_select_share", "loop_bound_trap_share")


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("obs", [
    # the parent of the PR that added them: no such counter; nothing
    # explored in the window; another kind of run; nothing at all
    {**OBS, "registry_after": {"counters": {
        "engine_supersteps_total": 5.0, 'engine_paths_total{tx="2"}': 9.0}}},
    {**OBS, "registry_after": OBS["registry_before"]},
    {**OBS, "kind": "serve"},
    {"kind": "campaign"},
], ids=["parent", "idle", "serve", "empty"])
def test_reader_finds_nothing_and_does_not_raise(name, obs):
    assert reader(name).read(obs) is None


def test_the_cell_lists_its_readers_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in run.metrics_of_cell(bench, CELL,
                                                    "per_layer")]
    twocall = [m["name"] for m in run.metrics_of_cell(
        bench, "twocall.campaign", "per_layer")]
    assert set(names) == set(twocall) | set(NAMES)
    assert len(names) == len(set(names))
    for m in bench["per_layer"]:
        if m["name"] in NAMES:
            assert m["workloads"] == [CELL] and m["layer"] == "engine"
            assert (m["moves"], m["source"]) == ("contracts_per_min",
                                                 "program_counter")
    assert [m["name"] for m in run.metrics_of_cell(
        bench, CELL, "end_to_end")] == ["contracts_per_min", "setup_s"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["traffic"], entry["chips"]) == ("campaign-closed-create", 1)
    config = run.load_cell(ROOT, CELL).config
    assert config["corpus"] == "dynargs-v1"
    assert "dynamic_arguments" in config["guarantees"]
    assert set(config["controls"]) == {"module_withheld", "one_transaction"}
    # every cell the benchmark had keeps the metrics it had
    for cell_ in ("fullsuite.campaign", "intarith.campaign",
                  "deployed.campaign", "twocall.campaign"):
        assert not set(NAMES) & {m["name"] for m in run.metrics_of_cell(
            bench, cell_, "per_layer")}
