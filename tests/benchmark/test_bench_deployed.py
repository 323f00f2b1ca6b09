"""Corpus ``deployed-v1`` and cell ``deployed.campaign``: the corpus is
what it says it is (the repo's plain EVM runs every constructor, every
witness and every pair of calls on the safe sibling), the engine's
deploy leaves the plain EVM's storage and its two-transaction findings
replay there, and ``correct`` comes out true for the sound cell and
false where the second transaction or the creation code is taken away.
All at the test limits; the engine shape is built once for the file.
"""

import copy
import itertools
import os
import re
import sys

import pytest

from bench_paths import BENCH, ROOT, load

sys.path.insert(0, os.path.join(ROOT, "tests"))
from pyevm_ref import RefEnv, RefEVM  # noqa: E402

dep = load("corpora/deployed-v1.py", "bench_deployed_v1")
run = load("run.py", "bench_run_deployed")
verdicts = load("verdicts.py", "bench_verdicts_deployed")

CELL = "deployed.campaign"
LANES = 16
SMALL = ["--limits-profile", "test", "--lanes-per-contract", str(LANES),
         "--max-steps", "128"]
TWO_TX = {"reinit_kill": "106", "owner_change_unprotected": "105"}
SELECTOR = re.compile(rb"\x80\x63(....)\x14\x61(..)\x57", re.S)
WORDS = (dep.STRANGER, 1 << 255, 0, dep.M256)


class Probe(RefEVM):
    """The plain EVM, noting the value each CALL sent: (to, value)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.sent = []

    def step(self):
        op = self.code[self.pc] if self.pc < len(self.code) else 0
        if op == 0xF1 and len(self.stack) >= 7:
            self.sent.append((self.stack[-2], self.stack[-3]))
        super().step()


def deploy(c) -> dict:
    """The storage the constructor leaves; the code it returns is
    ``c["code"]``."""
    env = RefEnv()
    env.caller = env.origin = dep.CREATOR
    res = RefEVM(c["creation"], b"", env=env).run(max_steps=10000)
    assert res.halted and not (res.error or res.reverted), c["name"]
    assert res.retval == c["code"], c["name"]
    return res.storage


def call(c, storage, data, caller=dep.STRANGER):
    env = RefEnv()
    env.caller = env.origin = caller
    evm = Probe(c["code"], data, env=env, storage=storage)
    res = evm.run(max_steps=5000)
    assert not res.error, c["name"]
    return res, evm.sent


def effect(swc: str, c, steps, caller=dep.STRANGER) -> bool:
    """Whether the calls ``steps``, from a fresh deploy, end in what
    SWC ``swc`` flags."""
    storage, res, sent = deploy(c), None, []
    for data in steps:
        res, sent = call(c, storage, data, caller)
        if not res.reverted:
            storage = res.storage
    if res is None or res.reverted:
        return False
    return {
        "106": res.selfdestructed,
        "105": any(to == caller and value > 0 for to, value in sent),
        "104": bool(sent),
        "101": storage.get(dep.SUPPLY_SLOT, 0) < dep.SUPPLY,
    }[swc]


def both_sets(seed, max_code=24576):
    return dep.batch(seed, 0, max_code) + dep.batch(seed, 1, max_code)


# --- the corpus ------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_same_seed_same_stream_and_every_seed_the_same_work(seed):
    a, b = dep.batch(seed, 3), dep.batch(seed, 3)
    assert a == b
    other = dep.batch(seed + 1, 3)
    assert [c["creation"] for c in a] != [c["creation"] for c in other]
    assert sorted(c["kind"] for c in a) == sorted(c["kind"] for c in other)
    assert sorted(len(SELECTOR.findall(c["code"])) for c in a) == sorted(
        len(SELECTOR.findall(c["code"])) for c in other)
    assert len({tuple(c["kind"] for c in dep.batch(s, 3))
                for s in range(6)}) > 1
    kinds = [sorted(c["kind"] for c in dep.batch(seed, bi))
             for bi in range(4)]
    assert kinds[0] == kinds[2] != kinds[1] == kinds[3]
    for bi in (0, 1):
        batch = dep.batch(seed, bi)
        assert len(batch) == dep.BATCH == 8
        assert sum(c["kind"] == "init_once_safe" for c in batch) == 1
        # 4 flawed, 2 of them through two calls only
        assert sum(bool(c["must_report"]) for c in batch) == 4
        assert sum(c["kind"] in TWO_TX for c in batch) == 2


@pytest.mark.parametrize("max_code", [24576, 512])
def test_creation_code_returns_the_runtime_code_and_writes_the_state(
        max_code):
    sizes = []
    for c in both_sets(13, max_code):
        storage = deploy(c)
        assert storage[dep.OWNER_SLOT] == dep.CREATOR
        assert storage[dep.INIT_SLOT] == 1
        assert storage[dep.SUPPLY_SLOT] == dep.SUPPLY
        assert len(storage) == 4 and dep.SUPPLY in [
            v for k, v in storage.items() if k > 1 << 200]
        assert len(c["creation"]) <= max_code
        assert c["creation"].endswith(c["code"])
        sizes.append((c["kind"] in dep.CURATED, len(c["code"]),
                      len(SELECTOR.findall(c["code"]))))
    if max_code == 24576:
        wild = [s for s in sizes if not s[0]]
        curated = [s for s in sizes if s[0]]
        assert len(wild) == 10 and len(curated) == 6
        assert min(s[1] for s in wild) >= 3000
        assert max(s[1] for s in wild) >= 15000
        assert min(s[2] for s in wild) >= 20 and max(s[2] for s in wild) == 60
        assert all(300 <= s[1] <= 8192 and 2 <= s[2] <= 20 for s in curated)


@pytest.mark.parametrize("max_code", [24576, 512])
def test_each_witness_reaches_its_flaw_and_no_shorter_prefix_does(max_code):
    seen = set()
    for c in both_sets(21, max_code):
        assert sorted(c["witness"]) == c["must_report"]
        assert not set(c["must_report"]) & set(c["must_not_report"])
        for swc, steps in c["witness"].items():
            assert effect(swc, c, steps), (c["name"], swc)
            for k in range(len(steps)):
                assert not effect(swc, c, steps[:k]), (c["name"], swc, k)
            # a second call alone does not do it either
            assert len(steps) == 1 or not effect(swc, c, steps[1:])
            seen.add((c["kind"], swc, len(steps)))
    assert {(k, s, 2) for k, s in TWO_TX.items()} <= seen
    assert {("sweep", "105", 1), ("exec_unchecked", "104", 1),
            ("mint_supply_unchecked", "101", 1)} <= seen


def test_no_pair_of_calls_breaks_the_safe_sibling():
    """Every ordered pair of its functions, called by a stranger with
    adversarial words, neither destroys it nor pays the stranger; with
    the ``initialized`` flag cleared (no constructor) one pair does."""
    for c in both_sets(33, 512):
        if c["kind"] != "init_once_safe":
            continue
        sels = [int.from_bytes(m, "big")
                for m, _ in SELECTOR.findall(c["code"])]
        assert len(sels) == 4
        broke_undeployed = False
        for f1, f2 in itertools.product(sels, repeat=2):
            for w1, w2 in itertools.product(WORDS, repeat=2):
                steps = [dep.calldata(f1, (w1, w1)),
                         dep.calldata(f2, (w2, w2))]
                assert not effect("106", c, steps)
                assert not effect("105", c, steps)
                r1, _ = call(c, {}, steps[0])
                r2, sent = call(c, {} if r1.reverted else r1.storage,
                                steps[1])
                broke_undeployed |= not r2.reverted and (
                    r2.selfdestructed or any(v > 0 for _, v in sent))
        assert broke_undeployed


# --- the engine against the plain EVM, at the test limits -------------------

@pytest.fixture(scope="module")
def explored():
    """Both sets of the corpus through the device phase as the cell's
    campaign runs it (batches of 8, creation transaction, two message
    calls, concrete storage): (contracts, wrapper, report) a batch."""
    import mythril_tpu  # noqa: F401
    from mythril_tpu.analysis import fire_lasers
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.mythril.campaign import CorpusCampaign
    from mythril_tpu.symbolic import SymSpec

    camp = CorpusCampaign([], batch_size=8, lanes_per_contract=LANES,
                          limits=TEST_LIMITS, spec=SymSpec(storage=False),
                          max_steps=128, transaction_count=2)
    both = both_sets(2 ** 31 + 77, 512)
    out = []
    for k in range(0, len(both), 8):
        cs = both[k:k + 8]
        sym = camp._explore_batch(
            k // 8, [c["name"] for c in cs], [c["code"] for c in cs],
            creations=[c["creation"] for c in cs])
        out.append((cs, sym, fire_lasers(sym)))
    return out


def test_engine_deploy_leaves_the_plain_evms_storage(explored):
    import numpy as np

    from mythril_tpu.ops import u256

    for cs, sym, _ in explored:
        sf = sym.tx_contexts[0].sf      # the end of the creation run
        used = np.asarray(sf.base.st_used)
        keys = np.asarray(sf.base.st_keys)
        vals = np.asarray(sf.base.st_vals)
        assert not np.asarray(sf.st_val_sym).any()
        for ci, c in enumerate(cs):
            lane = ci * LANES
            assert bool(np.asarray(sf.base.halted)[lane])
            got = {u256.to_int(keys[lane, k]): u256.to_int(vals[lane, k])
                   for k in range(used.shape[1]) if used[lane, k]}
            assert got == deploy(c), c["name"]


def test_engine_verdicts_equal_the_labels_and_sequences_replay(explored):
    replayed = 0
    for cs, _, report in explored:
        reported = {c["name"]: set() for c in cs}
        for i in report.issues:
            if i.contract in reported:
                reported[i.contract].add(str(i.swc_id))
        rows = verdicts.compare(cs, reported)
        assert not [r for r in rows if verdicts.wrong(r)], rows
        by_name = {c["name"]: c for c in cs}
        for i in report.issues:
            c = by_name.get(i.contract)
            if c is None or TWO_TX.get(c["kind"]) != str(i.swc_id):
                continue
            seq = i.transaction_sequence
            assert len(seq) == 3, (c["name"], seq)
            assert int(seq[0]["caller"], 16) == dep.CREATOR
            callers = {int(t["caller"], 16) for t in seq[1:]}
            assert callers == {dep.STRANGER}
            steps = [bytes.fromhex(t["input"][2:]) for t in seq[1:]]
            assert effect(str(i.swc_id), c, steps), (c["name"], seq)
            assert not effect(str(i.swc_id), c, steps[1:])
            replayed += 1
    assert replayed >= 4


# --- the cell ------------------------------------------------------------------

def cell(extra=()):
    loaded = copy.deepcopy(run.load_cell(ROOT, CELL))
    loaded.config["analyze_args"] += SMALL + list(extra)
    return loaded


def drive(loaded, seed):
    lines = []
    out = run.run_cell(ROOT, CELL, seed, 2.0, False, require_tpu=False,
                       loaded=loaded, log=lines.append)
    return out, lines


def wrong_kinds(lines, what):
    return {m.group(1) for ln in lines if ln.startswith("wrong verdict")
            and what in ln
            for m in [re.search(r"d\d{6}_(\S+) ", ln)] if m}


def test_sound_run_is_correct(explored):
    out, lines = drive(cell(), 2 ** 31 + 11)
    assert out["correct"] is True, lines
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"contracts_per_min", "setup_s"}
    for kind in ("reinit_kill", "init_once_safe", "precompile_gate_safe"):
        assert any(f"kind={kind} " in ln and "(limit 0)" in ln
                   for ln in lines), kind
    assert any(ln.startswith("check programs compiled inside the "
                             "window: 0 (limit 0)") for ln in lines)


def test_one_transaction_is_not_correct(explored):
    loaded = cell()
    loaded.config["analyze_args"] += loaded.config["controls"][
        "one_transaction"]["args"]
    out, lines = drive(loaded, 12)
    assert out["correct"] is False and out["failed"] > 0
    assert wrong_kinds(lines, "missing=['106']") == {"reinit_kill"}
    assert wrong_kinds(lines, "missing=['105']") == {
        "owner_change_unprotected"}
    assert not wrong_kinds(lines, "extra=['1")


def test_creation_code_withheld_is_not_correct(monkeypatch):
    """The pairs go on as ``campaign``'s driver makes them: every
    contract starts from empty storage, so the safe sibling's
    ``require(!initialized)`` guards nothing."""
    real = run.load_module

    def withheld(path, name):
        mod = real(path, name)
        if name == "driver_campaign_create":
            mod.records = lambda pairs, creation: list(pairs)
        return mod

    monkeypatch.setattr(run, "load_module", withheld)
    out, lines = drive(cell(), 13)
    assert out["correct"] is False and out["failed"] > 0
    extra = wrong_kinds(lines, "extra=[")
    assert extra == {"init_once_safe"}, lines
    assert any("init_once_safe" in ln and "'106'" in ln for ln in lines
               if ln.startswith("wrong verdict"))


# --- the two readers, over spans and snapshots made by hand ------------------

def reader(name: str):
    return load(f"layer_metrics/{name}.py",
                "bench_" + name.replace(".", "_"))


def span(name, dur, **attrs):
    return {"kind": "span", "name": name, "mono": 0.0, "dur": dur,
            "tid": 1, **attrs}


def counters(paths, dropped):
    out = {}
    for tx, (p, d) in enumerate(zip(paths, dropped)):
        out[f'engine_paths_total{{tx="{tx}"}}'] = float(p)
        out[f'engine_dropped_forks_total{{tx="{tx}"}}'] = float(d)
    return {"counters": out}


OBS = {
    "kind": "campaign", "batches": 2,
    "registry_before": counters([16, 100, 400], [0, 10, 100]),
    "registry_after": counters([32, 300, 1000], [0, 30, 500]),
    "spans": [
        # two batches: the creation call, its harvest and its handoff
        span("superstep", 0.30, tx=0, tx_kind="creation"),
        span("drain", 0.02, tx=0, tx_kind="creation"),
        span("harvest", 0.03, tx=0, tx_kind="creation"),
        span("tx_seam", 0.05, tx=0, tx_kind="creation", carried=8),
        span("superstep", 0.50, tx=0, tx_kind="creation"),
        # a drain's own calls lie inside its span
        span("drain", 0.24, tx=0, tx_kind="creation"),
        span("superstep", 0.20, tx=0, tx_kind="creation", drain=True),
        span("harvest", 0.01, tx=0, tx_kind="creation"),
        span("tx_seam", 0.05, tx=0, tx_kind="creation", carried=8),
        # message calls are not deployment
        span("superstep", 1.60, tx=1, tx_kind="message"),
        span("harvest", 0.40, tx=1, tx_kind="message"),
        span("tx_seam", 0.07, tx=1, tx_kind="message", carried=300),
    ],
}


@pytest.mark.parametrize("name, want", [
    ("creation_tx_s", (0.30 + 0.02 + 0.03 + 0.05
                       + 0.50 + 0.24 + 0.01 + 0.05) / 2),
    ("last_tx_fork_admit_share", 100.0 * 600 / (600 + 400)),
])
def test_reader_over_a_hand_made_run(name, want):
    assert reader(name).read(OBS) == pytest.approx(want)


@pytest.mark.parametrize("name, obs", [
    # the parent of the PR that added them: no tx_kind, no counters
    ("creation_tx_s", {**OBS, "spans": [
        {k: v for k, v in s.items() if k != "tx_kind"}
        for s in OBS["spans"] if s["name"] != "tx_seam"]}),
    ("last_tx_fork_admit_share", {**OBS, "registry_after": {
        "counters": {"engine_supersteps_total": 5.0}}}),
    # a campaign that deploys nothing
    ("creation_tx_s", {**OBS, "spans": [
        s for s in OBS["spans"] if s["tx_kind"] == "message"]}),
    # nothing explored in the window
    ("last_tx_fork_admit_share", {**OBS,
                                  "registry_after": OBS["registry_before"]}),
    ("creation_tx_s", {**OBS, "kind": "serve"}),
    ("last_tx_fork_admit_share", {"kind": "serve"}),
])
def test_reader_finds_nothing_and_does_not_raise(name, obs):
    assert reader(name).read(obs) is None


def test_the_cell_lists_both_readers_and_the_old_ones():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in run.metrics_of_cell(bench, CELL,
                                                    "per_layer")]
    assert names[-2:] == ["creation_tx_s", "last_tx_fork_admit_share"]
    assert len(names) == 16 and "xla_compile_s" in names
    for other in ("fullsuite.campaign", "intarith.campaign"):
        assert len(run.metrics_of_cell(bench, other, "per_layer")) == 14
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       "campaign_create.py"))
