"""Corpus ``twocall-v1`` and cell ``twocall.campaign``: the corpus is
what it says it is (the repo's plain EVM runs every constructor, every
witness and its prefixes, and every ordered pair of calls on the safe
siblings at their real function counts), the engine's verdicts equal
the labels and its three-step sequences replay there, the seam's
admission step accounts for every state it was handed, two states it
merges are the same state to the plain EVM, and ``correct`` comes out
false where the second transaction is taken away. The helpers are
``test_bench_deployed.py``'s (the corpora share their blocks and their
accounts); the engine runs once at that file's test-limits shape, and
once at a real shape.
"""

import copy
import itertools
import json
import os
import random
import sys

import pytest

import test_bench_deployed as base
from bench_paths import BENCH, ROOT, load

tc = load("corpora/twocall-v1.py", "bench_twocall_v1")
run = load("run.py", "bench_run_twocall")
verdicts = load("verdicts.py", "bench_verdicts_twocall")

CELL = "twocall.campaign"
KINDS = ("reinit_kill", "owner_change_unprotected", "init_once_safe",
         "kill")
WORDS = (tc.STRANGER, tc.CREATOR, 0, 1, tc.M256)
effect, deploy, call, SELECTOR = (base.effect, base.deploy, base.call,
                                  base.SELECTOR)


def both_sets(seed, max_code=24576):
    return tc.batch(seed, 0, max_code) + tc.batch(seed, 1, max_code)


def functions(c) -> list:
    return [int.from_bytes(m, "big") for m, _ in SELECTOR.findall(c["code"])]


# --- the corpus ------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_same_seed_same_stream_and_every_seed_the_same_work(seed):
    a, b = tc.batch(seed, 3), tc.batch(seed, 3)
    assert a == b
    other = tc.batch(seed + 1, 3)
    assert [c["creation"] for c in a] != [c["creation"] for c in other]
    shape = [sorted((c["kind"], len(functions(c))) for c in x)
             for x in (a, other)]
    assert shape[0] == shape[1]
    assert len({tuple(c["kind"] for c in tc.batch(s, 3))
                for s in range(6)}) > 1
    sets = [sorted((c["kind"], len(functions(c)))
                   for c in tc.batch(seed, bi)) for bi in range(4)]
    assert sets[0] == sets[2] != sets[1] == sets[3]
    for batch in sets[:2]:
        kinds = [k for k, _ in batch]
        assert [kinds.count(k) for k in KINDS] == [2, 2, 3, 1]
        assert all(20 <= n <= 60 for _, n in batch)
        # the flawed ones at four function counts, one the WalletLibrary's
        flawed = [n for k, n in batch if k in tc.TWO_CALL]
        assert len(set(flawed)) == 4 and min(flawed) <= 21 < 50 < max(flawed)


@pytest.mark.parametrize("max_code", [24576, 512])
def test_creation_code_returns_the_runtime_code_and_writes_the_state(
        max_code):
    for c in both_sets(13, max_code):
        storage = deploy(c)     # asserts the code returned is c["code"]
        assert storage[tc.OWNER_SLOT] == tc.CREATOR
        assert storage[tc.INIT_SLOT] == 1
        assert storage[tc.SUPPLY_SLOT] == tc.SUPPLY and len(storage) == 4
        assert c["creation"].endswith(c["code"])
        assert len(c["creation"]) <= max_code
        if max_code == 24576:
            assert 3000 <= len(c["code"]) <= 19500 + 43, len(c["code"])
    sizes = sorted(len(c["code"]) for c in both_sets(13))
    assert sizes[0] < 3600 and sizes[-1] > 17000


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
        assert bool(c["witness"]) == (kind != "init_once_safe")
        for swc, steps in c["witness"].items():
            assert len(steps) == (2 if kind in tc.TWO_CALL else 1)
            assert tc.TWO_CALL.get(kind, "106") == swc
            assert effect(swc, c, steps), (c["name"], swc)
            for k in range(len(steps)):
                assert not effect(swc, c, steps[:k]), (c["name"], swc, k)
            # a second call alone does not do it either
            assert len(steps) == 1 or not effect(swc, c, steps[1:])
            seen += 1
    assert seen == {"init_once_safe": 0, "kill": 2}.get(kind, 4)


def after(c, storage, data):
    """The storage a stranger's call leaves, or None where it reverts."""
    res, _ = call(c, storage, data)
    return None if res.reverted else res.storage


def broke(c, storage, data) -> bool:
    res, sent = call(c, storage, data)
    return not res.reverted and (res.selfdestructed or any(
        to == tc.STRANGER and v > 0 for to, v in sent))


@pytest.mark.parametrize("which", [0, 1, 2])
def test_no_pair_of_calls_breaks_the_safe_sibling(which):
    """Every ordered pair of its external functions, called by a
    stranger with adversarial words, neither destroys it nor pays the
    stranger: all pairs at 24 and 38 functions, and at 60 every pair
    with one of the sibling's own three functions in it (the other 57
    are the blocks the smaller two run in full). The second call is run
    once from every storage a first call can leave; with the
    ``initialized`` flag cleared (no constructor) one pair does break
    it."""
    c = sorted((c for c in tc.batch(33, 0) if c["kind"] == "init_once_safe"),
               key=lambda c: len(c["code"]))[which]
    sels = functions(c)
    assert len(sels) == (24, 38, 60)[which]
    # its own three: what the creator can kill or be paid by, and what
    # sets the owner where no constructor ran
    own = {f for f in sels if broke(c, deploy(c), tc.calldata(f))
           or tc.OWNER_SLOT in (after(c, {}, tc.calldata(f, (1,))) or {})}
    own |= {f for f in sels
            if (lambda r: r[0].selfdestructed or r[1])(
                call(c, deploy(c), tc.calldata(f), tc.CREATOR))}
    assert len(own) == 3, own
    calls = [(f, tc.calldata(f, (w, w, w))) for f in sels for w in WORDS]
    for deployed in (True, False):
        start = deploy(c) if deployed else {}
        left = {json.dumps(sorted(start.items())): (start, True)}
        for f, data in calls:
            s = after(c, start, data)
            if s is not None:
                key = json.dumps(sorted(s.items()))
                left[key] = (s, left.get(key, (s, False))[1] or f in own)
        hit = False
        for storage, after_own in left.values():
            for f, data in calls:
                if which < 2 or after_own or f in own:
                    hit |= broke(c, storage, data)
        assert hit is not deployed, (c["name"], deployed, len(left))


# --- the engine against the plain EVM --------------------------------------

def twins(rng):
    """A lean contract of the corpus's blocks in which two functions
    leave the same storage (both clear the constructor's ``initialized``
    flag) and a third lets whoever calls destroy it once that is clear:
    the two end states are one to every later call."""
    def unlock_a(c, L):
        return [*tc.dep.nonpayable(L), 0, tc.INIT_SLOT, "SSTORE", "STOP"]

    def unlock_b(c, L):
        return [*tc.dep.nonpayable(L), 0, tc.INIT_SLOT, "SSTORE", "STOP"]

    def kill_unlocked(c, L):
        return [*tc.dep.nonpayable(L), tc.INIT_SLOT, "SLOAD", "ISZERO",
                *tc.dep.require(L, "open", "", True), "CALLER",
                "SELFDESTRUCT"]

    code, named = tc.dep.runtime(
        rng, [unlock_a, unlock_b, kill_unlocked, tc.dep.wild.deposit], 4,
        True)
    return {"name": "twins", "code": code, "kind": "twins",
            "creation": tc.dep.creation(code, True), "must_report": ["106"],
            "must_not_report": [], "named": named,
            "witness": {"106": [tc.calldata(named["unlock_a"]),
                                tc.calldata(named["kill_unlocked"])]}}


def seams(records) -> list:
    return [r for r in records if r.get("kind") == "span"
            and r["name"] == "tx_seam"]


def explore():
    """Both lean sets, the twins in the place of one safe sibling,
    through the device phase at ``test_bench_deployed.py``'s shape (8
    contracts x 16 lanes, creation transaction, two message calls,
    concrete storage, test limits): (contracts, report, spans) a
    batch."""
    import mythril_tpu  # noqa: F401
    from mythril_tpu.analysis import fire_lasers
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.mythril.campaign import CorpusCampaign
    from mythril_tpu.obs import trace as obs_trace
    from mythril_tpu.symbolic import SymSpec

    sys.path.insert(0, BENCH)
    import hostcb_cache

    # as the cell's driver does: this shape's executables are in the
    # workers' one compile cache WITH their host callbacks
    hostcb_cache.install()
    camp = CorpusCampaign([], batch_size=8, lanes_per_contract=base.LANES,
                          limits=TEST_LIMITS, spec=SymSpec(storage=False),
                          max_steps=128, transaction_count=2)
    seed = 2 ** 31 + 91
    first = tc.batch(seed, 0, 512)
    first[next(k for k, c in enumerate(first)
               if c["kind"] == "init_once_safe")] = twins(random.Random(5))
    out = []
    for k, cs in enumerate((first, tc.batch(seed, 1, 512))):
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


def check_engine(cs, report, spans) -> dict:
    """Verdicts equal the labels, every finding the labels ask for
    replays in the plain EVM (a two-call one in three steps, and not
    without its first call), and the seams' fates add up. Returns the
    sequences replayed, by kind."""
    reported = {c["name"]: set() for c in cs}
    for i in report.issues:
        if i.contract in reported:
            reported[i.contract].add(str(i.swc_id))
    rows = verdicts.compare(cs, reported)
    assert not [r for r in rows if verdicts.wrong(r)], rows
    by_name = {c["name"]: c for c in cs}
    replayed = {}
    for i in report.issues:
        c = by_name.get(i.contract)
        if c is None or str(i.swc_id) not in c["must_report"]:
            continue
        seq = i.transaction_sequence
        assert int(seq[0]["caller"], 16) == tc.CREATOR
        assert {int(t["caller"], 16) for t in seq[1:]} == {tc.STRANGER}
        steps = [bytes.fromhex(t["input"][2:]) for t in seq[1:]]
        assert effect(str(i.swc_id), c, steps), (c["name"], seq)
        if c["kind"] != "kill":
            assert len(seq) == 3, (c["name"], seq)
            assert not effect(str(i.swc_id), c, steps[1:])
        replayed[c["kind"]] = replayed.get(c["kind"], 0) + 1
    for s in seams(spans):
        assert s["passed"] == (s["admitted"] + s["merged"] + s["deferred"]
                               + s["dropped"]), s
        assert s["carried"] == s["passed"] - s["merged"] - s["dropped"]
    return replayed


def test_engine_against_the_plain_evm_at_the_test_limits():
    """One engine run for the file's test-limits checks (a fixture would
    be built again in every xdist worker a test of it lands on). The
    verdicts, the sequences and the fates of both sets; and the twins:
    their two ``unlock`` end states have the same storage, the seam
    starts the next call from one, the finding replays, and in the plain
    EVM either one gives the same (selector, word, how it halted, what
    it left) for every function and word."""
    explored = explore()
    replayed = {}
    for cs, report, spans in explored:
        for kind, n in check_engine(cs, report, spans).items():
            replayed[kind] = replayed.get(kind, 0) + n
    assert replayed == {"reinit_kill": 4, "owner_change_unprotected": 4,
                        "kill": 2, "twins": 1}, replayed
    cs, _, spans = explored[0]
    c = next(c for c in cs if c["kind"] == "twins")
    seam = seams(spans)[-1]
    assert seam["tx"] == 1 and seam["merged"] >= 1, seam
    assert seam["admitted"] < seam["passed"]

    def halts(first):
        storage = after(c, deploy(c), tc.calldata(c["named"][first]))
        out = set()
        for f, w in itertools.product(functions(c), WORDS):
            res, _ = call(c, storage, tc.calldata(f, (w, w)))
            out.add((f, w, res.reverted, res.selfdestructed,
                     json.dumps(sorted(res.storage.items()))))
        return out

    assert halts("unlock_a") == halts("unlock_b")
    assert any(dead for _, _, _, dead, _ in halts("unlock_a"))


def test_engine_at_a_real_shape_reports_the_wallets_two_call_flaw():
    """The ``WalletLibrary``'s own shape through the normal path at the
    cell's arguments, two contracts a batch: a ``reinit_kill`` of 20
    functions and an ``init_once_safe`` of 24, 128 lanes each, default
    limits. Alone with 128 lanes a contract of 14 functions used to miss
    the flaw (``corpus-deployed.json``). The one engine shape this file
    compiles for itself."""
    import mythril_tpu  # noqa: F401
    from mythril_tpu.obs import trace as obs_trace

    driver = load("drivers/campaign.py", "bench_driver_twocall")
    cfg = copy.deepcopy(run.load_cell(ROOT, CELL).config)
    cfg["analyze_args"] += ["--batch-size", "2"]
    args = driver.parse_analyze_args(cfg)
    args.pipeline = False
    cs = [c for c in tc.batch(2 ** 31 + 36, 0)
          if (c["kind"], len(functions(c))) in (("reinit_kill", 20),
                                                ("init_once_safe", 24))]
    assert len(cs) == 2
    tracer = obs_trace.configure(buffer=True)
    try:
        res = driver.build_campaign(
            args, [(c["name"], c["code"], c["creation"]) for c in cs]).run()
        spans = tracer.drain_buffer()
    finally:
        obs_trace.close()
    assert list(res.batch_status) == ["ok"] and not res.quarantined
    reported = driver.swc_sets(res.issues)
    rows = verdicts.compare(cs, {c["name"]: reported.get(c["name"], set())
                                 for c in cs})
    assert not [r for r in rows if verdicts.wrong(r)], rows
    flawed = cs[0] if cs[0]["kind"] == "reinit_kill" else cs[1]
    found = [i for i in res.issues if i["contract"] == flawed["name"]
             and str(i["swc-id"]) == "106"]
    assert found
    for i in found:
        seq = i["tx_sequence"]
        assert len(seq) == 3
        steps = [bytes.fromhex(t["input"][2:]) for t in seq[1:]]
        assert effect("106", flawed, steps)
        assert not effect("106", flawed, steps[1:])
    seam = seams(spans)[-1]
    assert seam["passed"] == (seam["admitted"] + seam["merged"]
                              + seam["deferred"] + seam["dropped"])
    # the step acted: fewer states started than passed the pruners
    assert 0 < seam["admitted"] < seam["passed"], seam
    harvest = [r for r in spans if r.get("kind") == "span"
               and r["name"] == "harvest"][-1]
    assert sum(harvest["paths_by_contract"]) == harvest["paths"]
    assert sum(harvest["dropped_by_contract"]) == harvest["dropped"]


# --- the cell ------------------------------------------------------------------

def cell(extra=()):
    loaded = copy.deepcopy(run.load_cell(ROOT, CELL))
    loaded.config["analyze_args"] += base.SMALL + list(extra)
    return loaded


def drive(loaded, seed):
    lines = []
    out = run.run_cell(ROOT, CELL, seed, 2.0, False, require_tpu=False,
                       loaded=loaded, log=lines.append)
    return out, lines


def test_one_transaction_is_not_correct():
    loaded = cell()
    loaded.config["analyze_args"] += loaded.config["controls"][
        "one_transaction"]["args"]
    out, lines = drive(loaded, 2 ** 31 + 12)
    wrong = {(m.group(1), "missing" if "missing=['1" in ln else "extra")
             for ln in lines if ln.startswith("wrong verdict")
             for m in [base.re.search(r"t\d{6}_(\S+) ", ln)]}
    assert out["correct"] is False and out["failed"] > 0
    assert set(out["metrics"]) == {"contracts_per_min", "setup_s"}
    # all four two-call labels of a set go missing, nothing else moves
    assert wrong == {(k, "missing") for k in tc.TWO_CALL}, lines
    assert out["failed"] == out["attempted"] // 2
    assert any(ln.startswith("check programs compiled inside the "
                             "window: 0 (limit 0)") for ln in lines)


# --- the two readers, over spans and snapshots made by hand ------------------

def fates(tx, **counts):
    return {"counters": {
        f'engine_seam_states_total{{fate="{fate}",tx="{tx}"}}': float(n)
        for fate, n in counts.items()}}


def merge(*snaps):
    out = {}
    for s in snaps:
        out.update(s["counters"])
    return {"counters": out}


span = base.span
OBS = {
    "kind": "campaign", "batches": 2,
    "registry_before": merge(
        fates(0, passed=16, admitted=16),
        fates(1, passed=100, admitted=10, merged=2, deferred=88,
              dropped=80)),
    "registry_after": merge(
        fates(0, passed=32, admitted=32),
        fates(1, passed=500, admitted=70, merged=30, deferred=340,
              dropped=310)),
    "spans": [
        span("superstep", 0.30, tx=0, tx_kind="creation"),
        span("harvest", 0.01, tx=0, tx_kind="creation"),
        span("superstep", 2.00, tx=1, tx_kind="message"),
        span("rebalance", 0.01, tx=1),
        span("harvest", 0.02, tx=1, tx_kind="message"),
        # the last call of batch one: two chunks, a seam's scheduling
        # step each, and a drain whose own call lies inside its span
        span("superstep", 1.50, tx=2, tx_kind="message"),
        span("rebalance", 0.02, tx=2),
        span("superstep", 1.00, tx=2, tx_kind="message", round=1),
        span("rebalance", 0.03, tx=2),
        span("drain", 0.70, tx=2, tx_kind="message", round=1),
        span("superstep", 0.60, tx=2, tx_kind="message", drain=True),
        span("harvest", 0.02, tx=2, tx_kind="message"),
        # and of batch two
        span("superstep", 1.25, tx=2, tx_kind="message"),
        span("drain", 0.00, tx=2, tx_kind="message"),
        span("harvest", 0.02, tx=2, tx_kind="message"),
    ],
}


def reader(name: str):
    return load(f"layer_metrics/{name}.py", "bench_" + name)


@pytest.mark.parametrize("name, want", [
    ("seam_admit_share", 100.0 * (60 + 28) / 400),
    ("last_tx_s", (1.50 + 0.02 + 1.00 + 0.03 + 0.70 + 1.25) / 2),
])
def test_reader_over_a_hand_made_run(name, want):
    assert reader(name).read(OBS) == pytest.approx(want)


@pytest.mark.parametrize("name, obs", [
    # the parent of the PR that added them: no counter, and nothing
    # explored in the window
    ("seam_admit_share", {**OBS, "registry_after": {
        "counters": {"engine_supersteps_total": 5.0}}}),
    ("seam_admit_share", {**OBS, "registry_after": OBS["registry_before"]}),
    ("seam_admit_share", {"kind": "serve"}),
    # spans without ``tx``, no span at all, a transaction none ended
    ("last_tx_s", {**OBS, "spans": [
        {k: v for k, v in s.items() if k != "tx"} for s in OBS["spans"]]}),
    ("last_tx_s", {**OBS, "spans": []}),
    ("last_tx_s", {**OBS, "spans": [
        s for s in OBS["spans"] if s["name"] != "harvest"]}),
    ("last_tx_s", {**OBS, "kind": "serve"}),
])
def test_reader_finds_nothing_and_does_not_raise(name, obs):
    assert reader(name).read(obs) is None


def test_the_cell_lists_its_readers_and_every_reader_of_the_deploying_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in run.metrics_of_cell(bench, CELL,
                                                    "per_layer")]
    deployed = [m["name"] for m in run.metrics_of_cell(
        bench, "deployed.campaign", "per_layer")]
    assert names == deployed + ["seam_admit_share", "last_tx_s"]
    assert [m["name"] for m in run.metrics_of_cell(
        bench, CELL, "end_to_end")] == ["contracts_per_min", "setup_s"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["traffic"], entry["chips"]) == ("campaign-closed-create", 1)
    config = run.load_cell(ROOT, CELL).config
    assert config["corpus"] == "twocall-v1"
    assert "second_transaction_at_scale" in config["guarantees"]
    assert set(config["controls"]) == {"module_withheld", "one_transaction"}
