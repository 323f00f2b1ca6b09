"""Corpus ``linked-v1`` and cell ``linked.campaign``: the corpus is what
it says it is (the plain multi-account EVM, ``tests/pyevm_world.py``,
deploys every system, replays every witness and the same calls on the
safe siblings, and counts the labelled paths' steps), the engine's
frames leave the reference's storage, balances and return data on
concrete inputs, its verdicts equal the labels at the test limits with
the manifests and lack exactly the two flawed kinds without them, what
it reports replays, the five readers read a hand-made run and nothing,
and a batch without systems gives the parent's reports byte for byte.

The engine runs at the test limits with ``max_accounts`` 6: a system of
four needs attacker, creator and four members in a lane's table.
"""

import copy
import dataclasses
import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

from bench_paths import BENCH, ROOT, load

sys.path.insert(0, os.path.join(ROOT, "tests"))
from pyevm_world import World  # noqa: E402

lk = load("corpora/linked-v1.py", "bench_linked_v1")
run = load("run.py", "bench_run_linked")
verdicts = load("verdicts.py", "bench_verdicts_linked")

CELL = "linked.campaign"
LANES = 16
SMALL = ["--limits-profile", "test", "--lanes-per-contract", str(LANES)]
FLAWED = {"depth3_theft": "weth", "hop_flag_twocall": "pair"}


def limits6():
    from mythril_tpu.config import TEST_LIMITS

    return dataclasses.replace(TEST_LIMITS, max_accounts=6)


def both_sets(seed, max_code=24576):
    return lk.batch(seed, 0, max_code) + lk.batch(seed, 1, max_code)


def systems_of(contracts):
    return [contracts[k:k + 4] for k in range(0, len(contracts), 4)]


def kind_of(members):
    return members[0]["kind"].split(".")[0]


def deploy(members) -> World:
    w = World(eoas=(lk.CREATOR, lk.STRANGER))
    for c in members:
        assert w.deploy(c["address"], c["creation"], lk.CREATOR), c["name"]
        assert w.code(c["address"]) == c["code"], c["name"]
    return w


def attack(members):
    """The witness, or the same calls on the safe sibling."""
    flawed = [c for c in members if c["must_report"]]
    if flawed:
        return flawed[0]["witness"]["105"]
    sel = [c for c in members if c["role"] == "router"][0]["selectors"]
    if kind_of(members).startswith("depth3"):
        return [lk.calldata(sel["router_sweep_eth"],
                            (lk.STRANGER, 10 ** 15))]
    return [lk.calldata(sel["router_nominate"], (lk.STRANGER,)),
            lk.calldata(sel["router_payout"], (10 ** 15,))]


def replay(members, steps, world=None):
    """(ether that reached the stranger, steps of each call)"""
    w = world or deploy(members)
    router = [c for c in members if c["role"] == "router"][0]
    took = []
    for data in steps:
        s0 = w.steps
        w.message(lk.STRANGER, router["address"], 0, data, lk.STRANGER)
        took.append(w.steps - s0)
    return sum(v for _, to, v in w.sent if to == lk.STRANGER), took, w


# --- the corpus ------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_same_seed_same_stream_and_every_seed_the_same_work(seed):
    a, b = lk.batch(seed, 3), lk.batch(seed, 3)
    assert a == b
    other = lk.batch(seed + 1, 3)
    assert [c["creation"] for c in a] != [c["creation"] for c in other]
    for batch in (a, other):
        assert len(batch) == lk.BATCH
        for members in systems_of(batch):
            assert [c["role"] for c in members] == list(lk.ROLES)
            assert len({c["system"] for c in members}) == 1
            assert len({c["address"] for c in members}) == 4
    kinds = [sorted(kind_of(m) for m in systems_of(lk.batch(seed, bi)))
             for bi in range(4)]
    assert kinds[0] == kinds[2] != kinds[1] == kinds[3]
    assert sorted(kinds[0] + kinds[1]) == sorted(lk.KINDS)
    assert len({tuple(kind_of(m) for m in systems_of(lk.batch(s, 0)))
                for s in range(8)}) == 2        # the seed orders a batch


def test_members_have_the_shapes_the_configuration_states():
    want = {"router": (18, 24, 16384, 24576), "pair": (22, 28, 8192, 12288),
            "token": (9, 14, 1500, 5120), "weth": (8, 11, 1500, 3072)}
    for c in both_sets(2 ** 31 + 9):
        lo, hi, small, big = want[c["role"]]
        n = len(re.findall(rb"\x80\x63....\x14\x61..\x57", c["code"], re.S))
        assert lo <= n <= hi, (c["name"], n)
        assert small <= len(c["code"]) <= big, (c["name"], len(c["code"]))
        assert c["creation"].find(c["code"]) > 0
        assert (c["must_report"] == ["105"]) == (
            FLAWED.get(kind_of([c])) == c["role"])
        assert not set(c["must_report"]) & set(c["must_not_report"])
        assert {"106"} <= set(c["must_not_report"])
    doc = lk.manifest(lk.batch(5, 0)[:4])
    assert [m["name"].rsplit("__")[-1] for m in doc["members"]] == list(
        lk.ROLES)
    assert all(int(m["address"], 16) >> 144 == 0x51A7
               for m in doc["members"])


@pytest.mark.parametrize("max_code", [24576, 512])
def test_witnesses_move_the_ether_and_safe_siblings_do_not(max_code):
    seen, longest = set(), 0
    for seed in (3, 11, 2 ** 31 + 21):
        for members in systems_of(both_sets(seed, max_code)):
            kind = kind_of(members)
            steps = attack(members)
            paid, took, w = replay(members, steps)
            assert (paid > 0) == (kind in FLAWED), (kind, paid)
            longest = max(longest, *took)
            if kind in FLAWED:
                # no shorter prefix, and not the last call alone
                assert replay(members, steps[:-1])[0] == 0
                assert len(steps) == 1 or replay(members, steps[1:])[0] == 0
                assert w.deepest == (2 if kind == "depth3_theft" else 1)
            seen.add((kind, len(steps)))
    assert seen == {("depth3_theft", 1), ("depth3_guarded", 1),
                    ("hop_flag_twocall", 2), ("hop_flag_ctor_safe", 2)}
    # the labelled paths end inside --max-steps 256, dispatchers included
    assert longest <= (240 if max_code > 512 else 200)


def test_safe_siblings_are_safe_only_through_another_members_state():
    for members in systems_of(both_sets(17, 512)):
        kind = kind_of(members)
        if kind == "hop_flag_ctor_safe":
            w = deploy(members)
            pair = [c for c in members if c["role"] == "pair"][0]
            # without the pair's constructor write the two calls pay out
            w.accounts[pair["address"]].storage[lk.INIT_SLOT] = 0
            assert replay(members, attack(members), w)[0] > 0
        if kind == "depth3_guarded":
            w = deploy(members)
            router = [c for c in members if c["role"] == "router"][0]
            w.accounts[router["address"]].storage[lk.OWNER_SLOT] = lk.STRANGER
            assert replay(members, attack(members), w)[0] > 0


# --- the engine's frames against the plain EVM, on concrete inputs ------------

def concrete_run(members, data: bytes, world: World):
    """One message call from the stranger to the router over ``world``'s
    state, every input concrete: the end frontier's lane 0."""
    import mythril_tpu  # noqa: F401
    from mythril_tpu.core import Corpus, make_env
    from mythril_tpu.core.frontier import ACCT_CONTRACT0
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.ops import u256
    from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run
    import jax.numpy as jnp

    # one frame more than the test limits' two: the ether leaves weth
    # at depth 2, and a plain send there wants headroom like a frame
    L = dataclasses.replace(limits6(), call_depth=3)
    corpus = Corpus.from_images([
        ContractImage.from_bytecode(c["code"], L.max_code) for c in members])
    P = 4
    cd = np.zeros((P, L.calldata_bytes), dtype=np.uint8)
    cd[:, :len(data)] = np.frombuffer(data, dtype=np.uint8)
    active = np.zeros(P, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(
        P, L, contract_id=np.full(P, 3, np.int32), active=active,
        n_contracts=4, contract_addrs=[c["address"] for c in members],
        systems=[[0, 1, 2, 3]] * 4, calldata=cd,
        calldata_len=np.full(P, len(data), np.int32), caller=lk.STRANGER)
    # the deployed world's storage, every member's rows under its slot
    b = sf.base
    keys, vals = np.array(b.st_keys), np.array(b.st_vals)
    used, acct = np.array(b.st_used), np.array(b.st_acct)
    seq = np.array(sf.st_seq)
    n = 0
    for k, c in enumerate(members):
        for key, val in world.accounts[c["address"]].storage.items():
            keys[:, n], vals[:, n] = u256.from_int(key), u256.from_int(val)
            used[:, n], acct[:, n], seq[:, n] = True, ACCT_CONTRACT0 + k, n + 1
            n += 1
    sf = sf.replace(
        base=b.replace(st_keys=jnp.asarray(keys), st_vals=jnp.asarray(vals),
                       st_used=jnp.asarray(used), st_acct=jnp.asarray(acct)),
        st_seq=jnp.asarray(seq), st_seq_ctr=jnp.full(P, n, jnp.int32))
    spec = SymSpec(calldata=False, callvalue=False, caller=False,
                   storage=False, block_env=False)
    return sym_run(sf, make_env(P, origin=lk.STRANGER), corpus, spec, L,
                   max_steps=256)


@pytest.mark.parametrize("what", ["sweep_eth", "reserves", "nominate"])
def test_frames_leave_the_plain_evms_storage_balances_and_return_data(what):
    from mythril_tpu.core.frontier import ACCT_ATTACKER, ACCT_CONTRACT0
    from mythril_tpu.ops import u256

    want_kind = {"nominate": "hop_flag_twocall"}.get(what, "depth3_theft")
    members = [m for m in systems_of(both_sets(2 ** 31 + 33, 512))
               if kind_of(m) == want_kind][0]
    sel = members[3]["selectors"]
    data = {"sweep_eth": lambda: attack(members)[0],
            "reserves": lambda: lk.calldata(sel["router_reserves"]),
            "nominate": lambda: attack(members)[0]}[what]()
    w = deploy(members)
    out = concrete_run(members, data, w)
    ok, ret = w.message(lk.STRANGER, members[3]["address"], 0, data,
                        lk.STRANGER)
    base = out.base
    assert ok and bool(np.asarray(base.halted)[0])
    assert not bool(np.asarray(base.error)[0] | np.asarray(base.reverted)[0])
    assert int(np.asarray(base.depth)[0]) == 0
    # storage: every member's rows
    got = {}
    for k in np.flatnonzero(np.asarray(base.st_used)[0]):
        slot = int(np.asarray(base.st_acct)[0, k]) - ACCT_CONTRACT0
        got.setdefault(members[slot]["address"], {})[
            u256.to_int(np.asarray(base.st_keys)[0, k])] = u256.to_int(
                np.asarray(base.st_vals)[0, k])
    assert not np.asarray(out.st_val_sym)[0].any()
    assert got == {a: s for a, s in w.storage().items() if s}
    # balances: the members', and the stranger's
    bal = np.asarray(base.acct_bal)[0]
    for k, c in enumerate(members):
        assert u256.to_int(bal[ACCT_CONTRACT0 + k]) == w.balance(c["address"])
    assert u256.to_int(bal[ACCT_ATTACKER]) == w.balance(lk.STRANGER)
    # return data
    n = int(np.asarray(base.retval_len)[0])
    assert bytes(np.asarray(base.retval)[0, :n]) == ret
    hop = np.asarray(out.hop_stats)[0]
    from mythril_tpu.symbolic import state as st
    assert hop[st.HOP_DEPTH] == w.deepest
    assert hop[st.HOP_TRAPPED] == 0 and hop[st.HOP_INTERNAL] == {
        "sweep_eth": 2, "reserves": 1, "nominate": 1}[what]
    if what == "reserves":
        assert int.from_bytes(ret, "big") == 0      # lean: no reserves
    if what == "sweep_eth":
        assert w.sent[-1] == (members[0]["address"], lk.STRANGER, 10 ** 15)


# --- the engine's verdicts, at the test limits ------------------------------------

def records(contracts, linked=True):
    return [(c["name"], c["code"], c["creation"])
            + (({"system": c["system"], "address": c["address"]},)
               if linked else ()) for c in contracts]


def campaign(contracts, linked=True):
    import mythril_tpu  # noqa: F401
    from mythril_tpu.mythril.campaign import CorpusCampaign
    from mythril_tpu.symbolic import SymSpec

    return CorpusCampaign(
        records(contracts, linked), batch_size=8, lanes_per_contract=LANES,
        limits=limits6(), spec=SymSpec(storage=False), max_steps=256,
        transaction_count=2)


def wrong_rows(contracts, res):
    reported = {c["name"]: set() for c in contracts}
    for i in res.issues:
        reported.setdefault(i["contract"], set()).add(str(i["swc-id"]))
    return [r for r in verdicts.compare(contracts, reported)
            if verdicts.wrong(r)]


@pytest.mark.parametrize("seed", [2 ** 31 + 77, 5, 2 ** 31 + 1234, 901])
def test_verdicts_equal_the_labels_and_reported_flaws_replay(seed):
    contracts = both_sets(seed, 512)
    res = campaign(contracts).run()
    assert res.batch_status == ["ok", "ok"] and not res.quarantined
    assert not wrong_rows(contracts, res)
    by_name = {c["name"]: c for c in contracts}
    replayed = 0
    for i in res.issues:
        c = by_name[i["contract"]]
        if str(i["swc-id"]) != "105" or not c["must_report"]:
            continue
        seq = i["tx_sequence"]
        members = [m for m in contracts if m["system"] == c["system"]]
        two = c["role"] == "pair"
        assert len(seq) == (3 if two else 2), seq
        assert int(seq[0]["caller"], 16) == lk.CREATOR
        assert {int(t["caller"], 16) for t in seq[1:]} == {lk.STRANGER}
        # the sequence names the member the calls went to: the router
        steps = [bytes.fromhex(t["input"][2:]) for t in seq[1:]]
        paid, _, w = replay(members, steps)
        assert paid > 0, (c["name"], seq)
        assert len(steps) == 1 or replay(members, steps[1:])[0] == 0
        replayed += 1
    assert replayed >= 2


def test_without_the_manifests_exactly_the_two_flawed_kinds_are_missing():
    contracts = both_sets(2 ** 31 + 77, 512)
    res = campaign(contracts, linked=False).run()
    rows = wrong_rows(contracts, res)
    assert sorted(r["kind"] for r in rows) == [
        "depth3_theft.weth", "hop_flag_twocall.pair"]
    assert all(r["missing"] == ["105"] and not r["extra"] for r in rows)


# --- the cell ------------------------------------------------------------------

def cell(extra=()):
    loaded = copy.deepcopy(run.load_cell(ROOT, CELL))
    loaded.config["analyze_args"] += SMALL + list(extra)
    return loaded


def drive(loaded, seed, monkeypatch):
    import mythril_tpu.config as config

    monkeypatch.setattr(config, "TEST_LIMITS", limits6())
    lines = []
    out = run.run_cell(ROOT, CELL, seed, 2.0, False, require_tpu=False,
                       loaded=loaded, log=lines.append)
    return out, lines


def wrong_kinds(lines, what):
    return {m.group(1) for ln in lines if ln.startswith("wrong verdict")
            and what in ln
            for m in [re.search(r"s\d{6}_(\w+?)__", ln)] if m}


def test_sound_run_is_correct(monkeypatch):
    out, lines = drive(cell(), 2 ** 31 + 11, monkeypatch)
    assert out["correct"] is True, lines
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"contracts_per_min", "setup_s"}
    for kind in ("depth3_theft.weth", "hop_flag_ctor_safe.pair"):
        assert any(f"kind={kind} " in ln and "(limit 0)" in ln
                   for ln in lines), kind
    assert any(ln.startswith("check programs compiled inside the "
                             "window: 0 (limit 0)") for ln in lines)
    assert any(ln.startswith("check quarantined contracts: 0")
               for ln in lines)


def test_manifests_withheld_is_not_correct(monkeypatch):
    """The ``unlinked`` control: the records go on as
    ``campaign_create``'s driver makes them."""
    real = run.load_module

    def withheld(path, name):
        mod = real(path, name)
        if name == "driver_campaign_system":
            mod.records = lambda pairs, seen: [
                (n, code, seen[n]["creation"]) for n, code in pairs]
        return mod

    monkeypatch.setattr(run, "load_module", withheld)
    out, lines = drive(cell(), 13, monkeypatch)
    assert out["correct"] is False and out["failed"] > 0
    # the window holds both sets or, on a slow machine, the first alone
    missing = wrong_kinds(lines, "missing=['105']")
    assert missing and missing <= {"depth3_theft", "hop_flag_twocall"}
    assert not wrong_kinds(lines, "extra=['")


def test_a_program_without_systems_is_refused_at_once(monkeypatch):
    from mythril_tpu.mythril.campaign import CorpusCampaign

    def old(self, bi, names, codes, lanes=None, width=None, creations=None,
            on_first_call=None):
        raise AssertionError("never reached")

    monkeypatch.setattr(CorpusCampaign, "_explore_batch", old)
    with pytest.raises(SystemExit) as e:
        drive(cell(), 1, monkeypatch)
    assert e.value.code == 4


def test_the_cell_and_its_files_are_entries_and_files_of_their_own():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "corpus-linked",
        "traffic": "campaign-closed-system", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    entry = bench["configs"][-1]
    assert entry["name"] == "corpus-linked"
    assert entry["reduced"] == ["corpus_count", "per_contract_budget",
                                "system_size"]
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert config["source"] == entry["source"]
    assert {"unlinked", "one_transaction", "module_withheld"} <= set(
        config["controls"])
    assert "linked_system" in config["guarantees"]
    names = [m["name"] for m in run.metrics_of_cell(bench, CELL, "per_layer")]
    new = ["internal_call_share", "depth3_path_share",
           "hop_word_exact_share", "call_limit_trap_share", "system_world_s"]
    assert names[-5:] == new
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "contracts_per_min"
    dyn = [m["name"] for m in run.metrics_of_cell(bench, "dynargs.campaign",
                                                   "per_layer")]
    # dynargs.campaign's three stay its own: an accepted test pins their
    # lists to that cell (tests/benchmark/test_bench_dynargs.py)
    assert set(names[:-5]) == set(dyn) - {
        "calldata_select_share", "mem_exact_share", "loop_bound_trap_share"}
    for name in names:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


# --- the five readers, over spans and snapshots made by hand ------------------

def reader(name: str):
    return load(f"layer_metrics/{name}.py", "bench_" + name)


def obs(before=None, after=None, spans=()):
    return {"kind": "campaign", "spans": list(spans),
            "registry_before": {"counters": before or {}},
            "registry_after": {"counters": after or {}}}


CALLS = 'engine_calls_total{fate="%s",tx="%d"}'
MEMBER = 'engine_member_calls_total{fate="%s",tx="%d"}'
WORDS = 'engine_hop_words_total{fate="%s",side="%s"}'


def test_counter_readers_difference_the_window_and_skip_the_creation():
    before = {CALLS % ("internal", 1): 10.0, CALLS % ("external", 1): 10.0}
    after = {CALLS % ("internal", 0): 99.0,
             CALLS % ("internal", 1): 40.0, CALLS % ("external", 1): 15.0,
             CALLS % ("internal", 2): 30.0, CALLS % ("eoa", 2): 5.0,
             MEMBER % ("framed", 0): 50.0,
             MEMBER % ("framed", 2): 90.0, MEMBER % ("trapped", 2): 10.0,
             WORDS % ("exact", "calldata"): 30.0,
             WORDS % ("exact", "return"): 10.0,
             WORDS % ("havoc", "calldata"): 10.0}
    o = obs(before, after)
    assert reader("internal_call_share").read(o) == pytest.approx(
        100.0 * 60 / 70)
    assert reader("call_limit_trap_share").read(o) == pytest.approx(10.0)
    assert reader("hop_word_exact_share").read(o) == pytest.approx(80.0)
    for name in ("internal_call_share", "call_limit_trap_share",
                 "hop_word_exact_share"):
        assert reader(name).read(obs()) is None         # the parent
        assert reader(name).read(obs(after, after)) is None
        assert reader(name).read({"kind": "serve"}) is None


def test_span_readers_read_the_routers_of_the_last_call_and_the_worlds():
    def harvest(tx, deep, paths, names=True):
        s = {"kind": "span", "name": "harvest", "tx": tx, "dur": 0.1,
             "depth3_by_contract": deep, "paths_by_contract": paths}
        if names:
            s["contract_names"] = ["a__weth", "a__router", "b__pair",
                                   "b__router"]
        return s

    spans = [harvest(1, [0, 9, 0, 9], [5, 10, 5, 10]),
             harvest(2, [0, 2, 7, 4], [50, 20, 50, 40]),
             harvest(2, [0, 0, 0, 6], [1, 20, 1, 40]),
             {"kind": "span", "name": "system_world", "dur": 0.25},
             {"kind": "span", "name": "system_world", "dur": 0.75}]
    assert reader("depth3_path_share").read(obs(spans=spans)) == (
        pytest.approx(100.0 * 12 / 120))
    assert reader("system_world_s").read(obs(spans=spans)) == (
        pytest.approx(0.5))
    # a program whose harvest names no contracts, or runs no system
    bare = [harvest(2, [0, 2, 7, 4], [50, 20, 50, 40], names=False)]
    assert reader("depth3_path_share").read(obs(spans=bare)) is None
    assert reader("system_world_s").read(obs(spans=bare)) is None
    assert reader("depth3_path_share").read({"kind": "serve"}) is None


# --- a batch without systems: the parent's reports, byte for byte ----------------

#: sha256 over the sorted issues of batch 0 (seed 2**31 + 41, the test
#: limits, 8 x 16 lanes, 128 steps, 2 transactions) at the parent commit
#: 3c2f47d, and (issues, paths) beside it
PARENT = {
    "wild-v1": ("a6d79e97ee6d3e7ec987775bc35970f549a3a41c2e23d098850e557ca"
                "d0e97cf", 5, 81),
    "wild-v1:intarith": ("3b512b6b4697c4b3d60e3ec5b45e636280985a92d2a75ed4"
                         "7d838cb0b349e899", 1, 81),
    "deployed-v1": ("f712e3fb2222bd91a50534cf20d13002eea94766363645360fd4c"
                    "89289ad3acb", 5, 72),
    "twocall-v1": ("ba06dba1097b22a13c7a2b1cb86b80169b71da17a1f3c764e92c62"
                   "462f366244", 7, 48),
    "dynargs-v1": ("7788863573e84ad40bedf55a0b9fe5796bcb3b0446354cdc40cfc3"
                   "14408849fe", 13, 26),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_a_batch_without_systems_reports_what_the_parent_did(name):
    import mythril_tpu  # noqa: F401
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.mythril.campaign import CorpusCampaign
    from mythril_tpu.symbolic import SymSpec

    corpus = load(f"corpora/{name.split(':')[0]}.py",
                  "bench_parent_" + name.split(":")[0].replace("-", "_"))
    cs = corpus.batch(2 ** 31 + 41, 0, 512)
    deploys = "creation" in cs[0]
    recs = [(c["name"], c["code"]) + ((c["creation"],) if deploys else ())
            for c in cs]
    res = CorpusCampaign(
        recs, batch_size=8, lanes_per_contract=16, limits=TEST_LIMITS,
        spec=SymSpec(storage=not deploys), max_steps=128,
        transaction_count=2,
        modules=["IntegerArithmetics"] if ":" in name else None).run()
    issues = sorted(json.dumps(i, sort_keys=True) for i in res.issues)
    digest = hashlib.sha256("\n".join(issues).encode()).hexdigest()
    assert (digest, len(issues), res.paths_total) == PARENT[name]
