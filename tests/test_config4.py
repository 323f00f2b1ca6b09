"""BASELINE config-4 end-to-end: 3 contracts, call depth 3, multi-tx.

First pinned evidence that the frame machinery
(engine.py `_h_sym_call` + frame stack) earns its complexity on its
target workload: a drain inside the CORE contract witnessed from the
PERIPHERY entry point through two real CALL hops. Reference analog:
``mythril/laser/ethereum/call.py`` multi-contract resolution (⚠unv,
SURVEY §3.2); fixture shape mirrors BASELINE.json configs[3].
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.analysis import SymExecWrapper, fire_lasers
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

from config4_fixture import build_system

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "fixtures", "config4")
GOLDEN = os.path.join(os.path.dirname(__file__), "fixtures", "goldens",
                      "config4.json")
REGEN = bool(os.environ.get("MYTHRIL_REGEN_GOLDENS"))

# depth-3 chain: entry frame + router + vault + value send. max_accounts
# must fit attacker + creator + all THREE contract accounts — at the
# TEST default (4) the trio doesn't fit the table, cross-contract
# targets resolve as unknown, and every CALL degrades to external havoc.
# That is ``make_frontier``'s all-or-own rule, which a batch WITHOUT
# manifests still follows (every contract of the batch in every lane's
# table if all fit, else each lane its own contract alone): this file
# drives ``SymExecWrapper`` directly, at ``contract_address(i)``. A
# campaign over a corpus directory that holds a system's manifest gives
# a member's lanes its own system at the manifest's addresses whatever
# else the batch holds (tests/test_linked_system.py,
# tests/benchmark/test_bench_linked.py).
LIMITS = dataclasses.replace(TEST_LIMITS, call_depth=4, max_accounts=6)


def test_fixture_files_match_builder():
    """The committed hex fixtures ARE the assembled system (provenance:
    regenerate with MYTHRIL_REGEN_GOLDENS=1 and review the diff)."""
    if REGEN:
        os.makedirs(FIXTURE_DIR, exist_ok=True)
    for name, creation, runtime in build_system():
        bin_p = os.path.join(FIXTURE_DIR, f"{name.lower()}.bin")
        run_p = os.path.join(FIXTURE_DIR, f"{name.lower()}.bin-runtime")
        if REGEN:
            with open(bin_p, "w") as fh:
                fh.write(creation.hex())
            with open(run_p, "w") as fh:
                fh.write(runtime.hex())
            continue
        assert os.path.exists(run_p), f"fixture missing: {run_p} (regen)"
        assert bytes.fromhex(open(run_p).read().strip()) == runtime
        assert bytes.fromhex(open(bin_p).read().strip()) == creation


def test_depth3_drain_reachable_from_caller_entry():
    """Seed ONLY the periphery caller: the vault's origin-drain must
    still be found — the witness necessarily crossed caller→router→vault
    (two real frames) before the value transfer was recorded."""
    system = build_system()
    imgs = [ContractImage.from_bytecode(r, LIMITS.max_code)
            for _, _, r in system]
    corpus = Corpus.from_images(imgs)
    P = 16
    active = np.zeros(P, dtype=bool)
    active[0] = True  # one seed, caller contract only
    sf = make_sym_frontier(P, LIMITS, contract_id=np.zeros(P, np.int32),
                           active=active, n_contracts=3)
    env = make_env(P)
    sf = sym_run(sf, env, corpus, SymSpec(), LIMITS, max_steps=192)

    from mythril_tpu.analysis.symbolic import AnalysisContext
    ctx = AnalysisContext(sf=sf, corpus=corpus, limits=LIMITS,
                          contract_names=[n for n, _, _ in system])
    report = fire_lasers(ctx, white_list=["EtherThief"])
    found = {(i.contract, i.swc_id) for i in report.issues}
    assert ("Vault", "105") in found, (
        f"depth-3 drain not witnessed from caller entry; got {found}")


def _issue_key(d):
    return {"contract": d["contract"], "swc-id": d["swc-id"],
            "address": d["address"], "title": d["title"],
            "severity": d["severity"]}


def test_config4_golden():
    """Full system analysis: creation tx + 2 message txs over all three
    entry points, issue set pinned as a golden."""
    system = build_system()
    sym = SymExecWrapper(
        [r for _, _, r in system],
        contract_names=[n for n, _, _ in system],
        creation_bytecodes=[c for _, c, _ in system],
        limits=LIMITS, lanes_per_contract=16, max_steps=192,
        transaction_count=2,
    )
    report = fire_lasers(sym)
    got = sorted((_issue_key(i.as_dict()) for i in report.issues),
                 key=lambda d: (d["contract"], d["swc-id"], d["address"],
                                d["title"]))
    if REGEN:
        with open(GOLDEN, "w") as fh:
            json.dump(got, fh, indent=1, sort_keys=True)
        return
    assert os.path.exists(GOLDEN), "golden missing; regen and review"
    with open(GOLDEN) as fh:
        want = json.load(fh)
    assert got == want, (
        f"config4 issue set diverged\n got: {json.dumps(got, indent=1)}\n"
        f"want: {json.dumps(want, indent=1)}")
    # the headline finding: the unguarded vault drain exists in the set
    assert any(d["contract"] == "Vault" and d["swc-id"] == "105"
               for d in want)
