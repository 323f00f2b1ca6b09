"""The TPU's lowerings report what the parent's did, corpus by corpus.

The backend picks a lowering at trace time (``interpreter._use_scatter``):
the CPU scatters and gathers elements, the TPU selects densely and reads
rows. A change to the TPU's side (PR 37's sweep, PR 41's ``_gather_bytes``,
PR 43's copies) runs nowhere in the tier-1 tests unless it is traced
here: one batch of each of the benchmark's six corpora at the test limits
with ``_use_scatter`` patched to the TPU's choice, its issue set hashed
and held to what the parent commit reported under the same patch.
"""

import dataclasses
import hashlib
import importlib.util
import json
import os

import pytest

import mythril_tpu  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: sha256 over the sorted issues of batch 0 (seed 2**31 + 41, the test
#: limits, 8 x 16 lanes, 2 transactions; 128 steps, ``linked-v1`` 256 and
#: ``max_accounts`` 6) at the parent commit d72977c with the TPU's
#: lowerings traced on the CPU, and (issues, paths) beside it. The first
#: five equal what the CPU's lowerings report
#: (tests/benchmark/test_bench_linked.py)
PARENT = {
    "wild-v1": ("a6d79e97ee6d3e7ec987775bc35970f5"
                "49a3a41c2e23d098850e557cad0e97cf", 5, 81),
    "wild-v1:intarith": ("3b512b6b4697c4b3d60e3ec5b45e6362"
                         "80985a92d2a75ed47d838cb0b349e899", 1, 81),
    "deployed-v1": ("f712e3fb2222bd91a50534cf20d13002"
                    "eea94766363645360fd4c89289ad3acb", 5, 72),
    "twocall-v1": ("ba06dba1097b22a13c7a2b1cb86b8016"
                   "9b71da17a1f3c764e92c62462f366244", 7, 48),
    "dynargs-v1": ("7788863573e84ad40bedf55a0b9fe579"
                   "6bcb3b0446354cdc40cfc314408849fe", 13, 26),
    "linked-v1": ("55135b26532197731171637eb0e9c676"
                  "33ec621d48aa831c2982aec1141d2402", 6, 26),
}


@pytest.fixture
def tpu_lowerings(monkeypatch):
    """Every program traced inside is the TPU's; none of them, and none
    traced before under the CPU's choice, is found again by ``jit``."""
    import jax

    from mythril_tpu.core import interpreter as ci

    jax.clear_caches()
    monkeypatch.setattr(ci, "_use_scatter", lambda: False)
    yield
    jax.clear_caches()


def _corpus(name):
    spec = importlib.util.spec_from_file_location(
        "issue_sets_" + name.replace("-", "_"),
        os.path.join(ROOT, "benchmark", "corpora", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", sorted(PARENT))
def test_tpu_lowerings_report_what_the_parents_did(name, tpu_lowerings):
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.mythril.campaign import CorpusCampaign
    from mythril_tpu.symbolic import SymSpec

    cs = _corpus(name.split(":")[0]).batch(2 ** 31 + 41, 0, 512)
    deploys = "creation" in cs[0]
    limits, steps = TEST_LIMITS, 128
    if "system" in cs[0]:
        recs = [(c["name"], c["code"], c["creation"],
                 {"system": c["system"], "address": c["address"]})
                for c in cs]
        limits, steps = dataclasses.replace(TEST_LIMITS, max_accounts=6), 256
    else:
        recs = [(c["name"], c["code"]) + ((c["creation"],) if deploys else ())
                for c in cs]
    res = CorpusCampaign(
        recs, batch_size=8, lanes_per_contract=16, limits=limits,
        spec=SymSpec(storage=not deploys), max_steps=steps,
        transaction_count=2,
        modules=["IntegerArithmetics"] if ":" in name else None).run()
    assert res.batch_status == ["ok"]
    issues = sorted(json.dumps(i, sort_keys=True) for i in res.issues)
    digest = hashlib.sha256("\n".join(issues).encode()).hexdigest()
    assert (digest, len(issues), res.paths_total) == PARENT[name]
