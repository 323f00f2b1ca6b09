"""One batch of each benchmark corpus, pinned to the parent's report.

PR 35 made the refuter prove what the witness search used to give up
on (a SafeMath-guarded operation: docs/solver.md "What refute
proves"). Every caller treats ``unsat`` and ``unknown`` alike, so the
report may not move: one ``wild-v1`` batch (every module) and one
``deployed-v1`` batch (creation code, concrete storage), built as the
benchmark's campaign driver builds them and cut to the limits
``tests/benchmark`` uses, must give the issues of the golden files
byte for byte. The goldens were written from commit fa23f8a (the
parent of PR 35) with ``python tests/test_campaign_refute_pin.py``
run in a checkout of it. And the solver must DECIDE at least 90% of
the batch's queries, the rule ``tests/test_swc_suite.py`` states for
the SWC fixtures: the parent decided 5 of 22 and 5 of 19.
"""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmark")
GOLDEN = os.path.join(HERE, "fixtures", "campaign_pin")
SEED = 2 ** 31 + 35
SMALL = ["--limits-profile", "test", "--lanes-per-contract", "16",
         "--max-steps", "128"]
CONFIGS = {"wild": "corpus-fullsuite", "deployed": "corpus-deployed"}


def _load(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def batch_report(kind: str):
    """(issues as canonical JSON, solver statistics of the batch)."""
    import mythril_tpu  # noqa: F401
    from mythril_tpu.smt.solver import _SOLVE_CACHE, SOLVER_STATS

    with open(os.path.join(BENCH, "configs", CONFIGS[kind] + ".json")) as fh:
        cfg = json.load(fh)
    cfg["analyze_args"] += SMALL
    driver = _load("drivers/campaign.py", "pin_driver_campaign")
    corpus = _load(f"corpora/{cfg['corpus']}.py", "pin_corpus_" + kind)
    args = driver.parse_analyze_args(cfg)
    args.pipeline = False
    contracts = corpus.batch(SEED, 0, max_code=512)[:args.batch_size]
    records = [(c["name"], c["code"]) + ((c["creation"],)
               if kind == "deployed" else ()) for c in contracts]
    _SOLVE_CACHE.clear()
    before = SOLVER_STATS.snapshot()
    res = driver.build_campaign(args, records).run()
    assert list(res.batch_status) == ["ok"] and not res.quarantined
    return (json.dumps(res.issues, sort_keys=True, indent=1) + "\n",
            SOLVER_STATS.delta(before))


# one engine compile a case (~60 s each on the CPU): the tier-1 gate
# (`-m 'not slow'`, 12 s under its time limit at PR 35) runs the
# deploying batch, which holds every refuted shape, three-step
# sequences and the constructor; `-m slow` adds the other
@pytest.mark.parametrize("kind", [
    "deployed", pytest.param("wild", marks=pytest.mark.slow)])
def test_batch_gives_the_parents_issues_and_decides_its_queries(kind):
    issues, solver = batch_report(kind)
    with open(os.path.join(GOLDEN, kind + ".json")) as fh:
        assert issues == fh.read()
    assert json.loads(issues), "the batch reports nothing: a dead pin"
    decided = solver["sat"] + solver["unsat"]
    total = decided + solver["unknown"]
    assert total >= 10, solver
    assert decided / total >= 0.90, solver


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    os.makedirs(GOLDEN, exist_ok=True)
    for k in sorted(CONFIGS):
        text, stats = batch_report(k)
        with open(os.path.join(GOLDEN, k + ".json"), "w") as out:
            out.write(text)
        print(k, len(json.loads(text)), "issues", stats)
