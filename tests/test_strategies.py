"""Fork-admission strategies (reference strategy/
{basic,beam}.py + coverage wrapper ⚠unv, SURVEY §1 row 7).

The frontier steps breadth-first by construction, so "strategy" here
decides WHICH forks are admitted when free lanes run short. The fixture
saturates an 8-lane block with 2^5 = 32 candidate paths; different
policies must keep observably different survivor populations.
"""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.analysis import SymExecWrapper

L = TEST_LIMITS


def branchy(n):
    toks = []
    for i in range(n):
        toks += [32 * i, "CALLDATALOAD", ("ref", f"L{i}"), "JUMPI",
                 ("label", f"L{i}")]
    toks += [1, 0, "SSTORE", "STOP"]
    return assemble(*toks)


def run_policy(strategy):
    # 12 lanes against 2^5 paths: the doubling frontier hits a PARTIAL
    # admission superstep (8 requests, 4 free) where policy order decides
    # which forks live — an exact-fit capacity would make every policy
    # identical (admission is all-or-nothing under lockstep doubling)
    sym = SymExecWrapper(
        [branchy(5)], limits=L, lanes_per_contract=12, max_steps=64,
        transaction_count=1, spill=False, strategy=strategy,
    )
    sf = sym.sf
    act = np.asarray(sf.base.active) & ~np.asarray(sf.base.error)
    # survivor identity = the sign pattern of its 5 branch constraints
    signs = np.asarray(sf.con_sign)[:, :5]
    lens = np.asarray(sf.con_len)
    pats = {tuple(signs[i, :lens[i]].tolist())
            for i in np.where(act)[0]}
    return pats, sym.coverage["dropped_forks"]


def test_policies_admit_different_survivors():
    pats_fifo, drop_fifo = run_policy("bfs")
    pats_w, drop_w = run_policy("weighted-random")
    pats_beam, drop_beam = run_policy("beam")
    assert drop_fifo > 0, "fixture must saturate"
    # the weighted hash admits a different fork population than arrival
    # order does
    assert pats_w != pats_fifo, "weighted-random matched fifo exactly"
    # beam's per-superstep admission cap (B//4) keeps slots in reserve
    # for LATER generations: a different survivor set (and here fewer
    # total drops) than greedy fifo admission
    assert pats_beam != pats_fifo
    assert drop_beam > 0


def test_coverage_policy_runs_and_survives():
    pats, _ = run_policy("coverage")
    assert len(pats) >= 1


def test_naive_random_policy_admits_and_differs():
    # deterministic, not luck: fixed hash + fixed fixture. The unbiased
    # hash order admits a DIFFERENT survivor population than lane-order
    # FIFO (verified at authoring time: 12 vs 12 survivors, disjoint
    # patterns) — a mapping regression that silently degenerates
    # naive-random to fifo fails this hard.
    pats_r, drop_r = run_policy("naive-random")
    pats_fifo, drop_fifo = run_policy("bfs")
    assert drop_r > 0 and drop_fifo > 0  # both ran out of lanes
    assert pats_r and pats_r != pats_fifo
