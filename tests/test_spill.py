"""Spill-to-host fork deferral + cross-block lane rebalancing.

SURVEY §5.7/§5.8: forks past block capacity must not
be silently lost — a starved fork parks its lane, retries, and the host
re-seeds persistently parked lanes into other blocks' free slots between
chunks. Done-criterion: a branchy+quiet contract mix that drops forks
without spill finishes with dropped_forks == 0 and the full path set
when spill is on.
"""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.analysis import SymExecWrapper, fire_lasers

L = TEST_LIMITS


def branchy(n_branches: int) -> bytes:
    """n sequential symbolic branches -> 2^n distinct surviving paths."""
    toks = []
    for i in range(n_branches):
        toks += [32 * i, "CALLDATALOAD", ("ref", f"L{i}"), "JUMPI",
                 ("label", f"L{i}")]
    toks += [1, 0, "SSTORE", "STOP"]  # mutate so paths survive the tx
    return assemble(*toks)


QUIET = assemble(1, 0, "SSTORE", "STOP")


def run_mix(spill: bool, migrate_every: int = 8):
    # branchy explores 2^4 = 16 paths but its block holds only 12 lanes;
    # the quiet contract's block idles with 11 free — global capacity (24)
    # fits every path, so spill must recover ALL of them
    return SymExecWrapper(
        [branchy(4), QUIET],
        limits=L,
        lanes_per_contract=12,
        fork_block=12,              # block-local forking (sharded layout)
        max_steps=64,
        transaction_count=1,
        spill=spill,
        migrate_every=migrate_every,
    )


def test_spill_requeues_dropped_forks_host_tier():
    """migrate_every=0 pins the HOST rebalance tier on its own."""
    base = run_mix(spill=False)
    cov0 = base.coverage
    assert cov0["dropped_forks"] > 0, \
        "fixture must saturate its block without spill"

    sym = run_mix(spill=True, migrate_every=0)
    cov1 = sym.coverage
    assert cov1["dropped_forks"] == 0, f"forks still lost: {cov1}"
    assert cov1["rebalanced_lanes"] > 0, "host rebalance never fired"
    # at least the full 2^4 path set for the branchy contract + 1 quiet
    # path (>= not ==, ADVICE r5: benign admission-order changes must
    # not flake the suite — zero DROPPED forks is the real contract)
    assert cov1["surviving_paths"] >= 17, cov1["surviving_paths"]
    assert cov1["surviving_paths"] > cov0["surviving_paths"]


def test_spill_in_jit_migration_tier():
    """Default driver config: the in-jit migration places starved lanes
    before the chunk seam, so the host tier has nothing left to do and
    the path set is still complete."""
    sym = run_mix(spill=True)   # migrate_every=8 (driver default)
    cov = sym.coverage
    # ADVICE r5 de-flake: the hard contract is zero LOST forks and a
    # complete path set; exact survivor counts and the migration/host
    # tier split shift with benign admission-order or cadence changes
    assert cov["dropped_forks"] == 0, f"forks still lost: {cov}"
    assert cov["surviving_paths"] >= 17, cov["surviving_paths"]


def test_spill_issue_parity():
    """Spill changes WHERE paths live, never WHAT is found."""
    r0 = fire_lasers(run_mix(spill=False))
    r1 = fire_lasers(run_mix(spill=True))
    key = lambda r: {(i.swc_id, i.address, i.contract) for i in r.issues}
    assert key(r1) >= key(r0), "spill lost findings"
