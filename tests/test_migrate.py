"""In-jit cross-block lane migration (``migrate_parked_device``).

The ICI tier of SURVEY §5.8's cross-device rebalancing: starved
fork-requesting lanes move between blocks INSIDE the jitted superstep
loop through a compact per-block payload buffer, with no host seam.
The host-planned ``rebalance_parked`` keeps the chunk-boundary tier;
these tests pin the device tier's semantics and its GSPMD compatibility
on the virtual 8-device mesh (conftest).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.symbolic import (SymSpec, make_sym_frontier,
                                  migrate_parked_device, sym_run)

L = TEST_LIMITS
P = 32
B = 4  # 8 blocks of 4 lanes


def synth(active_mask, parked_mask):
    """Frontier with per-lane pc = lane index (a movement tracer)."""
    sf = make_sym_frontier(P, L, active=np.asarray(active_mask))
    return sf.replace(
        base=sf.base.replace(pc=jnp.arange(P, dtype=jnp.int32)),
        fork_req=jnp.asarray(parked_mask),
    )


def test_starved_lane_moves_to_freest_block():
    active = np.zeros(P, dtype=bool)
    active[0:4] = True          # block 0 exhausted
    active[4:6] = True          # block 1: 2 free slots
    # block 2..7 empty: 4 free slots each -> freest, fills first
    parked = np.zeros(P, dtype=bool)
    parked[1] = True            # starved lane, tracer pc = 1
    sf = synth(active, parked)

    out = jax.jit(migrate_parked_device, static_argnums=(1,))(sf, B)
    act = np.asarray(out.base.active)
    pc = np.asarray(out.base.pc)
    req = np.asarray(out.fork_req)

    assert not act[1] and not req[1]          # vacated
    moved = np.where(act & (pc == 1))[0]
    assert moved.size == 1                     # exactly one copy
    assert moved[0] >= 8                       # landed in an empty block
    assert req[moved[0]]                       # still parked -> will retry
    assert act.sum() == active.sum()           # lane count conserved


def test_noop_when_own_block_has_free_slot():
    active = np.zeros(P, dtype=bool)
    active[0:3] = True          # block 0 has one free slot
    parked = np.zeros(P, dtype=bool)
    parked[1] = True
    sf = synth(active, parked)

    out = jax.jit(migrate_parked_device, static_argnums=(1,))(sf, B)
    np.testing.assert_array_equal(np.asarray(out.base.active), active)
    np.testing.assert_array_equal(np.asarray(out.fork_req), parked)
    np.testing.assert_array_equal(np.asarray(out.base.pc), np.arange(P))


def test_capacity_bounded_rest_stay_parked():
    active = np.ones(P, dtype=bool)
    active[28:32] = False       # only block 7 has room (4 free)
    parked = np.zeros(P, dtype=bool)
    parked[0:4] = True          # block 0: four starved lanes
    sf = synth(active, parked)

    out = jax.jit(migrate_parked_device, static_argnums=(1,))(sf, B)
    act = np.asarray(out.base.active)
    req = np.asarray(out.fork_req)
    # cap = min(free-1, MIG=B//2) = min(3, 2) = 2 migrants accepted
    assert act.sum() == active.sum()
    assert (act[28:32] & (np.asarray(out.base.pc)[28:32] < 4)).sum() == 2
    assert req.sum() == 4                      # none lost: moved OR parked


def test_iprof_rows_conserved_across_migration():
    active = np.zeros(P, dtype=bool)
    active[0:4] = True
    parked = np.zeros(P, dtype=bool)
    parked[2] = True
    sf = synth(active, parked)
    hist = jnp.zeros((P, 256), jnp.int32).at[2, 0x57].set(7).at[9, 0x01].set(3)
    sf = sf.replace(base=sf.base.replace(op_hist=hist))  # lane 9: dead counts

    out = jax.jit(migrate_parked_device, static_argnums=(1,))(sf, B)
    oh = np.asarray(out.base.op_hist)
    assert oh.sum() == 10                      # harvest totals conserved
    moved = np.where(np.asarray(out.base.active)
                     & (np.asarray(out.base.pc) == 2))[0]
    assert oh[moved[0], 0x57] == 7             # counts travelled with it


def test_iprof_residual_sidecar_keeps_rows_attributable():
    """With the sidecar attached (what ``attach_iprof`` now does), a
    replaced slot's unharvested counts land in ``op_resid`` instead of
    being folded into an arbitrary live lane's row (ADVICE r5) — the
    per-lane histogram stays attributable while harvest totals
    (rows + sidecar) are conserved."""
    active = np.zeros(P, dtype=bool)
    active[0:4] = True
    parked = np.zeros(P, dtype=bool)
    parked[2] = True
    sf = synth(active, parked)
    # lane 4 = first free slot of the freest block (block 1 — all empty
    # blocks tie, stable sort picks the lowest) = the import slot the
    # migrant lands in; its row holds a retired lane's unharvested counts
    hist = jnp.zeros((P, 256), jnp.int32).at[2, 0x57].set(7).at[4, 0x01].set(3)
    sf = sf.replace(base=sf.base.replace(
        op_hist=hist, op_resid=jnp.zeros(256, jnp.int32)))

    out = jax.jit(migrate_parked_device, static_argnums=(1,))(sf, B)
    oh = np.asarray(out.base.op_hist)
    resid = np.asarray(out.base.op_resid)
    moved = np.where(np.asarray(out.base.active)
                     & (np.asarray(out.base.pc) == 2))[0]
    assert moved.size == 1
    assert oh[moved[0], 0x57] == 7     # counts travelled with the lane
    assert resid[0x01] == 3            # orphaned row -> sidecar, not a lane
    assert resid.sum() == 3
    assert oh.sum() == 7               # no live row absorbed foreign counts
    assert oh.sum() + resid.sum() == 10  # harvest total conserved


def test_sharded_migration_matches_unsharded():
    active = np.zeros(P, dtype=bool)
    active[0:4] = True
    active[4:6] = True
    parked = np.zeros(P, dtype=bool)
    parked[0] = parked[3] = True
    sf = synth(active, parked)

    ref = jax.jit(migrate_parked_device, static_argnums=(1,))(sf, B)

    devices = np.array(jax.devices()[:8])
    mesh = Mesh(devices, axis_names=("dp",))

    def shard_leaf(x):
        if hasattr(x, "shape") and x.ndim >= 1 and x.shape[0] == P:
            return NamedSharding(mesh, PS("dp", *([None] * (x.ndim - 1))))
        return NamedSharding(mesh, PS())

    sh = jax.tree.map(shard_leaf, sf)
    out = jax.jit(migrate_parked_device, static_argnums=(1,),
                  in_shardings=(sh,), out_shardings=sh)(
        jax.device_put(sf, sh), B)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# end-to-end: seeds crowded into one block starve without migration;
# with it they spread into the empty blocks and finish more paths
CODE = assemble(
    0, "CALLDATALOAD", ("ref", "a"), "JUMPI",
    1, 0, "SSTORE",
    4, "CALLDATALOAD", ("ref", "b"), "JUMPI",
    2, 1, "SSTORE", "STOP",
    ("label", "a"), 3, 0, "SSTORE", "STOP",
    ("label", "b"), 4, 1, "SSTORE", "STOP",
)


def _run(migrate_every):
    img = ContractImage.from_bytecode(CODE, L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(P, dtype=bool)
    active[0:4] = True          # block 0 full; blocks 1..7 empty
    sf = make_sym_frontier(P, L, active=active)
    env = make_env(P)
    return sym_run(sf, env, corpus, SymSpec(), L, max_steps=64,
                   fork_block=B, defer_starved=True,
                   migrate_every=migrate_every)


def test_sym_run_migration_unblocks_starved_forks():
    stuck = _run(0)
    moved = _run(1)
    done_stuck = int(np.asarray(stuck.base.halted & ~stuck.base.error).sum())
    done_moved = int(np.asarray(moved.base.halted & ~moved.base.error).sum())
    assert done_moved > done_stuck             # migration freed real work
    # nothing dropped in either mode (defer_starved retries, never drops)
    assert int(np.asarray(moved.dropped_total)) == 0
    # migrated run explores every path of the 2-branch fixture: 4 leaves
    assert done_moved >= 4


# --- the floor of a full frontier (``relieve_starved``) ---------------------

# 4 contracts x 8 lanes: a share of 8 lanes, a floor of 2
_HOME = np.array([0] * 1 + [1] * 9 + [2] * 14 + [3] * 8, dtype=np.int32)


def _relieve(active, parked, running, home, n=4):
    from mythril_tpu.symbolic.engine import relieve_starved

    sf = synth(active, parked)
    out, k = relieve_starved(sf, n, active, parked, running, home)
    return (k, np.asarray(out.base.active), np.asarray(out.fork_req),
            np.asarray(out.base.pc))


@pytest.mark.parametrize("case", ["starved", "creation_images",
                                  "a_lane_is_free", "a_lane_still_moves",
                                  "nobody_under_the_floor",
                                  "the_starved_does_not_wait"])
def test_full_frontier_fixpoint_is_broken_for_the_starved_only(case):
    full = np.ones(P, dtype=bool)
    # every contract has parked lanes; the rest have halted
    parked = np.zeros(P, dtype=bool)
    parked[[0, 3, 4, 12, 13, 14, 20, 25, 26]] = True
    active, running, home = full.copy(), parked.copy(), _HOME.copy()
    if case == "creation_images":
        home = home + 4         # the lanes' homes are images 4..7 of 8
    elif case == "a_lane_is_free":
        active[31] = False
    elif case == "a_lane_still_moves":
        running[30] = True
    elif case == "nobody_under_the_floor":
        home[1] = 0             # contract 0 holds 2 lanes: its floor
    elif case == "the_starved_does_not_wait":
        parked[0] = running[0] = False
    k, act, req, pc = _relieve(active, parked, running, home)
    if case in ("starved", "creation_images"):
        # contracts 1 (9 lanes) and 2 (14) hold more than their share
        # of 8: their parked lanes go, and nothing else changes
        gone = [3, 4, 12, 13, 14, 20]
        assert k == len(gone)
        assert not act[gone].any() and not req[gone].any()
        keep = np.setdiff1d(np.arange(P), gone)
        assert act[keep].all() and (req[keep] == parked[keep]).all()
        assert (pc == np.arange(P)).all()      # no lane moved
    else:
        assert k == 0
        assert (act == active).all() and (req == parked).all()


# --- the rule that ends sym_run's loop at the pool's fixpoint ----------------

def _observations(case):
    """Two trips of one call that each held a feasibility sweep, as
    ``[active, fork_req, running, killed_total, dropped_total]``, and
    what the first of them found in the loop's carry."""
    from mythril_tpu.symbolic.engine import empty_observation

    full = np.ones(P, dtype=bool)
    parked = np.zeros(P, dtype=bool)
    parked[[0, 3, 4, 12, 13, 14, 20, 25, 26]] = True
    first = [full, parked, parked.copy(), 7, 2]
    second = [full.copy(), parked.copy(), parked.copy(), 7, 2]
    seen = empty_observation(P)
    if case == "a_lane_is_free":
        second[0][31] = False
    elif case == "a_lane_still_moves":
        second[2][30] = True
    elif case == "the_sweep_before_was_not_stuck":
        first[2] = parked.copy()
        first[2][30] = True
    elif case == "an_observation_from_before_the_call_does_not_count":
        # the call before left stuck at this very frontier and the
        # host's seam evicted: a new call starts from an empty carry
        first = None
    elif case == "a_parked_lane_halted":
        second[2][3] = False    # both stuck, ``running`` not as it was
    elif case == "a_sweep_killed":
        second[3] = 8           # and a parked fork took the freed lane
    elif case == "a_fork_was_dropped":
        second[4] = 3
    elif case == "another_lane_is_parked":
        # a halted lane went and a parked fork took its slot, parked
        # itself: ``active`` as before, ``fork_req`` not
        second[1][5] = second[2][5] = True
    return seen, first, second


@pytest.mark.parametrize("case", [
    "stuck_twice_and_equal", "a_lane_is_free", "a_lane_still_moves",
    "the_sweep_before_was_not_stuck",
    "an_observation_from_before_the_call_does_not_count",
    "a_parked_lane_halted", "a_sweep_killed", "a_fork_was_dropped",
    "another_lane_is_parked", "the_call_held_no_second_sweep"])
def test_pool_is_proven_stuck_only_by_stuck_stuck_and_equal(case):
    """``pool_fixpoint`` on tiny arrays, as the loop's body calls it."""
    from mythril_tpu.symbolic.engine import pool_fixpoint

    def trip(seen, obs, swept=True):
        seen, fixpoint = pool_fixpoint(seen, swept, *map(jnp.asarray, obs))
        return seen, bool(fixpoint)

    seen, first, second = _observations(case)
    if first is not None:
        seen, fixpoint = trip(seen, first)
        assert not fixpoint     # one observation alone proves nothing
        assert bool(seen[0]) is (case != "the_sweep_before_was_not_stuck")
    if case == "the_call_held_no_second_sweep":
        # the seven trips between two sweeps take no observation and
        # prove nothing, however stuck the frontier is
        for _ in range(7):
            was = seen
            seen, fixpoint = trip(seen, second, swept=False)
            assert not fixpoint
            assert all(np.array_equal(a, b) for a, b in zip(seen, was))
        return
    seen, fixpoint = trip(seen, second)
    assert fixpoint is (case == "stuck_twice_and_equal")
    stuck = case not in ("a_lane_is_free", "a_lane_still_moves")
    assert bool(seen[0]) is stuck
    # the observation is what this sweep saw: the next one like it is
    # the proof, whatever came before
    seen, fixpoint = trip(seen, second)
    assert fixpoint is stuck


@pytest.mark.parametrize("case", ["stuck_twice_and_equal", "a_sweep_killed",
                                  "a_lane_is_free"])
def test_sharded_pool_rule_matches_unsharded(case):
    """The rule's reductions over a lane axis sharded on the virtual
    8-device mesh (what ``cond`` already does with ``any(running)``):
    same observation, same verdict."""
    from mythril_tpu.symbolic.engine import pool_fixpoint

    seen, first, second = _observations(case)
    mesh = Mesh(np.array(jax.devices()[:8]), axis_names=("dp",))
    lanes, whole = NamedSharding(mesh, PS("dp")), NamedSharding(mesh, PS())

    def two_sweeps(seen, first, second):
        seen, _ = pool_fixpoint(seen, True, *first)
        return pool_fixpoint(seen, True, *second)

    def put(obs, sharded):
        return tuple(
            jax.device_put(jnp.asarray(x), lanes if sharded
                           and np.ndim(x) else whole) for x in obs)

    ref = jax.jit(two_sweeps)(seen, put(first, False), put(second, False))
    seen_sh = (*seen[:3], *(jax.device_put(m, lanes) for m in seen[3:]))
    out = jax.jit(two_sweeps)(seen_sh, put(first, True), put(second, True))
    assert bool(out[1]) is bool(ref[1]) is (case == "stuck_twice_and_equal")
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _leaves_that_differ(a, b):
    return sorted(
        jax.tree_util.keystr(path)
        for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                                jax.tree.leaves(b))
        if not np.array_equal(np.asarray(x), np.asarray(y)))


def test_a_stuck_pools_next_chunk_hands_back_the_frontier():
    """The rule on the compiled engine (the program ``_run(0)``
    compiles): every lane is taken and parks on the first branch, and
    the call leaves its loop at the second sweep after that, flag set;
    one more chunk from there runs two sweeps and changes no leaf but
    the two step counters."""
    from mythril_tpu.symbolic.engine import pool_stuck

    img = ContractImage.from_bytecode(CODE, L.max_code)
    corpus = Corpus.from_images([img])
    sf = make_sym_frontier(P, L, active=np.ones(P, dtype=bool))
    env = make_env(P)
    assert sf.fixpoint is None      # no ``defer_starved`` call has run

    def chunk(sf):
        # (the flag is an output only: handed in without it, every call
        # is the one program ``_run(0)`` compiled)
        return sym_run(sf.replace(fixpoint=None), env, corpus, SymSpec(),
                       L, max_steps=64, fork_block=B, defer_starved=True,
                       migrate_every=0)

    first = chunk(sf)
    assert pool_stuck(np.asarray(first.base.active),
                      np.asarray(first.fork_req),
                      np.asarray(first.base.running))
    assert bool(first.fixpoint)
    ran = int(first.steps_total)
    assert ran < 64 and ran % L.propagate_every == 0
    after = chunk(first)
    assert bool(after.fixpoint)
    assert int(after.steps_total) == ran + 2 * L.propagate_every <= ran + 16
    assert _leaves_that_differ(first, after) == [".base.n_steps",
                                                 ".steps_total"]
    # a call that ends another way clears the flag: nothing runs here
    idle = chunk(after.replace(base=after.base.replace(
        halted=jnp.ones(P, dtype=bool))))
    assert not bool(idle.fixpoint)
    assert int(idle.steps_total) == int(after.steps_total)
