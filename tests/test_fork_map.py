"""``engine.plan_fork_map`` against the source-major reference
(tests/fork_map_ref.py) on seeded block masks: every admission policy,
one block and four, a frontier with free lanes to spare and a saturated
one. No engine is compiled: the map is a function of three ``[G, B]``
arrays."""

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.symbolic.engine import plan_fork_map

from fork_map_ref import fork_map_ref

P = 64
MAX_CONSTRAINTS = 128
POLICIES = ("fifo", "shallow", "deep", "weighted", "random", "beam",
            "coverage")


def keys_for(policy, rng, shape):
    """Keys in the range ``expand_forks`` computes for ``policy``, with
    ties (the lane index breaks them)."""
    depth = rng.integers(0, 12, shape)
    if policy == "fifo":
        return None
    if policy in ("shallow", "beam"):
        return depth.astype(np.int32)
    if policy == "deep":
        return (MAX_CONSTRAINTS - depth).astype(np.int32)
    if policy == "weighted":
        return ((rng.integers(0, 1024, shape) * (depth + 1))
                % 65536).astype(np.int32)
    if policy == "random":
        return rng.integers(0, 0x8000, shape).astype(np.int32)
    assert policy == "coverage"
    return rng.integers(0, 2, shape).astype(np.int32)


def masks(rng, G, p_free, p_req):
    """A lane is free, or active and asking for a fork, or just active."""
    u = rng.random((G, P // G))
    free2 = u < p_free
    req2 = ~free2 & (u < p_free + p_req)
    return req2, free2


# (share of lanes free, share asking): spare lanes; more requests than
# free lanes; no free lane at all; nobody asks
MIXES = ((0.6, 0.2), (0.15, 0.6), (0.0, 0.5), (0.5, 0.0))


@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("policy", POLICIES)
def test_plan_fork_map_matches_reference(policy, G):
    rng = np.random.default_rng(1000 * G + POLICIES.index(policy))
    saturated = spare = 0
    for p_free, p_req in MIXES * 3:
        req2, free2 = masks(rng, G, p_free, p_req)
        key = keys_for(policy, rng, req2.shape)
        got = plan_fork_map(req2, free2, key, policy)
        for dense in (False, True):
            want = fork_map_ref(req2, free2, key, policy, dense=dense)
            for name, g, w in zip(("src2", "is_copy"), got, want):
                assert np.array_equal(np.asarray(g), np.asarray(w)), (
                    name, policy, G, dense)
            # all expand_forks reads of ``slot``: starved or admitted
            assert np.array_equal(np.asarray(got[2]) == P,
                                  np.asarray(want[2]) == P)
        starved = (np.asarray(got[2]) == P) & req2.reshape(P)
        saturated += bool(starved.any())
        spare += bool(req2.any() and not starved.any())
        # a copy lands in a free lane and comes from a requester
        is_copy = np.asarray(got[1]).reshape(G, -1)
        assert not (is_copy & ~free2).any()
        src = np.take_along_axis(req2, np.asarray(got[0]), axis=1)
        assert src[is_copy].all()
        assert is_copy.sum() == (req2.reshape(P) & ~starved).sum()
    assert saturated and spare
